"""Lanes the local sort gathers into its size-class tables, per record:
the program's counter ``local_sort_lanes`` (Σ rows × L over the classes
its plan fixes for ``n``, the argument of its ``hybrid_sort`` span) over
``n``.  Read in traced runs; a program without the counter gives none."""


def read(run):
    if run.trace is None:
        return None
    from repro.core import bijection, hybrid, model

    lanes = getattr(hybrid, "local_sort_lanes", None)
    if lanes is None:
        return None
    cell = run.cell
    cfg = model.default_config(bijection.key_bits(cell.key_dtype) // 8)
    return lanes(cell.n, cfg) / cell.n
