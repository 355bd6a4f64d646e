"""HLO-text analysis: collective bytes-on-wire for the roofline's third term.

``cost_analysis()`` reports FLOPs and HBM bytes but not collective traffic,
so we parse the partitioned HLO and sum the bytes every collective moves
across ICI, weighted by the op's wire factor:

  all-gather          out * (P-1)/P     (each chip receives P-1 shards)
  reduce-scatter      in  * (P-1)/P
  all-reduce          2 * size * (P-1)/P  (ring = RS + AG)
  all-to-all          size * (P-1)/P
  collective-permute  size              (one hop)

Shapes in the SPMD module are per-device, so the sums are per-chip wire bytes.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# async collectives split into ``-start``/``-done`` pairs: the start op's
# result is a tuple carrying operand + output + context buffers (summing it
# double-counts), the done op's result is the true output.  The suffix is
# captured so bytes can be read off done/plain lines and sites counted off
# start/plain lines — each pair exactly once either way.
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:                                   # [num_groups, group_size]
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


def collective_bytes(hlo_text: str, num_devices: int) -> Dict[str, float]:
    """Per-chip wire bytes by collective kind (+ 'total').

    Async pairs are counted once, at the ``-done`` op (its result is the
    true output shape; the ``-start`` result tuple also carries operand and
    context buffers).  ``replica_groups`` usually annotates only the start
    line, so the group size seen at a start is carried to its done.
    """
    out: Dict[str, float] = defaultdict(float)
    start_groups: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind, suffix = m.group(1), m.group(2), m.group(3) or ""
        if suffix == "-start":
            start_groups[kind] = _group_size(line, num_devices)
            continue
        size = _shape_bytes(shape_str)
        default_p = (start_groups.pop(kind, num_devices)
                     if suffix == "-done" else num_devices)
        p = max(_group_size(line, default_p), 1)
        frac = (p - 1) / p
        if kind == "all-reduce":
            wire = 2 * size * frac
        elif kind == "all-gather":
            wire = size * frac
        elif kind == "reduce-scatter":
            wire = size * p * frac          # shape is the scattered output
        elif kind == "all-to-all":
            wire = size * frac
        else:                                # collective-permute
            wire = size
        out[kind] += wire
        out["total"] += wire
    return dict(out)


# ----- generic op census (used to certify sort-free kernel engines) --------

# Lowered text is either StableHLO ("%3 = stablehlo.sort(...)" /
# '"stablehlo.sort"(...)') or HLO text ("%x = ... sort(...)").  Attribute
# noise like ``indices_are_sorted=`` or function names like ``@argsort`` must
# not count, hence the tight patterns.
_STABLEHLO_OP_RE = re.compile(r'"?stablehlo\.([\w.]+)"?\(')
_HLO_OP_RE = re.compile(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z][\w-]*)\(")


def op_counts(hlo_text: str) -> Dict[str, int]:
    """Histogram of op names appearing in lowered StableHLO/HLO text."""
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _STABLEHLO_OP_RE.search(line)
        if m:
            out[m.group(1)] += 1
            continue
        m = _HLO_OP_RE.search(line)
        if m:
            out[m.group(1)] += 1
    return dict(out)


def sort_op_count(hlo_text: str) -> int:
    """Number of (stable)HLO ``sort`` ops in lowered text.

    ``jnp.argsort``/``jnp.lexsort``/``jnp.sort`` all lower to this op, so a
    zero count certifies a computation is free of comparison sorts — the
    acceptance gate for the Pallas kernel engine of ``hybrid_sort``.
    """
    return op_counts(hlo_text).get("sort", 0)


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Collective sites by kind; an async ``-start``/``-done`` pair is one
    site (counted at the start, where the op is issued)."""
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m and (m.group(3) or "") != "-done":
            out[m.group(2)] += 1
    return dict(out)


# ----- Pallas launch census (fused-engine acceptance gate) ------------------

# On real hardware a pallas_call lowers to a custom call with one of these
# targets; in interpret mode (this CPU container) the launch only exists as
# the ``pallas_call`` primitive in the jaxpr, so both counters are provided.
_PALLAS_CUSTOM_CALL_RE = re.compile(
    r'custom[-_]call(?:_target)?\s*[=(]?\s*@?"?'
    r'(tpu_custom_call|mosaic|__gpu\$xla\.gpu\.triton|triton_kernel_call)')


def pallas_custom_call_count(hlo_text: str) -> int:
    """Number of Pallas-kernel custom calls in lowered StableHLO/HLO text."""
    return len(_PALLAS_CUSTOM_CALL_RE.findall(hlo_text))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "eqns"):                  # open Jaxpr
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr                       # ClosedJaxpr


def jaxpr_primitive_counts(jaxpr) -> Dict[str, int]:
    """Recursive histogram of primitive names in a (Closed)Jaxpr.

    Sub-jaxprs (jit/while/cond/scan bodies) are traversed; a while body is
    counted ONCE, which is exactly what makes this the per-pass launch
    census: the fused engine's counting-pass loop body must contain exactly
    one ``pallas_call`` no matter how many passes execute at runtime.
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    out: Dict[str, int] = defaultdict(int)
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        for sub in _sub_jaxprs(eqn):
            for name, c in jaxpr_primitive_counts(sub).items():
                out[name] += c
    return dict(out)


def pallas_launch_count(jaxpr) -> int:
    """Total ``pallas_call`` launch sites in a traced computation."""
    return jaxpr_primitive_counts(jaxpr).get("pallas_call", 0)


def launch_census(jaxpr) -> Dict[str, object]:
    """One-call launch census: total ``pallas_call`` sites + per-while-body
    counts.

    The two structural invariants of the sort pipelines read straight off
    this: the fused hybrid engine with ``c =
    len(core.hybrid.local_sort_classes(n, cfg))`` traces to ``{"total": 2 +
    c, "while_bodies": [1] * (1 + c)}`` (prologue + ONE launch per counting
    pass + one bitonic launch site per local-sort size class, run once per
    tile of that class's loop), and every
    out-of-core merge *round* — a host-driven jit with no device loop —
    traces to ``{"total": 1, "while_bodies": []}``: one ``pallas_call`` per
    round, ``⌈log_K(runs)⌉`` rounds per sort (§5).  Any binary-search loop
    of the merge-path partition that traces as a while must stay launch-free
    (a zero entry in ``while_bodies``).
    """
    return {"total": pallas_launch_count(jaxpr),
            "while_bodies": while_body_pallas_launches(jaxpr)}


def pallas_grid_sizes(jaxpr):
    """Grid shapes of every ``pallas_call`` site, in trace order.

    The batched-step census: packing B block descriptors per grid step
    (``plan.pack_region_blocks``) must shrink the fused launch's grid from
    ``g_max`` to ``⌈g_max/B⌉`` *without* changing the launch count — the
    launch-site invariants above stay as they are, and this counter pins the
    grid side of the contract.  Each entry is a tuple (the pallas grid), one
    per launch site found (while/cond/jit bodies are traversed like
    ``jaxpr_primitive_counts``; a while body's site is counted once).
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in _sub_jaxprs(eqn):
            out.extend(pallas_grid_sizes(sub))
    return out


def while_body_pallas_launches(jaxpr):
    """Launch sites inside each while-loop body, outermost-first.

    For the fused hybrid engine this returns ``[1] * (1 + classes)``: one
    Pallas launch per counting pass (the pass loop's body), then one bitonic
    launch per tile in each local-sort class's tile loop; the prologue
    histogram sits outside every loop.  Loops inside a kernel body run
    within its one launch and are not listed.
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            out.append(pallas_launch_count(eqn.params["body_jaxpr"]))
        elif eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn):
                out.extend(while_body_pallas_launches(sub))
    return out
