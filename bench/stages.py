"""The program's own names for its stages, read from a traced window.

``repro.core.hybrid_sort`` names its stages twice:

* on the host, as profiler spans on the calling thread: ``hybrid_sort``
  around the whole call, and inside it ``hybrid_sort.prologue`` (holding
  ``hybrid_sort.live_bit_window``, the keys' copy to the host and the bit
  reduce) and ``hybrid_sort.dispatch``.  The reduced trace keeps them by
  name among its ``host`` events (``bench/reduce.py``);
* on the device, as named scopes (``jax.named_scope``) under
  ``hybrid_sort``: ``ping_pong``, ``prologue_histogram``,
  ``pass_bookkeeping``, ``counting_pass``, ``local_sort/bounds``,
  ``local_sort/rows``, ``local_sort/bitonic``, ``local_sort/copy_back``,
  ``unpad``, and ``exchange`` in the distributed sort.  A scope lands in
  the ``op_name`` metadata of each HLO instruction, but a TPU v5e trace
  names a device op by its instruction alone (``fusion.55``) and carries no
  ``op_name``.  So the instruction-to-scope map is read from the compiled
  program's text: ``program_text`` lowers the cell's main program again, as
  its timed path does, and compiles it, which the persistent compile cache
  answers with the executable the window ran.  The map is used only if
  every op that the window's executions of that program ran is an
  instruction of it.

Device numbers are the union of the matching ops inside ``reduce.window``,
per call, as ``reduce.per_call_ns`` counts a layer.  A program that names no
such stage (an older one) gives ``None``.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional

from bench import reduce

CALL = "hybrid_sort"
CHILDREN = ("hybrid_sort.prologue", "hybrid_sort.live_bit_window",
            "hybrid_sort.dispatch")
SCOPES = ("ping_pong", "prologue_histogram", "pass_bookkeeping",
          "counting_pass", "local_sort/bounds", "local_sort/rows",
          "local_sort/bitonic", "local_sort/copy_back", "unpad", "exchange")
UNDER_NO_CHILD = "under no child"
OUTSIDE_SORT = "in call outside hybrid_sort"
BETWEEN_CALLS = "between calls"

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%(\S+) = (.*)$', re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# per-process memo of what one run's readers share, keyed by its trace
_memo: Dict[int, dict] = {}


# --- host spans ------------------------------------------------------------

def span_ns_per_call(trace: dict, name: str) -> Optional[float]:
    """Host time of the spans called ``name`` inside the window, per call;
    ``None`` when the trace holds no such span."""
    found = [e for e in trace["host"] if e[0] == name]
    if not found:
        return None
    lo, hi = reduce.window(trace)
    return reduce.covered(found, lo, hi) / len(trace["calls"])


def _pieces(named: List[list], lo: float, hi: float) -> List[tuple]:
    """``[lo, hi)`` cut into ``(start, end, label)`` pieces, each labelled
    with the innermost of the properly nested spans ``named`` over it."""
    out, stack, t = [], [], lo

    def emit(upto):
        nonlocal t
        upto = min(upto, hi)
        if upto > t:
            out.append((t, upto, stack[-1][0] if stack else BETWEEN_CALLS))
            t = upto

    for name, s, d in sorted(named, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((name, s + d))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return out


def idle_intervals(trace: dict) -> List[tuple]:
    """The device's idle intervals inside the window, in order."""
    lo, hi = reduce.window(trace)
    idle, reach = [], lo
    for s, d in sorted((op[1], op[2]) for op in trace["ops"]):
        if s > reach:
            idle.append((reach, min(s, hi)))
        reach = max(reach, s + d)
        if reach >= hi:
            break
    if reach < hi:
        idle.append((reach, hi))
    return [g for g in idle if g[1] > g[0]]


def idle_by_span(trace: dict) -> Dict[str, float]:
    """Device idle ns inside the window, split by the innermost program span
    on the host at each idle instant: one of ``CHILDREN``, ``hybrid_sort``
    outside them (``UNDER_NO_CHILD``), the benchmark's call span outside
    ``hybrid_sort`` (``OUTSIDE_SORT``: the wait for the outputs), or no
    call (``BETWEEN_CALLS``).  The parts sum to the window's idle time."""
    lo, hi = reduce.window(trace)
    named = [e for e in trace["host"] if e[0] == CALL or e[0] in CHILDREN]
    named += [[reduce.CALL_SPAN, s, d] for s, d in trace["calls"]]
    label = {CALL: UNDER_NO_CHILD, reduce.CALL_SPAN: OUTSIDE_SORT}
    parts = {name: 0.0 for name in (*CHILDREN, UNDER_NO_CHILD, OUTSIDE_SORT,
                                     BETWEEN_CALLS)}
    idle = idle_intervals(trace)
    i = 0
    for a, b, name in _pieces(named, lo, hi):
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < b:
            parts[label.get(name, name)] += (min(b, idle[j][1]) -
                                             max(a, idle[j][0]))
            j += 1
    return parts


def idle_line(trace: dict) -> str:
    """The attribution as one line, with the share of the idle time inside
    ``hybrid_sort`` that falls under one of its named children."""
    parts = idle_by_span(trace)
    named = sum(parts[name] for name in CHILDREN)
    in_sort = named + parts[UNDER_NO_CHILD]
    share = 100.0 * named / in_sort if in_sort else 0.0
    body = " ".join(f"{k.replace(' ', '_')}={v / 1e6:.6f}ms"
                    for k, v in parts.items())
    return (f"idle by program span (information, not a metric): "
            f"total={sum(parts.values()) / 1e6:.6f}ms {body} "
            f"named_share_of_idle_in_hybrid_sort={share:.2f}%")


# --- device scopes ---------------------------------------------------------

def scope_map(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` (empty where the compiler
    made the instruction without one), from a compiled module's text."""
    out = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        op_name = _OP_NAME.search(m.group(2))
        out[m.group(1)] = op_name.group(1) if op_name else ""
    return out


def in_scope(op_name: str, scope: str) -> bool:
    return f"/{scope}/" in f"/{op_name}/"


def _lower_hybrid_sort(cell, devices):
    """The program ``hybrid_sort(keys[, values])`` with default arguments
    dispatches for the cell's records over the full key width, the live
    window that uniform and skewed 32-bit keys of the cells' sizes have."""
    import jax
    import jax.numpy as jnp

    from repro.core import bijection, hybrid, model
    from repro.core.ranks import resolve_engine, resolve_interpret

    key_dtype = jnp.dtype(cell.key_dtype)
    bits = bijection.key_bits(key_dtype)
    cfg = model.default_config(bits // 8)
    place = jax.sharding.SingleDeviceSharding(devices[0])
    keys = jax.ShapeDtypeStruct((cell.n,), bijection.carrier_dtype(key_dtype),
                                sharding=place)
    vals = (jax.ShapeDtypeStruct((cell.n,), jnp.dtype(cell.value_dtype),
                                 sharding=place)
            if cell.with_values else ())
    return hybrid._hybrid_sort_bits.lower(
        keys, vals, cfg, bits, False, None,
        resolve_engine(cfg.rank_engine), resolve_interpret(None), lo=0,
        adaptive=cfg.adaptive)


def program_text(cell) -> Optional[str]:
    """Compiled text of the cell's main program: the entry module's own
    ``lower(cell, devices)`` where it has one, else that of
    ``hybrid_sort``'s program."""
    import jax

    from bench import traffic

    devices = jax.devices()[:cell.chips]
    module = traffic.load_module("entries", cell.entry)
    lower = getattr(module, "lower", None)
    if lower is None:
        if cell.entry != "hybrid_sort":
            return None
        lower = _lower_hybrid_sort
    return lower(cell, devices).compile().as_text()


def _program_ops(trace: dict) -> List[list]:
    """The ops inside the executions of the program that holds the most
    device time (one per call)."""
    runs = reduce.call_spans(trace)
    starts = [s for _, s, _ in runs]
    out = []
    for op in trace["ops"]:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] + op[2] <= runs[i][1] + runs[i][2]:
            out.append(op)
    return out


def scope_ns(trace: dict, names: Dict[str, str]) -> Optional[Dict[str, float]]:
    """Device ns per call of each of ``SCOPES`` in the window, given each
    instruction's ``op_name``; ``None`` when ``names`` lacks an op that the
    window's executions of its program ran, or the program names none of
    the scopes."""
    try:
        ops = _program_ops(trace)
    except ValueError:          # no single program with one run per call
        return None
    if not ops or any(op[0] not in names for op in ops):
        return None
    lo, hi = reduce.window(trace)
    calls = len(trace["calls"])
    found = {s: reduce.covered([op for op in ops if in_scope(names[op[0]], s)],
                               lo, hi) / calls
             for s in SCOPES}
    return found if any(found.values()) else None


def scope_ns_per_call(run) -> Optional[Dict[str, float]]:
    """``scope_ns`` of the run's window, with the names of the cell's
    program compiled again; computed once a run and printed (information)."""
    key = id(run.trace)
    if key in _memo:
        return _memo[key]
    try:
        names = scope_map(program_text(run.cell) or "")
    except Exception as e:  # a program this reader cannot lower again
        print(f"stages: cannot compile the cell's program again: {e!r}",
              flush=True)
        names = {}
    found = scope_ns(run.trace, names)
    if found is None:
        print("stages: the window's program names no stage in its device "
              "ops", flush=True)
    else:
        print("stages: device ms per call (information): " + " ".join(
            f"{s}={ns / 1e6:.6f}" for s, ns in found.items()), flush=True)
    _memo.clear()
    _memo[key] = found
    return found


def scope_ms(run, scope: str) -> Optional[float]:
    if run.trace is None:
        return None
    found = scope_ns_per_call(run)
    if not found or not found[scope]:
        return None
    return found[scope] / 1e6


def prologue_ms(run) -> Optional[float]:
    """Host ms per call of ``hybrid_sort.prologue``; prints the window's
    idle attribution once."""
    if run.trace is None:
        return None
    ns = span_ns_per_call(run.trace, "hybrid_sort.prologue")
    if ns is None:
        return None
    print(idle_line(run.trace), flush=True)
    return ns / 1e6
