"""From a profiler trace to the numbers the per-layer metrics read.

``reduce_xspace`` keeps four lists from the ``.xplane.pb`` that
``jax.profiler.trace`` writes, all ``[name, start, duration]`` in
nanoseconds:

* ``ops``: every operation on the first TPU's ``XLA Ops`` line, named by its
  HLO instruction (``fused_counting_pass.2``; a Pallas kernel carries the
  name of its ``pallas_call``).  A ``while`` or a ``conditional`` encloses
  the ops it runs, so ops nest;
* ``modules``: every program execution on that TPU's ``XLA Modules`` line;
* ``calls``: the benchmark's own ``CALL_SPAN`` annotations on the host, one
  per timed call (name left out: ``[start, duration]``);
* ``host``: the other host events, of every thread.

Device and host events come from two clocks that the profiler aligns only
to within about a millisecond: on a TPU v5e a program was seen to start on
the device half a millisecond before the host launched it.  So nothing here
compares a device time with a host time closer than that.  Per-call device
numbers follow the program's own executions (``call_spans``), and the host
lead is measured on the host alone.

The reduced trace is plain JSON, so a recorded one is kept as a test
fixture.  Which layer an op belongs to is data, ``bench/events.json``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
CALL_SPAN = "bench.call"
PLANNER = "pass planner"


def load_event_map() -> dict:
    with open(os.path.join(HERE, "events.json")) as f:
        return json.load(f)


def short_name(hlo_text: str) -> str:
    """``%fusion.55 = u32[...] fusion(...)`` -> ``fusion.55``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def reduce_xspace(path: str) -> dict:
    """The reduced trace of one ``.xplane.pb`` file (see the module doc)."""
    import jax  # the profiler's reader ships with jax

    data = jax.profiler.ProfileData.from_file(path)
    devices = sorted((p for p in data.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    out = {"ops": [], "modules": [], "calls": [], "host": []}
    for line in devices[0].lines:
        key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
        if key:
            out[key] = sorted([short_name(e.name), e.start_ns, e.duration_ns]
                              for e in line.events)
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == CALL_SPAN:
                    out["calls"].append([e.start_ns, e.duration_ns])
                else:
                    out["host"].append([e.name, e.start_ns, e.duration_ns])
    out["calls"].sort()
    out["host"].sort(key=lambda e: e[1])
    return out


def find_xspace(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"{log_dir}: expected one .xplane.pb, found "
                         f"{len(found)}")
    return found[0]


# --- intervals -----------------------------------------------------------

def window(trace: dict) -> Tuple[float, float]:
    """First call's start to last call's end (host clock)."""
    calls = trace["calls"]
    if not calls:
        raise ValueError("the trace holds no timed call")
    return calls[0][0], max(s + d for s, d in calls)


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``[start, start + dur)`` clipped to [lo, hi)."""
    total, reach = 0.0, lo
    for start, dur in sorted((i[-2], i[-1]) for i in intervals):
        a, b = max(start, reach), min(start + dur, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def top_level(ops: List[list]) -> List[list]:
    """Ops that no other op encloses (ops sorted by start)."""
    out, reach = [], float("-inf")
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        if op[1] >= reach:
            out.append(op)
            reach = op[1] + op[2]
    return out


def leaves(ops: List[list]) -> List[list]:
    """Ops that enclose no other op."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (name, s, d) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= s + d or d == 0:
            out.append([name, s, d])
    return out


def in_layer(name: str, prefixes) -> bool:
    return any(name.startswith(p) for p in prefixes)


def kernel_ops(trace: dict, layer: str, event_map: dict) -> List[list]:
    prefixes = event_map["layers"][layer]["prefixes"]
    return [op for op in trace["ops"] if in_layer(op[0], prefixes)]


def layer_ops(trace: dict, layer: str, event_map: dict) -> List[list]:
    """The intervals whose union is the layer's device time."""
    kernels = kernel_ops(trace, layer, event_map)
    if not event_map["layers"][layer]["whole_stage"] or not kernels:
        return kernels
    tops = top_level(trace["ops"])
    starts = [t[1] for t in tops]
    stages = {}
    for _, s, d in kernels:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and tops[i][1] + tops[i][2] >= s + d:
            stages[i] = tops[i]
    return list(stages.values())


def busy_ns(trace: dict) -> float:
    lo, hi = window(trace)
    return covered(trace["ops"], lo, hi)


def idle_share(trace: dict) -> float:
    """Per cent of the window in which no operation ran on the device."""
    lo, hi = window(trace)
    return 100.0 * (1.0 - busy_ns(trace) / (hi - lo))


def per_call_ns(trace: dict, layer: str, event_map: dict) -> float:
    """Device time of a layer in the window, per call.  The pass planner's
    is the busy time that no other layer covers."""
    lo, hi = window(trace)
    if layer == PLANNER:
        claimed = [iv for name in event_map["layers"]
                   for iv in layer_ops(trace, name, event_map)]
        ns = busy_ns(trace) - covered(claimed, lo, hi)
    else:
        ns = covered(layer_ops(trace, layer, event_map), lo, hi)
    return ns / len(trace["calls"])


def call_spans(trace: dict) -> List[list]:
    """The device span of each timed call: the executions of the program
    that holds the most device time, which must be one per call."""
    time_of: Dict[str, float] = collections.Counter()
    for name, _, d in trace["modules"]:
        time_of[name] += d
    if not time_of:
        raise ValueError("the trace holds no program execution")
    program = max(time_of, key=time_of.get)
    runs = [m for m in trace["modules"] if m[0] == program]
    if len(runs) != len(trace["calls"]):
        raise ValueError(f"{len(runs)} executions of {program} for "
                         f"{len(trace['calls'])} timed calls")
    return runs


def count_per_call(trace: dict, layer: str, event_map: dict) -> List[int]:
    """How many of a layer's kernels each call's program execution ran."""
    starts = sorted(s for _, s, _ in kernel_ops(trace, layer, event_map))
    return [bisect.bisect_left(starts, s + d) - bisect.bisect_left(starts, s)
            for _, s, d in call_spans(trace)]


def host_lead_ns(trace: dict, event_map: dict) -> Optional[float]:
    """Mean host time from a call's start to its first program launch."""
    launches = sorted(s for name, s, _ in trace["host"]
                      if in_layer(name, event_map["launch"]))
    leads = []
    for c0, cd in trace["calls"]:
        i = bisect.bisect_left(launches, c0)
        if i < len(launches) and launches[i] < c0 + cd:
            leads.append(launches[i] - c0)
    return sum(leads) / len(leads) if leads else None


def top_ops(trace: dict, k: int = 10) -> List[list]:
    """The ``k`` ops with the most device time in the window, [name, s].
    Enclosing ops are left out, so no time counts twice."""
    lo, hi = window(trace)
    tot: Dict[str, float] = collections.Counter()
    for name, s, d in leaves(trace["ops"]):
        if s < hi and s + d > lo:
            tot[name] += min(s + d, hi) - max(s, lo)
    return [[name, ns / 1e9] for name, ns in tot.most_common(k)]


def idle_gaps(trace: dict, k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps of the device in the window, each as
    [what the host was doing, s]: the innermost host event at the gap's
    middle, else the benchmark's call span or, between calls, ``between
    calls``."""
    lo, hi = window(trace)
    gaps, reach = [], lo
    for _, s, d in sorted(trace["ops"], key=lambda o: o[1]):
        if s > reach:
            gaps.append((reach, min(s, hi)))
        reach = max(reach, s + d)
        if reach >= hi:
            break
    if reach < hi:
        gaps.append((reach, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:k]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [e for e in trace["host"] if e[1] <= mid < e[1] + e[2]]
        if inside:
            label = min(inside, key=lambda e: e[2])[0]
        elif any(s <= mid < s + d for s, d in trace["calls"]):
            label = CALL_SPAN
        else:
            label = "between calls"
        out.append([re.sub(r"\s+", " ", label), (b - a) / 1e9])
    return out
