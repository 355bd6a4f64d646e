"""Trace reduction and roofline arithmetic against hand-computed values.

    python -m pytest bench/
"""
from __future__ import annotations

import json
import os

import pytest

from bench import reduce, roofline, run, traffic

EVENTS = {
    "layers": {
        "counting pass": {"prefixes": ["fused_counting_pass"],
                          "whole_stage": False},
        "local sort": {"prefixes": ["bitonic_sort_rows_stable"],
                       "whole_stage": True},
    },
    "launch": ["PjitFunction"],
}

# Two calls: host spans [100, 600) and [650, 1100), a window of 1000 ns;
# their program runs on the device over [110, 590) and [660, 1080).  A loop
# encloses the counting pass and overlaps nothing else; a conditional
# encloses the local sort's gather and kernel.  The device is busy over
# [120, 170), [180, 560), [700, 900) and [1000, 1050): 680 ns.
TRACE = {
    "ops": [["fusion.1", 120, 50],
            ["while.3", 180, 380],
            ["fused_counting_pass.2", 200, 300],
            ["cond.5", 700, 200],
            ["gather_fusion.6", 700, 120],
            ["bitonic_sort_rows_stable.7", 820, 80],
            ["copy.4", 1000, 50]],
    "modules": [["jit_copy(1)", 90, 5],
                ["jit_sort(2)", 110, 480],
                ["jit_copy(1)", 640, 5],
                ["jit_sort(2)", 660, 420]],
    "calls": [[100, 500], [650, 450]],
    "host": [["np.asarray(jax.Array)", 105, 10],
             ["PjitFunction(_sort)", 130, 80],
             ["np.asarray(jax.Array)", 660, 100],
             ["PjitFunction(_sort)", 790, 80],
             ["wait", 910, 80]],
}




def _run(trace, n=1000, with_values=True):
    cell = traffic.Cell("t", n=n, key_dtype="uint32",
                        value_dtype="uint32" if with_values else None,
                        keys="ands", entry="hybrid_sort", pool=1, chips=1,
                        config={"ands": 0})
    return run.Run(cell=cell, calls=[(0.0, 1.0)], setup_s=1.0, base_bytes=0,
                   peak_bytes=0, trace=trace, event_map=EVENTS,
                   peak={"hbm_bytes_per_s": 819e9})


def test_idle_share_and_busy():
    assert reduce.window(TRACE) == (100, 1100)
    assert reduce.busy_ns(TRACE) == 680
    assert reduce.idle_share(TRACE) == pytest.approx(32.0)


def test_per_layer_sums_per_call():
    assert reduce.per_call_ns(TRACE, "counting pass", EVENTS) == 150
    # the whole conditional around the local sort's gather and kernel
    assert reduce.per_call_ns(TRACE, "local sort", EVENTS) == 100
    # busy 680 less the counting pass's 300 and the local sort's 200
    assert reduce.per_call_ns(TRACE, reduce.PLANNER, EVENTS) == 90


def test_counts_follow_the_program_executions():
    assert reduce.call_spans(TRACE) == [["jit_sort(2)", 110, 480],
                                        ["jit_sort(2)", 660, 420]]
    assert reduce.count_per_call(TRACE, "counting pass", EVENTS) == [1, 0]
    short = dict(TRACE, modules=TRACE["modules"][:2])
    with pytest.raises(ValueError):
        reduce.call_spans(short)


def test_host_lead():
    # launches at 130 and 790 for calls starting at 100 and 650
    assert reduce.host_lead_ns(TRACE, EVENTS) == pytest.approx(85.0)


def test_breakdown():
    # enclosing ops (the loop, the conditional) are left out
    assert reduce.top_ops(TRACE, k=2) == [["fused_counting_pass.2", 300e-9],
                                          ["gather_fusion.6", 120e-9]]
    gaps = reduce.idle_gaps(TRACE)
    # [560, 700) 140, [900, 1000) 100, [1050, 1100) 50, [100, 120) 20,
    # [170, 180) 10, each named by the host's doing at its middle
    assert [g[1] for g in gaps] == pytest.approx(
        [140e-9, 100e-9, 50e-9, 20e-9, 10e-9])
    assert [g[0] for g in gaps] == ["between calls", "wait", "bench.call",
                                    "np.asarray(jax.Array)",
                                    "PjitFunction(_sort)"]


def test_roofline_arithmetic():
    # one executed pass over 1000 8-byte records: 16,000 bytes in 300 ns
    assert roofline.counting_pass_bytes(1000, 4, 4, 1) == 16000
    want = 100.0 * (16000 / 819e9) / 300e-9
    assert roofline.fused_pass_share(_run(TRACE)) == pytest.approx(want)
    assert roofline.fused_pass_share(_run(TRACE, with_values=False)) == \
        pytest.approx(want / 2)


def test_reader_returns_nothing_without_kernels():
    trace = dict(TRACE, ops=[op for op in TRACE["ops"]
                             if not op[0].startswith(("fused", "bitonic"))])
    assert roofline.fused_pass_share(_run(trace)) is None
    assert run.load_reader("local_sort_ms.bulk")(_run(trace)) is None


def test_metric_readers_on_the_trace():
    r = _run(TRACE)
    assert run.load_reader("device_idle.bulk")(r) == pytest.approx(32.0)
    assert run.load_reader("host_lead_ms.small")(r) == pytest.approx(85e-6)
    assert run.load_reader("xla_ops_ms.bulk")(r) == pytest.approx(90e-6)
    assert run.load_reader("local_sort_ms.bulk")(r) == pytest.approx(100e-6)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        run.load_peak("TPU v99 imaginary")
    assert run.load_peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_every_metric_has_a_reader():
    benchmark = run.load_benchmark()
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        assert callable(run.load_reader(m["name"]))


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "small_trace.json")


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three calls of the small cell."""
    with open(FIXTURE) as f:
        trace = json.load(f)
    events = reduce.load_event_map()
    assert len(trace["calls"]) == 3
    assert 0.0 < reduce.idle_share(trace) < 100.0
    assert reduce.count_per_call(trace, "counting pass", events) == [1, 1, 1]
    for layer in ("counting pass", "local sort", reduce.PLANNER):
        assert reduce.per_call_ns(trace, layer, events) > 0
    assert reduce.host_lead_ns(trace, events) > 0


def test_reference_canonical_form_for_any_record_types():
    """Packed 32-bit records and the general path give the same order."""
    import numpy as np

    from bench import reference

    rng = np.random.default_rng(5)
    keys = rng.integers(0, 8, 1000, dtype=np.uint32)
    vals = rng.permutation(1000).astype(np.uint32)
    packed = reference.canonical(keys, vals)
    general = reference.canonical(keys.astype(np.uint64),
                                  vals.astype(np.int64))
    assert (packed[0] == general[0]).all() and (packed[1] == general[1]).all()
    assert reference.compare(general, *general) == {"keys_wrong": 0,
                                                    "pairs_wrong": 0}
    swapped = general[1].copy()
    swapped[[0, -1]] = swapped[[-1, 0]]
    got = reference.compare(general, general[0], swapped)
    assert got["keys_wrong"] == 0 and got["pairs_wrong"] > 0
