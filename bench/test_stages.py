"""The readers of the program's own stage names, against hand-computed
values and against a trace recorded on the chip.

    python -m pytest bench/
"""
from __future__ import annotations

import json
import os

import pytest

from bench import reduce, stages
from bench.test_reduce import TRACE

HERE = os.path.dirname(os.path.abspath(__file__))

# The two calls of ``bench/test_reduce.py``'s trace, with the program's host
# spans: call 1 [100, 600) runs hybrid_sort over [100, 590) with its
# prologue [100, 130) holding the host copy [105, 125), then dispatch
# [130, 210); call 2 [650, 1100) over [650, 1080): prologue [650, 790),
# copy [660, 760), dispatch [790, 870).  Device idle: [100, 120),
# [170, 180), [560, 700), [900, 1000), [1050, 1100): 320 ns.
SPANNED = dict(TRACE, host=TRACE["host"] + [
    ["hybrid_sort", 100, 490], ["hybrid_sort.prologue", 100, 30],
    ["hybrid_sort.live_bit_window", 105, 20], ["hybrid_sort.dispatch", 130, 80],
    ["hybrid_sort", 650, 430], ["hybrid_sort.prologue", 650, 140],
    ["hybrid_sort.live_bit_window", 660, 100],
    ["hybrid_sort.dispatch", 790, 80]])
NAMES = {"fusion.1": "jit(f)/hybrid_sort/ping_pong/pad",
         "while.3": "jit(f)/hybrid_sort/while",
         "fused_counting_pass.2":
             "jit(f)/hybrid_sort/while/body/counting_pass/pallas_call",
         "cond.5": "jit(f)/hybrid_sort/cond",
         "gather_fusion.6": "jit(f)/hybrid_sort/cond/branch_1_fun/local_sort/"
                            "rows/gather",
         "bitonic_sort_rows_stable.7": "jit(f)/hybrid_sort/cond/branch_1_fun/"
                                       "local_sort/bitonic/pallas_call",
         "copy.4": "jit(f)/hybrid_sort/unpad/copy"}


def test_span_time_per_call():
    assert stages.span_ns_per_call(SPANNED, "hybrid_sort.prologue") == 85
    assert stages.span_ns_per_call(SPANNED, "hybrid_sort.dispatch") == 80
    assert stages.span_ns_per_call(TRACE, "hybrid_sort.prologue") is None


def test_idle_divides_among_the_innermost_spans():
    parts = stages.idle_by_span(SPANNED)
    assert parts == {"hybrid_sort.prologue": 5 + 10,   # [100,105) [650,660)
                     "hybrid_sort.live_bit_window": 15 + 40,
                     "hybrid_sort.dispatch": 10,
                     stages.UNDER_NO_CHILD: 30 + 100 + 30,
                     stages.OUTSIDE_SORT: 10 + 20,
                     stages.BETWEEN_CALLS: 50}
    lo, hi = reduce.window(SPANNED)
    assert sum(parts.values()) == (hi - lo) - reduce.busy_ns(SPANNED)
    assert "named_share_of_idle_in_hybrid_sort=33.33%" in stages.idle_line(SPANNED)


def test_a_trace_without_the_program_spans_is_all_harness():
    parts = stages.idle_by_span(TRACE)
    assert parts[stages.OUTSIDE_SORT] == 270
    assert parts[stages.BETWEEN_CALLS] == 50
    assert sum(parts.values()) == 320


def test_scope_time_per_call():
    ns = stages.scope_ns(TRACE, NAMES)
    assert ns["ping_pong"] == 25 and ns["counting_pass"] == 150
    assert ns["local_sort/rows"] == 60 and ns["local_sort/bitonic"] == 40
    assert ns["unpad"] == 25 and ns["pass_bookkeeping"] == 0


def test_scope_time_needs_every_op_named():
    partial = {k: v for k, v in NAMES.items() if k != "copy.4"}
    assert stages.scope_ns(TRACE, partial) is None
    unnamed = {k: "jit(f)/while/add" for k in NAMES}
    assert stages.scope_ns(TRACE, unnamed) is None


def test_scope_map_reads_compiled_text():
    text = ('  %fusion.55 = u32[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(f)/hybrid_sort/local_sort/rows/gather" '
            'source_file="x.py" source_line=3}\n'
            '  ROOT %copy.2 = u32[8]{0} copy(%fusion.55)\n')
    assert stages.scope_map(text) == {
        "fusion.55": "jit(f)/hybrid_sort/local_sort/rows/gather",
        "copy.2": ""}
    assert stages.in_scope("a/local_sort/rows/gather", "local_sort/rows")
    assert not stages.in_scope("a/local_sort/rows_x/g", "local_sort/rows")


# --- a trace of the change recorded on a TPU v5e: the first three calls of
# a traced ``kv32_uniform.small`` window (seed 2147491001), reduced by
# ``reduce.reduce_xspace``, with ``op_names``: the op_name of every
# instruction the program ran there.  The map comes from the same program
# compiled for a described v5e, whose instruction names matched every op
# of that chip's traces.

def _fixture(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def chip():
    return _fixture("stages_trace.json")


def _run(trace, n=1 << 18):
    from bench import run, traffic
    cell = traffic.Cell("kv32_uniform.small", n=n, key_dtype="uint32",
                        value_dtype="uint32", keys="ands",
                        entry="hybrid_sort", pool=16, chips=1,
                        config={"ands": 0})
    return run.Run(cell=cell, calls=[(0.0, 1.0)], setup_s=1.0, base_bytes=0,
                   peak_bytes=0, trace=trace,
                   event_map=reduce.load_event_map(),
                   peak={"hbm_bytes_per_s": 819e9})


def _compiled_text(op_names):
    return "\n".join(f'  %{i} = u32[] fusion(), metadata={{op_name="{o}"}}'
                     for i, o in op_names.items())


def test_the_recorded_trace_holds_the_program_names(chip):
    spans = {e[0] for e in chip["host"] if e[0].startswith("hybrid_sort")}
    assert spans == {stages.CALL, *stages.CHILDREN}
    assert sum(e[0] == stages.CALL for e in chip["host"]) == 3
    program = {op[0] for op in stages._program_ops(chip)}
    assert program <= set(chip["op_names"])
    assert all(any(stages.in_scope(o, s) for o in chip["op_names"].values())
               for s in stages.SCOPES if s != "exchange")


@pytest.mark.parametrize("metric,want", [
    ("prologue_ms.small", 1.1034826666666668),
    ("bookkeeping_ms.bulk", 6.417878666666667),
    ("local_sort_rows_ms.bulk", 21.583025666666668),
    ("local_sort_copy_ms.bulk", 91.451863),
    ("local_sort_lanes.bulk", 11.522216796875),
])
def test_new_readers_on_the_recorded_trace(chip, monkeypatch, capsys,
                                           metric, want):
    from bench import traffic
    monkeypatch.setattr(stages, "program_text",
                        lambda cell: _compiled_text(chip["op_names"]))
    stages._memo.clear()
    got = traffic.load_module("metrics", metric).read(_run(chip))
    assert got == pytest.approx(want, rel=1e-12)
    printed = capsys.readouterr().out
    assert ("stages: device ms per call" in printed or
            "idle by program span" in printed or metric.endswith("lanes.bulk"))


def test_exchange_reads_nothing_in_a_one_chip_trace(chip, monkeypatch):
    from bench import traffic
    monkeypatch.setattr(stages, "program_text",
                        lambda cell: _compiled_text(chip["op_names"]))
    stages._memo.clear()
    assert traffic.load_module("metrics", "exchange_ms.dist4").read(
        _run(chip)) is None


def test_an_older_program_gives_no_new_metric(monkeypatch):
    """The parent's trace: no program span, and a program whose op_names
    hold no stage scope."""
    from bench import traffic
    old = _fixture("small_trace.json")
    names = {op[0]: "jit(_hybrid_sort_bits)/while/body/add"
             for op in old["ops"]}
    monkeypatch.setattr(stages, "program_text",
                        lambda cell: _compiled_text(names))
    stages._memo.clear()
    for metric in ("prologue_ms.small", "bookkeeping_ms.bulk",
                   "local_sort_rows_ms.bulk", "local_sort_copy_ms.bulk"):
        assert traffic.load_module("metrics", metric).read(_run(old)) is None
    assert traffic.load_module("metrics", "prologue_ms.small").read(
        _run(None)) is None


def test_stage_times_agree_with_the_layers_read_from_outside(chip):
    events = reduce.load_event_map()
    ns = stages.scope_ns(chip, chip["op_names"])
    local = reduce.per_call_ns(chip, "local sort", events)
    parts = [ns[f"local_sort/{p}"] for p in ("bounds", "rows", "bitonic",
                                             "copy_back")]
    assert ns["local_sort/rows"] + ns["local_sort/copy_back"] <= local
    assert sum(parts) >= 0.95 * local
    assert ns["pass_bookkeeping"] <= reduce.per_call_ns(chip, reduce.PLANNER,
                                                        events)
    assert ns["counting_pass"] == pytest.approx(
        reduce.per_call_ns(chip, "counting pass", events), rel=1e-3)
    assert ns["prologue_histogram"] == pytest.approx(
        reduce.per_call_ns(chip, "prologue histogram", events), rel=1e-3)


def test_recorded_idle_divides_among_the_spans(chip):
    parts = stages.idle_by_span(chip)
    assert parts == {"hybrid_sort.prologue": 226718.0,
                     "hybrid_sort.live_bit_window": 874757.0,
                     "hybrid_sort.dispatch": 0.0,
                     stages.UNDER_NO_CHILD: 5032.0,
                     stages.OUTSIDE_SORT: 6383600.0,
                     stages.BETWEEN_CALLS: 3048120.0}
    lo, hi = reduce.window(chip)
    assert sum(parts.values()) == pytest.approx(
        (hi - lo) - reduce.busy_ns(chip), abs=1)
    assert "named_share_of_idle_in_hybrid_sort=99.55%" in stages.idle_line(
        chip)


def test_old_readers_read_the_old_fixture_as_before():
    """The readers of ``bench/reduce.py`` give, on its recorded fixture,
    the numbers they gave before the program named its stages."""
    old = _fixture("small_trace.json")
    events = reduce.load_event_map()
    assert reduce.idle_share(old) == 2.2578258851904676
    assert reduce.busy_ns(old) == 388949306.0
    assert [reduce.per_call_ns(old, layer, events)
            for layer in ("counting pass", "local sort",
                          "prologue histogram", reduce.PLANNER)] == [
        4355683.666666667, 118387323.33333333, 360147.0, 6546614.666666667]
    assert reduce.host_lead_ns(old, events) == 1004596.6666666666
    assert reduce.count_per_call(old, "counting pass", events) == [1, 1, 1]
    assert reduce.top_ops(old, 3) == [["fusion.55", 0.03765574],
                                      ["fusion.45", 0.02729071],
                                      ["fusion.50", 0.02614627]]
    assert reduce.idle_gaps(old, 3) == [
        ["DeferredTpuAllocator::Allocate", 0.002224699],
        ["bench.call", 0.002007265], ["DoEnqueueProgram", 0.001791621]]
