"""The sort's names for a profiler: device scopes in the program's op_name
metadata, host spans with the plan's counters, and the lane counter.

The compiled-for-v5e checks (kernel names and scopes of the Mosaic path)
live in ``tests/test_tpu_compile.py`` beside its described chip; these run
on the CPU, the kernel engine in interpret mode.
"""
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import SortConfig, hybrid, hybrid_sort, model
from repro.core.distributed import make_distributed_sort
from repro.kernels import ops

# passes, bucket merges and every local-sort class fire at this size
CFG = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)
N = 3000

KERNEL_SCOPES = ["hybrid_sort/ping_pong", "hybrid_sort/prologue_histogram",
                 "pass_bookkeeping", "counting_pass", "local_sort/bounds",
                 "local_sort/rows", "local_sort/bitonic",
                 "local_sort/copy_back", "hybrid_sort/unpad"]
JNP_SCOPES = ["pass_bookkeeping", "counting_pass", "local_sort"]
SPANS = ["hybrid_sort", "hybrid_sort.prologue", "hybrid_sort.live_bit_window",
         "hybrid_sort.dispatch"]

_texts = {}


def _program_text(engine):
    """The sort program's lowered text with its debug locations, which
    carry each op's op_name."""
    if engine not in _texts:
        keys = jax.ShapeDtypeStruct((N,), jnp.uint32)
        lowered = hybrid._hybrid_sort_bits.lower(
            keys, keys, CFG, 32, False, None, engine, True, lo=0,
            adaptive=True)
        _texts[engine] = lowered.as_text(debug_info=True)
    return _texts[engine]


@pytest.mark.parametrize("engine,scope",
                         [("kernel", s) for s in KERNEL_SCOPES] +
                         [("argsort", s) for s in JNP_SCOPES])
def test_scope_in_program(engine, scope):
    assert "/hybrid_sort/" in _program_text(engine)
    assert f"/{scope}/" in _program_text(engine)


def test_exchange_scope_in_distributed_program():
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = jax.jit(make_distributed_sort(mesh, "data", cfg=CFG,
                                       engine="argsort"))
    text = fn.lower(jax.ShapeDtypeStruct((1024,), jnp.uint32)).as_text(
        debug_info=True)
    assert "/exchange/" in text


def _profile(tmp_path, keys, **kw):
    jax.block_until_ready(hybrid_sort(keys, keys, **kw))   # compile outside
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(hybrid_sort(keys, keys, **kw))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return {e.name: (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name in SPANS}


def test_host_spans_nest_and_carry_the_plan(tmp_path):
    keys = jnp.asarray(np.random.default_rng(3).integers(
        0, 2**20, N, dtype=np.uint32) << 4)        # live bits [4, 24)
    spans = _profile(tmp_path, keys, cfg=CFG, engine="kernel")
    assert sorted(spans) == sorted(SPANS)
    (c0, c1, args), (p0, p1, _) = spans["hybrid_sort"], \
        spans["hybrid_sort.prologue"]
    w0, w1, _ = spans["hybrid_sort.live_bit_window"]
    d0, d1, _ = spans["hybrid_sort.dispatch"]
    assert c0 <= p0 <= w0 <= w1 <= p1 <= d0 <= d1 <= c1
    assert {k: int(v) if k != "engine" else v for k, v in args.items()
            if not k.startswith("_")} == {
        "n": N, "key_bits": 32, "engine": "kernel", "lo": 4, "hi": 24,
        "planned_passes": 3,
        "local_sort_lanes": hybrid.local_sort_lanes(N, CFG)}


def test_jnp_engine_span_has_no_lane_counter(tmp_path):
    keys = jnp.arange(N, dtype=jnp.uint32)[::-1]
    args = _profile(tmp_path, keys, cfg=CFG, engine="argsort")[
        "hybrid_sort"][2]
    assert args["engine"] == "argsort" and "local_sort_lanes" not in args


def test_counters_are_not_computed_with_the_profiler_off():
    keys = jnp.arange(N, dtype=jnp.uint32)
    hybrid.local_sort_lanes.cache_clear()
    jax.block_until_ready(hybrid_sort(keys, cfg=CFG, engine="kernel"))
    assert hybrid.local_sort_lanes.cache_info().currsize == 0


@pytest.mark.parametrize("n", [100, N, 1 << 16, 1 << 24])
def test_local_sort_lanes_sums_the_class_tables(n):
    cfg = model.default_config(4) if n > N else CFG
    classes = hybrid.local_sort_classes(n, cfg)
    assert hybrid.local_sort_lanes(n, cfg) == sum(l * rows
                                                  for l, rows in classes)


# --- the local sort's tile loop ---------------------------------------------

_compiled = {}


def _compiled_names():
    """Instruction -> op_name of the compiled kernel-engine program, read
    the way the benchmark reads a chip's program."""
    from bench import stages
    if not _compiled:
        keys = jax.ShapeDtypeStruct((N,), jnp.uint32)
        text = hybrid._hybrid_sort_bits.lower(
            keys, keys, CFG, 32, False, None, "kernel", True, lo=0,
            adaptive=True).compile().as_text()
        _compiled.update(stages.scope_map(text))
    return _compiled


@pytest.mark.parametrize("scope", ["local_sort/rows", "local_sort/bitonic",
                                   "local_sort/copy_back"])
def test_tile_loop_ops_keep_their_stage_names(scope):
    """Inside a class's tile loop the ops still sit under their stage, so
    the benchmark's scope readers find them."""
    from bench import stages
    hits = [o for o in _compiled_names().values() if stages.in_scope(o, scope)]
    assert any("/while/body/local_sort/" in o for o in hits)


def _final_buckets(x, cfg):
    """(seg_id, done) after the argsort engine's counting passes, on the
    schedule ``hybrid_sort`` plans for the concrete uint32 keys ``x``."""
    lo, hi = hybrid.live_bit_window(x)
    loop = jax.jit(functools.partial(
        hybrid._pass_loop_jnp, k=hi, lo=lo,
        nd=hybrid._planned_passes(hi, lo, cfg.d, None), cfg=cfg,
        engine="argsort", adaptive=True))
    _, _, seg, done, _, _ = loop(jnp.asarray(x), ())
    return np.asarray(seg), np.asarray(done)


def _numpy_tiles(seg, done, cfg):
    """Σ over size classes of ⌈occupied rows / tile rows⌉, from the final
    segments: a class of width L holds the done segments of size in
    (previous L, L] and runs tiles of ``T // L`` rows, at most its
    capacity."""
    n = seg.size
    starts = np.flatnonzero(np.r_[True, seg[1:] != seg[:-1]])
    sizes = np.diff(np.r_[starts, n])
    sortable = done[starts]
    classes = hybrid.local_sort_classes(n, cfg)
    tile = ops.local_sort_tile_lanes(n, classes[-1][0])
    tiles, prev = 0, -1
    for l, rows in classes:
        occupied = int(np.sum(sortable & (sizes > prev) & (sizes <= l)))
        tile_rows = min(rows, max(1, tile // l))
        tiles += -(-min(occupied, rows) // tile_rows)
        prev = l
    return tiles


@pytest.mark.parametrize("keys", ["uniform", "ands3", "constant"])
def test_local_sort_tiles_counts_the_occupied_rows(keys):
    """Every engine reports the tiles the kernel engine's local sort runs,
    and sorts the same; constant keys leave no done bucket, so no tile, and
    ands3 keys leave equal-key buckets that the local sort skips."""
    n = 20000
    rng = np.random.default_rng(15)
    draw = lambda: rng.integers(0, 2**32, n, dtype=np.uint32)
    x = {"uniform": draw, "ands3": lambda: draw() & draw() & draw() & draw(),
         "constant": lambda: np.full(n, 0xC0FFEE, np.uint32)}[keys]()
    seg, done = _final_buckets(x, CFG)
    want = _numpy_tiles(seg, done, CFG)
    assert (want == 0) == (keys == "constant")
    for engine in ("kernel", "argsort"):
        out, stats = hybrid_sort(jnp.asarray(x), cfg=CFG, engine=engine,
                                 return_stats=True)
        assert np.array_equal(np.asarray(out), np.sort(x)), engine
        assert int(stats.local_sort_tiles) == want, engine
