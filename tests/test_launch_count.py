"""Launch-census regression tests: the fused engine's structural guarantee.

The point of ``kernels.fused`` is that one counting pass is ONE Pallas
launch (§4.3–§4.4: partition + scatter + next-pass histogram fused), so the
whole hybrid sort traces to a fixed set of launch sites — the prologue
histogram, the per-pass fused launch inside the while loop, and one bitonic
local-sort launch per size class (``core.hybrid.local_sort_classes``), each
inside that class's tile loop — independent of the data and the executed
pass count.  Batched grid steps (``plan.pack_region_blocks``) shrink the
fused launch's *grid* from g_max to ⌈g_max/B⌉ but must not change the
launch count: each while body stays exactly one ``pallas_call``.  ``utils.hlo`` counts ``pallas_call`` sites in
the jaxpr and reads their grids (interpret mode has no custom-call in the
lowered HLO; on hardware ``pallas_custom_call_count`` covers the text).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts as an
from repro.core import SortConfig, hybrid_sort, lsd_sort, model, plan
from repro.core.hybrid import local_sort_classes
from repro.core.outofcore import _sort_chunk, merge_round
from repro.core.segmented import counting_partition
from repro.kernels import merge as kmerge
from repro.kernels.fused import pad_length
from repro.utils import hlo

TCFG = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)


def _hybrid_launches(n, cfg):
    """Prologue + fused pass + one bitonic launch per local-sort class —
    read from the registered contract's symbolic formula, so the test and
    the analyzer verify the SAME declaration."""
    return an.expected_census("hybrid_sort", an.hybrid_params(n, cfg))["total"]


def _hybrid_loops(n, cfg):
    """The pass loop's one launch, then one launch in each local-sort
    class's tile loop."""
    return [1] * (1 + len(local_sort_classes(n, cfg)))


def test_hybrid_fused_engine_one_launch_per_pass():
    """THE acceptance gate: the counting-pass loop body contains exactly one
    pallas_call, and the whole trace exactly prologue + pass + the static
    local-sort classes, for any input size."""
    for n in (257, 4096, 20000):
        jx = jax.make_jaxpr(
            lambda a: hybrid_sort(a, cfg=TCFG, engine="kernel"))(
                jnp.zeros(n, jnp.uint32))
        assert hlo.while_body_pallas_launches(jx) == _hybrid_loops(n, TCFG), n
        assert hlo.pallas_launch_count(jx) == _hybrid_launches(n, TCFG), n


def test_hybrid_fused_launches_with_values_and_stats():
    x = jnp.zeros(2048, jnp.uint32)
    v = {"a": jnp.zeros(2048, jnp.int32), "b": jnp.zeros(2048, jnp.float32)}
    jx = jax.make_jaxpr(lambda a, b: hybrid_sort(
        a, b, cfg=TCFG, engine="kernel", return_stats=True))(x, v)
    assert hlo.while_body_pallas_launches(jx) == _hybrid_loops(2048, TCFG)
    assert hlo.pallas_launch_count(jx) == _hybrid_launches(2048, TCFG)


def test_hybrid_batched_grid_steps_shrink_the_grid():
    """Packing B descriptor rows per super-step divides the fused launch's
    grid by B (⌈g_max/B⌉) while the launch census is unchanged — the
    batched-step contract of plan.pack_region_blocks."""
    n = 4096
    x = jnp.zeros(n, jnp.uint32)
    for b in (1, 4, 16):
        cfg = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32,
                         step_batch=b)
        a_max = model.max_active_buckets(n, cfg)
        g_max = plan.max_region_blocks(n, cfg.kpb, a_max)
        jx = jax.make_jaxpr(
            lambda a: hybrid_sort(a, cfg=cfg, engine="kernel"))(x)
        assert hlo.while_body_pallas_launches(jx) == _hybrid_loops(n, cfg), b
        assert hlo.pallas_launch_count(jx) == _hybrid_launches(n, cfg), b
        grids = hlo.pallas_grid_sizes(jx)
        # trace order: prologue histogram, fused pass (while body), classes
        assert grids[1] == (-(-g_max // b),), (b, grids)
        assert len(grids) == _hybrid_launches(n, cfg), b


def test_lsd_fused_engine_launch_count():
    """LSD unrolls: ⌈k/d⌉ fused launches + the single prologue histogram,
    each pass on the batched ⌈g_max/B⌉ grid."""
    x = jnp.zeros(2048, jnp.uint32)
    for d in (8, 5):
        jx = jax.make_jaxpr(
            lambda a: lsd_sort(a, d=d, engine="kernel", kpb=512,
                               step_batch=4))(x)
        want = an.expected_census("lsd_sort", an.lsd_params(2048, d, 512, 4))
        assert hlo.pallas_launch_count(jx) == want["total"], d
        g_max = plan.max_region_blocks(2048, 512, 1)
        assert all(g == (-(-g_max // 4),)
                   for g in hlo.pallas_grid_sizes(jx)[1:]), d


def test_counting_partition_fused_launch_count():
    """One standalone partition = prologue histogram + one fused launch."""
    ids = jnp.zeros(1000, jnp.int32)
    jx = jax.make_jaxpr(
        lambda i: counting_partition(i, 8, engine="kernel"))(ids)
    want = an.expected_census("single_pass_partition", an.spp_params(1000, 8))
    assert hlo.pallas_launch_count(jx) == want["total"]


def test_jnp_engines_launch_free():
    x = jnp.zeros(4096, jnp.uint32)
    for eng in ("argsort", "scan"):
        jx = jax.make_jaxpr(lambda a: hybrid_sort(a, cfg=TCFG, engine=eng))(x)
        assert hlo.pallas_launch_count(jx) == 0, eng


def test_ooc_merge_one_launch_per_round():
    """§5 census: EVERY merge round of an out-of-core sort — whatever the
    run-length mix, group width, or leftover single-run group — is exactly
    one pallas_call, so a full merge phase is ⌈log_K(runs)⌉ launches."""
    tile = 64
    lens = [256] * 8                       # 8 runs, kway=4 -> rounds of 2, 1
    kway = 4
    rounds = 0
    while len(lens) > 1:
        n = sum(lens)
        ck = jnp.zeros((pad_length(n, tile),), jnp.uint32)
        jx = jax.make_jaxpr(
            lambda a, b: merge_round(a, (), b, (), lens=tuple(lens),
                                     kway=kway, tile=tile, n=n,
                                     interpret=True))(ck, jnp.zeros_like(ck))
        census = hlo.launch_census(jx)
        assert census["total"] == 1, lens
        assert not any(census["while_bodies"]), lens
        lens = [sum(g) for g in kmerge.merge_groups(lens, kway)]
        rounds += 1
    assert rounds == kmerge.num_merge_rounds(8, kway) == 2


def test_ooc_chunk_sort_keeps_one_launch_per_pass():
    """The PR 2 invariant under the new driver: an oocsort chunk sort on the
    kernel engine still traces to one launch inside the pass loop, prologue
    + fused pass + the local-sort classes in total."""
    total = _hybrid_launches(256, TCFG)
    jx = jax.make_jaxpr(
        lambda a: _sort_chunk(a, (), TCFG, "kernel", True))(
            jnp.zeros(256, jnp.uint32))
    loops = _hybrid_loops(256, TCFG)
    assert hlo.while_body_pallas_launches(jx) == loops
    assert hlo.pallas_launch_count(jx) == total
    assert hlo.launch_census(jx) == {"total": total, "while_bodies": loops}


def test_spill_slab_sweep_single_launch_and_sort_free():
    """§5 spill census: one group-slab sweep — the device-side pad of the
    exact strip upload plus the merge-kernel launch — is exactly ONE
    pallas_call with no launches hiding in while bodies, and traces to zero
    (stable)HLO sort ops."""
    slab, tile, kway = 64, 16, 4
    buf = pad_length(slab, tile)
    G = slab // tile
    sentinel = ~jnp.zeros((), jnp.uint32)

    def sweep(up_k, alt_k, off, cnt, ws, wt):
        slab_k = jnp.concatenate(
            [up_k, jnp.full((buf - up_k.shape[0],), sentinel, jnp.uint32)])
        return kmerge.kway_merge_round(slab_k, (), alt_k, (), off, cnt, ws,
                                       wt, kway=kway, tpb=tile, n=slab,
                                       interpret=True)

    args = (jnp.zeros((48,), jnp.uint32), jnp.full((buf,), sentinel),
            jnp.zeros((G,), jnp.int32), jnp.zeros((G,), jnp.int32),
            jnp.full((G * kway,), slab, jnp.int32),
            jnp.zeros((G * kway,), jnp.int32))
    jx = jax.make_jaxpr(sweep)(*args)
    census = hlo.launch_census(jx)
    assert census["total"] == 1
    assert not any(census["while_bodies"])
    assert hlo.sort_op_count(jax.jit(sweep).lower(*args).as_text()) == 0


def test_spill_strip_tables_drive_single_launch(rng):
    """End-to-end slab sweep on real spill_group_plan tables: one launch,
    and the streamed strip output equals the whole-group reference."""
    runs = [np.sort(rng.integers(0, 50, l).astype(np.uint32))
            for l in (100, 37, 23)]
    tile, slab, kway = 16, 32, 4
    buf = pad_length(slab, tile)
    sent = np.uint32(0xFFFFFFFF)
    out = np.empty(sum(len(r) for r in runs), np.uint32)
    sweep = lambda a, b, *t: kmerge.kway_merge_round(
        a, (), b, (), *t, kway=kway, tpb=tile, n=slab, interpret=True)
    for i, strip in enumerate(kmerge.spill_group_plan(runs, kway, tile,
                                                      slab)):
        wins = [runs[r][strip.win_lo[r]:strip.win_lo[r] + strip.win_len[r]]
                for r in range(len(runs))]
        up = np.concatenate(wins + [np.full(buf - strip.out_len, sent)])
        args = (jnp.asarray(up), jnp.full((buf,), sent, jnp.uint32),
                *(jnp.asarray(t) for t in strip.tables))
        if i == 0:                        # census the real-tables sweep too
            census = hlo.launch_census(jax.make_jaxpr(sweep)(*args))
            assert census == {"total": 1, "while_bodies": []}
        ok, _ = sweep(*args)
        out[strip.out_lo:strip.out_lo + strip.out_len] = \
            np.asarray(ok[:strip.out_len])
    flat = np.concatenate(runs)
    rid = np.concatenate([[i] * len(r) for i, r in enumerate(runs)])
    ref = flat[np.lexsort((np.arange(len(flat)), rid, flat))]
    assert np.array_equal(out, ref)


def test_searchsorted_rank_byte_identical_to_counting_rank(rng):
    """The parity gate for the kernel's tile-local merge rework: the
    per-run-pair searchsorted co-rank kernel is byte-identical to the
    (K·T)² counting-rank kernel it replaces, keys and values, including
    sentinel-valued keys and heavy duplicates."""
    lens = (200, 64, 1, 129)
    n = sum(lens)
    tile = 32
    runs = [np.sort(np.where(rng.random(l) < 0.2, 0xFFFFFFFF,
                             rng.integers(0, 12, l)).astype(np.uint32))
            for l in lens]
    flat = np.concatenate(runs)
    n_pad = pad_length(n, tile)
    ck = jnp.asarray(np.concatenate(
        [flat, np.full(n_pad - n, 0xFFFFFFFF, np.uint32)]))
    vals = np.arange(n, dtype=np.int32)
    cv = (jnp.asarray(np.concatenate([vals, np.zeros(n_pad - n, np.int32)])),)
    tables = kmerge.merge_path_partition(ck, lens, 4, tile)
    outs = {}
    for rank in ("searchsorted", "counting"):
        ok, ov = kmerge.kway_merge_round(
            ck, cv, jnp.full_like(ck, 0xFFFFFFFF),
            (jnp.zeros_like(cv[0]),), *tables, kway=4, tpb=tile, n=n,
            interpret=True, rank=rank)
        outs[rank] = (np.asarray(ok).tobytes(), np.asarray(ov[0]).tobytes())
    assert outs["searchsorted"] == outs["counting"]
    with pytest.raises(ValueError, match="rank"):
        kmerge.kway_merge_round(ck, cv, ck, cv, *tables, kway=4, tpb=tile,
                                n=n, interpret=True, rank="bogus")


def _abstract_mesh(n, name):
    """AbstractMesh lets shard_map bodies trace/lower in-process with no
    fake devices, so the exchange census runs in the fast tier."""
    try:
        return jax.sharding.AbstractMesh((n,), (name,))
    except TypeError:                       # older ctor: ((name, size),)
        return jax.sharding.AbstractMesh(((name, n),))


def _dist_launches(n_local, num_chunks, max_attempts, cfg):
    """Launch-site formula for the distributed exchange body:

    per chunk one full hybrid sort (prologue + fused pass + local-sort
    classes), per ATTEMPT SITE per chunk one shard-bucketing counting pass
    (2 sites: prologue + fused), plus the single 2-bucket validity
    compaction pass.  Retry sites are lax.cond-guarded, so sites scale with
    ``max_attempts`` while *executed* launches scale with the attempts
    ledger — same executed-vs-nominal idiom as the adaptive pass elision.
    The formula lives in ``core.distributed.ANALYSIS_CONTRACT``; this reads
    it through the registry so test and analyzer cannot drift.
    """
    params = an.dist_params(8, n_local, num_chunks, max_attempts, cfg)
    return an.expected_census("distributed_shard", params)["total"]


def test_distributed_shard_body_launch_census():
    """ONE pallas_call per counting pass inside the shard_map body — for
    the local chunk sorts (a pass loop and a tile loop per class, one
    launch each), every
    cond-guarded exchange attempt's bucketing pass, and the compaction
    pass — at every (chunks, attempts) shape, keys-only and KV."""
    from repro.core.distributed import make_distributed_sort

    mesh = _abstract_mesh(8, "data")
    n_local = 512
    x = jnp.zeros(8 * n_local, jnp.uint32)
    for num_chunks, max_attempts in ((1, 1), (1, 3), (2, 3)):
        fn = make_distributed_sort(mesh, "data", cfg=TCFG, engine="kernel",
                                   num_chunks=num_chunks,
                                   max_attempts=max_attempts)
        census = hlo.launch_census(jax.make_jaxpr(fn)(x))
        expected = _dist_launches(n_local, num_chunks, max_attempts, TCFG)
        assert census["total"] == expected, (num_chunks, max_attempts)
        assert census["while_bodies"] == num_chunks * _hybrid_loops(
            n_local // num_chunks, TCFG), num_chunks
    # KV payloads ride as one int32 rank per key: census unchanged
    fn = make_distributed_sort(mesh, "data", cfg=TCFG, engine="kernel",
                               num_chunks=2, max_attempts=3)
    census = hlo.launch_census(
        jax.make_jaxpr(lambda k, v: fn(k, v))(x, jnp.zeros_like(x)))
    assert census["total"] == _dist_launches(n_local, 2, 3, TCFG)
    assert census["while_bodies"] == 2 * _hybrid_loops(n_local // 2, TCFG)


def test_distributed_retry_replay_conserves_per_pass_launches():
    """Raising max_attempts adds exactly 2 * num_chunks sites per extra
    cond-guarded attempt (prologue + fused bucketing per chunk) and
    nothing else — the replay re-uses the SAME counting-pass primitive,
    no hidden launches or re-sorts."""
    from repro.core.distributed import make_distributed_sort

    mesh = _abstract_mesh(8, "data")
    n_local, num_chunks = 512, 2
    x = jnp.zeros(8 * n_local, jnp.uint32)
    totals = []
    for max_attempts in (1, 2, 3):
        fn = make_distributed_sort(mesh, "data", cfg=TCFG, engine="kernel",
                                   num_chunks=num_chunks,
                                   max_attempts=max_attempts)
        census = hlo.launch_census(jax.make_jaxpr(fn)(x))
        assert census["while_bodies"] == num_chunks * _hybrid_loops(
            n_local // num_chunks, TCFG), max_attempts
        totals.append(census["total"])
    assert np.diff(totals).tolist() == [2 * num_chunks] * 2


def test_distributed_kernel_engine_sort_free():
    """Zero (stable)HLO sort ops in the whole lowered exchange under
    engine="kernel": local sorts are hybrid, splitter selection merges
    sorted samples (all_gather keeps rows intact), bucketing is the fused
    counting pass, the finish is searchsorted-based multiway merge +
    compaction.  The argsort engine keeps its sorts — the gate measures
    the kernel path, not the lowering."""
    from repro.core.distributed import make_distributed_sort

    mesh = _abstract_mesh(8, "data")
    x = jnp.zeros(8 * 512, jnp.uint32)
    fn = make_distributed_sort(mesh, "data", cfg=TCFG, engine="kernel",
                               num_chunks=2, max_attempts=2)
    assert hlo.sort_op_count(jax.jit(fn).lower(x).as_text()) == 0
    fn = make_distributed_sort(mesh, "data", cfg=TCFG, engine="argsort",
                               max_attempts=2)
    assert hlo.sort_op_count(jax.jit(fn).lower(x).as_text()) > 0


def test_pallas_custom_call_counter_on_text():
    """The text-side counter recognises hardware custom-call spellings."""
    txt = ('%0 = stablehlo.custom_call @tpu_custom_call(%arg0)\n'
           'ROOT %1 = (f32[8]) custom-call(%0), '
           'custom_call_target="tpu_custom_call"\n')
    assert hlo.pallas_custom_call_count(txt) == 2
    assert hlo.pallas_custom_call_count("stablehlo.sort(%arg0)") == 0