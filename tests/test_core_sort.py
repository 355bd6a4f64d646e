"""Core hybrid radix sort: correctness, paper invariants, property tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # hypothesis is an optional test dependency (see pyproject.toml)
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*_a, **_k):
        return lambda f: f

    class st:  # noqa: N801 - stand-in for hypothesis.strategies
        @staticmethod
        def lists(*_a, **_k):
            return None

        @staticmethod
        def integers(*_a, **_k):
            return None

        @staticmethod
        def floats(*_a, **_k):
            return None

from repro.core import (hybrid_sort, lsd_sort, SortConfig, memory_budget,
                        expected_speedup, to_ordered_bits, from_ordered_bits)
from repro.core import model as sort_model
from repro.core.ranks import (stable_partition_dest_argsort,
                              stable_partition_dest_scan)
from repro.core.segmented import (counting_partition, capacity_dispatch,
                                  merge_sorted, multiway_merge)
from conftest import entropy_keys

# small thresholds so counting passes + merging actually exercise at test sizes
TCFG = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)


# --------------------------- bijections ------------------------------------

@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32,
                                   np.uint8, np.int16, np.uint16])
def test_bijection_roundtrip_and_order(rng, dtype):
    if np.issubdtype(dtype, np.floating):
        x = rng.standard_normal(4096).astype(dtype) * 100
    else:
        info = np.iinfo(dtype)
        x = rng.integers(info.min, info.max, 4096, endpoint=True).astype(dtype)
    u = to_ordered_bits(jnp.asarray(x))
    back = np.asarray(from_ordered_bits(u, dtype))
    assert np.array_equal(back, x)
    order_u = np.argsort(np.asarray(u), kind="stable")
    order_x = np.argsort(x, kind="stable")
    assert np.array_equal(x[order_u], x[order_x])


# --------------------------- rank engines ----------------------------------

@pytest.mark.parametrize("num_buckets", [2, 16, 256])
def test_rank_engines_agree(rng, num_buckets):
    ids = jnp.asarray(rng.integers(0, num_buckets, 5000).astype(np.int32))
    a = stable_partition_dest_argsort(ids)
    b = stable_partition_dest_scan(ids, num_buckets, chunk=512)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------- hybrid sort -----------------------------------

@pytest.mark.parametrize("n", [0, 1, 2, 3, 47, 48, 49, 1000, 20000])
def test_hybrid_sizes(rng, n):
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    out = hybrid_sort(jnp.asarray(x), cfg=TCFG)
    assert np.array_equal(np.sort(x), np.asarray(out))


@pytest.mark.parametrize("ands", [0, 1, 3, 8])
def test_hybrid_entropy_sweep(rng, ands):
    x = entropy_keys(rng, 8192, ands)
    out = hybrid_sort(jnp.asarray(x), cfg=TCFG)
    assert np.array_equal(np.sort(x), np.asarray(out))


def test_hybrid_constant_runs_all_passes(rng):
    # zero-entropy keys: the adaptive schedule sees no live bits and plans
    # ZERO passes; with adaptive=False the 32/8 worst case runs all 4.
    x = np.full(5000, 0xDEADBEEF, dtype=np.uint32)
    out, stats = hybrid_sort(jnp.asarray(x), cfg=TCFG, return_stats=True)
    assert np.array_equal(x, np.asarray(out))
    assert int(stats.counting_passes) == 0
    assert int(stats.elided_passes) == 0
    assert not bool(stats.used_local_sort)
    out, stats = hybrid_sort(jnp.asarray(x), cfg=TCFG, return_stats=True,
                             adaptive=False)
    assert np.array_equal(x, np.asarray(out))
    assert int(stats.counting_passes) == 4          # 32/8: zero-entropy worst case
    assert not bool(stats.used_local_sort)


def test_hybrid_uniform_finishes_early(rng):
    # enough keys that pass 1 leaves buckets > threshold, pass 2 finishes
    x = rng.integers(0, 2**32, 50000, dtype=np.uint32)
    out, stats = hybrid_sort(jnp.asarray(x), cfg=TCFG, return_stats=True)
    assert np.array_equal(np.sort(x), np.asarray(out))
    assert int(stats.counting_passes) < 4           # local sort saves passes
    assert bool(stats.used_local_sort)
    assert int(stats.max_segment) <= TCFG.local_threshold


def test_hybrid_pairs_move_together(rng):
    x = entropy_keys(rng, 6000, 2)
    v = np.arange(6000, dtype=np.int32)
    ks, vs = hybrid_sort(jnp.asarray(x), jnp.asarray(v), cfg=TCFG)
    ks, vs = np.asarray(ks), np.asarray(vs)
    assert np.array_equal(np.sort(x), ks)
    assert np.array_equal(x[vs], ks)                # value-consistency (not stability)


def test_hybrid_value_pytree(rng):
    x = rng.integers(0, 2**16, 512, dtype=np.uint32)
    vals = {"a": jnp.arange(512, dtype=jnp.int32),
            "b": jnp.arange(512, dtype=jnp.float32) * 2}
    ks, vs = hybrid_sort(jnp.asarray(x), vals, cfg=TCFG)
    assert np.array_equal(np.asarray(vs["a"]) * 2.0, np.asarray(vs["b"]))


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_hybrid_signed_and_float(rng, dtype):
    if dtype == np.float32:
        x = np.concatenate([rng.standard_normal(3000).astype(dtype) * 1e3,
                            np.array([0.0, -0.0, np.inf, -np.inf], dtype)])
    else:
        x = rng.integers(-2**31, 2**31 - 1, 3000).astype(dtype)
    out = np.asarray(hybrid_sort(jnp.asarray(x), cfg=TCFG))
    assert np.array_equal(np.sort(x), out)


def test_hybrid_segment_bound_I3(rng):
    n = 30000
    x = entropy_keys(rng, n, 1)
    _, stats = hybrid_sort(jnp.asarray(x), cfg=TCFG, return_stats=True)
    assert int(stats.num_segments) <= sort_model.max_total_buckets(n, TCFG)


def test_memory_budget_under_5_percent():
    # paper §4.5: 2 GB of u32 keys with KPB=6912, ∂̂=9216, ∂=3000 -> aux < 5% of M1
    from repro.core import default_config
    b = memory_budget(500_000_000, 32, default_config(4))
    assert b["aux_over_m1"] < 0.05


def test_expected_speedups_match_paper():
    assert abs(expected_speedup(32) - 1.75) < 1e-6     # 7 vs 4 passes
    assert abs(expected_speedup(64) - 1.625) < 1e-6    # 13 vs 8 passes


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 2**32 - 1), min_size=0, max_size=400),
       st.integers(1, 6))
def test_hybrid_property_vs_npsort(xs, dbits):
    x = np.asarray(xs, dtype=np.uint32)
    cfg = SortConfig(d=dbits, kpb=32, local_threshold=16, merge_threshold=8)
    out = hybrid_sort(jnp.asarray(x), cfg=cfg)
    assert np.array_equal(np.sort(x), np.asarray(out))


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(st.lists(st.floats(allow_nan=False, width=32), min_size=0, max_size=300))
def test_hybrid_property_floats(xs):
    x = np.asarray(xs, dtype=np.float32)
    out = hybrid_sort(jnp.asarray(x), cfg=TCFG)
    assert np.array_equal(np.sort(x), np.asarray(out))


# --------------------------- LSD baseline ----------------------------------

@pytest.mark.parametrize("d", [2, 4, 5, 7, 8])
def test_lsd_digit_widths(rng, d):
    x = rng.integers(0, 2**32, 3000, dtype=np.uint32)
    assert np.array_equal(np.sort(x), np.asarray(lsd_sort(jnp.asarray(x), d=d)))


def test_lsd_is_stable(rng):
    # LSD with values: equal keys keep input order (the property MSD drops)
    x = rng.integers(0, 8, 2000).astype(np.uint32)   # many duplicates
    v = np.arange(2000, dtype=np.int32)
    ks, vs = lsd_sort(jnp.asarray(x), jnp.asarray(v), d=2)
    vs = np.asarray(vs)
    for key in range(8):
        grp = vs[np.asarray(ks) == key]
        assert (np.diff(grp) > 0).all()


# --------------------------- partition / merge ------------------------------

def test_counting_partition_groups(rng):
    ids = rng.integers(0, 16, 4096).astype(np.int32)
    part = counting_partition(jnp.asarray(ids), 16)
    sorted_ids = np.asarray(ids)[np.asarray(part.perm)]
    assert (np.diff(sorted_ids) >= 0).all()
    assert np.array_equal(np.asarray(part.counts), np.bincount(ids, minlength=16))


def test_capacity_dispatch_drops_overflow(rng):
    ids = np.zeros(100, np.int32)                    # all to bucket 0
    cd = capacity_dispatch(jnp.asarray(ids), 4, 32)
    assert int(np.asarray(cd.kept).sum()) == 32
    assert np.asarray(cd.slot_valid)[0].sum() == 32
    assert np.asarray(cd.slot_valid)[1:].sum() == 0


def test_vmapped_kernel_dispatch_matches_argsort(rng):
    """The MoE layer vmaps capacity_dispatch over token groups; the fused
    pass then runs once per group and must agree with the argsort engine."""
    ids = jnp.asarray(rng.integers(0, 16, (3, 700)).astype(np.int32))
    dispatch = lambda engine: jax.jit(jax.vmap(
        lambda i: capacity_dispatch(i, 16, 50, engine=engine)))(ids)
    got, want = dispatch("kernel"), dispatch("argsort")
    for name, a, b in zip(got._fields, got, want):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name


def test_vmapped_kernel_sort_matches_argsort(rng):
    """hybrid_sort vmapped over rows of different entropy: the local sort's
    tile loops run as one batched loop over rows that need different
    counts of tiles, and every row agrees with the argsort engine."""
    x = np.stack([entropy_keys(rng, 3000, a) for a in (0, 3)])
    v = np.tile(np.arange(3000, dtype=np.int32), (2, 1))

    def sort(engine):
        k, vv = jax.jit(jax.vmap(lambda a, b: hybrid_sort(
            a, b, cfg=TCFG, engine=engine)))(jnp.asarray(x), jnp.asarray(v))
        return np.asarray(k), np.asarray(vv)

    (gk, gv), (wk, wv) = sort("kernel"), sort("argsort")
    assert np.array_equal(gk, np.sort(x, axis=1))
    assert gk.tobytes() == wk.tobytes() and gv.tobytes() == wv.tobytes()


def test_merge_sorted_and_multiway(rng):
    a = np.sort(rng.integers(0, 1000, 257).astype(np.uint32))
    b = np.sort(rng.integers(0, 1000, 511).astype(np.uint32))
    m = np.asarray(merge_sorted(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(np.sort(np.concatenate([a, b])), m)
    runs = np.sort(rng.integers(0, 2**20, (8, 128)).astype(np.uint32), axis=1)
    mm = np.asarray(multiway_merge(jnp.asarray(runs)))
    assert np.array_equal(np.sort(runs.reshape(-1)), mm)


def test_merge_sorted_with_values(rng):
    a = np.sort(rng.integers(0, 500, 100).astype(np.uint32))
    b = np.sort(rng.integers(0, 500, 150).astype(np.uint32))
    va = np.arange(100, dtype=np.int32)
    vb = np.arange(1000, 1150, dtype=np.int32)
    m, vm = merge_sorted(jnp.asarray(a), jnp.asarray(b),
                         jnp.asarray(va), jnp.asarray(vb))
    m, vm = np.asarray(m), np.asarray(vm)
    assert np.array_equal(np.sort(np.concatenate([a, b])), m)
    src = np.concatenate([a, b])
    vals = np.concatenate([va, vb])
    assert np.array_equal(src[np.searchsorted(np.arange(0), [])] if False else
                          np.array([vals[np.where((src == k) & ok)[0][0]]
                                    for k, ok in zip(m, np.ones(len(m), bool))])
                          .shape, vm.shape)  # shape-level check
    # pair consistency: every (key, value) pair in the output existed in input
    pairs_in = set(zip(src.tolist(), vals.tolist()))
    assert all((k, v) in pairs_in for k, v in zip(m.tolist(), vm.tolist()))


def test_multiway_merge_with_values(rng):
    runs = np.sort(rng.integers(0, 2**16, (4, 64)).astype(np.uint32), axis=1)
    vals = np.arange(4 * 64, dtype=np.int32).reshape(4, 64)
    order = np.argsort(runs, axis=1, kind="stable")
    vals = np.take_along_axis(vals, order, axis=1)   # consistent with sorted runs
    m, vm = multiway_merge(jnp.asarray(runs), jnp.asarray(vals))
    assert np.array_equal(np.sort(runs.reshape(-1)), np.asarray(m))
    pairs_in = set(zip(runs.reshape(-1).tolist(), vals.reshape(-1).tolist()))
    assert all((k, v) in pairs_in
               for k, v in zip(np.asarray(m).tolist(), np.asarray(vm).tolist()))


def test_hybrid_zipf_distribution(rng):
    """Paper §6.2 compares on Zipfian keys (the PARADIS benchmark)."""
    from repro.data.distributions import zipf_keys
    x = zipf_keys(rng, 20000, a=1.2)
    out, stats = hybrid_sort(jnp.asarray(x), cfg=TCFG, return_stats=True)
    assert np.array_equal(np.sort(x), np.asarray(out))
    # zipf mass concentrates at tiny keys: heavily skewed -> more passes
    assert int(stats.counting_passes) >= 1
