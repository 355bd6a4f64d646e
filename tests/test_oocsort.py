"""Out-of-core parity wall: ``oocsort`` ≡ the argsort reference, bytewise.

Correctness of the §5 pipeline spans launch boundaries — chunk sorts, the
double-buffered staging, and ⌈log_K⌉ merge-kernel rounds — so the fence is a
byte-identical comparison against the one-shot references across dtypes, KV
payloads, and every chunk-boundary shape, plus the structural gates: the
merge phase is comparison-sort-free and exactly ONE Pallas launch per round,
and the chunk-sort loop keeps the PR 2 one-launch-per-pass invariant.

Floats: the radix total order splits -0.0 < +0.0 (NaN payloads likewise), so
float keys are byte-compared against ``hybrid_sort``'s argsort engine (the
same total order) and semantically against ``np.sort``.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SortConfig, hybrid_sort
from repro.core.outofcore import (OocStats, _sort_chunk, merge_round, oocsort)
from repro.kernels import merge as kmerge
from repro.kernels.fused import pad_length
from repro.utils import hlo
from conftest import entropy_keys

CHUNK = 256
# small thresholds so kernel-engine chunk sorts exercise every phase
TCFG = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)


def _reference(x):
    """Byte-exact reference: the argsort-engine sort (radix total order)."""
    return np.asarray(hybrid_sort(jnp.asarray(x)))


def _keys(rng, dtype, n):
    if dtype == np.float32:
        x = (rng.standard_normal(n) * 1e3).astype(dtype)
        if n >= 8:
            x[:4] = [0.0, -0.0, np.inf, -np.inf]
        return x
    return entropy_keys(rng, n, 1, dtype=np.uint32).astype(dtype)


# ---------------- keys parity across dtypes and chunk boundaries ------------

@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32])
@pytest.mark.parametrize(
    "n", [0, 1, 100, CHUNK, CHUNK + 1,
          pytest.param(8 * CHUNK, marks=pytest.mark.slow),
          pytest.param(8 * CHUNK + 1, marks=pytest.mark.slow),
          pytest.param(9 * CHUNK - 1, marks=pytest.mark.slow)],
    ids=["empty", "one", "lt-chunk", "eq-chunk", "mod1", "mod0",
         "8mod1", "modKm1"])
def test_oocsort_keys_parity(rng, dtype, n):
    x = _keys(rng, dtype, n)
    out = oocsort(x, CHUNK, tile=32)
    assert isinstance(out, np.ndarray) and out.dtype == x.dtype
    assert np.array_equal(out, np.sort(x))
    assert out.tobytes() == _reference(x).tobytes()


@pytest.mark.slow
def test_oocsort_uint64(rng):
    from jax import enable_x64
    with enable_x64():
        x = entropy_keys(rng, 5 * CHUNK + 3, 2, dtype=np.uint64)
        out = oocsort(x, CHUNK, tile=32)
        assert np.array_equal(out, np.sort(x))
        assert out.tobytes() == _reference(x).tobytes()


@pytest.mark.slow
def test_oocsort_duplicates_and_sentinel(rng):
    for x in (np.zeros(1000, np.uint32),
              np.full(1000, 0xFFFFFFFF, np.uint32),      # == pad sentinel
              rng.integers(0, 8, 1500, dtype=np.uint32),
              np.where(rng.random(2000) < 0.3, 0xFFFFFFFF,
                       rng.integers(0, 2**32, 2000)).astype(np.uint32)):
        out = oocsort(x, 300, tile=32, kway=3)
        assert np.array_equal(out, np.sort(x))


# ---------------- KV parity (the acceptance criterion) ----------------------

def test_oocsort_kv_byte_identical_to_argsort_reference(rng):
    """n = 8x chunk_elems with unique keys: keys AND values byte-identical to
    the np.sort/np.argsort reference — the PR acceptance gate."""
    n = 8 * CHUNK
    x = rng.permutation(n).astype(np.uint32)            # unique keys
    v = rng.integers(0, 2**31, n).astype(np.int32)
    k, p = oocsort(x, CHUNK, values=np.arange(n, dtype=np.int32), tile=32)
    assert k.tobytes() == np.sort(x).tobytes()
    assert p.tobytes() == np.argsort(x, kind="stable").astype(
        np.int32).tobytes()
    k2, v2 = oocsort(x, CHUNK, values=v, tile=32)
    assert k2.tobytes() == np.sort(x).tobytes()
    assert v2.tobytes() == v[np.argsort(x, kind="stable")].tobytes()


@pytest.mark.parametrize("n", [0, 1, 200, CHUNK, 3 * CHUNK + 1])
def test_oocsort_kv_pair_consistency(rng, n):
    """Duplicate keys: pair movement is consistent (values travel with their
    keys) even where stability is not promised."""
    x = entropy_keys(rng, n, 3)
    v = np.arange(n, dtype=np.int32)
    k, p = oocsort(x, CHUNK, values=v, tile=32)
    assert np.array_equal(k, np.sort(x))
    assert np.array_equal(x[p], k)                      # pair consistency
    assert np.array_equal(np.sort(p), v)                # p is a permutation


def test_oocsort_value_pytree(rng):
    n = 3 * CHUNK
    x = rng.permutation(n).astype(np.uint32)
    vals = {"a": np.arange(n, dtype=np.int32),
            "b": np.arange(n, dtype=np.float32) * 2.0}
    k, out = oocsort(x, CHUNK, values=vals, tile=32)
    order = np.argsort(x, kind="stable")
    assert np.array_equal(k, np.sort(x))
    assert np.array_equal(out["a"], vals["a"][order])
    assert np.array_equal(out["b"], vals["b"][order])


# ---------------- streaming readers and chunk plans -------------------------

def test_oocsort_iterator_reader(rng):
    pieces = [rng.integers(0, 2**32, m, dtype=np.uint32)
              for m in (100, 700, 3, 0, 450)]
    full = np.concatenate(pieces)
    out = oocsort(iter(pieces), 256, engine="argsort", tile=32)
    assert np.array_equal(out, np.sort(full))


def test_oocsort_iterator_kv_tuples(rng):
    pieces, off = [], 0
    for m in (300, 300, 123):
        k = rng.integers(0, 2**32, m, dtype=np.uint32)
        pieces.append((k, np.arange(off, off + m, dtype=np.int32)))
        off += m
    full = np.concatenate([k for k, _ in pieces])
    k, p = oocsort(iter(pieces), 256, tile=32)
    assert np.array_equal(k, np.sort(full))
    assert np.array_equal(full[p], k)


def test_oocsort_stats_and_round_count(rng):
    x = rng.integers(0, 2**32, 8 * CHUNK, dtype=np.uint32)
    for kway, rounds in ((2, 3), (4, 2), (8, 1)):
        out, stats = oocsort(x, CHUNK, engine="argsort", kway=kway, tile=32,
                             return_stats=True)
        assert np.array_equal(out, np.sort(x))
        assert isinstance(stats, OocStats)
        assert stats.num_chunks == 8
        assert stats.merge_rounds == rounds == kmerge.num_merge_rounds(8, kway)
        assert stats.h2d_bytes == x.nbytes and stats.d2h_bytes == x.nbytes


def test_oocsort_engine_parity(rng):
    """Chunked kernel-engine == chunked argsort-engine, byte for byte."""
    x = entropy_keys(rng, 6 * CHUNK + 17, 2)
    a = oocsort(x, CHUNK, cfg=TCFG, engine="argsort", tile=32)
    k = oocsort(x, CHUNK, cfg=TCFG, engine="kernel", tile=32)
    assert a.tobytes() == k.tobytes()
    assert np.array_equal(a, np.sort(x))


@pytest.mark.slow
def test_oocsort_chunking_invariance(rng):
    """The output is independent of the chunk plan (unique keys: bytewise)."""
    n = 2048
    x = rng.permutation(n).astype(np.uint32)
    ref = oocsort(x, n, tile=32)                        # single run
    for chunk in (100, 256, 1000):
        assert oocsort(x, chunk, tile=32).tobytes() == ref.tobytes(), chunk


def test_oocsort_validation(rng):
    with pytest.raises(ValueError):
        oocsort(np.zeros(4, np.uint32), 0)
    with pytest.raises(ValueError):
        oocsort(np.zeros(4, np.uint32), 4, kway=1)
    with pytest.raises(ValueError):
        oocsort(np.zeros((2, 2), np.uint32), 4)
    with pytest.raises(ValueError):
        oocsort(iter([np.zeros(4, np.uint32)]), 4,
                values=np.zeros(4, np.int32))
    with pytest.raises(ValueError):
        oocsort(iter([]), 4)
    with pytest.raises(ValueError, match="key dtype"):   # silent-promotion trap
        oocsort(iter([np.zeros(4, np.uint32), np.zeros(4, np.int32)]), 4)
    with pytest.raises(ValueError, match="value dtypes"):
        oocsort(iter([(np.zeros(4, np.uint32), np.zeros(4, np.int32)),
                      (np.zeros(4, np.uint32), np.zeros(4, np.int64))]), 4)
    with pytest.raises(ValueError, match="1-D"):         # flat-slab contract
        oocsort(np.zeros(4, np.uint32), 2,
                values=np.ones((4, 3), np.float32))
    if not jax.config.jax_enable_x64:  # no silent payload truncation
        with pytest.raises(RuntimeError, match="64-bit value"):
            oocsort(np.zeros(4, np.uint32), 2,
                    values=np.arange(4, dtype=np.int64))


def test_oocsort_validation_names_offending_chunk():
    """Mismatch errors point at the chunk index that broke the contract —
    a multi-GB reader stream is undebuggable without it."""
    ok = np.zeros(4, np.uint32)
    with pytest.raises(ValueError, match=r"chunk 1.*key dtype"):
        oocsort(iter([ok, np.zeros(4, np.int32)]), 4)
    with pytest.raises(ValueError, match=r"chunk 2.*value structure"):
        oocsort(iter([(ok, ok), (ok, ok),
                      (ok, (ok, ok))]), 4)
    with pytest.raises(ValueError, match=r"chunk 1.*value dtypes"):
        oocsort(iter([(ok, np.zeros(4, np.int32)),
                      (ok, np.zeros(4, np.int64))]), 4)
    with pytest.raises(ValueError, match=r"chunk 0.*1-D"):
        oocsort(iter([np.zeros((2, 2), np.uint32)]), 4)
    with pytest.raises(ValueError, match=r"chunk 1.*match the key length"):
        oocsort(iter([(ok, ok), (ok, np.zeros(3, np.uint32))]), 4)


def test_length_bucketing_ooc_route(rng):
    """data.pipeline routes shard-sized corpora through oocsort: same packing
    contract as the LSD path."""
    from repro.data import length_bucketed_batches
    lengths = rng.integers(1, 512, 600)
    order, bounds = length_bucketed_batches(lengths, batch_tokens=4096,
                                            ooc_chunk_elems=128)
    ref_order, ref_bounds = length_bucketed_batches(lengths,
                                                    batch_tokens=4096)
    assert sorted(order.tolist()) == list(range(600))
    sl = lengths[order]
    assert (np.diff(sl) >= 0).all()
    assert bounds == ref_bounds
    assert np.array_equal(sl, lengths[ref_order])
    for a, b in zip(bounds[:-1], bounds[1:]):
        assert sl[a:b].max() * (b - a) <= 4096


# ---------------- host-spill streaming merge (§5 beyond-device-memory) ------

SPILL_TILE = 16
SPILL_BUDGET = 4096      # device-byte budget; parity gates feed 16x its bytes


def test_oocsort_spill_16x_budget_keys(rng):
    """THE spill acceptance gate: an input 16x the device budget sorts
    byte-identically while the driver's device high-water mark stays under
    the budget — the test that fails if anyone re-materialises full runs on
    device."""
    n = 16 * SPILL_BUDGET // 4
    x = _keys(rng, np.uint32, n)
    out, st = oocsort(x, 1 << 20, engine="argsort", tile=SPILL_TILE,
                      spill_budget_bytes=SPILL_BUDGET, return_stats=True)
    assert x.nbytes >= 16 * SPILL_BUDGET
    assert out.tobytes() == _reference(x).tobytes()
    assert st.spill_slab_elems > 0
    assert st.rounds_spilled == st.merge_rounds > 0
    assert st.device_high_water_bytes <= SPILL_BUDGET
    assert st.device_high_water_bytes < x.nbytes // 8   # runs stayed host-side


def test_oocsort_spill_16x_budget_kv(rng):
    """Spill acceptance, KV flavour: keys AND values byte-identical to the
    np.sort/np.argsort reference under a 16x-budget (key+value bytes) load."""
    n = 16 * SPILL_BUDGET // 8                          # 8 B per (key, value)
    x = rng.permutation(n).astype(np.uint32)            # unique keys
    v = np.arange(n, dtype=np.int32)
    assert x.nbytes + v.nbytes >= 16 * SPILL_BUDGET
    k, p, st = oocsort(x, 1 << 20, values=v, engine="argsort",
                       tile=SPILL_TILE, spill_budget_bytes=SPILL_BUDGET,
                       return_stats=True)
    assert k.tobytes() == np.sort(x).tobytes()
    assert p.tobytes() == np.argsort(x, kind="stable").astype(
        np.int32).tobytes()
    assert st.device_high_water_bytes <= SPILL_BUDGET


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32])
def test_oocsort_spill_16x_budget_dtypes(rng, dtype):
    """Full-stage dtype sweep of the 16x-budget spill parity gate."""
    n = 16 * SPILL_BUDGET // 4
    x = _keys(rng, dtype, n)
    out, st = oocsort(x, 1 << 20, engine="argsort", tile=SPILL_TILE,
                      spill_budget_bytes=SPILL_BUDGET, return_stats=True)
    assert out.tobytes() == _reference(x).tobytes()
    assert np.array_equal(out, np.sort(x))
    assert st.device_high_water_bytes <= SPILL_BUDGET


@pytest.mark.slow
def test_oocsort_spill_uint64(rng):
    from jax import enable_x64
    with enable_x64():
        n = 16 * SPILL_BUDGET // 8
        x = entropy_keys(rng, n, 2, dtype=np.uint64)
        out, st = oocsort(x, 1 << 20, engine="argsort", tile=SPILL_TILE,
                          spill_budget_bytes=SPILL_BUDGET, return_stats=True)
        assert out.tobytes() == _reference(x).tobytes()
        assert st.device_high_water_bytes <= SPILL_BUDGET


def test_oocsort_spill_equals_device_resident(rng):
    """Regime invariance: the streamed merge is byte-identical to the
    device-resident merge (same merge path, same tie order) for any slab."""
    x = entropy_keys(rng, 3000, 3)                      # heavy duplicates
    flat = oocsort(x, 300, engine="argsort", tile=32)
    for slab in (64, 128, 960):
        sp = oocsort(x, 300, engine="argsort", tile=32,
                     device_slab_elems=slab)
        assert sp.tobytes() == flat.tobytes(), slab


def test_oocsort_spill_value_pytree(rng):
    n = 6 * 128
    x = rng.permutation(n).astype(np.uint32)
    vals = {"a": np.arange(n, dtype=np.int32),
            "b": np.arange(n, dtype=np.float32) * 2.0}
    k, out = oocsort(x, 128, values=vals, tile=SPILL_TILE,
                     device_slab_elems=64)
    order = np.argsort(x, kind="stable")
    assert np.array_equal(k, np.sort(x))
    assert np.array_equal(out["a"], vals["a"][order])
    assert np.array_equal(out["b"], vals["b"][order])


@pytest.mark.slow
def test_oocsort_spill_engine_parity(rng):
    """Spilled kernel-engine chunk sorts == spilled argsort-engine, bytewise."""
    x = entropy_keys(rng, 4 * CHUNK, 2)
    a = oocsort(x, CHUNK, cfg=TCFG, engine="argsort", tile=32,
                device_slab_elems=128)
    k = oocsort(x, CHUNK, cfg=TCFG, engine="kernel", tile=32,
                device_slab_elems=128)
    assert a.tobytes() == k.tobytes()
    assert np.array_equal(a, np.sort(x))


def test_oocsort_spill_link_byte_formula(rng):
    """Host-byte accounting matches the §5 formula exactly: the chunk phase
    crosses 2·N·b and every spilled round adds 2·N·b (16 = 4² runs: no
    leftover groups anywhere)."""
    n = 16 * 64
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    out, st = oocsort(x, 64, engine="argsort", kway=4, tile=8,
                      device_slab_elems=32, return_stats=True)
    assert np.array_equal(out, np.sort(x))
    nb = x.nbytes
    assert st.num_chunks == 16 and st.rounds_spilled == 2
    assert st.chunk_link_bytes == 2 * nb
    assert st.spill_link_bytes == 2 * nb * st.rounds_spilled
    assert st.h2d_bytes == st.d2h_bytes == nb * (1 + st.rounds_spilled)
    assert st.h2d_bytes + st.d2h_bytes == \
        st.chunk_link_bytes + st.spill_link_bytes

    v = np.arange(n, dtype=np.int32)                    # payload doubles b
    k, p, st = oocsort(x, 64, values=v, engine="argsort", kway=4, tile=8,
                       device_slab_elems=32, return_stats=True)
    assert st.chunk_link_bytes == 2 * (nb + v.nbytes)
    assert st.spill_link_bytes == 2 * (nb + v.nbytes) * st.rounds_spilled


def test_oocsort_spill_leftover_runs_skip_crossings(rng):
    """Single-run leftover groups carry over host-side for free: their
    round's crossings exclude them, exactly."""
    n = 5 * 64                                          # 5 runs, kway=4
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    out, st = oocsort(x, 64, engine="argsort", kway=4, tile=8,
                      device_slab_elems=32, return_stats=True)
    assert np.array_equal(out, np.sort(x))
    assert st.num_chunks == 5 and st.rounds_spilled == 2
    # round 1: only the 4-run group (256 keys) streams; round 2: both runs
    assert st.spill_link_bytes == 2 * (256 * 4) + 2 * (320 * 4)


def test_oocsort_spill_stats_defaults(rng):
    """Device-resident sorts report zeroed spill fields and a high-water
    mark that scales with the whole input (the footprint spill removes)."""
    x = rng.integers(0, 2**32, 8 * CHUNK, dtype=np.uint32)
    out, st = oocsort(x, CHUNK, tile=32, return_stats=True)
    assert st.rounds_spilled == 0 and st.spill_slab_elems == 0
    assert st.spill_link_bytes == 0
    assert st.chunk_link_bytes == 2 * x.nbytes
    assert st.device_high_water_bytes > x.nbytes        # flat ping-pong pair


def test_oocsort_spill_validation():
    x = np.zeros(64, np.uint32)
    with pytest.raises(ValueError, match="spill_budget_bytes"):
        oocsort(x, 16, spill_budget_bytes=0)
    with pytest.raises(ValueError, match="too small"):
        oocsort(x, 16, tile=32, spill_budget_bytes=100)
    with pytest.raises(ValueError, match="device_slab_elems"):
        oocsort(x, 16, tile=32, device_slab_elems=8)


def test_oocsort_spill_validation_is_input_independent():
    """A misconfigured slab/budget must fail on empty inputs too, not only
    on the first non-empty batch of a pipeline."""
    empty = np.empty(0, np.uint32)
    with pytest.raises(ValueError, match="device_slab_elems"):
        oocsort(empty, 16, tile=32, device_slab_elems=8)
    with pytest.raises(ValueError, match="too small"):
        oocsort(empty, 16, tile=32, spill_budget_bytes=100)
    out = oocsort(empty, 16, tile=32, device_slab_elems=64)   # valid: fine
    assert out.shape == (0,)


def test_oocsort_spill_budget_is_hard_even_when_tight(rng):
    """The budget is a HARD ceiling at every accepted size: tight budgets
    where the pad tile and descriptor tables rival the slab payload must
    either shrink the slab to fit or refuse — never silently overshoot."""
    x = rng.integers(0, 2**32, 2000, dtype=np.uint32)
    with pytest.raises(ValueError, match="too small"):     # < one-tile peak
        oocsort(x, 1 << 20, engine="argsort", tile=8, spill_budget_bytes=300)
    for budget in (650, 2000):
        out, st = oocsort(x, 1 << 20, engine="argsort", tile=8,
                          spill_budget_bytes=budget, return_stats=True)
        assert np.array_equal(out, np.sort(x))
        assert st.device_high_water_bytes <= budget, budget


def test_oocsort_spill_budget_models_kernel_engine_padding(rng):
    """Kernel-engine chunk sorts allocate pad_length(n, kpb)-sized ping-pong
    pairs; a budget far below that must refuse rather than let real device
    allocations overshoot while the ledger reports compliance."""
    x = rng.integers(0, 2**32, 512, dtype=np.uint32)
    with pytest.raises(ValueError, match="chunk phase"):
        # default cfg: kpb=3456 -> ~110 KB modeled for even a 1-elem chunk
        oocsort(x, 256, engine="kernel", tile=16, spill_budget_bytes=4096)
    # a small-kpb cfg fits the same budget, honestly accounted
    out, st = oocsort(x, 256, cfg=TCFG, engine="kernel", tile=16,
                      spill_budget_bytes=8192, return_stats=True)
    assert np.array_equal(out, np.sort(x))
    assert st.device_high_water_bytes <= 8192


def test_oocsort_spill_explicit_slab_with_roomy_budget(rng):
    """A valid explicit slab must not be rejected just because a (large)
    budget is also given: only the budget-DERIVED slab needs the 2-tile
    footprint headroom."""
    x = rng.integers(0, 2**32, 400, dtype=np.uint32)
    out, st = oocsort(x, 100, engine="argsort", tile=32,
                      device_slab_elems=32, spill_budget_bytes=1 << 30,
                      return_stats=True)
    assert np.array_equal(out, np.sort(x))
    assert st.spill_slab_elems == 32
    assert st.device_high_water_bytes <= 1 << 30
    # ... and under a TIGHT budget the explicit slab's own modeled peak is
    # what decides, not the derived-slab reservation
    out, st = oocsort(x, 100, engine="argsort", tile=32,
                      device_slab_elems=32, spill_budget_bytes=2000,
                      return_stats=True)
    assert np.array_equal(out, np.sort(x))
    assert st.spill_slab_elems == 32
    assert st.device_high_water_bytes <= 2000


def test_length_bucketing_spill_route(rng):
    """The spill options thread through data.pipeline: same packing contract
    as the device-resident ooc route."""
    from repro.data import length_bucketed_batches
    lengths = rng.integers(1, 512, 600)
    order, bounds = length_bucketed_batches(
        lengths, batch_tokens=4096, ooc_chunk_elems=128,
        ooc_spill_budget_bytes=64 * 1024)
    ref_order, ref_bounds = length_bucketed_batches(lengths,
                                                    batch_tokens=4096)
    assert sorted(order.tolist()) == list(range(600))
    assert bounds == ref_bounds
    assert np.array_equal(lengths[order], lengths[ref_order])
    # spill options without the ooc route are a misconfiguration, not a no-op
    with pytest.raises(ValueError, match="ooc_chunk_elems"):
        length_bucketed_batches(lengths, batch_tokens=4096,
                                ooc_spill_budget_bytes=64 * 1024)


# ---------------- structural gates (acceptance criteria) --------------------

def _merge_round_jaxpr(lens, kway, tile, num_vals=0):
    n = sum(lens)
    n_pad = pad_length(n, tile)
    ck = jnp.zeros((n_pad,), jnp.uint32)
    cv = tuple(jnp.zeros((n_pad,), jnp.int32) for _ in range(num_vals))
    f = lambda a, b: merge_round(a, cv, b, tuple(jnp.zeros_like(v)
                                                 for v in cv),
                                 lens=tuple(lens), kway=kway, tile=tile, n=n,
                                 interpret=True)
    return f, ck, jnp.zeros_like(ck)


def test_merge_phase_is_comparison_sort_free():
    """utils.hlo.sort_op_count == 0 over the whole merge phase: the diagonal
    partition is binary search, the tile merge a counting rank."""
    for lens in ((256, 256, 256, 256), (256, 100), (300, 300, 300, 300, 17)):
        f, ck, ak = _merge_round_jaxpr(lens, kway=4, tile=64)
        assert hlo.sort_op_count(jax.jit(f).lower(ck, ak).as_text()) == 0, lens


def test_merge_round_single_launch_with_values():
    f, ck, ak = _merge_round_jaxpr((256, 256, 256), kway=4, tile=64,
                                   num_vals=2)
    jx = jax.make_jaxpr(f)(ck, ak)
    assert hlo.pallas_launch_count(jx) == 1
    assert hlo.launch_census(jx)["total"] == 1


# ---------------------------------------------------------------------------
# compressed-key mode (entropy-adaptive): pack to the live-bit carrier BEFORE
# any key crosses the link, so every link/slab/budget row shrinks with b_eff
# ---------------------------------------------------------------------------


def test_oocsort_compress_spill_clustered_smoke():
    """Clustered keys + ``compress=True`` through the host-spill regime.

    Live bits {0..5, 12..13} pack 4-byte keys into a uint8 carrier, so every
    link crossing (chunk staging, both spill rounds) pays 1 byte/key instead
    of 4 — and the chunk sorts run the 1-pass packed schedule instead of the
    2-pass narrowed (4-pass nominal) uint32 one.  The skewed cluster (56 of
    every 64 keys share the top digit) keeps a >local_threshold bucket alive
    after pass 0 of the UNcompressed sort, so the executed-pass reduction is
    strict, and the packed max value 0xFF doubles as a sentinel-collision
    probe.
    """
    rng = np.random.default_rng(11)
    n = 16 * 64
    c = np.where(np.arange(n) % 8 != 0, 0,
                 rng.integers(1, 4, n)).astype(np.uint32)
    x = (c << np.uint32(12)) | rng.integers(0, 64, n).astype(np.uint32)
    kw = dict(engine="argsort", cfg=TCFG, kway=4, tile=8,
              device_slab_elems=32, return_stats=True)
    plain, st_p = oocsort(x, 64, **kw)
    out, st = oocsort(x, 64, compress=True, **kw)

    assert out.dtype == np.uint32
    assert out.tobytes() == plain.tobytes() == np.sort(x).tobytes()

    # link formulas stay exact on the PACKED carrier byte size (b_eff = 1)
    for s, b in ((st_p, 4), (st, 1)):
        assert s.rounds_spilled == 2
        assert s.chunk_link_bytes == 2 * n * b
        assert s.spill_link_bytes == 2 * n * b * s.rounds_spilled
        assert s.retry_link_bytes == 0
        assert s.h2d_bytes + s.d2h_bytes == \
            s.chunk_link_bytes + s.spill_link_bytes + s.retry_link_bytes

    # chunk sorts report the reduced executed-pass totals: packed 8-bit keys
    # need 1 pass/chunk, the uncompressed narrowed window 2, nominal ⌈32/8⌉=4
    assert st.num_chunks == st_p.num_chunks == 16
    assert st.chunk_passes_executed == st.num_chunks
    assert st.chunk_passes_executed < st_p.chunk_passes_executed
    assert st_p.chunk_passes_executed < st_p.num_chunks * 4


def test_oocsort_compress_uint64_without_x64():
    """≤32 live bits let uint64 keys sort WITHOUT jax_enable_x64: the host
    packs to a uint32 carrier before the device ever sees a key (plain
    uint64 still refuses), and the link rows price the 4-byte carrier."""
    if jax.config.jax_enable_x64:
        pytest.skip("guard only meaningful with x64 disabled")
    rng = np.random.default_rng(7)
    n = 512
    x = ((rng.integers(0, 1 << 20, n).astype(np.uint64) << np.uint64(8))
         | np.uint64(0xA5 << 40))
    with pytest.raises(RuntimeError, match="x64"):
        oocsort(x, 128, engine="argsort")
    out, st = oocsort(x, 128, engine="argsort", cfg=TCFG, return_stats=True,
                      compress=True)
    assert out.dtype == np.uint64
    assert out.tobytes() == np.sort(x).tobytes()
    assert st.chunk_link_bytes == 2 * n * 4   # 20 live bits -> uint32 carrier
    # executed passes stay under the PACKED nominal ⌈20/8⌉ = 3 per chunk —
    # far below the ⌈64/8⌉ = 8 the uncompressed uint64 schedule would run
    assert 0 < st.chunk_passes_executed <= st.num_chunks * 3
