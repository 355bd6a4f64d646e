"""Entropy-adaptive pass elision wall: parity, census, compression.

Three claims are pinned here:

* **Parity** — the adaptive schedule (static live-bit narrowing + mid-sort
  elision of single-occupied-digit passes) is byte-identical to the full
  nominal schedule and to ``engine="argsort"``, across engines, dtypes
  (incl. NaN float keys), KV payloads, the entropy ladder, empty and
  all-equal inputs, and under ``max_passes`` truncation.  An elided pass is
  an identity permutation, so even tie order cannot move.
* **Census** — elision removes *executed launches*, not launch sites: the
  while body still traces to exactly ONE ``pallas_call`` (the launch branch
  of the skip cond; the elide branch launches nothing), and the statically
  unrolled LSD kernel's site count drops with the narrowed window — the
  structural, non-timing proof that dead passes cost nothing.
* **Compression** — ``core.bijection``'s pack/unpack is an order-preserving
  bijection on the live bits, and ``hybrid_sort(compress=True)`` /
  ``oocsort(compress=True)`` round-trip through the packed carrier
  byte-identically (the oocsort spill smoke lives in tests/test_oocsort.py).

The ISSUE 7 acceptance inputs — ``entropy_keys(ands=3)`` and
``clustered_keys`` at n=16384 — are gated below at their natural configs:
AND-ed keys keep every bit live, so their executed-pass win needs the
local-sort threshold the paper's GPU local sort actually has (thousands of
keys), while clustered keys finish early at the small-tile config too.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # hypothesis is an optional test dependency (see pyproject.toml)
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare interpreters
    HAVE_HYPOTHESIS = False

from repro.core import SortConfig, hybrid_sort, lsd_sort, model
from repro.core import bijection
from repro.core.hybrid import live_bit_window
from repro.data.distributions import clustered_keys, entropy_keys
from repro.utils import hlo

TCFG = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)
# acceptance config: GPU-realistic local-sort threshold (CUB BlockRadixSort
# handles thousands of keys per block), so the Thearling AND chain goes
# local before the nominal schedule runs out
ACFG = SortConfig(d=8, kpb=256, local_threshold=4096, merge_threshold=2048)
ENGINES = ("argsort", "scan", "kernel")
NOMINAL = model.num_digits(32, 8)


def _aligned_clusters(rng, n, clusters=4, top_shift=24, low_bits=8):
    """Cluster ids in the top digit, live low bytes, dead middle — the
    mid-sort elision shape: segments stay above the local threshold while
    whole digit positions are constant."""
    return ((rng.integers(0, clusters, n).astype(np.uint32)
             << np.uint32(top_shift))
            | rng.integers(0, 1 << low_bits, n).astype(np.uint32))


def _sort(x, eng, adaptive, cfg=TCFG, values=None, **kw):
    out = hybrid_sort(jnp.asarray(x),
                      None if values is None else jnp.asarray(values),
                      cfg=cfg, engine=eng, adaptive=adaptive, **kw)
    return jax.tree.map(np.asarray, out)


# --------------------- acceptance gates (ISSUE 7) ---------------------------

CFG768 = SortConfig(d=8, kpb=256, local_threshold=768, merge_threshold=512)


@pytest.mark.parametrize("make,cfg", [
    pytest.param(lambda r, n: entropy_keys(r, n, 3), ACFG, id="ands3"),
    pytest.param(lambda r, n: clustered_keys(r, n), CFG768, id="clustered"),
])
def test_acceptance_fewer_passes_than_nominal_n16384(rng, make, cfg):
    """At n=16384 the adaptive kernel executes strictly fewer counting
    passes than the nominal ⌈k/d⌉, byte-identically to argsort — and the
    launch census (below) pins one launch per *executed* pass, so fewer
    executed passes IS fewer launches, no timing involved."""
    n = 16384
    x = make(rng, n)
    k, st_ = _sort(x, "kernel", True, cfg=cfg, return_stats=True)
    assert int(st_.counting_passes) < NOMINAL
    assert int(st_.counting_passes) + int(st_.elided_passes) <= NOMINAL
    ka, sa = _sort(x, "argsort", True, cfg=cfg, return_stats=True)
    assert k.tobytes() == ka.tobytes()
    assert np.array_equal(k, np.sort(x))
    # the engines ran the same adaptive schedule, not merely the same sort
    assert tuple(int(s) for s in st_) == tuple(int(s) for s in sa)


def test_census_one_launch_site_per_executed_pass():
    """Adaptive loop body: ONE pallas_call site — it lives in the launch
    branch of the skip cond and the elide branch has none, so runtime
    launches == executed passes.  Total sites stay prologue + pass + local
    classes, exactly the non-adaptive census."""
    from repro.core.hybrid import local_sort_classes
    for n in (257, 4096, 20000):
        jx = jax.make_jaxpr(
            lambda a: hybrid_sort(a, cfg=TCFG, engine="kernel",
                                  adaptive=True))(jnp.zeros(n, jnp.uint32))
        assert hlo.while_body_pallas_launches(jx) == \
            [1] * (1 + len(local_sort_classes(n, TCFG))), n
        assert hlo.pallas_launch_count(jx) == \
            2 + len(local_sort_classes(n, TCFG)), n


def test_census_lsd_narrowed_window_drops_launch_sites(rng):
    """The statically unrolled LSD kernel is the trace-level proof that
    dead bits elide whole launches: 16 dead high bits remove two of the
    five launch sites (⌈16/8⌉ + prologue left)."""
    x = (np.uint32(0xABCD) << np.uint32(16)) \
        | rng.integers(0, 1 << 16, 2048).astype(np.uint32)
    jx = jax.make_jaxpr(
        lambda: lsd_sort(x, d=8, engine="kernel", kpb=512))()
    assert hlo.pallas_launch_count(jx) == model.num_digits(16, 8) + 1 == 3
    jx_full = jax.make_jaxpr(
        lambda: lsd_sort(x, d=8, engine="kernel", kpb=512,
                         adaptive=False))()
    assert hlo.pallas_launch_count(jx_full) == NOMINAL + 1 == 5
    k, passes = lsd_sort(jnp.asarray(x), d=8, return_passes=True)
    assert passes == 2 and np.array_equal(np.asarray(k), np.sort(x))


def test_mid_sort_elision_fires_and_stats_agree(rng):
    """Aligned clusters: the dead middle digit is elided mid-sort off the
    fused launch's free next-pass histogram — executed + elided < nominal
    executed-without-adaptivity, identical stats across all engines."""
    x = _aligned_clusters(rng, 3000)
    ref = None
    for eng in ENGINES:
        k, st_ = _sort(x, eng, True, return_stats=True)
        got = (k.tobytes(), tuple(int(s) for s in st_))
        ref = ref or got
        assert got == ref, eng
    assert np.array_equal(k, np.sort(x))
    assert int(st_.elided_passes) >= 1
    ks, ss = _sort(x, "kernel", False, return_stats=True)
    assert ks.tobytes() == k.tobytes()
    assert int(ss.counting_passes) > int(st_.counting_passes)
    assert int(ss.elided_passes) == 0


# --------------------- parity wall ------------------------------------------

def _wall_keys(rng, dtype, n, shape):
    if dtype == np.float32:
        x = (rng.standard_normal(n) * 1e3).astype(np.float32)
        if n >= 8:
            x[:6] = [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan]
        return x
    if shape == "entropy":
        return entropy_keys(rng, n, 3, dtype=dtype)
    if shape == "clustered":
        return clustered_keys(rng, n, dtype=dtype)
    if shape == "allequal":
        return np.full(n, np.iinfo(dtype).max // 3, dtype)
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, n, endpoint=True).astype(dtype)


@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32])
@pytest.mark.parametrize("shape", ["uniform", "entropy", "clustered",
                                   "allequal"])
def test_adaptive_parity_grid(rng, dtype, shape):
    """Deterministic fast-tier wall: adaptive on/off x engines x dtypes
    (NaN floats included) x entropy/clustered/all-equal, byte-identical
    keys AND values."""
    n = 1500
    x = _wall_keys(rng, dtype, n, shape)
    v = np.arange(n, dtype=np.int32)
    ref = None
    for eng in ENGINES:
        for adaptive in (True, False):
            k, v_ = _sort(x, eng, adaptive, values=v)
            got = (k.tobytes(), v_.tobytes())
            ref = ref or got
            assert got == ref, (eng, adaptive)


@pytest.mark.parametrize("adaptive", [True, False])
def test_adaptive_empty_and_tiny(adaptive):
    for n in (0, 1, 2):
        x = np.arange(n, dtype=np.uint32)[::-1].copy()
        for eng in ENGINES:
            k, st_ = _sort(x, eng, adaptive, return_stats=True)
            assert np.array_equal(k, np.sort(x)), (eng, n)
            assert int(st_.elided_passes) >= 0


def test_max_passes_interaction(rng):
    """max_passes caps pass *slots* (executed + elided), so truncated
    adaptive results stay identical across engines and an elided slot
    cannot smuggle extra progress past the cap."""
    x = rng.integers(0, 2**32, 4000, dtype=np.uint32)
    x[:2000] &= 0x00FFFFFF
    outs = []
    for eng in ENGINES:
        for adaptive in (True, False):
            k, st_ = _sort(x, eng, adaptive, max_passes=1, return_stats=True)
            outs.append((eng, adaptive, k, st_))
            assert int(st_.counting_passes) + int(st_.elided_passes) <= 1
    ref = outs[0][2]
    for eng, adaptive, k, _ in outs:
        assert k.tobytes() == ref.tobytes(), (eng, adaptive)
    assert not np.array_equal(ref, np.sort(x))     # 1 pass can't finish
    assert np.array_equal(np.sort(ref), np.sort(x))


if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @settings(deadline=None, max_examples=20,
              suppress_health_check=[HealthCheck.too_slow])
    @given(data=st.data())
    def test_adaptive_parity_hypothesis_wall(data):
        """Randomised cross-product the grid can't cover: every engine x
        adaptive on/off is byte-identical (keys, values) on arbitrary
        dtype/shape/size/seed draws."""
        dtype = data.draw(st.sampled_from([np.uint32, np.int32, np.float32]),
                          label="dtype")
        shape = data.draw(st.sampled_from(
            ["uniform", "entropy", "clustered", "allequal"]), label="shape")
        n = data.draw(st.sampled_from([0, 1, 3, 63, 257, 1000]), label="n")
        kv = data.draw(st.booleans(), label="kv")
        seed = data.draw(st.integers(0, 2**16), label="seed")
        rng = np.random.default_rng(seed)
        x = _wall_keys(rng, dtype, n, shape)
        v = np.arange(n, dtype=np.int32) if kv else None
        ref = None
        for eng in ENGINES:
            for adaptive in (True, False):
                out = _sort(x, eng, adaptive, values=v, return_stats=True)
                if kv:
                    k, v_, _ = out
                else:
                    (k, _), v_ = out, np.empty(0)
                got = (k.tobytes(), v_.tobytes())
                ref = ref or got
                assert got == ref, (eng, adaptive)


# --------------------- live-bit window / compression ------------------------

def test_live_bit_window():
    assert live_bit_window(np.array([], np.uint32)) == (0, 0)
    assert live_bit_window(np.full(5, 0xF0F0, np.uint32)) == (0, 0)
    # 0b1010_0000 vs 0b1110_0000: only bit 6 varies -> window [6, 7)
    assert live_bit_window(
        np.array([0b1010_0000, 0b1110_0000], np.uint32)) == (6, 7)
    assert live_bit_window(np.array([0, 1], np.uint64)) == (0, 1)


def test_compression_plan_and_roundtrip(rng):
    ub = rng.integers(0, 1 << 12, 500).astype(np.uint64) << np.uint64(13)
    ub |= np.uint64(0b101) << np.uint64(40)    # dead set bits
    plan = bijection.compression_plan_np(ub)
    assert plan.source_bits == 64
    assert plan.packed_bits <= 12
    assert np.dtype(bijection.packed_carrier_dtype(plan)) == np.uint16
    packed = bijection.pack_ordered_bits_np(ub, plan)
    assert packed.dtype == np.uint16
    back = bijection.unpack_ordered_bits_np(packed, plan)
    assert np.array_equal(back, ub)
    # order preservation: the pack is monotone on the live bits
    order = np.argsort(ub, kind="stable")
    assert np.array_equal(packed[order], np.sort(packed))
    # jnp mirror agrees (32-bit-safe carrier side only without x64)
    ub32 = (ub >> np.uint64(13)).astype(np.uint32)
    plan32 = bijection.compression_plan_np(ub32)
    p_np = bijection.pack_ordered_bits_np(ub32, plan32)
    p_j = np.asarray(bijection.pack_ordered_bits(jnp.asarray(ub32), plan32))
    assert np.array_equal(p_np, p_j)
    assert np.array_equal(
        np.asarray(bijection.unpack_ordered_bits(jnp.asarray(p_j), plan32)),
        ub32)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=40)
    @given(mask=st.integers(1, (1 << 32) - 1), seed=st.integers(0, 2**16))
    def test_compression_order_preserving_hypothesis(mask, seed):
        """For ANY live-bit mask, packing is a strictly monotone bijection
        of the masked values: sort order and equality survive compression."""
        rng = np.random.default_rng(seed)
        dead = rng.integers(0, 1 << 32, dtype=np.uint32) & ~np.uint32(mask)
        ub = ((rng.integers(0, 1 << 32, 200, dtype=np.uint32)
               & np.uint32(mask)) | dead)
        plan = bijection.CompressionPlan(mask=int(mask), dead=int(dead),
                                         source_bits=32)
        packed = bijection.pack_ordered_bits_np(ub, plan)
        assert np.array_equal(
            bijection.unpack_ordered_bits_np(packed, plan), ub)
        srt = np.argsort(ub, kind="stable")
        assert np.array_equal(packed[srt], np.sort(packed))
        same = ub[:, None] == ub[None, :]
        assert np.array_equal(packed[:, None] == packed[None, :], same)


def test_hybrid_sort_compressed_keys(rng):
    x = _aligned_clusters(rng, 2000)
    k, st_ = _sort(x, "kernel", True, compress=True, return_stats=True)
    ka = _sort(x, "argsort", True)
    assert k.tobytes() == ka.tobytes()
    assert np.array_equal(k, np.sort(x))
    # 10 live bits sort in one d=8-window pass or two
    assert int(st_.counting_passes) + int(st_.elided_passes) <= 2


@pytest.mark.slow
def test_hybrid_sort_compressed_uint64(rng):
    from jax import enable_x64
    with enable_x64():
        x = (rng.integers(0, 1 << 10, 2500).astype(np.uint64)
             << np.uint64(30)) | np.uint64(1 << 60)
        k, st_ = _sort(x, "kernel", True, compress=True, return_stats=True)
        assert np.array_equal(k, np.sort(x))
        assert int(st_.counting_passes) <= 2      # 10 live bits, not 64
        ks = _sort(x, "argsort", False)
        assert ks.tobytes() == k.tobytes()


def test_compress_requires_concrete_keys():
    with pytest.raises(ValueError, match="compress"):
        jax.jit(lambda a: hybrid_sort(a, cfg=TCFG, compress=True))(
            jnp.zeros(64, jnp.uint32))


def test_lsd_adaptive_parity_and_pass_counts(rng):
    x = (np.uint32(0xBEEF) << np.uint32(16)) \
        | rng.integers(0, 1 << 16, 3000).astype(np.uint32)
    v = np.arange(3000, dtype=np.int32)
    ref = None
    for eng in ENGINES:
        for adaptive in (True, False):
            k, v_ = lsd_sort(jnp.asarray(x), jnp.asarray(v), d=8, engine=eng,
                             kpb=512, adaptive=adaptive)
            got = (np.asarray(k).tobytes(), np.asarray(v_).tobytes())
            ref = ref or got
            assert got == ref, (eng, adaptive)
    assert np.array_equal(np.asarray(k), np.sort(x))
