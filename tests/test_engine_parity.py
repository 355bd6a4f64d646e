"""Engine parity: ``kernel`` ≡ ``argsort`` ≡ ``scan`` ≡ ``np.sort``.

The hybrid sort's three partition engines must produce *byte-identical*
output — keys and values — on every input, and the kernel engine's traced
HLO must be free of comparison sorts (the structural property that separates
the paper's O(n·k/d) pipeline from argsort-based GPU sorts).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ENGINES, SortConfig, hybrid_sort, lsd_sort, resolve_engine
from repro.core.ranks import resolve_interpret
from repro.utils import hlo
from conftest import entropy_keys

# small thresholds so counting passes, merging and the local sort all fire
TCFG = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)
# d=5 leaves a partial-width (2-bit) last pass for 32-bit keys
PCFG = SortConfig(d=5, kpb=32, local_threshold=16, merge_threshold=8)

JNP_ENGINES = ("argsort", "scan")


def _keys(rng, dtype, n):
    if dtype == np.float32:
        x = (rng.standard_normal(n) * 1e3).astype(dtype)
        if n >= 8:
            x[:4] = [0.0, -0.0, np.inf, -np.inf]
        return x
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, n, endpoint=True).astype(dtype)


def _all_engine_outputs(x, cfg, values=None):
    outs = {}
    for eng in ENGINES:
        if values is None:
            outs[eng] = (np.asarray(hybrid_sort(jnp.asarray(x), cfg=cfg,
                                                engine=eng)), None)
        else:
            k, v = hybrid_sort(jnp.asarray(x), jnp.asarray(values), cfg=cfg,
                               engine=eng)
            outs[eng] = (np.asarray(k), np.asarray(v))
    return outs


@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32])
@pytest.mark.parametrize(
    "n", [0, 1, 2, 257, pytest.param(4096, marks=pytest.mark.slow)])
def test_parity_keys(rng, dtype, n):
    x = _keys(rng, dtype, n)
    outs = _all_engine_outputs(x, TCFG)
    for eng, (k, _) in outs.items():
        assert np.array_equal(k, np.sort(x)), eng
        assert k.tobytes() == outs["argsort"][0].tobytes(), eng


@pytest.mark.slow
def test_parity_uint64(rng):
    from jax import enable_x64
    with enable_x64():
        x = rng.integers(0, 2**64, 3000, dtype=np.uint64)
        outs = _all_engine_outputs(x, TCFG)
        for eng, (k, _) in outs.items():
            assert np.array_equal(k, np.sort(x)), eng
            assert k.tobytes() == outs["argsort"][0].tobytes(), eng


@pytest.mark.parametrize("cfg", [TCFG, PCFG], ids=["d8", "d5-partial"])
def test_parity_pairs_byte_identical(rng, cfg):
    """Key-value pairs: every engine applies the exact same permutation."""
    x = entropy_keys(rng, 5000, 2)
    v = np.arange(5000, dtype=np.int32)
    outs = _all_engine_outputs(x, cfg, values=v)
    ka, va = outs["argsort"]
    assert np.array_equal(ka, np.sort(x))
    assert np.array_equal(x[va], ka)                   # pair consistency
    for eng in ENGINES:
        k, v_ = outs[eng]
        assert k.tobytes() == ka.tobytes(), eng
        assert v_.tobytes() == va.tobytes(), eng


def test_parity_value_pytree(rng):
    x = _keys(rng, np.uint32, 1500)
    vals = {"a": jnp.arange(1500, dtype=jnp.int32),
            "b": jnp.arange(1500, dtype=jnp.float32) * 2}
    ka, va = hybrid_sort(jnp.asarray(x), vals, cfg=TCFG, engine="argsort")
    kk, vk = hybrid_sort(jnp.asarray(x), vals, cfg=TCFG, engine="kernel")
    assert np.array_equal(np.asarray(ka), np.asarray(kk))
    for leaf in ("a", "b"):
        assert np.array_equal(np.asarray(va[leaf]), np.asarray(vk[leaf])), leaf


@pytest.mark.parametrize(
    "ands", [0, pytest.param(1, marks=pytest.mark.slow),
             pytest.param(3, marks=pytest.mark.slow), 8])
def test_parity_entropy_sweep(rng, ands):
    """Thearling & Smith reduced-entropy inputs (paper §6's distributions)."""
    x = entropy_keys(rng, 8192, ands)
    outs = _all_engine_outputs(x, TCFG)
    for eng, (k, _) in outs.items():
        assert np.array_equal(k, np.sort(x)), eng


def test_parity_all_equal_and_sentinel(rng):
    for x in (np.full(3000, 0xDEADBEEF, np.uint32),
              np.full(300, 0xFFFFFFFF, np.uint32),
              np.where(rng.random(4000) < 0.3, 0xFFFFFFFF,
                       rng.integers(0, 2**32, 4000)).astype(np.uint32)):
        outs = _all_engine_outputs(x, TCFG)
        for eng, (k, _) in outs.items():
            assert np.array_equal(k, np.sort(x)), eng


def test_parity_stats(rng):
    """Pass counts, segment structure and local-sort usage agree: the engines
    run the *same algorithm*, not merely equivalent sorts."""
    x = entropy_keys(rng, 20000, 1)
    ref = None
    for eng in ENGINES:
        _, stats = hybrid_sort(jnp.asarray(x), cfg=TCFG, return_stats=True,
                               engine=eng)
        got = tuple(int(s) for s in stats)
        ref = ref or got
        assert got == ref, eng


def test_lsd_engine_parity(rng):
    x = rng.integers(0, 2**32, 4000, dtype=np.uint32)
    v = np.arange(4000, dtype=np.int32)
    ref_k, ref_v = None, None
    for eng in ENGINES:
        k, v_ = lsd_sort(jnp.asarray(x), jnp.asarray(v), d=8, engine=eng,
                         kpb=512)
        k, v_ = np.asarray(k), np.asarray(v_)
        assert np.array_equal(k, np.sort(x)), eng
        if ref_k is None:
            ref_k, ref_v = k, v_
        # LSD is stable in every engine, so values are byte-identical too
        assert np.array_equal(v_, ref_v), eng


def test_parity_truncated_max_passes(rng):
    """Under max_passes truncation every engine returns the same partial
    result: partition-ordered, with only done buckets finished."""
    x = rng.integers(0, 2**32, 4000, dtype=np.uint32)
    x[:2000] &= 0x00FFFFFF                   # half the keys share top byte 0
    outs = [np.asarray(hybrid_sort(jnp.asarray(x), cfg=TCFG, engine=e,
                                   max_passes=1)) for e in ENGINES]
    for eng, o in zip(ENGINES, outs):
        assert o.tobytes() == outs[0].tobytes(), eng
    # one pass cannot fully sort the giant shared-prefix bucket
    assert not np.array_equal(outs[0], np.sort(x))
    assert np.array_equal(np.sort(outs[0]), np.sort(x))


def test_cfg_rank_engine_is_honoured(rng):
    """SortConfig.rank_engine is the default when no engine= is passed."""
    x = rng.integers(0, 2**32, 2000, dtype=np.uint32)
    cfg = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32,
                     rank_engine="kernel")
    f = jax.jit(lambda a: hybrid_sort(a, cfg=cfg))
    assert hlo.sort_op_count(f.lower(jnp.asarray(x)).as_text()) == 0
    assert np.array_equal(np.asarray(f(jnp.asarray(x))), np.sort(x))


def test_resolve_engine():
    assert resolve_engine("kernel") == "kernel"
    assert resolve_engine(None) in ENGINES
    assert resolve_engine("auto") == resolve_engine(None)
    with pytest.raises(ValueError):
        resolve_engine("bogosort")


@pytest.mark.parametrize("backend,want", [("tpu", "kernel"), ("cpu", "argsort"),
                                          ("gpu", "argsort")])
def test_auto_engine_per_backend(backend, want):
    """``auto`` on a TPU is the Mosaic kernel engine — nothing demotes it."""
    assert resolve_engine("auto", backend=backend) == want
    assert resolve_engine(None, backend=backend) == want


def test_resolve_interpret():
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


@pytest.mark.parametrize("entry", ["hybrid", "lsd"])
def test_compiled_kernel_path_refuses_64bit_keys(rng, entry):
    """Mosaic has no 64-bit vector integers: a uint64 key on the compiled
    kernel path names its dtype and the engine that sorts it, before any
    lowering starts — and never switches engine by itself."""
    from jax import enable_x64
    x = rng.integers(0, 2**64, 300, dtype=np.uint64)
    with enable_x64():
        sort = hybrid_sort if entry == "hybrid" else lsd_sort
        with pytest.raises(TypeError, match=r"uint64.*engine='argsort'"):
            sort(jnp.asarray(x), engine="kernel", interpret=False)


# --------------------- HLO structure (acceptance gate) ----------------------

def test_kernel_engine_hlo_is_sort_free():
    """engine="kernel" must trace to zero (stable)HLO sort ops — the whole
    point of the pipeline; argsort is the positive control."""
    x = jnp.zeros(4096, jnp.uint32)
    f = lambda eng: jax.jit(
        lambda a: hybrid_sort(a, cfg=TCFG, engine=eng)).lower(x).as_text()
    assert hlo.sort_op_count(f("kernel")) == 0
    assert hlo.sort_op_count(f("argsort")) > 0


def test_kernel_engine_hlo_sort_free_with_values_and_stats():
    x = jnp.zeros(2048, jnp.uint32)
    v = jnp.zeros(2048, jnp.int32)
    txt = jax.jit(lambda a, b: hybrid_sort(
        a, b, cfg=TCFG, engine="kernel", return_stats=True)).lower(x, v).as_text()
    assert hlo.sort_op_count(txt) == 0


def test_lsd_kernel_engine_hlo_is_sort_free():
    x = jnp.zeros(2048, jnp.uint32)
    txt = jax.jit(lambda a: lsd_sort(a, d=8, engine="kernel",
                                     kpb=512)).lower(x).as_text()
    assert hlo.sort_op_count(txt) == 0
