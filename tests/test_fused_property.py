"""Property tests: the fused kernel engine is byte-identical to argsort.

Hypothesis (optional test dependency, as in test_core_sort) drives random
(dtype, size, entropy, payload) combinations through ``hybrid_sort`` with
``engine="kernel"`` (the fused single-launch pipeline) and ``engine="argsort"``
and requires byte-identical keys AND values.  A deterministic sweep below
covers the same grid — including empty and all-equal inputs — so the
invariant is exercised even where hypothesis is not installed.
"""
import numpy as np
import jax.numpy as jnp
import pytest

try:  # hypothesis is an optional test dependency (see pyproject.toml)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare interpreters
    HAVE_HYPOTHESIS = False

from repro.core import SortConfig, hybrid_sort
from conftest import entropy_keys

# small thresholds so counting passes, merging and the local sort all fire
TCFG = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)

DTYPES = (np.uint32, np.int32, np.float32)


def _keys(rng, dtype, n, ands):
    if dtype == np.float32:
        x = (rng.standard_normal(n) * 10.0 ** rng.integers(0, 6)).astype(dtype)
        if n >= 8:
            x[:4] = [0.0, -0.0, np.inf, -np.inf]
        return x
    x = entropy_keys(rng, n, ands, dtype=np.uint32)
    return x.astype(dtype)


def _assert_fused_matches_argsort(x, with_values):
    v = np.arange(x.shape[0], dtype=np.int32) if with_values else None
    if v is None:
        ka = hybrid_sort(jnp.asarray(x), cfg=TCFG, engine="argsort")
        kk = hybrid_sort(jnp.asarray(x), cfg=TCFG, engine="kernel")
        va = vk = None
    else:
        ka, va = hybrid_sort(jnp.asarray(x), jnp.asarray(v), cfg=TCFG,
                             engine="argsort")
        kk, vk = hybrid_sort(jnp.asarray(x), jnp.asarray(v), cfg=TCFG,
                             engine="kernel")
    ka, kk = np.asarray(ka), np.asarray(kk)
    assert np.array_equal(ka, np.sort(x)), "argsort oracle broken"
    assert ka.tobytes() == kk.tobytes(), "fused keys diverge"
    if v is not None:
        assert np.asarray(va).tobytes() == np.asarray(vk).tobytes(), \
            "fused values diverge"


if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**32 - 1),
           st.sampled_from(DTYPES),
           st.integers(0, 500),
           st.integers(0, 8),
           st.booleans())
    def test_fused_matches_argsort_property(seed, dtype, n, ands, with_values):
        rng = np.random.default_rng(seed)
        _assert_fused_matches_argsort(_keys(rng, dtype, n, ands), with_values)

    @pytest.mark.slow
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**32 - 1), st.integers(0, 400), st.integers(0, 4))
    def test_fused_matches_argsort_property_uint64(seed, n, ands):
        from jax import enable_x64
        rng = np.random.default_rng(seed)
        x = entropy_keys(rng, n, ands, dtype=np.uint64)
        with enable_x64():
            _assert_fused_matches_argsort(x, with_values=False)


# ------- deterministic sweep: runs with or without hypothesis ---------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize(
    "n", [0, 1, 2, 63, 64, 65, pytest.param(257, marks=pytest.mark.slow)])
@pytest.mark.parametrize("with_values", [False, True])
def test_fused_matches_argsort_sweep(rng, dtype, n, with_values):
    _assert_fused_matches_argsort(_keys(rng, dtype, n, 1), with_values)


@pytest.mark.parametrize("ands", [0, 3, 8, 30])
def test_fused_matches_argsort_entropy(rng, ands):
    _assert_fused_matches_argsort(entropy_keys(rng, 3000, ands), True)


def test_fused_matches_argsort_all_equal_and_sentinel(rng):
    for x in (np.zeros(1000, np.uint32),
              np.full(1000, 0xFFFFFFFF, np.uint32),     # == pad sentinel
              np.full(257, 0xDEADBEEF, np.uint32)):
        _assert_fused_matches_argsort(x, True)


def test_fused_matches_argsort_uint64(rng):
    from jax import enable_x64
    x = entropy_keys(rng, 2000, 2, dtype=np.uint64)
    with enable_x64():
        _assert_fused_matches_argsort(x, with_values=False)