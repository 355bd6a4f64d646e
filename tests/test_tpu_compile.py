"""Compile-only rehearsal: the kernel path lowers for a TPU v5e.

Interpret mode cannot see what Mosaic refuses (tiling, memory spaces,
primitives without a TPU lowering), so these tests compile the main-path
kernels — and the whole kernel-engine sort at 2^26 keys — for a *described*
v5e chip.  Nothing runs; the compiler's verdict and its memory analysis are
the result.  The topology is described inside a module-scoped fixture, so
only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hybrid, model, plan
from repro.core.segmented import capacity_dispatch
from repro.kernels import fused
from repro.kernels.bitonic import bitonic_sort_rows_stable

HBM_BYTES = 16 * 2**30            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-chip compiles cannot be read back from the persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
            ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return used


def test_prologue_histogram_compiles(one_chip):
    n, kpb = 1 << 24, 6912
    buf = _spec(one_chip, (fused.buffer_length(n, kpb) // 128, 128),
                jnp.uint32)
    c = _compile(lambda b: fused.initial_histogram(
        b, n, 24, 8, 256, 8, interpret=False), buf)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("vals", [0, 1], ids=["keys", "kv"])
def test_fused_pass_compiles(one_chip, vals):
    """One counting pass at the default uint32 config, lookahead on."""
    n, cfg = 1 << 22, model.default_config(4)
    r = cfg.radix
    a_max = model.max_active_buckets(n, cfg)
    g_max = plan.max_region_blocks(n, cfg.kpb, a_max)
    g_steps = -(-g_max // cfg.step_batch)
    rows = fused.buffer_length(n, cfg.kpb) // 128
    buf = _spec(one_chip, (rows, 128), jnp.uint32)
    tab = _spec(one_chip, (g_steps, cfg.step_batch), jnp.int32)
    args = (buf, (buf,) * vals, buf, (buf,) * vals,
            _spec(one_chip, (6,), jnp.int32), tab, tab, tab, tab, tab,
            _spec(one_chip, (a_max, r), jnp.int32),
            _spec(one_chip, (a_max * r,), jnp.int32))
    c = _compile(lambda *a: fused.fused_counting_pass(
        *a, kpb=cfg.kpb, r=r, a_max=a_max, interpret=False,
        lookahead=True), *args)
    assert "tpu_custom_call" in c.as_text()


def test_widest_bitonic_class_compiles(one_chip):
    n, cfg = 1 << 26, model.default_config(4)
    width, rows = hybrid.local_sort_classes(n, cfg)[-1]
    assert width == 16384
    c = _compile(lambda k, i: bitonic_sort_rows_stable(k, i, interpret=False),
                 _spec(one_chip, (rows, width), jnp.uint32),
                 _spec(one_chip, (rows, width), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_vmapped_moe_dispatch_compiles(one_chip):
    """The MoE layer's dispatch: capacity_dispatch vmapped over token groups
    (4 x 8,192 tokens x top-8 of qwen3's 128 experts) on the kernel engine.
    Mosaic cannot batch the fused pass's HBM refs, so it loops per group."""
    from repro.utils import hlo
    groups, tokens, top_k, experts = 4, 8192, 8, 128
    capacity = int(1.25 * tokens * top_k / experts)
    fn = jax.vmap(lambda i: capacity_dispatch(
        i, experts, capacity, engine="kernel", interpret=False))
    lowered = jax.jit(fn).lower(
        _spec(one_chip, (groups, tokens * top_k), jnp.int32))
    assert hlo.sort_op_count(lowered.as_text()) == 0
    c = lowered.compile()
    assert c.as_text().count("tpu_custom_call") == 2
    _fits(c)


def test_kernel_engine_sort_compiles_and_fits_one_chip(one_chip):
    """The whole kernel-engine sort at 2^26 keys: Mosaic kernels only, no
    comparison sort, and a program that fits one chip's 16 GiB."""
    from repro.utils import hlo
    n, cfg = 1 << 26, model.default_config(4)
    fn = lambda k: hybrid._hybrid_sort_bits(
        k, (), cfg, 32, True, None, "kernel", False, adaptive=True)
    lowered = jax.jit(fn).lower(_spec(one_chip, (n,), jnp.uint32))
    assert hlo.sort_op_count(lowered.as_text()) == 0
    c = lowered.compile()
    assert c.as_text().count("tpu_custom_call") == \
        2 + len(hybrid.local_sort_classes(n, cfg))
    _fits(c)


# --- names a profile of the chip reads (bench/stages.py, bench/events.json)

KERNELS = ["fused_counting_pass", "radix_histogram_total",
           "bitonic_sort_rows_stable"]
SCOPES = ["ping_pong", "prologue_histogram", "pass_bookkeeping",
          "counting_pass", "local_sort/bounds", "local_sort/rows",
          "local_sort/bitonic", "local_sort/copy_back", "unpad"]


@pytest.fixture(scope="module")
def named_program(one_chip):
    """HLO instruction name -> op_name of the compiled kernel-engine sort of
    2^14 key-value pairs: what a trace of the chip names and what the
    benchmark maps back to the sort's stages."""
    import re
    n, cfg = 1 << 14, model.default_config(4)
    spec = _spec(one_chip, (n,), jnp.uint32)
    c = _compile(lambda k, v: hybrid._hybrid_sort_bits(
        k, v, cfg, 32, False, None, "kernel", False, adaptive=True),
        spec, spec)
    return dict(re.findall(r'%(\S+) = [^\n]*op_name="([^"]*)"', c.as_text()))


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_instructions_keep_their_names(named_program, kernel):
    """Each Mosaic kernel is an instruction named after its pallas_call, the
    prefix ``bench/events.json`` finds it by, and its ``name=`` is in its
    op_name."""
    named = {i: o for i, o in named_program.items()
             if i.startswith(kernel + ".")}
    assert named and all(f"/{kernel}/pallas_call" in o
                         for o in named.values())


@pytest.mark.parametrize("scope", SCOPES)
def test_stage_scopes_reach_the_compiled_program(named_program, scope):
    assert any("/hybrid_sort/" in o and f"/{scope}/" in o
               for o in named_program.values())
