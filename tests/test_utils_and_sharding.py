"""Unit tests: HLO collective parsing, roofline math, sharding rules,
64-bit key support, gradient compression."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.utils.hlo import collective_bytes, collective_counts
from repro.utils.roofline import Roofline, model_flops, PEAK_FLOPS


HLO = """
  %ar = f32[16,4096,2048]{2,1,0} all-reduce(%x), replica_groups={{0,1,2,3}}
  %ag = bf16[8,128]{1,0} all-gather(%y), replica_groups=[32,16]<=[512]
  %rs = f32[64]{0} reduce-scatter(%z), replica_groups={{0,1}}
  %a2a = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) all-to-all(%a, %b), replica_groups={{0,1,2,3}}
  %cp = u32[10]{0} collective-permute(%c), source_target_pairs={{0,1}}
  %notacoll = f32[2]{0} add(%p, %q)
"""


def test_collective_bytes_parsing():
    out = collective_bytes(HLO, 512)
    # all-reduce: 16*4096*2048*4 bytes * 2 * 3/4
    assert abs(out["all-reduce"] - 16 * 4096 * 2048 * 4 * 2 * 0.75) < 1
    # all-gather: 8*128*2 * 15/16 (group size 16 from [32,16] form)
    assert abs(out["all-gather"] - 8 * 128 * 2 * 15 / 16) < 1
    # reduce-scatter: out 64*4 * P=2 * 1/2
    assert abs(out["reduce-scatter"] - 64 * 4 * 2 * 0.5) < 1
    # permute: full size
    assert abs(out["collective-permute"] - 40) < 1
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_collective_counts():
    c = collective_counts(HLO)
    assert c == {"all-reduce": 1, "all-gather": 1, "reduce-scatter": 1,
                 "all-to-all": 1, "collective-permute": 1}


# async collectives split into -start/-done: the start result is a tuple
# (operand, output, context buffers) — summing it would double-count — and
# replica_groups usually annotates only the start line
HLO_ASYNC = """
  %cps = (u32[10]{0}, u32[10]{0}, u32[], u32[]) collective-permute-start(%c), source_target_pairs={{0,1}}
  %cpd = u32[10]{0} collective-permute-done(%cps)
  %ags = (bf16[8,128]{1,0}, bf16[8,2048]{1,0}) all-gather-start(%y), replica_groups=[32,16]<=[512]
  %agd = bf16[8,2048]{1,0} all-gather-done(%ags)
  %ar = f32[64]{0} all-reduce(%x), replica_groups={{0,1}}
"""


def test_async_collective_pairs_count_once():
    """-start/-done pairs: bytes from the done op's true output shape (at
    the start line's group size), sites counted at the start — one each,
    never zero, never double."""
    out = collective_bytes(HLO_ASYNC, 512)
    assert abs(out["collective-permute"] - 40) < 1          # one hop, 10*u32
    # all-gather output 8*2048*bf16, group size 16 carried from the start
    assert abs(out["all-gather"] - 8 * 2048 * 2 * 15 / 16) < 1
    assert abs(out["all-reduce"] - 64 * 4 * 2 * 0.5) < 1    # sync op intact
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
    assert collective_counts(HLO_ASYNC) == {
        "collective-permute": 1, "all-gather": 1, "all-reduce": 1}


def test_roofline_terms():
    r = Roofline(arch="a", shape="s", step="train", mesh="pod", chips=256,
                 flops_per_chip=197e12, hbm_bytes_per_chip=819e9,
                 coll_bytes_per_chip=50e9, model_flops_global=197e12 * 256,
                 mem_per_chip=8 * 2**30)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert abs(r.t_collective - 1.0) < 1e-9
    assert r.fits and abs(r.useful_flops_fraction - 1.0) < 1e-9


def test_model_flops_kinds():
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    cfg = get_config("deepseek_7b")
    n = cfg.active_param_count()
    assert model_flops(cfg, SHAPES["train_4k"]) == 6.0 * n * SHAPES["train_4k"].tokens
    assert model_flops(cfg, SHAPES["decode_32k"]) == 2.0 * n * 128


def test_param_spec_rules():
    from repro.launch.sharding import param_spec
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    from repro.configs import get_config
    cfg = get_config("deepseek_67b")          # 64 heads, fsdp
    mesh = FakeMesh()
    # column-parallel q (stacked layer param)
    assert param_spec("['layers']['attn']['wq']", (95, 8192, 8192), cfg, mesh) \
        == P(None, None, "model")
    # kv heads (8) not divisible by 16 and fsdp fallback on in-dim
    assert param_spec("['layers']['attn']['wk']", (95, 8192, 1024), cfg, mesh) \
        == P(None, "data", None)
    # factored adafactor state for lm_head: rank-1 -> replicate
    assert param_spec("['s']['lm_head']['vr']", (8192,), cfg, mesh) in (P(), P(None))
    # musicgen: 24 heads padded to 32 (head_pad_to) -> attention now shards
    mg = get_config("musicgen_medium")
    assert mg.n_heads_padded == 32 and mg.n_kv_padded == 32
    assert param_spec("['layers']['attn']['wq']", (48, 1536, 2048), mg, mesh) \
        == P(None, None, "model")
    assert param_spec("['layers']['mlp']['w_gate']", (48, 1536, 6144), mg, mesh) \
        == P(None, None, "model")
    # hymba keeps 25 unpadded heads -> attention replicates
    hy = get_config("hymba_1_5b")
    assert param_spec("['layers']['attn']['wq']", (32, 1600, 1600), hy, mesh) \
        == P(None, None, None)


def test_param_spec_normalizes_single_axis_tuples():
    """Every rule branch that shards over the data axes must emit the bare
    axis name on a one-axis data mesh — ``P(None, 'data', None)``, never
    ``P(None, ('data',), None)`` — and keep the real tuple on a pod+data
    mesh.  Covers each fsdp/data branch of param_spec plus the batch/cache
    spec helpers."""
    from repro.launch.sharding import (param_spec, batch_specs, cache_specs,
                                       _norm_axis)
    from repro.configs import get_config
    from jax.sharding import PartitionSpec as P

    class DataMesh:
        shape = {"data": 16, "model": 7}      # model=7: head dims don't divide
        axis_names = ("data", "model")

    class PodMesh:
        shape = {"pod": 2, "data": 8, "model": 7}
        axis_names = ("pod", "data", "model")

    cfg = get_config("deepseek_67b")          # fsdp_params=True, 64/8 heads
    mesh = DataMesh()

    def flat(spec):
        return [ax for ax in spec]

    # wq/wk/wv fsdp fallback (heads % 7 != 0): in-dim shards over data
    for w in ("wq", "wk", "wv"):
        spec = param_spec(f"['layers']['attn']['{w}']", (95, 8192, 1024),
                          cfg, mesh)
        assert spec == P(None, "data", None), w
        assert not any(isinstance(ax, tuple) for ax in flat(spec)), w
    # wo fsdp fallback: out-dim shards over data
    assert param_spec("['layers']['attn']['wo']", (95, 8192, 8192), cfg,
                      mesh) == P(None, None, "data")
    # dense FFN fsdp: the non-f dim shards over data (f dim % 7 != 0)
    assert param_spec("['layers']['mlp']['w_gate']", (95, 8192, 22016), cfg,
                      mesh) == P(None, "data", None)
    assert param_spec("['layers']['mlp']['w_down']", (95, 22016, 8192), cfg,
                      mesh) == P(None, None, "data")
    # MoE expert stacks (E, d, f): fsdp shards d over data
    moe = get_config("qwen3_moe_30b_a3b")
    assert moe.fsdp_params
    assert param_spec("['layers']['moe']['w_gate']", (48, 3, 2048, 768), moe,
                      mesh) == P(None, None, "data", None)
    # batch / cache specs emit the bare name too
    from repro.configs.base import SHAPES
    shape = next(iter(SHAPES.values()))
    tok = batch_specs(cfg, mesh, shape)["tokens"]
    assert not any(isinstance(ax, tuple) for ax in flat(tok))
    cs = cache_specs(cfg, mesh, batch=16, max_len=128)
    assert cs.kv_k is not None
    assert not any(isinstance(ax, tuple) for ax in flat(cs.kv_k))

    # a genuine multi-axis data mesh keeps the ('pod', 'data') tuple
    pod = PodMesh()
    spec = param_spec("['layers']['attn']['wk']", (95, 8192, 1024), cfg, pod)
    assert spec == P(None, ("pod", "data"), None)
    # the helper itself: scalars and multi-tuples pass through, () -> None
    assert _norm_axis("data") == "data"
    assert _norm_axis(("data",)) == "data"
    assert _norm_axis(("pod", "data")) == ("pod", "data")
    assert _norm_axis(()) is None
    assert _norm_axis(None) is None


def test_u64_keys_subprocess():
    """64-bit keys need x64 — isolated in a subprocess."""
    script = textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax.numpy as jnp
        from repro.core import hybrid_sort, SortConfig
        rng = np.random.default_rng(0)
        x = rng.integers(0, 2**64, 20000, dtype=np.uint64)
        cfg = SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)
        out, stats = hybrid_sort(jnp.asarray(x), cfg=cfg, return_stats=True)
        assert np.array_equal(np.sort(x), np.asarray(out))
        xf = rng.standard_normal(5000)
        assert np.array_equal(np.sort(xf), np.asarray(hybrid_sort(jnp.asarray(xf), cfg=cfg)))
        print("U64-OK", int(stats.counting_passes))
    """)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert "U64-OK" in res.stdout, res.stdout + res.stderr


@pytest.mark.slow
def test_compressed_psum_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum
        from repro.core.distributed import _shard_map   # jax-version shim
        mesh = jax.make_mesh((4,), ("pod",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 256)).astype(np.float32))
        exact = _shard_map(lambda v: jax.lax.psum(v, "pod"), mesh,
                           (P("pod"),), P())(x)
        comp = _shard_map(lambda v: compressed_psum(v, "pod"), mesh,
                          (P("pod"),), P())(x)
        rel = float(jnp.max(jnp.abs(comp - exact)) / jnp.max(jnp.abs(exact)))
        assert rel < 0.05, rel
        print("COMP-OK", rel)
    """)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert "COMP-OK" in res.stdout, res.stdout + res.stderr


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"], ids=["unset", "set"])
def test_compile_cache_dir(monkeypatch, env):
    """Unset: the checkout's fixed, git-ignored ``.jax_cache``.  Set: JAX's
    own variable wins and no other directory is configured."""
    from repro.utils import compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = compile_cache.enable_compile_cache()
        if env is None:
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            with open(os.path.join(root, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
        else:
            assert got == env
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
