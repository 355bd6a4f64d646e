"""Model-zoo behaviour tests: decode/forward consistency, MoE dispatch
equivalence (sort == dense), SSM chunking invariance, window masking."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params, forward, decode_step, init_cache
from repro.models.moe import moe_layer, init_moe
from repro.models.ssm import ssm_forward, init_ssm

KEY = jax.random.PRNGKey(1)


@pytest.mark.parametrize("arch", ["internlm2_1_8b", "mamba2_1_3b",
                                  "hymba_1_5b", "qwen3_moe_30b_a3b"])
def test_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    if cfg.is_moe:   # no-drop capacity so both paths keep all tokens
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    params = init_params(cfg, KEY)
    b, s = 2, 12
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    logits_full, _ = forward(params, cfg, {"tokens": tokens})
    cache = init_cache(cfg, b, 32)
    step = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c))
    outs = []
    for t in range(s):
        lg, cache = step(params, tokens[:, t:t + 1], cache)
        outs.append(lg[:, 0])
    err = float(jnp.max(jnp.abs(jnp.stack(outs, 1) - logits_full)))
    assert err < 2e-3, (arch, err)


def test_moe_sort_equals_dense_dispatch():
    cfg = get_smoke_config("qwen3_moe_30b_a3b")
    params = init_params(cfg, KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    l1, _ = forward(params, cfg, {"tokens": tokens})
    l2, _ = forward(params, dataclasses.replace(cfg, moe_dispatch="dense"),
                    {"tokens": tokens})
    assert float(jnp.max(jnp.abs(l1 - l2))) < 2e-4


def test_moe_grouped_dispatch_invariance():
    # with no drops, dispatch groups must not change the math
    cfg = dataclasses.replace(get_smoke_config("qwen3_moe_30b_a3b"),
                              capacity_factor=16.0)
    params = init_params(cfg, KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    l1, _ = forward(params, cfg, {"tokens": tokens})
    l2, _ = forward(params, dataclasses.replace(cfg, dispatch_groups=4),
                    {"tokens": tokens})
    assert float(jnp.max(jnp.abs(l1 - l2))) < 1e-4


def test_moe_capacity_drops_tokens():
    cfg = dataclasses.replace(get_smoke_config("qwen3_moe_30b_a3b"),
                              capacity_factor=0.1)
    params = init_params(cfg, KEY)
    moe_p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])  # layer 0
    x = jax.random.normal(KEY, (1, 16, cfg.d_model), jnp.float32)
    out, aux = moe_layer(moe_p, x, cfg)
    assert np.isfinite(np.asarray(out)).all()
    # with such a tiny capacity some tokens MUST differ from the no-drop run
    cfg2 = dataclasses.replace(cfg, capacity_factor=16.0)
    out2, _ = moe_layer(moe_p, x, cfg2)
    assert float(jnp.max(jnp.abs(out - out2))) > 1e-7


def test_ssm_chunk_invariance():
    cfg = get_smoke_config("mamba2_1_3b")
    params = init_params(cfg, KEY)["layers"]["ssm"]
    p0 = jax.tree.map(lambda a: a[0], params)     # layer 0
    x = jax.random.normal(KEY, (2, 64, cfg.d_model), jnp.float32)
    y1 = ssm_forward(p0, x, cfg)                                  # chunk 16
    y2 = ssm_forward(p0, x, dataclasses.replace(cfg, ssm_chunk=64))
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-3


def test_hymba_window_masks_differ():
    cfg = get_smoke_config("hymba_1_5b")          # window 8, layer 0 global
    params = init_params(cfg, KEY)
    b, s = 1, 24
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    l1, _ = forward(params, cfg, {"tokens": tokens})
    cfg_full = dataclasses.replace(cfg, attn_window=0)
    l2, _ = forward(params, cfg_full, {"tokens": tokens})
    # early positions identical (window not yet binding), late differ
    assert float(jnp.max(jnp.abs(l1[:, :4] - l2[:, :4]))) < 1e-4
    assert float(jnp.max(jnp.abs(l1[:, -1] - l2[:, -1]))) > 1e-6


def test_vlm_frontend_changes_output():
    cfg = get_smoke_config("internvl2_26b")
    params = init_params(cfg, KEY)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    p1 = jax.random.normal(KEY, (2, cfg.num_patches, cfg.d_model))
    logits, _ = forward(params, cfg, {"tokens": tokens, "patches": p1})
    assert logits.shape[1] == cfg.num_patches + 8
    logits2, _ = forward(params, cfg, {"tokens": tokens, "patches": p1 * 2})
    assert float(jnp.max(jnp.abs(logits - logits2))) > 1e-6


@pytest.mark.parametrize("arch", ["internlm2_1_8b", "mamba2_1_3b",
                                  "hymba_1_5b", "qwen3_moe_30b_a3b"])
def test_prefill_then_decode_matches_forward(arch):
    from repro.models import prefill
    cfg = get_smoke_config(arch)
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    if cfg.has_ssm:
        cfg = dataclasses.replace(cfg, ssm_chunk=8)
    params = init_params(cfg, KEY)
    b, s = 2, 16
    tokens = jax.random.randint(KEY, (b, s + 4), 0, cfg.vocab)
    lg, cache = prefill(params, cfg, {"tokens": tokens[:, :s]}, max_len=s + 8)
    full, _ = forward(params, cfg, {"tokens": tokens})
    errs = [float(jnp.max(jnp.abs(lg[:, 0] - full[:, s - 1])))]
    for t in range(4):
        lg, cache = decode_step(params, cfg, tokens[:, s + t:s + t + 1], cache)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, s + t]))))
    assert max(errs) < 2e-3, (arch, errs)


@pytest.mark.parametrize("s,block,window", [(17, 4, None), (32, 8, None),
                                            (40, 16, 8), (64, 64, None)])
def test_flash_attention_matches_naive(s, block, window):
    """Property sweep: blockwise flash == materialised softmax attention,
    including ragged tails and sliding windows."""
    import jax.numpy as jnp
    from repro.models.layers import flash_attention, _gqa_scores, _gqa_values
    key = jax.random.PRNGKey(0)
    b, h, hd = 2, 3, 16
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, hd))
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    w = None if window is None else jnp.int32(window)

    out_f = flash_attention(q, k, v, positions, w, block)
    scores = _gqa_scores(q, k, 1) / jnp.sqrt(jnp.float32(hd))
    ii, jj = positions[:, None, :, None], positions[:, None, None, :]
    mask = jj <= ii
    if w is not None:
        mask &= (w == 0) | (jj > ii - w)
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    out_n = _gqa_values(probs.astype(v.dtype), v, 1)
    assert float(jnp.max(jnp.abs(out_f - out_n))) < 1e-5
