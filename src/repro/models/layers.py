"""Shared transformer building blocks (RMSNorm, RoPE, GQA attention, SwiGLU).

Functional style: ``init_*`` builds parameter pytrees (plain dicts), ``apply``
functions are pure.  Sharding is applied by name-based rules at the launcher
level (launch/sharding.py), so layers stay mesh-agnostic.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float = 0.02):
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def abstract_mesh():
    """The trace-time mesh (``jax.sharding.get_abstract_mesh``), or ``None``
    when no mesh is bound — the layer helpers below then degrade to their
    off-mesh no-ops."""
    am = jax.sharding.get_abstract_mesh()
    return am if am and am.axis_names else None


def dp_axes():
    """Batch-carrying mesh axes visible at trace time (() off-mesh)."""
    am = abstract_mesh()
    names = tuple(am.axis_names or ()) if am else ()
    return tuple(a for a in ("pod", "data") if a in names)


def constrain(x, spec):
    """with_sharding_constraint that no-ops off-mesh (smoke tests, 1 device).

    Layers stay mesh-agnostic: constraints bind only when the launcher traces
    under ``jax.set_mesh`` (axis names resolved from the abstract mesh).
    """
    am = abstract_mesh()
    names = set(am.axis_names or ()) if am else set()
    used = {a for part in spec if part is not None
            for a in (part if isinstance(part, tuple) else (part,))}
    if not used or not used.issubset(names):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def rms_norm(x, weight, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------- GQA attention ----------------------------------

def init_attention(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = cfg.n_heads_padded, cfg.n_kv_padded   # TP head padding (see config)
    return {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kv * hd, dtype),
        "wv": dense_init(ks[2], d, kv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }


def _pad_head_mask(cfg):
    """Validity mask over padded Q heads: pad heads contribute exactly zero
    (and receive zero gradients), so the padded model computes the unpadded
    architecture while every head tensor shards over the model axis."""
    h, kv = cfg.n_heads_padded, cfg.n_kv_padded
    n_rep = h // kv
    rep_real = cfg.n_heads // cfg.n_kv_heads
    hidx = jnp.arange(h)
    return ((hidx // n_rep < cfg.n_kv_heads)
            & (hidx % n_rep < rep_real))


def _gqa_scores(q, k, n_rep: int):
    """q: (B,S,H,hd), k: (B,T,KV,hd) -> (B,H,S,T).

    KV heads are broadcast to H (jnp.repeat) instead of folding H into
    (KV, rep): a reshape of the model-sharded H axis would break the head
    sharding and make GSPMD replicate the O(S^2) score tensor per chip.
    """
    k = jnp.repeat(k, n_rep, axis=2) if n_rep > 1 else k
    return jnp.einsum("bshd,bthd->bhst", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_values(probs, v, n_rep: int):
    """probs: (B,H,S,T) in v.dtype, v: (B,T,KV,hd) -> (B,S,H,hd) f32-accum."""
    v = jnp.repeat(v, n_rep, axis=2) if n_rep > 1 else v
    return jnp.einsum("bhst,bthd->bshd", probs, v,
                      preferred_element_type=jnp.float32)


def attention(params, x, cfg, *, positions=None, kv_cache=None,
              cache_len=None, window=None, dtype=None):
    """GQA attention in three modes:

      train/prefill: kv_cache=None — full causal self-attention (optionally
        sliding-window limited for hybrid archs);
      decode: kv_cache=(k,v) with static length T — x is (B, 1, d), cache_len
        gives the number of valid cache entries; returns updated cache.
    """
    b, s, d = x.shape
    hd, h, kvh = cfg.head_dim, cfg.n_heads_padded, cfg.n_kv_padded
    n_rep = h // kvh
    padded = bool(cfg.head_pad_to or cfg.kv_pad_to)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]

    q = (x @ params["wq"]).reshape(b, s, h, hd)
    k = (x @ params["wk"]).reshape(b, s, kvh, hd)
    v = (x @ params["wv"]).reshape(b, s, kvh, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if kv_cache is None:
        if getattr(cfg, "attention_impl", "naive") == "flash":
            k_rep = jnp.repeat(k, n_rep, axis=2) if n_rep > 1 else k
            v_rep = jnp.repeat(v, n_rep, axis=2) if n_rep > 1 else v
            out = flash_attention(q, k_rep, v_rep, positions, window,
                                  min(cfg.flash_block, s))
        else:
            scores = _gqa_scores(q, k, n_rep) / jnp.sqrt(hd).astype(jnp.float32)
            ii = positions[:, None, :, None]              # query pos
            jj = positions[:, None, None, :]              # key pos
            mask = jj <= ii
            if window is not None:                 # traced per-layer window; 0 = full
                mask &= (window == 0) | (jj > ii - window)
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            out = _gqa_values(probs, v, n_rep)
        new_cache = (k, v)          # callers collecting a prefill cache use this
    else:
        ck, cv = kv_cache                                  # (B, T, KV, hd)
        t = ck.shape[1]
        idx = cache_len.astype(jnp.int32)
        ck = jax.lax.dynamic_update_slice(ck, k, (0, idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, idx, 0, 0))
        scores = _gqa_scores(q, ck, n_rep) / jnp.sqrt(hd).astype(jnp.float32)
        jj = jnp.arange(t, dtype=jnp.int32)[None, None, None, :]
        valid = jj <= cache_len
        if window is not None:                     # traced per-layer window
            valid &= (window == 0) | (jj > cache_len - window)
        scores = jnp.where(valid, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
        out = _gqa_values(probs, cv, n_rep)
        new_cache = (ck, cv)

    if padded:
        out = out * _pad_head_mask(cfg)[None, None, :, None].astype(out.dtype)
    out = out.reshape(b, s, h * hd).astype(x.dtype) @ params["wo"]
    return out, new_cache


# --------------------------- flash attention --------------------------------

def flash_attention(q, k, v, positions, window, block: int):
    """Blockwise online-softmax attention (Rabe & Staats / FlashAttention).

    q: (B,S,H,hd); k,v already KV-head-broadcast to (B,T,H,hd).
    Never materialises the (B,H,S,T) score tensor: the KV axis is streamed in
    ``block``-sized tiles with running max/denominator — the structural fix
    for the memory-bound roofline term of every train/prefill cell.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    pad = (-t) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = k.shape[1] // block
    kb = k.reshape(b, nblk, block, h, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, block, h, hd).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / float(np_sqrt(hd))
    ii = positions[:, None, :, None]                       # (B,1,S,1)

    def step(carry, inp):
        m, l, acc = carry                                  # (B,H,S),(B,H,S),(B,S,H,hd)
        kblk, vblk, t0 = inp
        sblk = jnp.einsum("bshd,bthd->bhst", q, kblk,
                          preferred_element_type=jnp.float32) * scale
        jj = (t0 + jnp.arange(block, dtype=jnp.int32))[None, None, None, :]
        mask = (jj <= ii) & (jj < t)
        if window is not None:
            mask &= (window == 0) | (jj > ii - window)
        sblk = jnp.where(mask, sblk, -1e30)
        m_new = jnp.maximum(m, sblk.max(axis=-1))
        p = jnp.exp(sblk - m_new[..., None])               # (B,H,S,blk)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, h, s), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, s), jnp.float32),
            jnp.zeros((b, s, h, hd), jnp.float32))
    t0s = jnp.arange(nblk, dtype=jnp.int32) * block
    (m, l, acc), _ = jax.lax.scan(step, init, (kb, vb, t0s))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out


def np_sqrt(x):
    import math
    return math.sqrt(x)


# --------------------------- SwiGLU MLP -------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    return {"w_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "w_up": dense_init(ks[1], d_model, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d_model, dtype)}


def mlp(params, x):
    return (jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]
