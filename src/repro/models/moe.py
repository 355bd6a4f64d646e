"""Mixture-of-Experts layer with *sort-based* token dispatch.

The integration point of the paper: routing top-k tokens to E experts is a
single hybrid-radix counting pass on the expert id (E <= 2^d: qwen3's 128
experts are one d=7 digit, kimi-k2's 384 one d=9 digit).  The dispatch uses
``repro.core.segmented.capacity_dispatch`` — histogram, prefix-sum, scatter
(§4.1 steps 1–3) with the capacity row playing the paper's reserved memory
chunk (§4.4).  The underlying pass is ``core.plan.single_pass_partition``,
the same engine-selected primitive as length bucketing and the distributed
shard partition: on a TPU the Mosaic-compiled fused Pallas launch (the
dispatch is vmapped over token groups, so the pass runs once per group),
an XLA stable sort elsewhere unless ``engine="kernel"`` is asked for.

Dispatch is *grouped*: tokens are viewed as (G, T/G) with G = number of data
shards, so every group's counting pass stays shard-local (the distributed
analogue of the paper's per-block shared-memory partitioning) and only the
expert-major buffers cross the mesh to reach their (model-axis sharded)
experts.

A GShard-style dense one-hot dispatch is kept as the measured baseline
(``moe_dispatch="dense"``) — it is to the sort-based dispatch what CUB's LSD
sort is to the hybrid sort: same result, more memory traffic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.segmented import capacity_dispatch
from repro.models.layers import dense_init


def init_moe(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    scale = 0.02
    return {
        "router": dense_init(ks[0], d, e, jnp.float32),   # fp32 routing
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * scale).astype(dtype),
    }


def _route(x_flat, router, top_k: int):
    from repro.models.layers import constrain, dp_axes
    from jax.sharding import PartitionSpec as P
    logits = x_flat.astype(jnp.float32) @ router            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = constrain(probs, P(dp_axes(), None))            # token-sharded top_k
    weights, ids = jax.lax.top_k(probs, top_k)              # (T, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary (Switch-style)
    e = router.shape[1]
    frac_tokens = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return weights, ids.astype(jnp.int32), aux


def _expert_ffn(buf, params):
    """buf: (E, C, d) expert-major tokens -> (E, C, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def _sort_dispatch(xg, ids, wts, params, e: int, capacity: int):
    """Sort-based dispatch/combine over (G, Tg, ·) grouped tokens.

    The heavy buffers are built OUTSIDE any vmap so their mesh layout can be
    constrained explicitly: groups stay on their data shards, the expert axis
    lives on the model axis — the per-chip wire volume is the EP optimum
    (tokens/chip x top_k x d each way), not a replicated buffer.
    """
    from repro.models.layers import constrain, dp_axes
    from jax.sharding import PartitionSpec as P
    g, tg, k = ids.shape
    d = xg.shape[-1]
    dp = dp_axes()

    flat_ids = ids.reshape(g, tg * k)
    # engine=None -> the backend-resolved shared partition engine (core.plan)
    cd = jax.vmap(lambda i: capacity_dispatch(i, e, capacity, engine=None))(
        flat_ids)
    token_of = jnp.minimum(cd.gather_idx, tg * k - 1) // k        # (G, E, C)
    # indices take the EP layout FIRST: the gather then reads the (model-)
    # replicated activations locally on each expert shard — zero dispatch wire.
    # gathers/scatters are vmapped over G so the batch dim stays structural
    # (an explicit arange(G) index makes GSPMD replicate the whole buffer).
    token_of = constrain(token_of, P(dp, "model", None))
    xg = constrain(xg, P(dp, None, None))
    buf = jax.vmap(lambda xr, tr: xr[tr])(xg, token_of)           # (G,E,C,d)
    buf = jnp.where(cd.slot_valid[..., None], buf, 0).astype(xg.dtype)
    buf = constrain(buf, P(dp, "model", None, None))              # EP layout

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, params["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    out = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    out = constrain(out, P(dp, "model", None, None))

    # combine: weighted scatter-add back to token-major order — each expert
    # shard contributes partial sums, GSPMD reduces them (one all-reduce of
    # (Tg, d) per group: the same wire as a dense TP block, independent of E).
    # Every scatter operand is constrained so the partial scatters stay
    # data-sharded on G and model-sharded on E (never replicated).
    wts_flat = jnp.take_along_axis(
        wts.reshape(g, tg * k),
        jnp.minimum(cd.gather_idx, tg * k - 1).reshape(g, -1), axis=1
    ).reshape(g, e, capacity)
    contrib = out * jnp.where(cd.slot_valid, wts_flat, 0.0)[..., None].astype(out.dtype)
    contrib = constrain(contrib, P(dp, "model", None, None))
    zeros = constrain(jnp.zeros((g, tg, d), out.dtype), P(dp, None, None))
    comb = jax.vmap(lambda z, t, c: z.at[t].add(c))(zeros, token_of, contrib)
    return constrain(comb, P(dp, None, None))                     # (G, Tg, d)


def _group_dispatch_dense(xg, ids, wts, params, e: int, capacity: int):
    """GShard-style dense one-hot dispatch (the measured baseline)."""
    tg, k = ids.shape
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.int32)        # (Tg, k, E)
    pos = jnp.cumsum(onehot.reshape(tg * k, e), axis=0).reshape(tg, k, e) - onehot
    kept = (pos < capacity) & (onehot > 0)
    poh = jax.nn.one_hot(jnp.where(kept, pos, capacity), capacity,
                         dtype=xg.dtype)                    # (Tg, k, E, C)
    mask = poh * kept[..., None].astype(xg.dtype)
    buf = jnp.einsum("tkec,td->ecd", mask, xg)              # dense scatter
    out = _expert_ffn(buf, params)
    per_assign = jnp.einsum("tkec,ecd->tkd", mask, out)
    return jnp.sum(per_assign * wts[..., None].astype(out.dtype), axis=1)


def moe_layer(params, x, cfg, *, groups: int = 1):
    """x: (B, S, d) -> (B, S, d), aux loss scalar."""
    b, s, d = x.shape
    t = b * s
    g = groups if t % groups == 0 else 1
    x_flat = x.reshape(t, d)
    wts, ids, aux = _route(x_flat, params["router"], cfg.top_k)
    from repro.models.layers import constrain, dp_axes
    from jax.sharding import PartitionSpec as P
    dp = dp_axes()
    wts = constrain(wts, P(dp, None))      # keep routing tables token-sharded
    ids = constrain(ids, P(dp, None))

    tg = t // g
    capacity = max(4, int(cfg.capacity_factor * tg * cfg.top_k / cfg.num_experts))
    capacity = min(capacity, tg * cfg.top_k)
    if cfg.moe_dispatch == "sort":
        out = _sort_dispatch(x_flat.reshape(g, tg, d),
                             ids.reshape(g, tg, cfg.top_k),
                             wts.reshape(g, tg, cfg.top_k),
                             params, cfg.num_experts, capacity)
    else:
        out = jax.vmap(_group_dispatch_dense, in_axes=(0, 0, 0, None, None, None))(
            x_flat.reshape(g, tg, d), ids.reshape(g, tg, cfg.top_k),
            wts.reshape(g, tg, cfg.top_k), params, cfg.num_experts, capacity)
    return out.reshape(b, s, d), aux


# --- contract declaration (verified by repro.analysis; see analysis/contracts)
# The sort-path MoE dispatch is one capacity_dispatch per token group: ONE
# counting pass (prologue histogram + fused launch) with the iota permutation
# riding as the single value leaf — the same contract shape as
# plan.single_pass_partition, declared here because the dispatch is the
# consumer whose traffic budget depends on it.
ANALYSIS_CONTRACT = {
    "entry": "repro.core.segmented.capacity_dispatch",
    "census": {
        "launch_total": "2",
        "while_body_launches": "[]",
        "fused_grid": "ceil_div(g_max, B)",
    },
    "sort_free": True,
    "donation": {"fused_counting_pass": "1 + vals"},
    "transfer": {
        "sweep_kernels": ["radix_histogram_total", "fused_counting_pass"],
        "bytes": "(2 * passes + 1) * n_pad * kb + 2 * passes * n_pad * vb",
    },
}
