"""Counting-sort partitioning primitives reused across the framework.

``counting_partition`` is one hybrid-radix counting pass (paper §4.1 steps
1–3) exposed as a standalone op — a thin client of
``core.plan.single_pass_partition``, the engine-selected implementation every
layer shares.  It is the core of:

  * MoE token dispatch (group tokens expert-major; E <= 2^d ⇒ exactly one pass),
  * data-pipeline length bucketing,
  * the shard-partitioning step of the distributed sort (§5).

``engine=None`` resolves exactly like the sort drivers
(``core.ranks.resolve_engine``: the fused Pallas ``kernel`` launch on a TPU,
``argsort`` elsewhere).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from repro.core import plan


class Partition(NamedTuple):
    dest: jnp.ndarray     # (m,) slot of element i in bucket-major order
    perm: jnp.ndarray     # (m,) gather order: sorted[j] = x[perm[j]]
    counts: jnp.ndarray   # (num_buckets,)
    offsets: jnp.ndarray  # (num_buckets,) exclusive prefix of counts


def counting_partition(bucket_ids: jnp.ndarray, num_buckets: int,
                       engine: Optional[str] = None,
                       interpret: Optional[bool] = None) -> Partition:
    """Stable partition of elements by ``bucket_ids`` (one counting pass)."""
    dest, perm, counts = plan.single_pass_partition(
        bucket_ids, num_buckets, engine=engine, interpret=interpret)
    offsets = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    return Partition(dest=dest, perm=perm, counts=counts, offsets=offsets)


class CapacityDispatch(NamedTuple):
    """Bucket-major, capacity-padded gather layout (the MoE dispatch shape)."""
    gather_idx: jnp.ndarray   # (num_buckets, capacity) source element per slot
    slot_valid: jnp.ndarray   # (num_buckets, capacity) bool
    position: jnp.ndarray     # (m,) element's slot within its bucket
    kept: jnp.ndarray         # (m,) bool — False if dropped by capacity
    counts: jnp.ndarray       # (num_buckets,)


def capacity_dispatch(bucket_ids: jnp.ndarray, num_buckets: int, capacity: int,
                      engine: Optional[str] = None,
                      interpret: Optional[bool] = None) -> CapacityDispatch:
    """Counting-sort dispatch into a dense (buckets, capacity) layout.

    This is the paper's scatter step with the destination chunk *reserved* per
    bucket (§4.4) — here the reservation is the static capacity row.  Elements
    beyond capacity are marked dropped (standard MoE semantics).
    """
    m = bucket_ids.shape[0]
    part = counting_partition(bucket_ids, num_buckets, engine=engine,
                              interpret=interpret)
    position = part.dest - part.offsets[bucket_ids]
    kept = position < capacity
    slot = jnp.where(kept, bucket_ids * capacity + position, num_buckets * capacity)
    gather_flat = jnp.full((num_buckets * capacity + 1,), m, jnp.int32)
    gather_flat = gather_flat.at[slot].set(jnp.arange(m, dtype=jnp.int32), mode="drop")
    gather_idx = gather_flat[:-1].reshape(num_buckets, capacity)
    slot_valid = gather_idx < m
    return CapacityDispatch(gather_idx=gather_idx, slot_valid=slot_valid,
                            position=position.astype(jnp.int32), kept=kept,
                            counts=part.counts)


def merge_sorted(a: jnp.ndarray, b: jnp.ndarray, va=None, vb=None):
    """Parallel merge of two sorted arrays (GPU merge-path analogue, §5's
    multiway merge building block) via vectorised binary search; optional
    values ride along (§4.6 pair semantics)."""
    na, nb = a.shape[0], b.shape[0]
    out = jnp.zeros((na + nb,), a.dtype)
    pos_a = jnp.arange(na) + jnp.searchsorted(b, a, side="left")
    pos_b = jnp.arange(nb) + jnp.searchsorted(a, b, side="right")
    merged = out.at[pos_a].set(a).at[pos_b].set(b)
    if va is None:
        return merged
    vout = jnp.zeros((na + nb,), va.dtype).at[pos_a].set(va).at[pos_b].set(vb)
    return merged, vout


def multiway_merge(runs: jnp.ndarray, values=None):
    """Merge (s, run_len) sorted runs by pairwise reduction (log2 s passes);
    optional (s, run_len) values permute alongside."""
    s = runs.shape[0]
    flat = [runs[i] for i in range(s)]
    vals = [values[i] for i in range(s)] if values is not None else None
    while len(flat) > 1:
        nxt, vnxt = [], []
        for i in range(0, len(flat) - 1, 2):
            if vals is None:
                nxt.append(merge_sorted(flat[i], flat[i + 1]))
            else:
                m, vm = merge_sorted(flat[i], flat[i + 1], vals[i], vals[i + 1])
                nxt.append(m)
                vnxt.append(vm)
        if len(flat) % 2:
            nxt.append(flat[-1])
            if vals is not None:
                vnxt.append(vals[-1])
        flat = nxt
        if vals is not None:
            vals = vnxt
    return flat[0] if values is None else (flat[0], vals[0])
