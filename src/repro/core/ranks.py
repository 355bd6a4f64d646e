"""Stable-partition rank/permutation engines for counting-sort passes.

A counting-sort pass needs, for every key, its destination slot: keys are
grouped by bucket id (digit value, possibly composite with a segment id) with
ties broken by input position (stability *within a pass* — the paper drops
stability *across* passes, not within one partitioning step).

Two engines compute the same permutation:

  * ``argsort`` — one XLA stable sort of the composite id.  O(n log n)
    comparisons but a single fused, heavily-optimised op; the default on CPU
    (and a perfectly good TPU fallback).
  * ``scan``    — the paper-faithful O(n) two-level scheme: per-chunk
    histograms + in-chunk ranks (what the Pallas kernels implement per tile),
    with a carried running histogram across chunks.  A first-class fallback
    engine wherever the Pallas kernels are unavailable but O(n log n)
    comparison sorts are unwanted.

A third engine name, ``kernel``, selects the Pallas tile pipeline (histogram →
multisplit → run copies); it lives in ``repro.kernels.ops`` because it moves
keys directly rather than producing a standalone ``dest`` array.  The sort
drivers accept any of the three (or ``auto``) and route through
``resolve_engine`` below.

Both jnp engines return ``dest`` with: element i moves to slot ``dest[i]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: Engines understood by the sort drivers (hybrid_sort / lsd_sort).
ENGINES = ("argsort", "scan", "kernel")


def resolve_engine(engine=None, backend=None) -> str:
    """Resolve ``None``/``"auto"`` to the per-backend default engine.

    TPUs default to the Pallas ``kernel`` engine (the paper's O(n) pipeline);
    everything else defaults to ``argsort`` — interpret-mode kernels are
    bit-exact but slow, so on CPU they are opt-in.
    """
    if engine in (None, "auto"):
        backend = backend or jax.default_backend()
        return "kernel" if backend == "tpu" else "argsort"
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")
    return engine


def resolve_interpret(interpret=None) -> bool:
    """Pallas interpret mode: on iff the backend is not a TPU.

    The one place the rule lives: every sort entry point resolves its
    ``interpret`` argument here, and the kernel wrappers take it explicitly,
    so no call on a TPU reaches a kernel in interpret mode unasked.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def invert_permutation(perm: jnp.ndarray) -> jnp.ndarray:
    """dest[i] such that sorted[dest[i]] = x[i], given perm = argsort order."""
    n = perm.shape[0]
    return jnp.zeros((n,), dtype=perm.dtype).at[perm].set(jnp.arange(n, dtype=perm.dtype))


def stable_partition_dest_argsort(bucket: jnp.ndarray) -> jnp.ndarray:
    """Destination slots of a stable partition by ``bucket`` (int array)."""
    perm = jnp.argsort(bucket, stable=True)
    return invert_permutation(perm)


@functools.partial(jax.jit, static_argnames=("num_buckets", "chunk"))
def stable_partition_dest_scan(bucket: jnp.ndarray, num_buckets: int,
                               chunk: int = 2048) -> jnp.ndarray:
    """O(n) counting-rank engine: chunked scan with a carried histogram.

    Mirrors the TPU kernel structure: per-chunk one-hot histogram (MXU-shaped),
    in-chunk exclusive cumulative count, global exclusive bucket offsets, and a
    cross-chunk carry — the jnp analogue of the paper's block histograms (M3)
    plus the scatter offsets.
    """
    n = bucket.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    pad = (-n) % chunk
    b = jnp.pad(bucket.astype(jnp.int32), (0, pad), constant_values=num_buckets)
    nb = num_buckets + 1  # one trash bucket for padding
    tiles = b.reshape(-1, chunk)

    def tile_hist(row):
        return jnp.zeros((nb,), jnp.int32).at[row].add(1)

    hists = jax.vmap(tile_hist)(tiles)                       # (T, nb)
    total = hists.sum(axis=0)
    # global exclusive offsets per bucket
    g_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(total)[:-1].astype(jnp.int32)])
    # exclusive-over-tiles carry per bucket
    carry = jnp.concatenate([jnp.zeros((1, nb), jnp.int32),
                             jnp.cumsum(hists, axis=0)[:-1].astype(jnp.int32)])

    def tile_ranks(row, carry_row):
        if nb <= 4096:
            onehot = jax.nn.one_hot(row, nb, dtype=jnp.int32)  # (chunk, nb)
            incl = jnp.cumsum(onehot, axis=0)
            excl = incl - onehot                               # rank within tile
            in_tile = jnp.take_along_axis(excl, row[:, None], axis=1)[:, 0]
        else:
            # wide bucket spaces (the hybrid's composite segment x digit ids)
            # would make the one-hot (chunk, nb) matrix explode; count equal
            # predecessors pairwise instead — O(chunk) per element, still O(n)
            # overall with the chunk size fixed.
            i = jnp.arange(row.shape[0])
            eq_before = (row[None, :] == row[:, None]) & (i[None, :] < i[:, None])
            in_tile = eq_before.sum(axis=1).astype(jnp.int32)
        return g_off[row] + carry_row[row] + in_tile

    dest = jax.vmap(tile_ranks)(tiles, carry).reshape(-1)
    return dest[:n]


def stable_partition_dest(bucket: jnp.ndarray, num_buckets: int,
                          engine: str = "argsort") -> jnp.ndarray:
    if engine == "argsort":
        return stable_partition_dest_argsort(bucket)
    if engine == "scan":
        # wide bucket spaces take the pairwise in-chunk rank path, whose
        # time/memory is O(n * chunk) — shrink the chunk to keep it O(n)-ish
        chunk = 2048 if num_buckets <= 4096 else 256
        return stable_partition_dest_scan(bucket, num_buckets, chunk=chunk)
    raise ValueError(f"unknown rank engine {engine!r}")
