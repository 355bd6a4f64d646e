"""Distributed sorting across a device mesh (paper §5, re-derived for TPU).

The paper's heterogeneous sort pipelines PCIe H2D / on-GPU sort / D2H over s
chunks and merges the sorted runs on the CPU.  On a TPU pod the slow link is
ICI (chip-to-chip) instead of PCIe and the merge lives next to the exchange.
The structure is isomorphic:

  paper (Figs. 4/5)                     this module
  ---------------------------------     ------------------------------------
  split input into s chunks             split each shard's data into s chunks
  H2D transfer of chunk i+1             all_to_all exchange of chunk i+1
    overlapped with on-GPU sort of        overlapped with local hybrid sort /
    chunk i (full-duplex PCIe)            merge of chunk i (bidirectional ICI;
                                          XLA latency-hiding scheduler)
  in-place replacement of returned      XLA buffer donation/reuse of the
    chunk memory (Fig. 5)                 exchanged chunk buffers
  CPU parallel multiway merge           per-shard multiway merge of received
                                          sorted runs (merge-path via
                                          vectorised binary search)

Shard splitters are *sample-based* (GPU Sample Sort shape: oversampled
splitter selection — Dehne & Zaboli, cited §1) with duplicate-only rank
interleaving, so only exactly-equal keys are split across shards and global
order is preserved for any distribution — the zero-entropy case degrades to
zero exchange traffic.

Capacity overflow (a splitter set that routes more than the static
all_to_all capacity to one (source, dest) pair) is handled by bounded
*splitter refinement*: the exchange re-samples at ``refine``x the previous
sample density and replays bucketing + all_to_all, up to ``max_attempts``
times.  Attempts are ledgered in ``DistStats.exchange_attempts``; only if
every attempt overflows does ``DistStats.overflow`` stay set (the residual
flag, not a silent one).  The retry sites are ``lax.cond``-guarded, so the
launch census keeps ONE ``pallas_call`` per *executed* counting pass — the
same executed-vs-nominal idiom as the adaptive pass elision (§4.2).

The whole pipeline is comparison-sort-free under ``engine="kernel"``: local
chunk sorts are ``hybrid_sort``, sample/splitter selection merges sorted
pieces (merge-path binary search), per-shard bucketing routes through
``plan.single_pass_partition`` (one fused counting pass), and the finish is
a single high-fan-in ``multiway_merge`` over all received runs plus one
2-bucket validity-compaction pass.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import bijection, model
from repro.core.hybrid import hybrid_sort
from repro.core.segmented import counting_partition, multiway_merge


class DistStats(NamedTuple):
    """Per-shard exchange ledger (global shapes ``(nshards,)`` after
    shard_map; replicated entries repeat the same value on every shard).

    exchange_attempts  executed splitter-refinement attempts (replicated;
                       1 = first splitter set fit, k > 1 = k-1 retries)
    overflow           residual overflow after the last attempt (replicated;
                       True means ``valid`` undercounts — capacity clipped)
    valid              number of real keys in this shard's output prefix
    peak_recv          max keys received over (chunk, source) rows — the
                       balance measure the ≤ 2x skew gate reads
    """
    exchange_attempts: jnp.ndarray
    overflow: jnp.ndarray
    valid: jnp.ndarray
    peak_recv: jnp.ndarray


def _select_splitters(gsample_sorted: jnp.ndarray, nshards: int,
                      oversample: int = 8) -> jnp.ndarray:
    """(nshards - 1,) splitters from a sorted global sample.

    Takes an ``oversample * nshards`` evenly-ranked oversample of the sorted
    sample first and selects every ``oversample``-th entry — equivalent to
    even quantiles ``gsample[(i * total) // nshards]``.  The previous
    ``step::step`` regular stride truncated ``total % nshards`` trailing
    samples, which on clustered data parks every key above the last retained
    rank on the final shard (> 2x imbalance whenever the sample total is not
    ≈ a multiple of nshards).

    The degenerate case (gathered sample smaller than the shard count, e.g.
    ``num_chunks > n_local`` leaves empty chunks) needs no special stride
    guard any more: even-rank selection just repeats sample values, shard
    boundaries collapse onto few splitter levels, and the duplicate-rank
    interleaving of ``_dest_shards`` spreads the ties — correctness (global
    order) is preserved, only balance degrades, which is the best any sample
    sort can do sample-starved.
    """
    total = gsample_sorted.shape[0]
    if total == 0 or nshards == 1:
        return jnp.zeros((nshards - 1,), gsample_sorted.dtype)
    s = max(1, int(oversample))
    ranks = (jnp.arange(s * nshards) * total) // (s * nshards)
    over = gsample_sorted[ranks]
    return over[s::s]


def _even_sample_ranks(n: int, m: int) -> jnp.ndarray:
    """m evenly-spaced ranks into a length-n sorted array (sorted output)."""
    return (jnp.arange(m) * n) // m


def _make_splitters(local_sample, axis_name: str, nshards: int,
                    sel_oversample: int = 8):
    """Global shard splitters from per-shard sorted samples.

    Sort-free: ``all_gather`` keeps the per-shard rows intact (each already
    sorted) and the global order comes from a ``multiway_merge`` — no HLO
    sort op, so the kernel engine's sort-free gate extends over the
    exchange.  ``nshards`` is the static mesh axis size.
    """
    g = jax.lax.all_gather(local_sample, axis_name)     # (nshards, m) sorted
    gsorted = g.reshape(-1) if nshards == 1 else multiway_merge(g)
    return _select_splitters(gsorted, nshards, oversample=sel_oversample)


def _dest_shards(sorted_ukeys, splitters, nshards: int, my):
    """Destination shard per (locally sorted) key.

    Ties with splitter values are cycled across their allowed shard range —
    safe, because only equal keys ever cross a splitter boundary, and it
    keeps the per-(source, dest) load <= chunk/spread so the static
    all_to_all capacity holds even for the constant (zero-entropy)
    distribution.  ``my`` is this shard's index (pass
    ``jax.lax.axis_index(axis)`` inside shard_map; any int in host tests),
    offsetting the cycle so different sources hit different shards first.
    """
    n_local = sorted_ukeys.shape[0]
    lo = jnp.searchsorted(splitters, sorted_ukeys, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(splitters, sorted_ukeys, side="right").astype(jnp.int32)
    spread = hi - lo + 1
    first = jnp.searchsorted(sorted_ukeys, sorted_ukeys, side="left")
    tie_rank = jnp.arange(n_local, dtype=jnp.int32) - first.astype(jnp.int32)
    return lo + (tie_rank + my) % spread


@jax.named_scope("exchange")
def _exchange(sorted_ukeys, leaves, dest_shard, nshards: int, capacity: int,
              sentinel, axis_name: str, engine=None, interpret=None):
    """Partition by destination shard (one counting pass, §4.1), pad to the
    static all_to_all capacity, exchange keys, payload leaves and validity
    counts.

    The shard partition routes through the same engine-selected
    ``counting_partition`` as MoE dispatch and length bucketing (core.plan),
    so the one-launch-per-counting-pass census extends to the exchange.
    Its ops sit in the named scope ``exchange``.
    """
    part = counting_partition(dest_shard, nshards, engine=engine,
                              interpret=interpret)
    position = part.dest - part.offsets[dest_shard]
    kept = position < capacity
    slot = jnp.where(kept, dest_shard * capacity + position, nshards * capacity)
    buf = jnp.full((nshards * capacity + 1,), sentinel, sorted_ukeys.dtype)
    buf = buf.at[slot].set(sorted_ukeys, mode="drop")
    recv = jax.lax.all_to_all(buf[:-1].reshape(nshards, capacity), axis_name,
                              split_axis=0, concat_axis=0)
    recv_leaves = []
    for leaf in leaves:
        lbuf = jnp.zeros((nshards * capacity + 1,), leaf.dtype)
        lbuf = lbuf.at[slot].set(leaf, mode="drop")
        recv_leaves.append(
            jax.lax.all_to_all(lbuf[:-1].reshape(nshards, capacity),
                               axis_name, split_axis=0, concat_axis=0))
    sent_counts = jnp.minimum(part.counts, capacity)
    recv_counts = jax.lax.all_to_all(sent_counts.reshape(nshards, 1),
                                     axis_name, split_axis=0,
                                     concat_axis=0).reshape(nshards)
    overflow = (part.counts > capacity).any()
    return recv, tuple(recv_leaves), recv_counts, overflow


def make_distributed_sort(mesh, axis_name: str = "data", *,
                          oversample: int = 64, slack: float = 2.0,
                          num_chunks: int = 1, max_attempts: int = 3,
                          refine: int = 4,
                          cfg: Optional[model.SortConfig] = None,
                          spec: Optional[P] = None,
                          engine: Optional[str] = None,
                          interpret: Optional[bool] = None):
    """Build a shard_map'd distributed sort over one mesh axis.

    Returns ``fn(keys[, values]) -> (out_keys[, out_values], DistStats)``:
    per shard ``(n_local,)`` keys (plus an optional pytree of same-length
    payload leaves) map to capacity-padded sorted outputs whose first
    ``stats.valid[i]`` entries per shard concatenate to the global sorted
    sequence (``valid_concat``).  ``num_chunks > 1`` enables the §5
    pipelined schedule; ``oversample`` is the per-shard splitter sample
    size; overflow triggers up to ``max_attempts - 1`` splitter-refinement
    replays at ``refine``x sample density (see module docstring).
    """
    spec = spec if spec is not None else P(axis_name)
    nshards = mesh.shape[axis_name]
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")

    def dsort(keys, leaves):
        ukeys = bijection.to_ordered_bits(keys)
        sentinel = ~jnp.zeros((), ukeys.dtype)   # all-ones == top of key order
        n_local = ukeys.shape[0]
        chunk = n_local // num_chunks
        # slack prices the skew splitter error leaves behind; the additive
        # term prices what no splitter can fix — the per-(source, dest)
        # binomial cell variance of a small chunk (~4 standard deviations of
        # headroom, so tiny shards don't overflow on noise retries cannot
        # remove).  A source never sends more than its whole chunk to one
        # destination, so `chunk` caps the cell outright.
        base = slack * chunk / nshards
        capacity = max(1, min(chunk,
                              int(base + 4.0 * math.sqrt(max(base, 1.0)))))
        out_len = num_chunks * nshards * capacity

        if chunk == 0:
            # degenerate: num_chunks > n_local — nothing to exchange, keep
            # the shape contract traceable (valid = 0, zero attempts)
            zero = jnp.zeros((1,), jnp.int32)
            return (bijection.from_ordered_bits(
                        jnp.full((out_len,), sentinel, ukeys.dtype),
                        keys.dtype),
                    tuple(jnp.zeros((out_len,), l.dtype) for l in leaves),
                    DistStats(zero, jnp.zeros((1,), bool), zero, zero))
        if n_local % num_chunks:
            raise ValueError(
                f"n_local={n_local} must divide into num_chunks={num_chunks}")

        my = jax.lax.axis_index(axis_name)

        # stage 1 (paper: on-GPU sort of each chunk): local hybrid sorts.
        # With payloads a single int32 rank rides the sort so the local-sort
        # launch count stays independent of the number of payload leaves.
        pieces = []
        for c in range(num_chunks):
            uchunk = ukeys[c * chunk:(c + 1) * chunk]
            if leaves:
                pk, pidx = hybrid_sort(uchunk,
                                       jnp.arange(chunk, dtype=jnp.int32),
                                       cfg=cfg, engine=engine,
                                       interpret=interpret)
            else:
                pk, pidx = hybrid_sort(uchunk, cfg=cfg, engine=engine,
                                       interpret=interpret), None
            pieces.append((pk, pidx))

        def attempt(a):
            """One splitter-selection + exchange round at refine^a density."""
            s_a = oversample * (refine ** a)
            m = max(1, min(-(-s_a // num_chunks), chunk))
            ranks = _even_sample_ranks(chunk, m)
            samples = jnp.stack([pk[ranks] for pk, _ in pieces])
            local_sample = (samples[0] if num_chunks == 1
                            else multiway_merge(samples))
            splitters = _make_splitters(local_sample, axis_name, nshards)
            rks, rls, rcs, ovs = [], [], [], []
            for c, (pk, pidx) in enumerate(pieces):
                dest = _dest_shards(pk, splitters, nshards, my)
                pleaves = (tuple(l[c * chunk:(c + 1) * chunk][pidx]
                                 for l in leaves) if leaves else ())
                rk, rl, rc, ov = _exchange(pk, pleaves, dest, nshards,
                                           capacity, sentinel, axis_name,
                                           engine=engine, interpret=interpret)
                rks.append(rk)
                rls.append(rl)
                rcs.append(rc)
                ovs.append(ov)
            rk = jnp.stack(rks)                      # (C, nshards, capacity)
            rl = (tuple(jnp.stack(ls) for ls in zip(*rls)) if leaves else ())
            rc = jnp.stack(rcs)                      # (C, nshards)
            local_over = functools.reduce(jnp.logical_or, ovs)
            # replicated predicate: every shard must take the same retry
            # branch (the retry replays collectives)
            over = jax.lax.psum(local_over.astype(jnp.int32), axis_name) > 0
            return rk, rl, rc, over

        carry = attempt(0)
        attempts = jnp.int32(1)
        for a in range(1, max_attempts):
            prev_over = carry[3]
            carry = jax.lax.cond(prev_over,
                                 lambda _, a=a: attempt(a),
                                 lambda c: c, carry)
            attempts = attempts + prev_over.astype(jnp.int32)
        rk, rl, rc, over = carry

        # stage 2/3 finish: ONE high-fan-in merge over all C * nshards
        # received runs (Multiway Mergesort shape — no merge cascade), with
        # a flat slot id riding along to recover payloads and validity,
        # then one 2-bucket counting pass compacts valid keys in front of
        # the capacity padding (stable, so global order is preserved).
        runs = rk.reshape(num_chunks * nshards, capacity)
        slot_ids = jnp.arange(runs.size, dtype=jnp.int32).reshape(runs.shape)
        if runs.shape[0] == 1:
            merged, midx = runs[0], slot_ids[0]
        else:
            merged, midx = multiway_merge(runs, slot_ids)
        ok = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
              < rc.reshape(-1, 1)).reshape(-1)[midx]
        cpart = counting_partition((~ok).astype(jnp.int32), 2, engine=engine,
                                   interpret=interpret)
        out_u = merged[cpart.perm]
        out_leaves = tuple(l.reshape(-1)[midx][cpart.perm] for l in rl)
        stats = DistStats(
            exchange_attempts=attempts.reshape(1),
            overflow=over.reshape(1),
            valid=rc.sum().astype(jnp.int32).reshape(1),
            peak_recv=rc.max().astype(jnp.int32).reshape(1))
        return bijection.from_ordered_bits(out_u, keys.dtype), out_leaves, stats

    def fn(keys, values: Any = None):
        leaves, treedef = jax.tree.flatten(values)
        for leaf in leaves:
            if leaf.shape[0] != keys.shape[0]:
                raise ValueError(
                    f"payload leaf length {leaf.shape[0]} != keys length "
                    f"{keys.shape[0]}")
        stats_spec = DistStats(spec, spec, spec, spec)
        sharded = _shard_map(dsort, mesh,
                             (spec, (spec,) * len(leaves)),
                             (spec, (spec,) * len(leaves), stats_spec))
        out_keys, out_leaves, stats = sharded(keys, tuple(leaves))
        if values is None:
            return out_keys, stats
        return out_keys, jax.tree.unflatten(treedef, list(out_leaves)), stats

    return fn


def valid_concat(out, valid):
    """Host-side: concatenate the valid prefixes of every shard's padded
    output (keys or any payload leaf) into the global sorted sequence."""
    valid = np.asarray(valid).reshape(-1)
    per = np.asarray(out).reshape(valid.shape[0], -1)
    return np.concatenate([per[i][: valid[i]] for i in range(valid.shape[0])])


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --- contract declaration (verified by repro.analysis; see analysis/contracts)
# Shard-body census: per chunk one full hybrid sort (its pass loop and one
# tile loop per local-sort class, one launch site each), per cond-guarded
# attempt per chunk one bucketing counting pass (2 sites), plus the 2-bucket
# validity compaction.  Link bytes re-derive the ICI table of kernels/__init__ from
# the collective-primitive result shapes: per attempt per chunk one keys +
# ``leaves`` payload + one counts all_to_all at capacity padding, per attempt
# one splitter-sample all_gather (samp lists the gathered per-shard sample
# lengths) and one scalar overflow psum.
ANALYSIS_CONTRACT = {
    "entry": "repro.core.distributed.make_distributed_sort",
    "census": {
        "launch_total": "chunks * (2 + classes)"
                        " + 2 * attempts * chunks + 2",
        "while_body_launches": "[1] * (chunks * (1 + classes))",
    },
    "sort_free": True,
    "link": {
        "collective_counts": {
            "all_to_all": "attempts * chunks * (2 + leaves)",
            "all_gather": "attempts",
            "psum": "attempts",
        },
        "link_bytes": "((P - 1) / P) * ("
                      "attempts * chunks * P * (cap * (kb + vb) + 4)"
                      " + kb * P * sum(samp) + attempts * 2 * 4)",
    },
}
