"""Pallas merge-path kernel: ONE launch per k-way run-merge round (§5).

The out-of-core pipeline (``core.outofcore``) sorts device-sized chunks with
the fused counting-pass engine and then merges the resulting sorted runs.
This module is the device half of that merge: a merge-path k-way merge in the
style of Casanova et al. (*An Efficient Multiway Mergesort for GPU
Architectures*), expressed with the same constant-grid / scalar-prefetch
discipline as ``kernels.fused``:

  * runs live contiguously in a flat ping-pong buffer; a merge *round* fuses
    groups of up to K adjacent runs into one run each, all groups in ONE
    Pallas launch (one ``pallas_call`` per round — the census invariant),
  * the output is chopped into fixed-size tiles; for every tile boundary the
    *diagonal partition* (the k-dimensional co-rank split of the merged
    prefix across the K runs, ties broken by run index then position — the
    merge path) is computed by a sort-free bitwise binary search
    (``merge_path_partition``) and scalar-prefetched as window tables,
  * each grid step loads one tile-sized window per run at a dynamic offset,
    ranks the union in-VMEM (per-run-pair ``searchsorted`` co-ranks — the
    tile-local merge, O(K²·T·log T); the all-pairs counting rank it replaced
    is kept as ``rank="counting"``, the byte-parity oracle), and scatters
    keys and value slabs as coalesced per-tile runs into the donated
    alternate buffer; masked lanes land in the trash slot ``n``.

The partition math also comes in a host-side numpy flavour
(:func:`host_coranks`, :func:`spill_group_plan`) for the out-of-core spill
path: when runs live host-side between rounds, the merge path is computable
from O(bits · K · log L) probed elements per diagonal without materialising
anything on device, and a group cuts into slab-sized strips of whole output
tiles —
each strip one bounded upload + ONE kernel launch + one download.

Stability: ties are broken (key, run index, in-run position), so a round is
stable with respect to run order — runs of equal keys keep their chunk order,
which is what makes ``oocsort`` deterministic across any chunking.

No comparison sorts anywhere: the diagonal search is ``jnp.searchsorted``
(binary-search scan) and the in-tile rank is built from binary searches too,
so the merge phase traces to zero (stable)HLO ``sort`` ops — certified by
the oocsort test wall alongside the one-launch-per-round census.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def merge_groups(lens, kway: int):
    """Group adjacent runs for one round: [[len, ...], ...] of <= kway runs."""
    lens = list(lens)
    return [lens[i:i + kway] for i in range(0, len(lens), kway)]


def num_merge_rounds(num_runs: int, kway: int) -> int:
    """⌈log_kway(num_runs)⌉ — rounds until a single run remains."""
    rounds = 0
    while num_runs > 1:
        num_runs = -(-num_runs // kway)
        rounds += 1
    return rounds


def _coranks(grp: jnp.ndarray, glens, diags) -> jnp.ndarray:
    """Diagonal partition of K sorted runs at every requested diagonal.

    ``grp`` is (K, Lmax) sorted unsigned keys (rows sentinel-padded past
    their static lengths ``glens``); ``diags`` is a static array of merged
    prefix lengths m.  Returns (D, K) co-ranks c with ``sum(c[i]) == m[i]``
    and the selected elements exactly the m smallest under (key, run,
    position) order — the k-way merge path, found by building the m-th order
    statistic's key bit-by-bit (MSB down) with per-run binary searches.
    """
    kdt = grp.dtype
    kbits = jnp.iinfo(kdt).bits
    lens_a = jnp.asarray(glens, jnp.int32)
    m = jnp.asarray(diags, jnp.int32)
    one = jnp.ones((), kdt)

    def count(v, side):  # (D,) key bound -> (D, K) per-run counts, pad-free
        c = jax.vmap(lambda row: jnp.searchsorted(row, v, side=side),
                     in_axes=0, out_axes=1)(grp)
        return jnp.minimum(c.astype(jnp.int32), lens_a[None, :])

    # v* = smallest key with #(keys <= v*) >= m: greedy MSB-down, keeping a
    # candidate bit whenever even all keys strictly below it fall short of m
    v = jnp.zeros(m.shape, kdt)
    for b in reversed(range(kbits)):
        cand = v | (one << b)
        below = count(cand - one, "right").sum(axis=1)
        v = jnp.where(below < m, cand, v)

    lb = count(v, "left")                      # keys <  v* per run
    ties = count(v, "right") - lb              # keys == v* per run
    # distribute the remaining slots among the v*-ties in run order
    rem = (m - lb.sum(axis=1))[:, None]
    excl = jnp.cumsum(ties, axis=1) - ties
    return lb + jnp.clip(rem - excl, 0, ties)


def merge_path_partition(keys: jnp.ndarray, lens, kway: int, tpb: int):
    """Tile descriptor tables for one merge round over ``keys``.

    ``keys`` is the flat run buffer (sorted unsigned runs back to back,
    padding beyond ``sum(lens)``), ``lens`` the static per-run lengths.
    Output runs occupy exactly the concatenated span of their group, so the
    merged buffer keeps the same layout with coarser boundaries.

    Returns ``(out_off, out_cnt, win_start, win_take)``: per grid step the
    absolute output offset and live lane count (static, (G,) int32), and the
    flattened (G * kway,) per-run window tables — absolute start of the run
    window feeding the tile and how many of its lanes are live.  Runs beyond
    a group's width get ``start = n`` (the trash-adjacent pad region) and
    ``take = 0``; single-run groups degenerate to a copy-through partition.
    """
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    n = int(offs[-1])
    out_off, out_cnt = [], []
    ws_parts, wt_parts = [], []
    g0 = 0
    for glens in merge_groups(lens, kway):
        K = len(glens)
        gbase = int(offs[g0])
        glen = int(sum(glens))
        ntiles = max(1, -(-glen // tpb))
        diags = np.minimum(np.arange(ntiles + 1) * tpb, glen)
        if K == 1:
            cor = jnp.asarray(diags[:, None], jnp.int32)     # trivial path
        else:
            lmax = max(glens)
            sentinel = ~jnp.zeros((), keys.dtype)
            rows = [
                jnp.concatenate(
                    [keys[int(offs[g0 + r]):int(offs[g0 + r]) + glens[r]],
                     jnp.full((lmax - glens[r],), sentinel, keys.dtype)])
                for r in range(K)]
            cor = _coranks(jnp.stack(rows), glens, diags)
        run_base = jnp.asarray([int(offs[g0 + r]) for r in range(K)],
                               jnp.int32)
        start = cor[:-1] + run_base[None, :]                 # (T, K)
        take = cor[1:] - cor[:-1]
        pad = kway - K
        if pad:
            start = jnp.concatenate(
                [start, jnp.full((ntiles, pad), n, jnp.int32)], axis=1)
            take = jnp.concatenate(
                [take, jnp.zeros((ntiles, pad), jnp.int32)], axis=1)
        ws_parts.append(start)
        wt_parts.append(take)
        out_off.extend((gbase + diags[:-1]).tolist())
        out_cnt.extend((diags[1:] - diags[:-1]).tolist())
        g0 += K
    return (jnp.asarray(out_off, jnp.int32), jnp.asarray(out_cnt, jnp.int32),
            jnp.concatenate(ws_parts).reshape(-1).astype(jnp.int32),
            jnp.concatenate(wt_parts).reshape(-1).astype(jnp.int32))


# --------- host-side partition math (the out-of-core spill path) ------------

def host_coranks(runs, diags) -> np.ndarray:
    """NumPy mirror of :func:`_coranks` over host-resident runs.

    ``runs`` is a list of 1-D sorted unsigned numpy arrays (no padding —
    ``np.searchsorted`` bounds each row by its own length); ``diags`` the
    merged prefix lengths m.  Returns (D, K) int64 co-ranks with
    ``sum(c[i]) == m[i]`` and the selected elements exactly the m smallest
    under (key, run, position) order.  Each diagonal costs O(bits · K · log L)
    probed elements, so the merge path of host-spilled runs is computable
    without touching more than a window of each run.
    """
    dt = np.dtype(runs[0].dtype)
    bits = np.iinfo(dt).bits
    m = np.asarray(diags, np.int64)

    def count(vals, side):                      # (D,) bounds -> (D, K)
        return np.stack([np.searchsorted(r, vals, side=side)
                         for r in runs], axis=1).astype(np.int64)

    # v* = smallest key with #(keys <= v*) >= m: greedy MSB-down, keeping a
    # candidate bit whenever even all keys strictly below it fall short of m
    v = np.zeros(m.shape, dt)
    for b in reversed(range(bits)):
        cand = v | np.asarray(1 << b, dt)
        below = count(cand, "left").sum(axis=1)
        v = np.where(below < m, cand, v)

    lb = count(v, "left")                       # keys <  v* per run
    ties = count(v, "right") - lb               # keys == v* per run
    # distribute the remaining slots among the v*-ties in run order
    rem = (m - lb.sum(axis=1))[:, None]
    excl = np.cumsum(ties, axis=1) - ties
    return lb + np.clip(rem - excl, 0, ties)


class SpillStrip(NamedTuple):
    """One slab-sized strip of a merge group's output (host-spill path).

    ``win_lo``/``win_len`` select each run's window feeding the strip
    (``sum(win_len) == out_len``); the windows pack back to back into a
    slab and ``tables`` are the slab-local scalar-prefetch descriptors for
    :func:`kway_merge_round` (``n = slab_elems``), zero-count-padded to the
    slab's full ``G = slab_elems // tile`` grid so every strip of a round
    shares one kernel signature.
    """
    out_lo: int                 # group-relative output offset of the strip
    out_len: int                # live output elements (== sum(win_len))
    win_lo: Tuple[int, ...]     # per-run window start within each run
    win_len: Tuple[int, ...]    # per-run window length
    tables: Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def spill_group_plan(runs, kway: int, tile: int, slab_elems: int):
    """Cut one merge group of host-resident runs into slab-sized strips.

    ``runs`` is a list of <= ``kway`` sorted unsigned numpy runs;
    ``slab_elems`` (a multiple of ``tile``) bounds each strip's output.  The
    group's merge path is solved once at tile granularity by
    :func:`host_coranks`, then sliced into strips of ``slab_elems // tile``
    whole output tiles — so strips tile the group's output exactly once and
    the (key, run, position) tie order is preserved across strip boundaries.
    Returns the list of :class:`SpillStrip` descriptors.
    """
    if slab_elems < tile or slab_elems % tile:
        raise ValueError("slab_elems must be a positive multiple of tile")
    K = len(runs)
    glens = [int(r.shape[0]) for r in runs]
    glen = sum(glens)
    ntiles = max(1, -(-glen // tile))
    diags = np.minimum(np.arange(ntiles + 1, dtype=np.int64) * tile, glen)
    if K == 1:
        cor = diags[:, None]                            # trivial path
    else:
        cor = host_coranks(runs, diags)
    G = slab_elems // tile
    strips = []
    for t0 in range(0, ntiles, G):
        t1 = min(t0 + G, ntiles)
        nt = t1 - t0
        out_lo = int(diags[t0])
        out_len = int(diags[t1] - diags[t0])
        win_lo = tuple(int(cor[t0, r]) for r in range(K))
        win_len = tuple(int(cor[t1, r] - cor[t0, r]) for r in range(K))
        seg = np.concatenate([[0], np.cumsum(win_len)])
        # dead tiles / runs point their window at the slab's pad region
        # (start = slab_elems, take = 0) exactly like merge_path_partition
        out_off = np.zeros(G, np.int32)
        out_cnt = np.zeros(G, np.int32)
        ws = np.full((G, kway), slab_elems, np.int32)
        wt = np.zeros((G, kway), np.int32)
        out_off[:nt] = (diags[t0:t1] - diags[t0]).astype(np.int32)
        out_cnt[:nt] = (diags[t0 + 1:t1 + 1] - diags[t0:t1]).astype(np.int32)
        for r in range(K):
            ws[:nt, r] = (seg[r] + cor[t0:t1, r] - cor[t0, r]).astype(np.int32)
            wt[:nt, r] = (cor[t0 + 1:t1 + 1, r] -
                          cor[t0:t1, r]).astype(np.int32)
        strips.append(SpillStrip(out_lo, out_len, win_lo, win_len,
                                 (out_off, out_cnt, ws.reshape(-1),
                                  wt.reshape(-1))))
    return strips


def _tile_rank(keys, live, takes, *, kway: int, tpb: int, rank: str):
    """Rank of every window element under (key, run, lane) order.

    ``keys``/``live`` are the (kway, tpb) window union; ``takes`` the per-run
    live lane counts (live lanes are a *prefix* of each sorted window).  The
    returned (kway·tpb,) ranks are exact for live elements and arbitrary for
    dead ones (the caller masks them into the trash slot).

    ``rank="searchsorted"`` resolves each element as its own lane index plus
    per-run-pair binary-search co-ranks — O(K²·T·log T): element (r, j) is
    preceded by its own live prefix (j), by every element <= it in runs
    r' < r, and by every element < it in runs r' > r.  ``rank="counting"``
    is the all-pairs comparison rank it replaced — O((K·T)²), kept as the
    byte-parity oracle for the searchsorted path.
    """
    kf = keys.reshape(-1)
    lf = live.reshape(-1)
    flat = jax.lax.iota(jnp.int32, kway * tpb)
    if rank == "counting":
        # the run-major flat index encodes (run, lane), so a single index
        # compare breaks key ties — runs of equal keys keep chunk order
        before = lf[None, :] & ((kf[None, :] < kf[:, None]) |
                                ((kf[None, :] == kf[:, None]) &
                                 (flat[None, :] < flat[:, None])))
        return jnp.sum(before, axis=1, dtype=jnp.int32)
    # dead lanes mask to the all-ones sentinel, so every row stays sorted and
    # binary-searchable; counts clip to the live prefix because a sentinel
    # query would otherwise count the dead lanes as <=-ties
    sentinel = ~jnp.zeros((), keys.dtype)
    win = jnp.where(live, keys, sentinel)
    takes_a = jnp.stack(takes).astype(jnp.int32)

    def counts(side):
        c = jax.vmap(lambda w: jnp.searchsorted(
            w, kf, side=side, method="scan_unrolled"))(win)
        return jnp.minimum(c.astype(jnp.int32), takes_a[:, None])

    below = counts("left")                       # per run: # keys strictly <
    below_eq = counts("right")                   # per run: # keys <=
    run_of = flat // tpb
    rid = jax.lax.iota(jnp.int32, kway)
    contrib = jnp.where(rid[:, None] < run_of[None, :], below_eq,
                        jnp.where(rid[:, None] > run_of[None, :], below, 0))
    return flat % tpb + jnp.sum(contrib, axis=0, dtype=jnp.int32)


def _kway_merge_kernel(off_ref, cnt_ref, wstart_ref, wtake_ref, *refs,
                       kway: int, tpb: int, n: int, num_vals: int, rank: str):
    """One grid step = one output tile of one merge group."""
    srck_ref = refs[0]
    srcv_refs = refs[1:1 + num_vals]
    # refs[1+num_vals : 2+2*num_vals] are the aliased alternate buffers —
    # donation targets only, never read.
    dstk_ref = refs[2 + 2 * num_vals]
    dstv_refs = refs[3 + 2 * num_vals:3 + 3 * num_vals]

    g = pl.program_id(0)
    out_off = off_ref[g]
    cnt = cnt_ref[g]
    lane = jax.lax.iota(jnp.int32, tpb)

    starts = [wstart_ref[g * kway + r] for r in range(kway)]
    takes = [wtake_ref[g * kway + r] for r in range(kway)]
    keys = jnp.stack([srck_ref[pl.ds(starts[r], tpb)] for r in range(kway)])
    live = jnp.stack([lane < takes[r] for r in range(kway)])

    # tile-local merge: element j precedes element i iff
    # (key_j, run_j, lane_j) < (key_i, run_i, lane_i)
    kf = keys.reshape(-1)
    lf = live.reshape(-1)
    ranks = _tile_rank(keys, live, takes, kway=kway, tpb=tpb, rank=rank)

    # coalesced per-tile write; masked lanes drain into trash slot n
    dest = jnp.where(lf & (ranks < cnt), out_off + ranks, n)
    dstk_ref[dest] = kf
    for sv_ref, dv_ref in zip(srcv_refs, dstv_refs):
        vals = jnp.stack([sv_ref[pl.ds(starts[r], tpb)] for r in range(kway)])
        dv_ref[dest] = vals.reshape(-1)


@functools.partial(jax.jit, static_argnames=("kway", "tpb", "n", "interpret",
                                             "rank"))
def kway_merge_round(src_keys, src_vals, alt_keys, alt_vals, out_off, out_cnt,
                     win_start, win_take, *, kway: int, tpb: int, n: int,
                     interpret: bool, rank: str = "searchsorted"):
    """One k-way merge round over all groups in ONE Pallas launch.

    ``src_keys``/``src_vals`` hold the sorted runs back to back in a
    ``pad_length``-sized buffer (``src_vals`` is a tuple of value slabs);
    ``alt_*`` are the donated ping-pong targets.  The descriptor tables come
    from :func:`merge_path_partition` (device-resident rounds) or
    :func:`spill_group_plan` (host-spilled slab strips — there ``n`` is the
    slab capacity and the buffers are slab-sized).  Returns ``(new_keys,
    new_vals)`` with every group's runs merged in place of their span —
    exactly one ``pallas_call`` in the trace, the per-round / per-slab-sweep
    census invariant.  ``rank`` picks the tile-local merge: per-run-pair
    ``searchsorted`` co-ranks (default) or the legacy ``counting`` all-pairs
    rank; both produce byte-identical output (see :func:`_tile_rank`).
    """
    if rank not in ("searchsorted", "counting"):
        raise ValueError(f"unknown tile rank mode {rank!r}")
    g_max = out_off.shape[0]
    num_vals = len(src_vals)

    whole = lambda x: pl.BlockSpec(x.shape, lambda i, *_: (0,) * x.ndim)
    in_specs = ([whole(src_keys)] + [whole(v) for v in src_vals] +
                [whole(alt_keys)] + [whole(v) for v in alt_vals])
    out_specs = [whole(src_keys)] + [whole(v) for v in src_vals]
    out_shape = ([jax.ShapeDtypeStruct(src_keys.shape, src_keys.dtype)] +
                 [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in src_vals])
    # operand index space includes the 4 scalar-prefetch tables; the
    # alternate buffers donate their memory to the outputs
    alt0 = 4 + 1 + num_vals
    aliases = {alt0 + i: i for i in range(1 + num_vals)}

    out = pl.pallas_call(
        functools.partial(_kway_merge_kernel, kway=kway, tpb=tpb, n=n,
                          num_vals=num_vals, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g_max,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(out_off, out_cnt, win_start, win_take,
      src_keys, *src_vals, alt_keys, *alt_vals)

    return out[0], tuple(out[1:1 + num_vals])
