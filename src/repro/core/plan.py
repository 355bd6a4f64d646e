"""Pass plan + buffer manager: the one partition engine under every consumer.

Every counting-sort pass in the framework — the hybrid MSD driver, the LSD
baseline, ``segmented.counting_partition`` (MoE dispatch, length bucketing),
and the distributed sort's shard partitioning — needs the same plumbing:

  * digit extraction windows (``digit_at`` / ``digit_window``),
  * active-segment descriptors derived from the dense per-key bucket state
    (``active_segments`` — the JAX analogue of the paper's bucket lists),
  * block descriptor tables that chop segments AND the done gaps between
    them into KPB blocks for the constant-size fused launch (§4.2,
    ``make_region_blocks``), packed into fixed-width super-steps of B rows
    per grid step (``pack_region_blocks``) so the launch grid is
    ⌈g_max/B⌉,
  * R3 merge bookkeeping (``merge_rows``) and the positional segment/done
    updates after a pass (``apply_pass_bookkeeping``),
  * the (sub-bucket -> next-pass active segment) map that keys the fused
    next-digit histogram (§4.3, ``next_active_table``),
  * ping-pong buffer management with donation (``kernels.fused``).

This module owns all of it; ``core.hybrid``, ``core.lsd``,
``core.segmented`` and ``core.distributed`` are thin clients.  The jnp
engines (``argsort``/``scan``) share the same bookkeeping so all three
engines stay byte-identical.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.ranks import (invert_permutation, resolve_engine,
                              resolve_interpret, stable_partition_dest)
from repro.kernels import fused


class ActiveSegments(NamedTuple):
    """Dense descriptors of the active (> ∂̂) buckets, in position order."""
    base: jnp.ndarray      # (a_max,) first key of each active segment; n pad
    size: jnp.ndarray      # (a_max,) keys per active segment; 0 pad
    index: jnp.ndarray     # (n,) compact active-segment id per key
    boundary: jnp.ndarray  # (n,) bool: first key of any bucket (done or not)


class RegionBlocks(NamedTuple):
    """Block descriptor tables for one fused launch (§4.2, model M4/I4).

    One row per descriptor: active segments are partitioned, the done gaps
    between them are copied through, so one launch rewrites the whole
    ping-pong buffer.  Padding rows (beyond the pass's real block count)
    carry ``count == 0`` and scatter nothing.  Tables are either flat (G,)
    — one row per grid step — or packed (G', B) super-steps
    (``pack_region_blocks``): grid step g then loops over its B rows in
    order, which shrinks the launch grid B-fold without touching the carry
    chains (rows stay in descriptor order).
    """
    seg: jnp.ndarray     # (G,) compact active-segment id; a_max for copies/pads
    offset: jnp.ndarray  # (G,) absolute offset of the block's first key
    reset: jnp.ndarray   # (G,) 1 = first block of its region (carry reset)
    count: jnp.ndarray   # (G,) live lanes in the block
    active: jnp.ndarray  # (G,) 1 = partition block, 0 = copy-through block


def digit_at(ukeys: jnp.ndarray, pass_idx, k: int, d: int,
             lo: int = 0) -> jnp.ndarray:
    """MSD digit for pass ``pass_idx`` (0 = most significant); handles k % d != 0.

    ``lo`` is the static live-bit floor of the entropy-adaptive schedule:
    pass windows count down from ``k`` but never extend below ``lo`` (bits
    under the floor are globally dead and carry no ordering information).
    """
    udt = ukeys.dtype
    hi = k - pass_idx * d
    width = jnp.clip(jnp.minimum(d, hi - lo), 0, d)
    wlo = (hi - width).astype(udt)
    mask = ((jnp.array(1, udt) << width.astype(udt)) - 1).astype(udt)
    return ((ukeys >> wlo) & mask).astype(jnp.int32)


def digit_window(pass_idx, k: int, d: int, lo: int = 0) -> jnp.ndarray:
    """(6,) int32 [lo, width, next_lo, next_width, next2_lo, next2_width]
    MSD windows of a pass.

    The first pair locates this pass's digit, the second the next pass's —
    the window the fused kernel histograms during the scatter (§4.3) — and
    the third the pass after that, the *lookahead* window the adaptive
    schedule histograms alongside so an elided pass p+1 still leaves pass
    p+2's histogram in hand.  A width of 0 marks a window past the last
    pass (no fused histogram).  ``lo`` is the static live-bit floor: all
    windows clip against it, so a narrowed schedule runs ⌈(k - lo)/d⌉
    passes without touching the dead low bits.
    """
    hi = k - pass_idx * d
    width = jnp.clip(jnp.minimum(d, hi - lo), 0, d)
    wlo = hi - width
    nwidth = jnp.clip(jnp.minimum(d, wlo - lo), 0, d)
    nlo = wlo - nwidth
    n2width = jnp.clip(jnp.minimum(d, nlo - lo), 0, d)
    n2lo = nlo - n2width
    return jnp.stack([wlo, width, nlo, nwidth, n2lo, n2width]).astype(jnp.int32)


def lsd_digit_window(pass_idx: int, k: int, d: int, lo: int = 0) -> jnp.ndarray:
    """(6,) int32 LSD windows: pass p covers bits [lo + p*d, min(lo+(p+1)*d, k)).

    ``lo``/``k`` bound the live window of the adaptive schedule (static
    narrowing); the lookahead slots are 0 — the LSD driver unrolls its
    passes statically and never elides mid-sort.
    """
    wlo = lo + pass_idx * d
    width = min(d, k - wlo)
    nlo = wlo + width
    nwidth = max(0, min(d, k - nlo))
    return jnp.asarray([wlo, width, nlo, nwidth, 0, 0], jnp.int32)


def active_segments(seg_id: jnp.ndarray, done: jnp.ndarray,
                    a_max: int) -> ActiveSegments:
    """Derive the active-segment descriptors from dense per-key state."""
    n = seg_id.shape[0]
    boundary = jnp.concatenate([jnp.ones((1,), bool),
                                seg_id[1:] != seg_id[:-1]])
    astart = boundary & ~done
    asid = jnp.cumsum(astart.astype(jnp.int32)) - 1
    base = jnp.nonzero(astart, size=a_max, fill_value=n)[0].astype(jnp.int32)
    size = jnp.zeros((a_max,), jnp.int32).at[
        jnp.where(~done, asid, a_max)].add(1, mode="drop")
    return ActiveSegments(base=base, size=size, index=asid, boundary=boundary)


def max_region_blocks(n: int, kpb: int, a_max: int) -> int:
    """Static bound on fused-launch grid size (model I4 extended to gaps):
    ⌊n/KPB⌋ full blocks + one partial per active segment + one per gap."""
    return n // kpb + 2 * a_max + 2


def pack_region_blocks(blocks: RegionBlocks, batch: int,
                       seg_pad: int = None) -> RegionBlocks:
    """Pack flat descriptor rows into fixed-width (G', B) super-steps (§4.2).

    The paper over-decomposes buckets into equal blocks so thread blocks do
    equal work; the fused launch's analogue is the flat descriptor table —
    but one row per grid step pays the per-step launch machinery ``g_max``
    times.  Packing groups B *consecutive* rows per grid step instead.
    Consecutive-in-order is the compatibility rule that keeps every segment's
    carry chain intact: a region's blocks occupy consecutive rows, the kernel
    walks each super-step's rows sequentially, and the grid itself is
    sequential — so the in-segment running offset accumulates across
    super-step boundaries exactly as before.  The tail pads with inert rows
    (count 0, copy-through, carry-reset) which sit after every real row, so
    a reset there never clips a live carry; ``seg_pad`` is the tail's seg
    value — pass ``a_max`` to keep the flat table's pad convention (seg ==
    a_max marks copies/pads), as ``make_region_blocks`` does.
    """
    if batch < 1:
        raise ValueError("batch must be >= 1")
    g = blocks.seg.shape[0]
    pad = (-g) % batch
    fills = dict(seg=0 if seg_pad is None else seg_pad, offset=0, reset=1,
                 count=0, active=0)
    packed = {}
    for name, fill in fills.items():
        t = getattr(blocks, name)
        if pad:
            t = jnp.concatenate([t, jnp.full((pad,), fill, t.dtype)])
        packed[name] = t.reshape(-1, batch)
    return RegionBlocks(**packed)


def make_region_blocks(base: jnp.ndarray, size: jnp.ndarray, n: int, kpb: int,
                       g_max: int, batch: int = None) -> RegionBlocks:
    """Chop active segments and the done gaps between them into KPB blocks.

    ``base``/``size`` are (a_max,) active-segment descriptors (``n``/0 on
    padding rows).  Regions interleave gap_0, active_0, gap_1, ..., tail gap;
    every key position lands in exactly one block, so one fused launch
    rewrites the whole buffer (actives partitioned, gaps copied through).
    With ``batch`` the flat rows are additionally packed into (⌈g_max/batch⌉,
    batch) super-steps (``pack_region_blocks``) — the batched-grid form the
    fused kernel consumes.
    """
    a_max = base.shape[0]
    nreg = 2 * a_max + 1
    ends = (base + size).astype(jnp.int32)
    prev_end = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])

    rbase = jnp.zeros((nreg,), jnp.int32)
    rbase = rbase.at[0:2 * a_max:2].set(prev_end)
    rbase = rbase.at[1:2 * a_max:2].set(base.astype(jnp.int32))
    rbase = rbase.at[2 * a_max].set(ends[-1])
    rsize = jnp.zeros((nreg,), jnp.int32)
    rsize = rsize.at[0:2 * a_max:2].set(jnp.maximum(base - prev_end, 0))
    rsize = rsize.at[1:2 * a_max:2].set(size.astype(jnp.int32))
    rsize = rsize.at[2 * a_max].set(jnp.maximum(n - ends[-1], 0))
    ract = jnp.zeros((nreg,), jnp.int32).at[1:2 * a_max:2].set(1)
    rseg = jnp.full((nreg,), a_max, jnp.int32).at[1:2 * a_max:2].set(
        jnp.arange(a_max, dtype=jnp.int32))

    # block ownership via marks + prefix sum (as the paper's M4 generation):
    # mark each non-empty region's first block, count marks up to g, map the
    # count back through the list of non-empty regions.
    nblk = (rsize + kpb - 1) // kpb
    blk_excl = jnp.cumsum(nblk) - nblk
    total = blk_excl[-1] + nblk[-1]
    marks = jnp.zeros((g_max,), jnp.int32).at[
        jnp.where(nblk > 0, blk_excl, g_max)].add(1, mode="drop")
    reg_ord = jnp.cumsum(marks) - 1
    nonempty = jnp.nonzero(nblk > 0, size=nreg, fill_value=nreg)[0]
    g = jnp.arange(g_max, dtype=jnp.int32)
    valid = g < total
    reg = jnp.clip(jnp.where(valid, nonempty[jnp.clip(reg_ord, 0, nreg - 1)],
                             nreg - 1), 0, nreg - 1)
    blk_in_reg = jnp.where(valid, g - blk_excl[reg], 0)
    offset = jnp.where(valid, rbase[reg] + blk_in_reg * kpb, 0)
    count = jnp.where(valid,
                      jnp.clip(rsize[reg] - blk_in_reg * kpb, 0, kpb), 0)
    seg = jnp.where(valid & (ract[reg] == 1), rseg[reg], a_max)
    active = jnp.where(valid, ract[reg], 0)
    reset = jnp.where(valid, (blk_in_reg == 0).astype(jnp.int32), 1)
    blocks = RegionBlocks(seg=seg.astype(jnp.int32),
                          offset=offset.astype(jnp.int32),
                          reset=reset.astype(jnp.int32),
                          count=count.astype(jnp.int32),
                          active=active.astype(jnp.int32))
    if batch is None:
        return blocks
    return pack_region_blocks(blocks, batch, seg_pad=a_max)


def merge_rows(hist: jnp.ndarray, local_threshold: int, merge_threshold: int):
    """Apply R3 to each active bucket's sub-bucket size row.

    Returns (group_start, group_done): (A, r) bools — whether sub-bucket v
    starts a new (merged) bucket, and whether that bucket is finished (<= ∂̂).
    """
    def row(s_row):
        def step(carry, s):
            acc, gid = carry
            big = s > local_threshold
            extend = (s == 0) | ((~big) & (acc + s < merge_threshold))
            ngid = jnp.where(extend, gid, gid + 1)
            nacc = jnp.where(extend, acc + s,
                             jnp.where(big, merge_threshold, s))
            return (nacc, ngid), (~extend, ~big)
        (_, _), (gstart, gdone) = lax.scan(
            step, (jnp.int32(merge_threshold), jnp.int32(0)), s_row)
        return gstart, gdone
    return jax.vmap(row)(hist)


def next_active_table(hist: jnp.ndarray, local_threshold: int,
                      a_max: int) -> jnp.ndarray:
    """(a_max * r,) map from (active segment, digit) sub-bucket to its
    compact next-pass active-segment id (``a_max`` = done next pass).

    R3 merging makes every next-pass *active* bucket a single sub-bucket
    larger than ∂̂ (merged runs are by construction <= ∂ <= ∂̂, hence done),
    so rank-among-(> ∂̂)-sub-buckets in position order IS the id the next
    pass's ``active_segments`` will assign — the invariant that lets the
    fused kernel write its §4.3 histogram straight into compact rows.
    """
    mask = (hist > local_threshold).reshape(-1)
    sid = jnp.cumsum(mask.astype(jnp.int32)) - 1
    return jnp.where(mask, sid, a_max).astype(jnp.int32)


def apply_pass_bookkeeping(seg_id, done, asegs: ActiveSegments, hist,
                           gstart, gdone, dest_base):
    """Positional segment/done updates after a counting pass.

    Works purely from the (A, r) tables — no per-key digit array — so the
    fused engine (whose digits never leave VMEM) and the jnp engines share
    it: merged-group starts (R3) become the new bucket boundaries, done
    groups are range-filled, done buckets persist in place.
    """
    n = seg_id.shape[0]
    nb = jnp.zeros((n,), bool)
    keep = asegs.boundary & done                  # done buckets persist
    nb = nb.at[jnp.where(keep, jnp.arange(n), n)].set(True, mode="drop")
    nb = nb.at[jnp.where(gstart.reshape(-1), dest_base.reshape(-1), n)].set(
        True, mode="drop")
    nb = nb.at[0].set(True)
    new_seg = jnp.cumsum(nb.astype(jnp.int32)) - 1

    # done ranges via +1/-1 marks and a prefix sum (empty groups cancel)
    gd = (gdone & (hist > 0)).reshape(-1)
    db = dest_base.reshape(-1)
    de = db + hist.reshape(-1)
    dm = jnp.zeros((n + 1,), jnp.int32)
    dm = dm.at[jnp.where(gd, db, n)].add(1, mode="drop")
    dm = dm.at[jnp.where(gd, de, n)].add(-1, mode="drop")
    new_done = done | (jnp.cumsum(dm)[:n] > 0)
    return new_seg, new_done


def single_pass_partition(ids: jnp.ndarray, num_buckets: int,
                          engine: str = None, interpret: bool = None,
                          kpb: int = 1024, step_batch: int = 8):
    """One engine-selected stable counting pass over flat bucket ids.

    The primitive under ``segmented.counting_partition`` (MoE dispatch,
    length bucketing, shard partitioning): returns ``(dest, perm, counts)``.
    ``engine="kernel"`` runs ONE fused Pallas launch (plus the prologue
    histogram); the jnp engines use ``ranks.stable_partition_dest``.

    ``None``/``"auto"`` resolves per backend (``ranks.resolve_engine``): the
    fused kernel on a TPU, ``argsort`` elsewhere.
    """
    interpret = resolve_interpret(interpret)
    engine = resolve_engine(engine)
    m = ids.shape[0]
    ids = ids.astype(jnp.int32)
    if m == 0 or engine != "kernel":
        jnp_engine = engine if engine != "kernel" else "argsort"
        dest = stable_partition_dest(ids, num_buckets, engine=jnp_engine)
        perm = invert_permutation(dest)
        counts = jnp.bincount(ids, length=num_buckets).astype(jnp.int32)
        return dest, perm, counts

    width = max(1, (num_buckets - 1).bit_length())
    r = 1 << width
    kpb = max(8, min(kpb, 1 << (m - 1).bit_length()))   # one block if m small
    iota = jnp.arange(m, dtype=jnp.int32)
    (ck, cv), (ak, av) = fused.make_ping_pong(ids, (iota,), kpb)
    hist0 = fused.initial_histogram(ck, m, 0, width, r, 1,
                                    interpret=interpret)
    base_excl = jnp.cumsum(hist0, axis=1) - hist0            # base 0
    blocks = make_region_blocks(jnp.zeros((1,), jnp.int32),
                                jnp.full((1,), m, jnp.int32), m, kpb,
                                max_region_blocks(m, kpb, 1),
                                batch=step_batch)
    sc = jnp.asarray([0, width, 0, 0, 0, 0], jnp.int32)
    nsid = jnp.zeros((r,), jnp.int32)
    _, (perm_pad,), _ = fused.fused_counting_pass(
        ck, cv, ak, av, sc, *blocks, base_excl, nsid,
        kpb=kpb, r=r, a_max=1, interpret=interpret)
    perm = fused.unpad(perm_pad, m)
    dest = invert_permutation(perm)
    return dest, perm, hist0[0, :num_buckets]


# --- contract declaration (verified by repro.analysis; see analysis/contracts)
# One standalone partition = prologue histogram + ONE fused launch; the iota
# permutation payload rides as one value leaf (vals = 1), so the pass moves
# (2·1+1) key sweeps + 2 payload sweeps over the padded buffer.
ANALYSIS_CONTRACT = {
    "entry": "repro.core.plan.single_pass_partition",
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
