"""Pallas TPU kernel: one fused launch per counting pass (§4.3–§4.4).

The paper's headline traffic reduction comes from *fusing* the three steps of
a counting pass — and the first step of the next pass — into one kernel:

  * §4.3: the scatter of pass i computes the digit histogram of pass i+1 on
    the keys it is already holding in VMEM, so every pass after the first
    reads the keys ONCE (scatter) instead of twice (histogram + scatter):
    per-pass traffic drops from 2R+1W to 1R+1W key-array sweeps,
  * §4.4: keys are partitioned digit-major inside VMEM first, so the HBM
    writes are per-digit contiguous runs (write combining for any skew),
  * §4.2: the launch has a *constant* grid; each grid step reads its block
    descriptors (which segment, which offset, how many live lanes) from a
    table, so one compiled kernel serves every data-dependent set of active
    buckets.

``fused_counting_pass`` is that launch.  The ping-pong buffers stay in HBM
(``memory_space=pl.ANY``) viewed flat as (rows, 128) lines; everything moves
by DMA.  Grid step g walks the B descriptor rows of its super-step (see
*Batched grid steps*); for each row:

  1. DMA the lines ``[off // 128, off // 128 + window_lines(kpb))`` that
     hold the block, of the *current* buffer (keys + value leaves), into
     the head of a ``window_rows(kpb)``-line VMEM window,
  2. extract the pass digit at the (lo, width) window of the pass scalars,
  3. partition the block digit-major in VMEM: a bitonic network
     (``kernels.bitonic``) over the unique composite ``digit << pb | lane``
     — the stable in-block rank is the sorted position, live lanes first,
  4. count (digit, next digit) pairs with one-hot MXU contractions: the
     column sums are the block histogram (§4.4's write counters), the matrix
     itself accumulates the §4.3 histogram of pass i+1 per segment,
  5. append each digit run to its *stream* — the destination range of one
     (segment, digit) sub-bucket, at segment base + in-segment digit offset
     (fused out of the previous pass) + in-segment block carry.  A run is
     rotated to its destination lane offset and merged into the stream's
     open line; every line it completes is written with a row DMA into the
     *alternate* buffer (``input_output_aliases`` donates it, §4.4's
     in-place replacement).  Done-bucket gap blocks are one stream each,
     copied through at their own offsets.
  6. at a region's last block, the partial lines at stream boundaries —
     shared by neighbouring streams — are merged in VMEM and written once,
     and the segment's next-pass histogram rows go to their compact
     next-pass segment ids.

Every destination line is written exactly once and never read back, so the
alternate buffer stays write-only.  Mosaic has no per-lane scatter into a
ref and no sub-line DMA on 32-bit HBM buffers; lines of 128 keys are the
smallest unit the kernel moves.

Batched grid steps (§4.2's over-decomposition, amortised)
---------------------------------------------------------
The descriptor tables arrive packed (``plan.pack_region_blocks``) as
(G', B) super-steps: grid step g owns the B consecutive descriptor rows
``[g*B, (g+1)*B)`` — padding rows on the masked tail carry ``count == 0``
and move nothing.  Packing rows *in descriptor order* is what keeps every
stream intact: the blocks of one region are consecutive rows and the TPU
grid is sequential, so the in-segment carries (the stream positions)
accumulate across rows and super-steps exactly as with one row per step.
The launch-census invariant is untouched — one pass is ONE ``pallas_call``
— on a grid of ``⌈g_max/B⌉`` steps.

Memory-transfer accounting per pass over n keys (k-bit, v-bit values):
  unfused (histogram launch + scatter launch):  keys 2R+1W, values 1R+1W
  fused   (this kernel):                        keys 1R+1W, values 1R+1W
plus one extra 1R histogram sweep for the very first pass (the prologue,
``initial_histogram``) — exactly the paper's accounting in §4.3/Table 2.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic import LANES, bitonic_network
from repro.kernels.histogram import radix_histogram_total

# descriptor fields, packed field-major into one (G', len * B) table
_SEG, _OFF, _RESET, _CNT, _ACT, _LAST = range(6)
_FIELDS = 6
# SMEM counters
_CUR_LINE, _ROWS, _HROWS, _BOUT, _OUTROW, _HPREV = range(6)


def window_rows(kpb: int) -> int:
    """Lines per block window: a power of two covering any kpb-key block at
    any lane offset (the bitonic partition sorts the whole window)."""
    need = -(-(kpb + LANES - 1) // LANES)
    return 1 << (need - 1).bit_length()


def window_lines(kpb: int) -> int:
    """Lines a block load moves from HBM: the lines any kpb-key block at any
    lane offset touches, rounded up to whole (8, 128) tiles.  The window's
    remaining rows hold no live key; what they hold never reaches HBM."""
    need = -(-(kpb + LANES - 1) // LANES)
    return min(window_rows(kpb), -(-need // 8) * 8)


def buffer_length(n: int, kpb: int) -> int:
    """Ping-pong buffer length: whole 128-key lines covering ``n`` plus one
    block load of slack, so every block load stays in bounds."""
    return (-(-n // LANES) + window_lines(kpb)) * LANES


def pad_length(n: int, kpb: int) -> int:
    """Padded tile-buffer length: whole KPB tiles plus one spare tile (the
    out-of-core merge slabs' layout)."""
    return n + ((-n) % kpb) + kpb


def require_kernel_keys(carrier_dtype, key_dtype, interpret: bool) -> None:
    """Refuse 64-bit key carriers on the compiled (TPU) kernel path.

    Mosaic has no 64-bit vector integers, so a compiled fused pass cannot
    hold a uint64 key in a vreg; the interpreter can.  The caller names the
    dtype and the engine that does sort it rather than failing deep in the
    lowering — and nothing switches engines behind the caller's back.
    """
    if not interpret and jnp.dtype(carrier_dtype).itemsize > 4:
        raise TypeError(
            f"{jnp.dtype(key_dtype).name} keys cannot run on the TPU kernel "
            f"path: Mosaic has no 64-bit vector integers. Sort them with "
            f"engine='argsort' (or compress=True when at most 32 key bits "
            f"are live).")


def make_ping_pong(keys: jnp.ndarray, val_leaves, kpb: int):
    """Pad keys + value leaves into (current, alternate) ping-pong buffers.

    Buffers are (rows, 128) lines of ``buffer_length(n, kpb)`` elements.
    Keys narrower than 32 bits ride in a uint32 carrier.  Key padding is the
    all-ones sentinel *of the key width* so the prologue histogram can
    subtract it from the top digit bucket; value padding is zeros.  Returns
    ``(cur_keys, cur_vals), (alt_keys, alt_vals)`` with ``vals`` as tuples.
    """
    n = keys.shape[0]
    n_pad = buffer_length(n, kpb)
    sentinel = ~jnp.zeros((), keys.dtype)
    if keys.dtype.itemsize < 4:
        keys, sentinel = keys.astype(jnp.uint32), sentinel.astype(jnp.uint32)

    def lines(x, fill):
        return jnp.concatenate(
            [x, jnp.full((n_pad - n,), fill, x.dtype)]).reshape(-1, LANES)

    ck = lines(keys, sentinel)
    cv = tuple(lines(v, 0) for v in val_leaves)
    ak = jnp.full_like(ck, sentinel)
    av = tuple(jnp.zeros_like(v) for v in cv)
    return (ck, cv), (ak, av)


def unpad(buf: jnp.ndarray, n: int, dtype=None) -> jnp.ndarray:
    """The first ``n`` elements of a (rows, 128) buffer, as ``dtype``."""
    out = buf.reshape(-1)[:n]
    return out if dtype is None else out.astype(dtype)


def initial_histogram(buf_keys: jnp.ndarray, n: int, lo: int, width: int,
                      r: int, a_max: int, *, interpret: bool) -> jnp.ndarray:
    """Histogram of the first pass's digit over the single segment [0, n).

    This is the one unfused key sweep of the whole sort (§4.3: pass 0 has no
    previous scatter to fuse with).  ``buf_keys`` is a sentinel-padded
    ping-pong buffer from ``make_ping_pong``; the sentinels extract the
    all-ones digit and are subtracted from the top bucket.  Returns the
    (a_max, r) per-active-segment histogram table with row 0 populated.
    """
    r0 = 1 << width
    hist = radix_histogram_total(buf_keys, lo, width, interpret=interpret)
    hist = hist.at[r0 - 1].add(-(buf_keys.size - n))
    out = jnp.zeros((a_max, r), jnp.int32)
    return out.at[0, :r0].set(hist)


def _rotate_flat(x, sh, rows: int):
    """out[p] = x[(p - sh) mod rows*128] for a (rows, 128) array (sh >= 0)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = sh % LANES
    t = sh // LANES
    y = pltpu.roll(x, s, 1)
    return jnp.where(lane >= s, pltpu.roll(y, t % rows, 0),
                     pltpu.roll(y, (t + 1) % rows, 0))


def _fused_pass_kernel(sc_ref, srck_hbm, *refs, r: int, a_max: int,
                       num_vals: int, batch: int, g_steps: int,
                       lookahead: bool, kpb: int, rows_w: int,
                       out_rows: int, lpr: int, interpret: bool):
    """One grid step = one packed super-step of ``batch`` descriptor rows
    (see module docstring)."""
    nv = num_vals
    it = iter(refs)
    srcv_hbm = [next(it) for _ in range(nv)]
    # the aliased alternate buffers (1 + nv) follow the sources: they only
    # donate their memory to the outputs and are never touched here
    for _ in range(1 + nv):
        next(it)
    desc_hbm, segtab_hbm = next(it), next(it)
    dstk_hbm = next(it)
    dstv_hbm = [next(it) for _ in range(nv)]
    hist_hbm = next(it)
    hist2_hbm = next(it) if lookahead else None
    desc_s, stab, shist, spos, ctr = [next(it) for _ in range(5)]
    na = 1 + nv                            # moved arrays: keys + value leaves
    win, sorted_buf, outbuf, stg, hd, cur, bout = (
        [next(it) for _ in range(na)] for _ in range(7))
    h2t, h2t2, hbuf, hacc, hout, zbuf, hv_v, dig_s, nd_s, nd2_s, sems = [
        next(it) for _ in range(11)]
    srcs = [srck_hbm] + srcv_hbm
    dsts = [dstk_hbm] + dstv_hbm
    hists = [hist_hbm] + ([hist2_hbm] if lookahead else [])
    hsem = sems.at[na]

    g = pl.program_id(0)
    n_win = rows_w * LANES
    fetch = window_lines(kpb)
    pbits = (n_win - 1).bit_length()
    kdt = srck_hbm.dtype
    one = jnp.ones((), kdt)
    lo = sc_ref[0].astype(kdt)
    width = sc_ref[1].astype(kdt)
    nlo = sc_ref[2].astype(kdt)
    nwidth = sc_ref[3].astype(kdt)
    max_rows = out_rows + 2 * r + 8        # line DMAs one block may issue
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    rowi = jax.lax.broadcasted_iota(jnp.int32, (rows_w, LANES), 0)
    flat = rowi * LANES + jax.lax.broadcasted_iota(jnp.int32,
                                                   (rows_w, LANES), 1)

    def wait_rows(count, sem, src, dst, max_count: int):
        """Wait for ``count`` (<= max_count) line DMAs signalled on ``sem``:
        a semaphore counts bytes, so waits of any split of the total do.
        Every destination line is written once, so ``count`` never exceeds
        the destination's rows either."""
        cap = 1 << (min(src.shape[0], dst.shape[0]).bit_length() - 1)
        for b in range(min(max_count, dst.shape[0]).bit_length()):
            size = 1 << b

            @pl.when((count >> b) & 1 == 1)
            def _():
                for _ in range(max(1, size // cap)):
                    sz = min(size, cap)
                    pltpu.make_async_copy(src.at[pl.ds(0, sz)],
                                          dst.at[pl.ds(0, sz)], sem).wait()

    def put_rows(a, src_row, dst_line, count):
        """Row DMAs of ``count`` (< 2^k) lines: out rows -> dst lines."""
        for b in range(rows_w.bit_length()):
            size = 1 << b

            @pl.when((count >> b) & 1 == 1)
            def _():
                low = count & (size - 1)
                pltpu.make_async_copy(
                    outbuf[a].at[pl.ds(src_row + low, size)],
                    dsts[a].at[pl.ds(dst_line + low, size)],
                    sems.at[a]).start()

    @pl.when(g == 0)
    def _init():
        ctr[_CUR_LINE] = -1
        zbuf[...] = jnp.zeros_like(zbuf)
        zr = zbuf.shape[0]
        copies = []
        lines = a_max * lpr                    # next-pass histograms: zero
        sizes = [zr] * (lines // zr) + [lines % zr] * (lines % zr > 0)
        for h in hists:
            for c0, size in zip(itertools.accumulate([0] + sizes), sizes):
                cp = pltpu.make_async_copy(zbuf.at[pl.ds(0, size)],
                                           h.at[pl.ds(c0, size)], hsem)
                cp.start()
                copies.append(cp)
        for cp in copies:
            cp.wait()

    pltpu.sync_copy(desc_hbm.at[pl.ds(g, 1)], desc_s)

    def field(f, j):
        return desc_s[0, f * batch + j]

    def base_of(v):
        """Stream v's start: the segment's run base for digit v."""
        return stab[v // LANES, v % LANES]

    def sid_of(v):
        """Compact next-pass segment id of the segment's digit-v bucket."""
        return stab[lpr + v // LANES, v % LANES]

    def flush_line(line):
        """Write the merged boundary line ``cur`` to ``line`` (once)."""
        bo = ctr[_BOUT]
        for a in range(na):
            bout[a][pl.ds(bo, 1), :] = cur[a][...]
            pltpu.make_async_copy(bout[a].at[pl.ds(bo, 1)],
                                  dsts[a].at[pl.ds(line, 1)],
                                  sems.at[a]).start()
        ctr[_BOUT] = bo + 1
        ctr[_ROWS] = ctr[_ROWS] + 1

    def add_piece(line, a_lo, a_hi, src):
        """Merge lanes [a_lo, a_hi) of ``src(a)`` into the boundary line."""
        @pl.when(line != ctr[_CUR_LINE])
        def _():
            @pl.when(ctr[_CUR_LINE] >= 0)
            def _():
                flush_line(ctr[_CUR_LINE])
            ctr[_CUR_LINE] = line

        m = (lane1 >= a_lo) & (lane1 < a_hi)
        for a in range(na):
            cur[a][...] = jnp.where(m, src(a), cur[a][...])

    def close_stream(v, s, e):
        """Hand the stream's partial head / tail lines to the line merger."""
        hl, hs = s // LANES, s % LANES
        tl, ts = e // LANES, e % LANES
        stg_row = lambda a: stg[a][pl.ds(v, 1), :]
        hd_row = lambda a: hd[a][pl.ds(v, 1), :]

        @pl.when(e > s)
        def _():
            @pl.when((hs != 0) & (tl == hl))
            def _():
                add_piece(hl, hs, ts, stg_row)

            @pl.when((hs != 0) & (tl != hl))
            def _():
                add_piece(hl, hs, LANES, hd_row)

            @pl.when((ts != 0) & ((hs == 0) | (tl != hl)))
            def _():
                add_piece(tl, 0, ts, stg_row)

    def append(v, data, e0, c):
        """Append ``c`` keys at window position ``e0`` to stream ``v``."""
        p = spos[v]
        s = base_of(v)
        q = p % LANES
        line0 = p // LANES
        sh = (q - e0 + n_win) % n_win
        o = ctr[_OUTROW]
        nfull = (q + c) // LANES
        head_open = (s % LANES != 0) & (line0 == s // LANES)
        for a in range(na):
            y = _rotate_flat(data[a], sh, rows_w)
            y = jnp.where((rowi == 0) & (flat < q), stg[a][pl.ds(v, 1), :], y)
            outbuf[a][pl.ds(o, rows_w), :] = y
            stg[a][pl.ds(v, 1), :] = outbuf[a][pl.ds(o + nfull, 1), :]

            @pl.when(head_open & (nfull >= 1))
            def _():
                hd[a][pl.ds(v, 1), :] = outbuf[a][pl.ds(o, 1), :]

        first = (head_open & (nfull >= 1)).astype(jnp.int32)
        for a in range(na):
            put_rows(a, o + first, line0 + first, nfull - first)
        ctr[_ROWS] = ctr[_ROWS] + nfull - first
        ctr[_OUTROW] = o + nfull
        spos[v] = p + c

    def block(j, carry):
        cnt = field(_CNT, j)

        @pl.when(cnt > 0)
        def _():
            seg = field(_SEG, j)
            off = field(_OFF, j)
            act = field(_ACT, j)
            first_blk = field(_RESET, j) == 1
            asafe = jnp.clip(seg, 0, a_max - 1)
            ctr[_ROWS] = 0
            ctr[_HROWS] = 0
            ctr[_BOUT] = 0
            ctr[_OUTROW] = 0

            @pl.when(first_blk & (act == 1))
            def _():
                pltpu.sync_copy(
                    segtab_hbm.at[pl.ds(asafe * 2 * lpr, 2 * lpr)], stab)

                def reset(v, c_):
                    spos[v] = base_of(v)
                    return c_
                jax.lax.fori_loop(0, r, reset, 0)
                h2t[...] = jnp.zeros_like(h2t)
                if lookahead:
                    h2t2[...] = jnp.zeros_like(h2t2)

            @pl.when(first_blk & (act == 0))
            def _():
                stab[0, 0] = off
                spos[0] = off

            line_lo = off // LANES
            copies = [pltpu.make_async_copy(
                srcs[a].at[pl.ds(line_lo, fetch)],
                win[a].at[pl.ds(0, fetch)], sems.at[a])
                for a in range(na)]
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()
            e_lo = off % LANES
            live = (flat >= e_lo) & (flat < e_lo + cnt)

            @pl.when(act == 1)
            def _partition():
                keys = win[0][...]
                digit = ((keys >> lo) & ((one << width) - one)).astype(
                    jnp.int32)
                comp = jnp.where(live, digit, r) * (1 << pbits) + flat
                srt = bitonic_network([comp] + [win[a][...] for a in range(na)],
                                      lambda x, y: x[0] < y[0], n_win,
                                      interpret=interpret)
                for a in range(na):
                    sorted_buf[a][...] = srt[1 + a]

                # (next digit x digit) counts on the MXU, 128 keys at a time
                dig_s[...] = jnp.where(live, digit, r)
                nd_s[...] = ((keys >> nlo) & ((one << nwidth) - one)).astype(
                    jnp.int32)
                if lookahead:
                    n2lo = sc_ref[4].astype(kdt)
                    n2width = sc_ref[5].astype(kdt)
                    nd2_s[...] = ((keys >> n2lo) & ((one << n2width) - one)
                                  ).astype(jnp.int32)
                iota_r = jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 0)

                def count_row(i, acc):
                    oh = lambda ref: (ref[pl.ds(i, 1), :] == iota_r).astype(
                        jnp.bfloat16)
                    a_oh = oh(dig_s)
                    mm = lambda b_oh: jax.lax.dot_general(
                        b_oh, a_oh, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    out = (acc[0] + mm(oh(nd_s)),)
                    if lookahead:
                        out += (acc[1] + mm(oh(nd2_s)),)
                    return out

                zero = jnp.zeros((r, r), jnp.float32)
                mats = jax.lax.fori_loop(0, rows_w, count_row,
                                         (zero,) * (2 if lookahead else 1))
                # per-block counts are exact in f32 (<= one window); the
                # segment total can pass 2^24, so it accumulates in int32
                h2t[...] += mats[0].astype(jnp.int32)
                if lookahead:
                    h2t2[...] += mats[1].astype(jnp.int32)
                hv_v[...] = jnp.sum(mats[0], axis=0,
                                    keepdims=True).astype(jnp.int32)
                pltpu.sync_copy(hv_v, shist)

                def digit_run(v, e):
                    c = shist[0, v]

                    @pl.when(c > 0)
                    def _():
                        append(v, [sorted_buf[a][...] for a in range(na)], e, c)
                    return e + c
                jax.lax.fori_loop(0, r, digit_run, 0)

            @pl.when(act == 0)
            def _copy():
                append(0, [win[a][...] for a in range(na)], e_lo, cnt)

            @pl.when(field(_LAST, j) == 1)
            def _region_end():
                def close(v, c_):
                    close_stream(v, base_of(v), spos[v])
                    return c_

                @pl.when(act == 1)
                def _():
                    jax.lax.fori_loop(0, r, close, 0)

                @pl.when(act == 0)
                def _():
                    close(0, 0)

                @pl.when(act == 1)
                def _next_hist():
                    for h, mat, w_slot in ((hist_hbm, h2t, 3),
                                           (hist2_hbm, h2t2, 5))[
                                               :1 + lookahead]:
                        @pl.when(sc_ref[w_slot] > 0)
                        def _():
                            h2 = jnp.transpose(mat[...])
                            if h2.shape[1] == hbuf.shape[1]:
                                hbuf[...] = h2
                            else:                      # r < 128: pad lanes
                                hbuf[...] = jnp.zeros_like(hbuf)
                                hbuf[:, :r] = h2

                            # next-pass ids rise with the digit; runs of
                            # digits sharing an id (LSD: every digit) add up
                            ctr[_HPREV] = -1

                            def emit():
                                k = ctr[_HROWS]
                                row = hacc[...]
                                for i in range(lpr):
                                    hout[pl.ds(k * lpr + i, 1), :] = row[
                                        :, i * LANES:(i + 1) * LANES]
                                pltpu.make_async_copy(
                                    hout.at[pl.ds(k * lpr, lpr)],
                                    h.at[pl.ds(ctr[_HPREV] * lpr, lpr)],
                                    hsem).start()
                                ctr[_HROWS] = k + 1

                            def put(v, c_):
                                sid = sid_of(v)

                                @pl.when(sid < a_max)
                                def _():
                                    @pl.when(sid != ctr[_HPREV])
                                    def _():
                                        @pl.when(ctr[_HPREV] >= 0)
                                        def _():
                                            emit()
                                        ctr[_HPREV] = sid
                                        hacc[...] = jnp.zeros_like(hacc)
                                    hacc[...] += hbuf[pl.ds(v, 1), :]
                                return c_
                            jax.lax.fori_loop(0, r, put, 0)

                            @pl.when(ctr[_HPREV] >= 0)
                            def _():
                                emit()
                            wait_rows(ctr[_HROWS] * lpr, hsem, hout, h,
                                      r * lpr)
                            ctr[_HROWS] = 0

            for a in range(na):
                wait_rows(ctr[_ROWS], sems.at[a], outbuf[a], dsts[a],
                          max_rows)
        return carry

    jax.lax.fori_loop(0, batch, block, 0)

    @pl.when(g == g_steps - 1)
    def _final():
        @pl.when(ctr[_CUR_LINE] >= 0)
        def _():
            ctr[_ROWS] = 0
            ctr[_BOUT] = 0
            flush_line(ctr[_CUR_LINE])
            for a in range(na):
                wait_rows(ctr[_ROWS], sems.at[a], outbuf[a], dsts[a], 1)


def _descriptor_table(blk_seg, blk_off, blk_reset, blk_count, blk_active):
    """Pack the (G', B) descriptor tables field-major into one row of
    128-lane lines per grid step, with the derived ``last`` flag (the row
    closes its region)."""
    g_steps, batch = blk_seg.shape
    flat_reset = blk_reset.reshape(-1)
    last = jnp.concatenate([flat_reset[1:], jnp.ones((1,), jnp.int32)])
    last = last.reshape(g_steps, batch)
    desc = jnp.concatenate([blk_seg, blk_off, blk_reset, blk_count,
                            blk_active, last], axis=1).astype(jnp.int32)
    return jnp.pad(desc, ((0, 0), (0, (-desc.shape[1]) % LANES)))


@functools.partial(jax.jit, static_argnames=("kpb", "r", "a_max",
                                             "interpret", "lookahead"))
def fused_counting_pass(src_keys, src_vals, alt_keys, alt_vals, pass_scalars,
                        blk_seg, blk_off, blk_reset, blk_count, blk_active,
                        base_excl, next_sid, *, kpb: int, r: int, a_max: int,
                        interpret: bool, lookahead: bool = False):
    """One full counting pass over all active buckets in ONE Pallas launch.

    Arguments:
      src_keys / src_vals     — current ping-pong buffers (``make_ping_pong``:
                                (rows, 128) lines; vals is a tuple of arrays
                                shaped like the keys),
      alt_keys / alt_vals     — alternate buffers, donated to the outputs via
                                ``input_output_aliases`` (§4.4 in-place
                                replacement),
      pass_scalars            — int32 [lo, width, next_lo, next_width] digit
                                windows (``plan.digit_window``); with
                                ``lookahead`` two extra slots
                                [next2_lo, next2_width] locate the pass-i+2
                                window the adaptive schedule histograms
                                alongside,
      blk_*                   — int32 block descriptor tables
                                (``plan.make_region_blocks``): compact segment
                                index (a_max = copy-through), key offset,
                                carry-reset flag, live-lane count, active
                                flag.  Either flat (G,) rows (one per grid
                                step) or (G', B) super-steps packed by
                                ``plan.pack_region_blocks`` — the grid is the
                                leading axis either way,
      base_excl               — (a_max, r) int32 absolute run starts per
                                (active segment, digit): base + exclusive scan
                                of the carried histogram,
      next_sid                — (a_max * r,) int32 map from (segment, digit)
                                sub-bucket to its compact next-pass active
                                segment id (a_max = done / not active).

    Returns ``(new_keys, new_vals, hist_next)`` where ``hist_next`` is the
    (a_max * r,) fused histogram of the NEXT pass's digit (reshape to
    (a_max, r)); row j matches the j-th next-pass active segment in position
    order.  With ``lookahead=True`` the return gains a fourth element
    ``hist_next2`` — the pass-i+2 window histogrammed under the same
    next-pass segment keys, which is exactly pass i+2's histogram whenever
    pass i+1 is elided (single occupied digit per segment => identity
    scatter and a 1:1 segment map).  Exactly one ``pallas_call`` in the
    trace either way — the property the launch-counter regression test pins
    down.
    """
    if blk_seg.ndim == 1:                    # flat rows = B=1 super-steps
        blk_seg, blk_off, blk_reset, blk_count, blk_active = (
            t.reshape(-1, 1)
            for t in (blk_seg, blk_off, blk_reset, blk_count, blk_active))
    g_steps, batch = blk_seg.shape
    num_vals = len(src_vals)
    na = 1 + num_vals
    rows_w = window_rows(kpb)
    assert (r.bit_length() - 1) + (rows_w * LANES - 1).bit_length() < 31, \
        "digit and window position must pack into one int32"
    out_rows = rows_w + r + -(-kpb // LANES) + 8
    desc = _descriptor_table(blk_seg, blk_off, blk_reset, blk_count,
                             blk_active)
    # per-digit tables travel as 128-lane lines (the granularity row DMAs
    # of HBM and VMEM support): one digit row is ``lpr`` lines, and segment
    # a's run bases and next-pass ids sit side by side, one DMA per segment
    rw = -(-r // LANES) * LANES
    lpr = rw // LANES
    as_lines = lambda t: jnp.pad(t.reshape(a_max, r).astype(jnp.int32),
                                 ((0, 0), (0, rw - r))).reshape(a_max, lpr,
                                                                LANES)
    segtab = jnp.concatenate([as_lines(base_excl), as_lines(next_sid)],
                             axis=1).reshape(-1, LANES)

    arrays = [src_keys, *src_vals]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    hists = 2 if lookahead else 1
    vmem = lambda shape, dt=jnp.int32: pltpu.VMEM(shape, dt)
    moved = lambda shape: [pltpu.VMEM(shape, x.dtype) for x in arrays]
    scratch = [
        pltpu.SMEM((1, desc.shape[1]), jnp.int32),        # desc_s
        pltpu.SMEM((2 * lpr, LANES), jnp.int32),          # stab
        pltpu.SMEM((1, r), jnp.int32),                    # shist
        pltpu.SMEM((r,), jnp.int32),                      # spos
        pltpu.SMEM((8,), jnp.int32),                      # ctr
        *moved((rows_w, LANES)),                          # win
        *moved((rows_w, LANES)),                          # sorted_buf
        *moved((out_rows, LANES)),                        # outbuf
        *moved((r, LANES)),                               # stg
        *moved((r, LANES)),                               # hd
        *moved((1, LANES)),                               # cur
        *moved((2 * r + 8, LANES)),                       # bout
        vmem((r, r)),                                     # h2t
        vmem((r, r) if lookahead else (1, 1)),            # h2t2
        vmem((r, rw)),                                    # hbuf
        vmem((1, rw)),                                    # hacc
        vmem((r * lpr, LANES)),                           # hout
        vmem((min(a_max * lpr, 512), LANES)),             # zbuf
        vmem((1, r)),                                     # hv_v
        vmem((rows_w, LANES)),                            # dig_s
        vmem((rows_w, LANES)),                            # nd_s
        vmem((rows_w, LANES)),                            # nd2_s
        pltpu.SemaphoreType.DMA((na + 1,)),               # sems
    ]
    out_shape = ([jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arrays] +
                 [jax.ShapeDtypeStruct((a_max * lpr, LANES), jnp.int32)] *
                 hists)
    # operand index space: the pass scalars, the sources, the alternate
    # buffers — which donate their memory to the new key / value outputs —
    # then the descriptor, base and next-segment tables
    alt0 = 1 + na
    aliases = {alt0 + i: i for i in range(na)}

    # under vmap (MoE dispatch over token groups) the pass runs once per
    # batch element: Mosaic cannot batch a kernel whose operands stay in HBM
    out = jax.custom_batching.sequential_vmap(pl.pallas_call(
        functools.partial(_fused_pass_kernel, r=r, a_max=a_max,
                          num_vals=num_vals, batch=batch, g_steps=g_steps,
                          lookahead=lookahead, kpb=kpb, rows_w=rows_w,
                          out_rows=out_rows, lpr=lpr, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g_steps,),
            in_specs=[hbm] * (2 * na + 2),
            out_specs=[hbm] * (na + hists),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_counting_pass",
    ))(pass_scalars, src_keys, *src_vals, alt_keys, *alt_vals, desc,
       segtab)

    new_keys = out[0]
    new_vals = tuple(out[1:na])
    table = lambda h: h.reshape(a_max, rw)[:, :r].reshape(-1)
    hist_next = table(out[na])
    if lookahead:
        return new_keys, new_vals, hist_next, table(out[na + 1])
    return new_keys, new_vals, hist_next
