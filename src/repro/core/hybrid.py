"""The hybrid MSD radix sort (paper §4) as a composable JAX transform.

Algorithm (faithful to §4.1–§4.5):

  * counting-sort passes proceed from the most-significant d-bit digit; each
    pass partitions every *active* bucket (size > ∂̂) into up to r = 2^d
    sub-buckets (R2),
  * runs of tiny sub-buckets are merged while their total stays below ∂ (R3),
  * buckets at or below ∂̂ become *done* and are finished by a single local
    sort that touches device memory only twice (R1),
  * the loop exits as soon as no active bucket remains (data-dependent trip
    count — the "finish early" behaviour that yields the 4x uniform-input
    speedup) or when digits are exhausted,
  * all bookkeeping arrays are statically sized by the analytical model §4.5
    (see core.model) — the paper's bounds are what make the algorithm
    expressible under XLA's static shapes.

Bucket state is carried *per key* (segment ids + done flags), which is the
dense JAX analogue of the paper's block-assignment lists; ``core.plan`` owns
every derived descriptor (active segments, block tables, merge bookkeeping,
digit windows) so the three engines share one set of invariants.

Three interchangeable engines compute each pass (byte-identical outputs):

  * ``kernel``  — ONE fused Pallas launch per pass (``kernels.fused``):
    block-descriptor-driven tile partition + coalesced scatter of pass i
    fused with the digit histogram of pass i+1 (§4.2–§4.4), on ping-pong
    key/value buffers with donation.  Per pass the keys are read once and
    written once; only the very first pass pays an extra histogram sweep.
    Zero comparison sorts in the traced HLO.
  * ``argsort`` — two fused XLA stable sorts per pass; the CPU default.
  * ``scan``    — the O(n) chunked-histogram fallback from ``core.ranks``.

Entropy-adaptive schedule (``cfg.adaptive`` / the ``adaptive`` argument):

  * *static narrowing* — when the keys are concrete (not traced), one host
    OR/AND-reduce finds the globally live bit window [lo, hi): dead high
    bits (shared prefixes) and dead low bits never get a pass, so the
    schedule runs ⌈(hi - lo)/d⌉ passes instead of ⌈k/d⌉.  Traced keys keep
    the full window — narrowing never changes a compiled trace's shape.
  * *mid-sort elision* — the pass loop watches the histogram it already has
    (fused out of the previous scatter): when every active segment has a
    single occupied digit the pass's scatter is the identity, so the launch
    is skipped while the bookkeeping still advances.  The fused kernel
    histograms a second *lookahead* window (pass i+2) alongside pass i+1's
    so an elided pass leaves the next histogram in hand; elision therefore
    needs no extra key sweep and no extra launch — each elided pass is an
    elided ``pallas_call``.  All engines evaluate the identical predicate,
    keeping outputs and ``SortStats`` byte-identical across engines.
  * *compressed keys* (``hybrid_sort(compress=True)``, concrete keys only)
    — ``bijection.CompressionPlan`` packs out every dead bit column and
    sorts the narrowed carrier (uint64 keys with <= 32 live bits sort as
    uint32), inverting exactly afterwards.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bijection, model, plan
from repro.core.ranks import (resolve_engine, resolve_interpret,
                              stable_partition_dest)
from repro.kernels import fused
from repro.kernels.ops import (local_sort_class_plan, local_sort_tile_count,
                               segmented_local_sort)


class SortStats(NamedTuple):
    counting_passes: jnp.ndarray   # number of executed counting-sort passes
    used_local_sort: jnp.ndarray   # bool: did the final local sort run
    num_segments: jnp.ndarray      # segments at exit (I3 bound check)
    max_segment: jnp.ndarray       # largest segment at exit
    elided_passes: jnp.ndarray = jnp.int32(0)  # adaptive: passes advanced
                                               # with no launch/partition
    local_sort_tiles: jnp.ndarray = jnp.int32(0)  # tiles the kernel
                                                  # engine's local sort runs


def live_bit_window(ukeys) -> tuple:
    """Static live-bit window [lo, hi) of a host-resident ordered-bits array.

    One OR-reduce and one AND-reduce: bits where they agree are globally
    constant and carry no ordering information.  Returns ``(lo, hi)`` as
    Python ints — ``(0, 0)`` when every key is equal (or the array is
    empty), so a narrowed schedule plans zero passes.
    """
    ukeys = np.asarray(ukeys).reshape(-1)
    if ukeys.size == 0:
        return 0, 0
    orv = int(np.bitwise_or.reduce(ukeys))
    andv = int(np.bitwise_and.reduce(ukeys))
    live = orv ^ andv
    if not live:
        return 0, 0
    return (live & -live).bit_length() - 1, live.bit_length()


def _skip_predicate(hist, nxt_valid, p, nd):
    """The shared elision predicate (identical across all engines).

    A pass is elidable when every active segment has at most one occupied
    digit — its stable scatter is then the identity permutation.  It may
    actually be skipped only when the NEXT pass's histogram is already in
    hand (``nxt_valid``: the previous pass executed with lookahead) or when
    this is the final pass (the loop exits; no next histogram is needed).
    """
    single = jnp.all(jnp.sum(hist > 0, axis=1) <= 1)
    return single & (nxt_valid | (p >= nd - 1))


def _counting_pass_jnp(state, *, k, d, lo, a_max, nd, cfg, engine, adaptive):
    """One counting pass, jnp engines: XLA stable sorts or the scan ranks."""
    ukeys, vals, seg_id, done, nxt_valid, p, p_exec, n_eld = state
    n = ukeys.shape[0]
    r = 1 << d
    active = ~done
    with jax.named_scope("pass_bookkeeping"):
        asegs = plan.active_segments(seg_id, done, a_max)
    asid = asegs.index

    with jax.named_scope("counting_pass"):
        digit = plan.digit_at(ukeys, p, k, d, lo=lo)
        # (a, digit) histogram — only active keys contribute (M2 of the model)
        idx = jnp.where(active, asid * r + digit, 0)
        hist = jnp.zeros((a_max * r,), jnp.int32).at[idx].add(
            active.astype(jnp.int32)).reshape(a_max, r)

    @jax.named_scope("counting_pass")
    def partition():
        # destination permutation: stable partition by (active segment,
        # digit); done keys carry a +inf-like composite and stay in place.
        sentinel = jnp.int32(a_max * r)
        composite = jnp.where(active, asid * r + digit, sentinel)
        dest0 = stable_partition_dest(composite, a_max * r + 1, engine=engine)
        done_rank = stable_partition_dest(done.astype(jnp.int32), 2,
                                          engine=engine)
        slots = jnp.zeros((n,), jnp.int32).at[done_rank].set(
            jnp.arange(n, dtype=jnp.int32))   # active slots asc, then done asc
        dest = slots[dest0]
        new_keys = jnp.zeros_like(ukeys).at[dest].set(ukeys)
        new_vals = jax.tree.map(lambda v: jnp.zeros_like(v).at[dest].set(v),
                                vals)
        return new_keys, new_vals

    if adaptive:
        with jax.named_scope("pass_bookkeeping"):
            skip = _skip_predicate(hist, nxt_valid, p, nd)
        new_keys, new_vals = lax.cond(skip, lambda: (ukeys, vals), partition)
        nvalid = (~skip) & (p + 2 < nd)
        p_exec = p_exec + (~skip).astype(jnp.int32)
        n_eld = n_eld + skip.astype(jnp.int32)
    else:
        new_keys, new_vals = partition()
        nvalid = nxt_valid
        p_exec = p_exec + 1

    # bucket bookkeeping: merged-group starts (R3) become the new boundaries
    with jax.named_scope("pass_bookkeeping"):
        gstart, gdone = plan.merge_rows(hist, cfg.local_threshold,
                                        cfg.merge_threshold)
        excl = jnp.cumsum(hist, axis=1) - hist
        dest_base = asegs.base[:, None] + excl                # (a_max, r)
        new_seg, new_done = plan.apply_pass_bookkeeping(
            seg_id, done, asegs, hist, gstart, gdone, dest_base)
    return (new_keys, new_vals, new_seg, new_done, nvalid, p + 1, p_exec,
            n_eld)


def _counting_pass_fused(state, *, k, d, lo, a_max, g_max, n, nd, cfg,
                         adaptive, interpret):
    """One counting pass, kernel engine: a single fused Pallas launch.

    ``state`` carries the ping-pong buffers, the dense bucket state and the
    per-active-segment histogram of THIS pass's digit — fused out of the
    previous pass's scatter (§4.3; the first pass's comes from the prologue
    sweep).  The launch reads the keys once and writes them once.  Under
    the adaptive schedule the state also carries the *lookahead* histogram
    (next pass's window, fused out of the same scatter) and its validity
    flag; when the shared skip predicate fires, the launch is elided — the
    identity scatter never runs, the ping-pong buffers stand still, and
    only the bookkeeping advances.  Exactly one ``pallas_call`` sits in the
    loop body either way (the elided branch contains none), which is what
    keeps the launch census per EXECUTED pass.
    """
    (ck, cv, ak, av, seg_id, done, hist_cur, hist_nxt, nxt_valid, p, p_exec,
     n_eld) = state
    r = 1 << d
    with jax.named_scope("pass_bookkeeping"):
        asegs = plan.active_segments(seg_id, done, a_max)
        gstart, gdone = plan.merge_rows(hist_cur, cfg.local_threshold,
                                        cfg.merge_threshold)
        excl = jnp.cumsum(hist_cur, axis=1) - hist_cur
        dest_base = asegs.base[:, None] + excl                # (a_max, r)
        nsid = plan.next_active_table(hist_cur, cfg.local_threshold, a_max)
        new_seg, new_done = plan.apply_pass_bookkeeping(
            seg_id, done, asegs, hist_cur, gstart, gdone, dest_base)

    def launch():
        with jax.named_scope("pass_bookkeeping"):
            blocks = plan.make_region_blocks(asegs.base, asegs.size, n,
                                             cfg.kpb, g_max,
                                             batch=cfg.step_batch)
            sc = plan.digit_window(p, k, d, lo=lo)
        pass_args = (ck, cv, ak, av, sc, *blocks, dest_base, nsid)
        if adaptive:
            with jax.named_scope("counting_pass"):
                nk, nv, h1, h2 = fused.fused_counting_pass(
                    *pass_args, kpb=cfg.kpb, r=r, a_max=a_max,
                    interpret=interpret, lookahead=True)
            return (nk, nv, ck, cv, h1.reshape(a_max, r),
                    h2.reshape(a_max, r), p + 2 < nd, p_exec + 1, n_eld)
        with jax.named_scope("counting_pass"):
            nk, nv, h1 = fused.fused_counting_pass(
                *pass_args, kpb=cfg.kpb, r=r, a_max=a_max,
                interpret=interpret)
        return (nk, nv, ck, cv, h1.reshape(a_max, r), hist_nxt, nxt_valid,
                p_exec + 1, n_eld)

    if adaptive:
        def elide():
            # identity scatter: buffers and positions stand still; the
            # lookahead histogram becomes the next pass's current histogram
            return (ck, cv, ak, av, hist_nxt, jnp.zeros_like(hist_nxt),
                    jnp.bool_(False), p_exec, n_eld + 1)
        with jax.named_scope("pass_bookkeeping"):
            skip = _skip_predicate(hist_cur, nxt_valid, p, nd)
        nk, nv, nak, nav, h_cur, h_nxt, nvalid, npe, nne = lax.cond(
            skip, elide, launch)
    else:
        nk, nv, nak, nav, h_cur, h_nxt, nvalid, npe, nne = launch()
    # flip: the freshly written buffers become current, the old ones the
    # donation targets of the next pass (an elided pass flips nothing)
    return (nk, nv, nak, nav, new_seg, new_done, h_cur, h_nxt, nvalid,
            p + 1, npe, nne)


@jax.named_scope("local_sort")
def _local_sort(ukeys, vals, seg_id, done):
    """Finish done buckets in one read+write: sort by (bucket, remaining key).

    Keys within a bucket share their already-processed digit prefix, so
    ordering by the full key equals ordering by the remaining digits — this is
    the LSD-on-remaining-digits local sort of §4.1, realised as a segmented
    sort (the Pallas bitonic kernel is the on-TPU tile engine for it).

    Only *done* buckets sort (masked key + stable lexsort keeps the rest in
    place).  At genuine digit exhaustion non-done buckets hold equal keys so
    this changes nothing; under ``max_passes`` truncation it keeps every
    engine's output identical: partition-ordered, unfinished buckets as-is.
    """
    perm = jnp.lexsort((jnp.where(done, ukeys, jnp.zeros_like(ukeys)), seg_id))
    return ukeys[perm], jax.tree.map(lambda v: v[perm], vals)


def _done_segments(seg_id, done, s_max: int):
    """``(starts, sizes, sortable)`` of the segments, padded to ``s_max``
    slots (start n, size 0): which done buckets the local sort finishes."""
    n = seg_id.shape[0]
    boundary = jnp.concatenate([jnp.ones((1,), bool),
                                seg_id[1:] != seg_id[:-1]])
    starts = jnp.nonzero(boundary, size=s_max,
                         fill_value=n)[0].astype(jnp.int32)
    ends = jnp.concatenate([starts[1:], jnp.array([n], jnp.int32)])
    sizes = ends - starts                                 # 0 on padding rows
    sortable = done[jnp.clip(starts, 0, n - 1)] & (starts < n)
    return starts, sizes, sortable


def _local_sort_kernel(ukeys, vals, seg_id, done, *, s_max, row_len, classes,
                       interpret):
    """Kernel-engined local sort: done buckets gather into sentinel-padded
    rows binned by power-of-two size class (R1 guarantees the widest class
    is next_pow2(∂̂); §4.2's local sort configurations keep tiny buckets off
    worst-case padding), the stable bitonic kernel sorts each class's rows
    by (key, position), a tile of occupied rows at a time, and the run
    copies scatter the sorted prefixes back.  Non-done buckets at digit
    exhaustion hold equal keys, so skipping them matches the jnp engines'
    stable lexsort exactly.
    """
    with jax.named_scope("local_sort"):
        with jax.named_scope("bounds"):
            starts, sizes, sortable = _done_segments(seg_id, done, s_max)
        return segmented_local_sort((ukeys, vals), starts, sizes, sortable,
                                    row_len, interpret=interpret,
                                    classes=classes)


def _local_row_len(n: int, cfg: model.SortConfig) -> int:
    """Bitonic row width: next power of two covering a done bucket (<= ∂̂)."""
    cap = max(1, min(cfg.local_threshold, n))
    return 1 << (cap - 1).bit_length()


def local_sort_classes(n: int, cfg: model.SortConfig):
    """Static size-class plan of the kernel engine's local sort: the (L,
    rows) bins of ``ops.local_sort_class_plan`` for this (n, cfg) — also the
    source of truth for how many bitonic launches the finish stage traces
    (one per class, the launch-census tests pin ``2 + len(...)`` total).
    """
    return local_sort_class_plan(n, _local_row_len(n, cfg),
                                 model.max_total_buckets(n, cfg))


@functools.lru_cache(maxsize=64)
def local_sort_lanes(n: int, cfg: model.SortConfig) -> int:
    """The plan's capacity of the kernel engine's local sort: Σ rows × L
    over ``local_sort_classes(n, cfg)``, fixed by (n, cfg).  An upper bound:
    the lanes a call actually runs are at most ``local_sort_tiles`` ×
    ``ops.local_sort_tile_lanes(n, row_len)``."""
    return sum(l * rows for l, rows in local_sort_classes(n, cfg))


def local_sort_tiles(seg_id, done, n: int, cfg: model.SortConfig):
    """Tiles the kernel engine's local sort runs on the final bucket state
    ``(seg_id, done)``: Σ over size classes of ⌈occupied rows / tile
    rows⌉.  Every engine reports it (``SortStats.local_sort_tiles``)."""
    _, sizes, sortable = _done_segments(seg_id, done,
                                        model.max_total_buckets(n, cfg))
    return local_sort_tile_count(sizes, sortable, n, _local_row_len(n, cfg),
                                 local_sort_classes(n, cfg))


def _pass_loop_jnp(ukeys, vals, *, k, lo, nd, cfg, engine, adaptive):
    """The jnp engines' counting passes from the initial bucket state:
    ``(ukeys, vals, seg_id, done, executed passes, elided passes)``."""
    n = ukeys.shape[0]
    z = jnp.int32(0)

    def cond(state):
        done, p = state[3], state[5]
        return (p < nd) & jnp.any(~done)

    body = functools.partial(_counting_pass_jnp, k=k, d=cfg.d, lo=lo,
                             a_max=model.max_active_buckets(n, cfg), nd=nd,
                             cfg=cfg, engine=engine, adaptive=adaptive)
    ukeys, vals, seg, done, _, _, p_exec, n_eld = lax.while_loop(
        cond, body, (ukeys, vals, jnp.zeros((n,), jnp.int32),
                     jnp.full((n,), n <= cfg.local_threshold),
                     jnp.bool_(False), z, z, z))
    return ukeys, vals, seg, done, p_exec, n_eld


def _planned_passes(k: int, lo: int, d: int, max_passes: Optional[int]):
    """Counting passes the schedule plans over the live window [lo, k)."""
    nd = model.num_digits(max(k - lo, 0), d)
    return nd if max_passes is None else min(nd, max_passes)


@functools.partial(jax.jit, static_argnames=("cfg", "k", "return_stats",
                                             "max_passes", "engine",
                                             "interpret", "lo", "adaptive"))
@jax.named_scope("hybrid_sort")
def _hybrid_sort_bits(ukeys, vals, cfg: model.SortConfig, k: int,
                      return_stats: bool, max_passes: Optional[int],
                      engine: str, interpret: bool,
                      lo: int = 0, adaptive: bool = False):
    n = ukeys.shape[0]
    d = cfg.d
    r = 1 << d
    nd = _planned_passes(k, lo, d, max_passes)
    a_max = model.max_active_buckets(n, cfg)

    if engine == "kernel":
        done0 = jnp.full((n,), n <= cfg.local_threshold)
        seg0 = jnp.zeros((n,), jnp.int32)
        z = jnp.int32(0)
        g_max = plan.max_region_blocks(n, cfg.kpb, a_max)
        leaves, treedef = jax.tree.flatten(vals)
        with jax.named_scope("ping_pong"):
            (ck, cv), (ak, av) = fused.make_ping_pong(ukeys, leaves, cfg.kpb)
        # the one unfused sweep of the sort: pass 0's histogram (§4.3)
        w0 = min(d, max(k - lo, 1))
        with jax.named_scope("prologue_histogram"):
            seg_hist0 = fused.initial_histogram(ck, n, max(k - w0, 0), w0, r,
                                                a_max, interpret=interpret)

        def cond(state):
            done, p = state[5], state[9]
            return (p < nd) & jnp.any(~done)

        body = functools.partial(_counting_pass_fused, k=k, d=d, lo=lo,
                                 a_max=a_max, g_max=g_max, n=n, nd=nd,
                                 cfg=cfg, adaptive=adaptive,
                                 interpret=interpret)
        (ck, cv, ak, av, seg, done, _, _, _, p, p_exec, n_eld) = \
            lax.while_loop(cond, body,
                           (ck, cv, ak, av, seg0, done0, seg_hist0,
                            jnp.zeros_like(seg_hist0), jnp.bool_(False),
                            z, z, z))
        with jax.named_scope("unpad"):
            ukeys = fused.unpad(ck, n, ukeys.dtype)
            vals = jax.tree.unflatten(treedef,
                                      [fused.unpad(v, n) for v in cv])
    else:
        ukeys, vals, seg, done, p_exec, n_eld = _pass_loop_jnp(
            ukeys, vals, k=k, lo=lo, nd=nd, cfg=cfg, engine=engine,
            adaptive=adaptive)

    needs_local = jnp.any(done)
    if engine == "kernel":
        finish = functools.partial(
            _local_sort_kernel, s_max=model.max_total_buckets(n, cfg),
            row_len=_local_row_len(n, cfg),
            classes=local_sort_classes(n, cfg), interpret=interpret)
    else:
        finish = _local_sort
    ukeys, vals = lax.cond(needs_local, finish,
                           lambda k_, v_, s_, d_: (k_, v_),
                           ukeys, vals, seg, done)
    if not return_stats:
        return ukeys, vals, None
    sizes = jnp.bincount(seg, length=n if n else 1)
    stats = SortStats(counting_passes=p_exec, used_local_sort=needs_local,
                      num_segments=seg[-1] + 1 if n else jnp.int32(0),
                      max_segment=sizes.max(), elided_passes=n_eld,
                      local_sort_tiles=local_sort_tiles(seg, done, n, cfg))
    return ukeys, vals, stats


def hybrid_sort(keys: jnp.ndarray, values: Any = None,
                cfg: Optional[model.SortConfig] = None,
                return_stats: bool = False, max_passes: Optional[int] = None,
                engine: Optional[str] = None, interpret: Optional[bool] = None,
                adaptive: Optional[bool] = None, compress: bool = False):
    """Sort ``keys`` (any supported primitive dtype) with the hybrid radix sort.

    ``values`` is an optional array or pytree of arrays permuted alongside the
    keys (decomposed key-value layout, §4.6).  Pair movement is consistent but
    — by the paper's central design choice — NOT stable across equal keys.

    ``engine`` selects the per-pass partition engine: ``"kernel"`` (ONE fused
    Pallas launch per counting pass — partition + scatter + next-pass
    histogram on donated ping-pong buffers — plus the bitonic local sort),
    ``"argsort"`` (fused XLA stable sorts), or ``"scan"`` (the O(n) chunked
    jnp fallback).  ``None`` defers to ``cfg.rank_engine`` (``"auto"`` by
    default); ``"auto"`` resolves per backend (``core.ranks.resolve_engine``)
    — the Mosaic-compiled ``kernel`` engine on a TPU, ``argsort`` elsewhere.
    All engines produce byte-identical output.  ``interpret`` forces Pallas
    interpret mode (``None``: on iff the backend is not a TPU).  The TPU
    kernel path sorts keys of at most 32 bits; 64-bit keys there raise and
    sort with ``engine="argsort"``.

    ``adaptive`` enables the entropy-adaptive schedule (``None`` defers to
    ``cfg.adaptive``, on by default): concrete keys get a statically
    narrowed live-bit window, and single-digit passes are elided mid-sort
    (bookkeeping advances; no launch happens).  Every pass of the schedule
    is a stable partition, so the output is byte-identical with the
    adaptive schedule on or off.  ``compress=True`` (concrete keys only)
    additionally bit-packs out all dead key columns and sorts the narrowed
    carrier, inverting the packing exactly afterwards.

    Returns ``sorted_keys``, or ``(sorted_keys, permuted_values)`` if values
    were given; append ``stats`` when ``return_stats``.

    Under ``jax.profiler`` the call is a host span ``hybrid_sort`` whose
    arguments are the plan's counters (``n``, ``key_bits``, ``engine``, the
    live window ``lo``/``hi``, ``planned_passes`` and, on the kernel engine,
    ``local_sort_lanes``: the local sort's capacity, an upper bound; the
    lanes it runs are at most ``stats.local_sort_tiles`` × the tile's
    lanes), with the children ``hybrid_sort.prologue``
    (holding ``hybrid_sort.live_bit_window``, the host copy and bit
    reduce) and ``hybrid_sort.dispatch``.  Inside the program every stage
    sits in a named scope under ``hybrid_sort`` (``ping_pong``,
    ``prologue_histogram``, ``pass_bookkeeping``, ``counting_pass``,
    ``local_sort/{bounds,rows,bitonic,copy_back}``, ``unpad``), which the
    device trace carries in each op's ``op_name``.
    """
    if keys.ndim != 1:
        raise ValueError("hybrid_sort expects a 1-D key array")
    with jax.profiler.TraceAnnotation("hybrid_sort") as span:
        with jax.profiler.TraceAnnotation("hybrid_sort.prologue"):
            interpret = resolve_interpret(interpret)
            k = bijection.key_bits(keys.dtype)
            if k > 32 and not jax.config.jax_enable_x64:
                raise RuntimeError("64-bit keys require jax_enable_x64")
            cfg = cfg or model.default_config(k // 8)
            if adaptive is None:
                adaptive = cfg.adaptive
            # explicit argument > cfg.rank_engine > backend default
            engine = resolve_engine(engine if engine is not None
                                    else cfg.rank_engine)
            n = keys.shape[0]
            if n == 0:
                out = (keys, values) if values is not None else keys
                if return_stats:
                    z = jnp.int32(0)
                    return (*((out,) if values is None else out),
                            SortStats(z, jnp.bool_(False), z, z, z))
                return out

            concrete = not isinstance(keys, jax.core.Tracer)
            cplan = None
            lo, hi = 0, k
            if compress:
                if not concrete:
                    raise ValueError("compress=True requires concrete "
                                     "(non-traced) keys: the packing plan "
                                     "is data-dependent")
                with jax.profiler.TraceAnnotation(
                        "hybrid_sort.live_bit_window"):
                    cplan = bijection.compression_plan_np(
                        bijection.to_ordered_bits_np(np.asarray(keys)))
                ukeys = bijection.pack_ordered_bits(
                    bijection.to_ordered_bits(keys), cplan)
                # every packed column is live by construction; sort just
                # those bits
                lo, hi = 0, cplan.packed_bits
            else:
                ukeys = bijection.to_ordered_bits(keys)
                if adaptive and concrete:
                    with jax.profiler.TraceAnnotation(
                            "hybrid_sort.live_bit_window"):
                        lo, hi = live_bit_window(bijection.to_ordered_bits_np(
                            np.asarray(keys)))

            if engine == "kernel":
                fused.require_kernel_keys(ukeys.dtype, keys.dtype, interpret)
            vals = values if values is not None else ()
        if span.is_enabled():
            counters = dict(n=n, key_bits=k, engine=engine, lo=lo, hi=hi,
                            planned_passes=_planned_passes(hi, lo, cfg.d,
                                                           max_passes))
            if engine == "kernel":
                counters["local_sort_lanes"] = local_sort_lanes(n, cfg)
            span.set_metadata(**counters)
        with jax.profiler.TraceAnnotation("hybrid_sort.dispatch"):
            ukeys, vals, stats = _hybrid_sort_bits(
                ukeys, vals, cfg, hi, return_stats, max_passes, engine,
                interpret, lo=lo, adaptive=adaptive)
        if cplan is not None:
            ukeys = bijection.unpack_ordered_bits(ukeys, cplan)
        out_keys = bijection.from_ordered_bits(ukeys, keys.dtype)
    if values is None:
        return (out_keys, stats) if return_stats else out_keys
    return (out_keys, vals, stats) if return_stats else (out_keys, vals)


# --- contract declaration (verified by repro.analysis; see analysis/contracts)
# Formulas are symbolic in the structural parameters the analyzer derives per
# (n, cfg): classes = len(local_sort_classes(n, cfg)), passes = ⌈k/d⌉ nominal
# schedule slots, n_pad = fused.buffer_length(n, cfg.kpb), kb/vb = key/value
# bytes, vals = payload leaves, g_max/B = descriptor rows / super-step width.
# Loops: the counting-pass loop, then one tile loop per local-sort class,
# each body holding one launch site.
ANALYSIS_CONTRACT = {
    "entry": "repro.core.hybrid.hybrid_sort",
    "census": {
        "launch_total": "2 + classes",
        "while_body_launches": "[1] * (1 + classes)",
        "fused_grid": "ceil_div(g_max, B)",
    },
    "sort_free": True,
    "donation": {"fused_counting_pass": "1 + vals"},
    "transfer": {
        "sweep_kernels": ["radix_histogram_total", "fused_counting_pass"],
        "bytes": "(2 * passes + 1) * n_pad * kb + 2 * passes * n_pad * vb",
    },
}
