"""Jit'd composition of the non-fused Pallas kernels (local sort + histogram).

The counting passes themselves live in ``repro.kernels.fused`` — one fused
launch per pass (§4.3–§4.4) driven by ``repro.core.plan`` — which retired the
per-bucket multi-launch drivers (``kernel_counting_pass`` /
``segmented_kernel_pass`` and friends) that previously composed the
``tile_multisplit`` kernels here.  What remains are the pieces used outside
the fused pass:

  * ``apply_run_copies``      — the run-copy consumption idiom,
  * ``segmented_local_sort``  — finish done buckets via the stable bitonic
                                kernel (R1: one read + one write),
  * ``kernel_local_sort``     — plain padded-row bitonic driver,
  * ``tile_histogram_pass``   — standalone histogram sweep (tests /
                                doctest; the fused engine only needs it via
                                ``fused.initial_histogram``).

Every wrapper takes ``interpret`` explicitly: the sort entry points resolve it
once (``core.ranks.resolve_interpret``) — Mosaic on a TPU, the Pallas
interpreter elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.histogram import radix_histogram
from repro.kernels.bitonic import (_STEP_ELEMS, bitonic_sort_rows,
                                  bitonic_sort_rows_stable)


def apply_run_copies(src: jnp.ndarray, dst: jnp.ndarray, tree):
    """Apply (src, dst) run-copy pairs to a pytree of per-key arrays.

    The single idiom for consuming the local sort's run copies: invalid
    lanes carry ``src == n``/``dst == n`` (clipped on gather, dropped on
    scatter), and untouched slots keep their old contents — done buckets
    persist in place for free.
    """
    n = jax.tree.leaves(tree)[0].shape[0]
    safe_src = jnp.clip(src, 0, n - 1)
    return jax.tree.map(
        lambda v: v.at[dst].set(v[safe_src], mode="drop"), tree)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_local_sort(keys: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Local sort of (S, L) padded buckets via the bitonic kernel."""
    return bitonic_sort_rows(keys, interpret=interpret)


def local_sort_class_plan(n: int, row_len: int, s_max: int,
                          min_len: int = 32):
    """Power-of-two size classes for the local sort (§4.2's *local sort
    configurations*): ``((L_0, rows_0), (L_1, rows_1), ...)``.

    Class widths double from ``min_len`` up to ``row_len``; a bucket of size
    s sorts in the narrowest class with ``L >= s`` (class 0 additionally
    catches every bucket ``<= min_len``), so tiny done-buckets stop paying
    ∂̂-sized padding.  Row capacities are static counting bounds: a bucket in
    class i > 0 holds more than ``L_i/2`` keys, so at most
    ``n // (L_i/2 + 1) + 1`` such buckets exist; class 0 is bounded only by
    the total bucket count ``s_max``.  The capacities are what make one
    fixed-shape ``_rows_call`` per class possible under XLA's static shapes.
    """
    row_len = max(1, row_len)
    l = min(row_len, max(1, min_len))
    classes = [(l, max(1, s_max))]
    while l < row_len:
        l *= 2
        cap = n // (l // 2 + 1) + 1
        classes.append((l, max(1, min(s_max, cap))))
    return tuple(classes)


def local_sort_tile_lanes(n: int, row_len: int) -> int:
    """Lanes of one local-sort tile: a power of two of about n/32, and at
    least one bitonic grid step and one row of the widest class.  A class of
    width L runs tiles of ``T // L`` rows (fewer if its capacity is less),
    so a sort of n keys runs a few dozen tiles whatever its classes.
    Tiles of n/16 lanes crowd the distributed sort's merge searches out of
    a TPU v5e's VMEM: the compiler then leaves the sorted keys in HBM."""
    return max(_STEP_ELEMS, row_len, 1 << max(n // 32 - 1, 0).bit_length())


def _class_plan(n: int, row_len: int, s: int, classes):
    """(L, rows, prev_L, tile_rows) per class; class 0 catches every size
    <= its L.  ``rows`` is the class's static capacity, at most ``s``."""
    if classes is None:
        classes = ((row_len, s),)
    tile = local_sort_tile_lanes(n, row_len)
    prev = [-1] + [l for l, _ in classes[:-1]]
    out = []
    for (l, rows), p in zip(classes, prev):
        rows = min(rows, s)
        out.append((l, rows, p, min(rows, max(1, tile // l))))
    return out


def _in_class(seg_size, seg_sortable, l: int, prev_l: int):
    """The flagged segments of one size class: sizes in (prev_l, l]."""
    return seg_sortable & (seg_size <= l) & (seg_size > prev_l)


def _class_tiles(in_cls, rows: int, tile_rows: int):
    """Tiles a class runs: its occupied rows, at most its capacity, in
    tiles of ``tile_rows``."""
    m = jnp.minimum(jnp.sum(in_cls, dtype=jnp.int32), rows)
    return (m + tile_rows - 1) // tile_rows


def local_sort_tile_count(seg_size: jnp.ndarray, seg_sortable: jnp.ndarray,
                          n: int, row_len: int, classes=None):
    """Tiles ``segmented_local_sort`` runs on these segments of n keys:
    Σ over classes of ⌈occupied rows / tile rows⌉ (int32 scalar)."""
    total = jnp.int32(0)
    for l, rows, prev, tile_rows in _class_plan(n, row_len,
                                                seg_size.shape[0], classes):
        total += _class_tiles(_in_class(seg_size, seg_sortable, l, prev),
                              rows, tile_rows)
    return total


def _tile_run_copies(keys, starts, sizes, l: int, interpret: bool):
    """(src, dst) run copies sorting one tile: each segment ``(start,
    size)`` gathered into a sentinel-padded row of width ``l`` (size 0: an
    empty row, start n)."""
    n = keys.shape[0]
    sentinel = ~jnp.zeros((), keys.dtype)
    with jax.named_scope("rows"):
        lane = jnp.arange(l, dtype=jnp.int32)
        gidx = starts[:, None] + lane[None, :]                # (rows, L)
        lv = lane[None, :] < sizes[:, None]
        safe = jnp.clip(gidx, 0, max(n - 1, 0))
        row_keys = jnp.where(lv, keys[safe], sentinel)
        idx = jnp.where(lv, gidx, n).astype(jnp.int32)

    with jax.named_scope("bitonic"):
        _, si = bitonic_sort_rows_stable(row_keys, idx, interpret=interpret)

    # valid lanes form each row's prefix both before and after the sort
    with jax.named_scope("copy_back"):
        dst = jnp.where(lv, gidx, n)
        return si.reshape(-1), dst.reshape(-1)


def _sort_class(tree, seg_start, seg_size, seg_sortable, l: int, rows: int,
                prev_l: int, tile_rows: int, interpret: bool):
    """Sort one size class's flagged segments in place, a tile of
    ``tile_rows`` occupied rows at a time."""
    n = jax.tree.leaves(tree)[0].shape[0]
    s = seg_start.shape[0]
    with jax.named_scope("rows"):
        in_cls = _in_class(seg_size, seg_sortable, l, prev_l)
        rsel = jnp.nonzero(in_cls, size=rows, fill_value=s)[0]
        slots = -(-rows // tile_rows) * tile_rows
        rsel = jnp.pad(rsel, (0, slots - rows), constant_values=s)
        valid = rsel < s
        sel = jnp.clip(rsel, 0, s - 1)
        starts = jnp.where(valid, seg_start[sel], n)
        sizes = jnp.where(valid, seg_size[sel], 0)
        tiles = _class_tiles(in_cls, rows, tile_rows)

    def tile(t, tree):
        # a while body restarts the scope stack: name the stage again
        with jax.named_scope("local_sort"):
            at = t * tile_rows
            src, dst = _tile_run_copies(
                jax.tree.leaves(tree)[0],
                lax.dynamic_slice_in_dim(starts, at, tile_rows),
                lax.dynamic_slice_in_dim(sizes, at, tile_rows), l, interpret)
            with jax.named_scope("copy_back"):
                return apply_run_copies(src, dst, tree)

    return lax.fori_loop(0, tiles, tile, tree)


def segmented_local_sort(tree, seg_start: jnp.ndarray, seg_size: jnp.ndarray,
                         seg_sortable: jnp.ndarray, row_len: int, *,
                         interpret: bool, classes=None):
    """Finish flagged buckets in one read+write via the stable bitonic kernel.

    ``tree`` is a pytree of per-key arrays whose first leaf is the key
    array.  Gathers each flagged segment into a sentinel-padded row, sorts
    rows by (key, global index) — so pads (index n) lose every tie and the
    order is stable — and run-copies the sorted prefix back over the
    segment, carrying every leaf.  Unflagged segments are untouched.  The
    stages sit in the named scopes ``rows`` (the gathers into tiles),
    ``bitonic`` and ``copy_back`` (the run copies), each under
    ``local_sort`` inside the tile loop.

    ``classes`` is an optional size-class plan (``local_sort_class_plan``):
    segments are binned into power-of-two row widths — one fixed-shape
    bitonic launch site per class — so a 3-key bucket sorts in a ``min_len``
    row instead of a ``row_len`` one.  ``None`` keeps the single worst-case
    class: width ``row_len`` with a row per segment slot.  A class's static
    capacity bounds its rows, but only its occupied rows run: a device loop
    over tiles of ``local_sort_tile_lanes(n, row_len) // L`` rows, as many
    as ``local_sort_tile_count`` gives (an empty class runs none).  Each
    tile's copies are applied before the next tile gathers, so one tile is
    live at a time.  Classes and tiles cover disjoint segments, so the
    order of application does not matter.
    """
    n = jax.tree.leaves(tree)[0].shape[0]
    for l, rows, prev, tile_rows in _class_plan(n, row_len,
                                                seg_start.shape[0], classes):
        tree = _sort_class(tree, seg_start, seg_size, seg_sortable, l, rows,
                           prev, tile_rows, interpret)
    return tree


def tile_histogram_pass(keys: jnp.ndarray, shift: int, width: int,
                        kpb: int = 8192, *, interpret: bool):
    """Histogram step of a pass: (n,) keys -> ((T, r) tile hists, (r,) total).

    Example — count the top-byte digits of two u32 keys (the trailing
    sentinel padding is removed from ``total``)::

        >>> import numpy as np, jax.numpy as jnp
        >>> from repro.kernels import tile_histogram_pass
        >>> x = jnp.asarray(np.array([0x01020304, 0xFF000000], np.uint32))
        >>> hist, total = tile_histogram_pass(x, shift=24, width=8, kpb=8,
        ...                                   interpret=True)
        >>> int(total[0x01]), int(total[0xFF]), int(total.sum())
        (1, 1, 2)
    """
    n = keys.shape[0]
    pad = (-n) % kpb
    sentinel = ~jnp.zeros((), keys.dtype)
    padded = jnp.concatenate([keys, jnp.full((pad,), sentinel, keys.dtype)])
    hist = radix_histogram(padded.reshape(-1, kpb), shift, width,
                           interpret=interpret)
    total = hist.sum(axis=0)
    if pad:
        total = total.at[(1 << width) - 1].add(-pad)
    return hist, total
