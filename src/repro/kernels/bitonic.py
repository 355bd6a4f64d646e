"""Pallas TPU kernel: VMEM-resident bitonic local sort (paper §4.1's local sort).

A bucket that fits on-chip is sorted with exactly one HBM read and one HBM
write no matter how many digit positions remain — the paper's biggest lever
for favourable distributions (4x on uniform keys).  The GPU version uses CUB's
BlockRadixSort in shared memory; the TPU-native engine is a bitonic sorting
network: branch-free, fully lane-parallel compare-exchange stages on the VPU.

Layout: every operand is viewed *flat* as (rows, 128) lanes — position
``p = row * 128 + lane`` — and a sort of width ``L`` orders each aligned run
``[j*L, (j+1)*L)`` of flat positions.  A compare-exchange partner ``p ^ s``
is a lane rotation for strides below 128 and a sublane rotation above
(``pltpu.roll``, the XLU's native permutes), so the network needs no
gather, no reverse and no reshape of the vreg tiling.  The same network is
the in-VMEM partition step of the fused counting pass (``kernels.fused``).

The host side realises the paper's *local sort configurations* optimisation
(§4.2): buckets are binned by size class and each class launches this kernel
with its own row width L, so tiny buckets don't pay ∂̂-sized padding.

Key-value pairs ride along through the same swap masks (§4.6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _partner(x, stride: int, row, lane):
    """x at flat position ``p ^ stride`` for every p of a (rows, 128) array."""
    rows = x.shape[0]
    if stride < LANES:
        up = pltpu.roll(x, LANES - stride, 1)          # x[p + stride]
        dn = pltpu.roll(x, stride, 1)                  # x[p - stride]
        return lax.select((lane & stride) == 0, up, dn)
    t = stride // LANES
    up = pltpu.roll(x, rows - t, 0)
    dn = pltpu.roll(x, t, 0)
    return lax.select((row & t) == 0, up, dn)


def _partner_traced(x, stride, row, lane):
    """``_partner`` for a traced stride (the interpreter's rolled network)."""
    rows = x.shape[0]
    s = stride % LANES
    t = stride // LANES
    by_lane = lax.select((lane & stride) == 0, jnp.roll(x, LANES - s, 1),
                         jnp.roll(x, s, 1))
    by_row = lax.select((row & t) == 0, jnp.roll(x, rows - t, 0),
                        jnp.roll(x, t, 0))
    return lax.select(jnp.broadcast_to(stride < LANES, x.shape), by_lane,
                      by_row)


def bitonic_network(arrs, less, width: int, *, interpret: bool):
    """Sort each aligned run of ``width`` flat positions of (rows, 128) arrays.

    ``arrs`` is a list of equally shaped arrays moved together; ``less(a, b)``
    compares two such lists (``a[0] < b[0]`` for a plain key sort).  Each
    compare-exchange takes one swap decision per *pair*, evaluated
    identically on both sides, so payloads stay attached to their keys even
    when keys tie.  ``width`` must be a power of two dividing rows * 128.

    Compiled, the stages unroll (static rolls, one schedule for Mosaic).
    Interpreted, they run as a loop over traced strides: the unrolled
    network would make every CPU executable hundreds of stages long.
    """
    rows = arrs[0].shape[0]
    assert width & (width - 1) == 0 and (rows * LANES) % width == 0
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    pos = row * LANES + lane

    def stage(arrs, size, stride, partner):
        part = [partner(a, stride, row, lane) for a in arrs]
        lower = (pos & stride) == 0
        lo = [lax.select(lower, a, p) for a, p in zip(arrs, part)]
        hi = [lax.select(lower, p, a) for a, p in zip(arrs, part)]
        # the final merge (size == width) sorts every run ascending
        if isinstance(size, int) and size == width:
            swap = less(hi, lo)
        else:                                  # (bool selects don't lower)
            asc = (pos & size) == 0
            if not isinstance(size, int):
                asc = asc | (size == width)
            swap = (asc & less(hi, lo)) | (~asc & less(lo, hi))
        return [lax.select(swap, p, a) for a, p in zip(arrs, part)]

    if not interpret:
        for size_log in range(1, width.bit_length()):
            for stride_log in range(size_log - 1, -1, -1):
                arrs = stage(arrs, 1 << size_log, 1 << stride_log, _partner)
        return arrs

    def merge(size_log, arrs):
        def step(j, arrs):
            return stage(arrs, 1 << size_log, 1 << (size_log - 1 - j),
                         _partner_traced)
        return lax.fori_loop(0, size_log, step, arrs)
    return lax.fori_loop(1, width.bit_length(), merge, list(arrs))


def _key_less(a, b):
    return a[0] < b[0]


def _key_idx_less(a, b):
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def _rows_kernel(*refs, less, width: int, interpret: bool):
    n = len(refs) // 2
    outs = bitonic_network([r[...] for r in refs[:n]], less, width,
                           interpret=interpret)
    for o_ref, v in zip(refs[n:], outs):
        o_ref[...] = v


# flat elements per grid step: 64 rows of 128 lanes (8 vreg tiles) or one
# row of the class, whichever is wider — the network's VMEM working set
# stays a few hundred KiB even for the widest (16,384-key) class
_STEP_ELEMS = 8192


def _rows_call(less, arrs, interpret: bool, name: str):
    """Sort each row of equally shaped (S, L) operands with one launch,
    named ``name`` in the program (and in a profile)."""
    s, l = arrs[0].shape
    assert l & (l - 1) == 0, "bitonic needs power-of-two rows"
    step = max(l, _STEP_ELEMS)
    pad = (-(s * l)) % step
    flat = [a.reshape(-1) for a in arrs]
    if pad:
        flat = [jnp.concatenate([f, jnp.zeros((pad,), f.dtype)]) for f in flat]
    rows = flat[0].shape[0] // LANES
    step_rows = step // LANES
    flat = [f.reshape(rows, LANES) for f in flat]
    spec = pl.BlockSpec((step_rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, less=less, width=l,
                          interpret=interpret),
        grid=(rows // step_rows,),
        in_specs=[spec] * len(flat),
        out_specs=[spec] * len(flat),
        out_shape=[jax.ShapeDtypeStruct(f.shape, f.dtype) for f in flat],
        interpret=interpret,
        name=name,
    )(*flat)
    return tuple(o.reshape(-1)[:s * l].reshape(s, l) for o in out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_rows(keys: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Sort each row of (S, L) ascending; L must be a power of two."""
    return _rows_call(_key_less, [keys], interpret, "bitonic_sort_rows")[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_rows_stable(keys: jnp.ndarray, idx: jnp.ndarray, *,
                             interpret: bool):
    """Sort (S, L) rows by (key, idx) lexicographically; L a power of two.

    ``idx`` must be distinct within each row (e.g. global positions): the sort
    is then stable in the original order and safe against sentinel-padding
    collisions — the segmented local-sort path of the hybrid sort's kernel
    engine relies on both properties.
    """
    return _rows_call(_key_idx_less, [keys, idx], interpret,
                      "bitonic_sort_rows_stable")


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_rows_kv(keys: jnp.ndarray, vals: jnp.ndarray, *,
                         interpret: bool):
    """Sort (S, L) rows by key, carrying values; L must be a power of two.

    NOTE: with duplicate keys the value attribution follows the network's
    swaps, which matches the paper's non-stable pair semantics.
    """
    return _rows_call(_key_less, [keys, vals], interpret,
                      "bitonic_sort_rows_kv")
