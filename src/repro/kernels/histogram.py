"""Pallas TPU kernel: radix histogram via one-hot MXU contraction.

This is the TPU-native replacement for the paper's shared-memory-atomic
histogram (§4.3).  A GPU thread block increments 256 shared counters with
atomicAdd — throughput collapses for skewed inputs because all lanes hit one
counter (paper Fig. 2, "atomics only").  On TPU we instead form the one-hot
matrix of 128 keys' digits and contract it with a ones matrix on the MXU:

    H += 1_{8 x 128} . onehot(digit)^T_{128 x r}

The contraction's cost is *independent of the digit distribution* — the
skew-robustness the paper gets from its thread-reduction trick (Fig. 2,
"thread reduction & atomics") falls out structurally.  Counts are exact:
0/1 operands in bf16 accumulate in f32 far below 2^24.

Tiling: a grid step owns a (rows, K) block — 8 tiles of K keys, or one
(512, 128) slab of the flat buffer in ``total`` mode — and walks it in
128-lane slices, so a block obeys the (8, 128) tiling for any K that is a
multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
_TOTAL_ROWS = 512          # rows per grid step of the flat ``total`` sweep


def _slice_counts(keys, shift: int, r: int, valid):
    """(1, r) f32 digit counts of one (1, w) key slice."""
    digit = ((keys >> jnp.array(shift, keys.dtype)) &
             jnp.array(r - 1, keys.dtype)).astype(jnp.int32)
    digit = jnp.where(valid, digit, r)                     # r matches nothing
    w = keys.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (r, w), 0)
    onehot = (digit == iota).astype(jnp.bfloat16)          # (r, w)
    ones = jnp.ones((8, w), jnp.bfloat16)
    return jax.lax.dot_general(
        ones, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)[0:1]           # (1, r)


def _hist_kernel(keys_ref, hist_ref, *, shift: int, width: int, total: bool,
                 rows: int):
    r = 1 << width
    tb, k = keys_ref.shape
    cw = LANES if k % LANES == 0 else k          # slice width (any K interprets)
    g = pl.program_id(0)

    def row_counts(i, valid):
        def chunk(c, acc):
            sl = keys_ref[pl.ds(i, 1), pl.ds(pl.multiple_of(c * cw, cw), cw)]
            return acc + _slice_counts(sl, shift, r, valid)
        return jax.lax.fori_loop(0, k // cw, chunk,
                                 jnp.zeros((1, r), jnp.float32))

    if total:
        @pl.when(g == 0)
        def _init():
            hist_ref[...] = jnp.zeros_like(hist_ref)

        def body(i, acc):
            return acc + row_counts(i, g * tb + i < rows)
        acc = jax.lax.fori_loop(0, tb, body, jnp.zeros((1, r), jnp.float32))
        hist_ref[...] += acc.astype(jnp.int32)
    else:
        for i in range(tb):
            hist_ref[pl.ds(i, 1), :] = row_counts(i, True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("shift", "width", "interpret"))
def radix_histogram(keys: jnp.ndarray, shift: int, width: int, *,
                    interpret: bool) -> jnp.ndarray:
    """(T, K) uint keys -> (T, 2^width) int32 per-tile histograms."""
    t, k = keys.shape
    r = 1 << width
    tb = min(t, 8)
    return pl.pallas_call(
        functools.partial(_hist_kernel, shift=shift, width=width,
                          total=False, rows=t),
        grid=(pl.cdiv(t, tb),),
        in_specs=[pl.BlockSpec((tb, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tb, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, r), jnp.int32),
        interpret=interpret,
        name="radix_histogram",
    )(keys)


@functools.partial(jax.jit, static_argnames=("shift", "width", "interpret"))
def radix_histogram_total(keys: jnp.ndarray, shift: int, width: int, *,
                          interpret: bool) -> jnp.ndarray:
    """(rows, 128) uint keys -> (2^width,) int32 histogram of every key.

    One sequential sweep accumulating into a resident (1, r) block — the
    prologue histogram of the fused engine, whose flat ping-pong buffer is
    far too long for a per-tile output.
    """
    rows, k = keys.shape
    r = 1 << width
    tb = min(rows, _TOTAL_ROWS)
    return pl.pallas_call(
        functools.partial(_hist_kernel, shift=shift, width=width,
                          total=True, rows=rows),
        grid=(pl.cdiv(rows, tb),),
        in_specs=[pl.BlockSpec((tb, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, r), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, r), jnp.int32),
        interpret=interpret,
        name="radix_histogram_total",
    )(keys)[0]
