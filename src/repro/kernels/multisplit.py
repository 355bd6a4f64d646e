"""Pallas TPU kernel: in-VMEM tile multisplit (the scatter's write combining).

Paper §4.4 / Fig. 3: scattering keys directly to their (r = 256) sub-bucket
chunks produces uncoalesced device-memory writes, so a thread block first
partitions its keys inside shared memory and then copies each sub-bucket as
one contiguous run.  The TPU translation stages the permutation in VMEM using
dense linear algebra instead of shared-memory atomics:

  1. one-hot cumulative counts give every key its *stable in-tile rank* within
     its digit (the shared-memory write counters of the paper),
  2. a KPB x KPB permutation matrix applied on the MXU moves the keys into
     digit-major order inside VMEM (exact: keys are split into 16-bit halves
     so the f32 MXU path is lossless),
  3. each digit's keys now form one contiguous run: the HBM write of a run is
     a single coalesced copy, and the run start offsets come from the global
     (scan of per-tile histograms) + in-tile exclusive offsets.

The per-thread "look-ahead" write combining of the paper is subsumed: a whole
run is combined by construction, for any skew.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _halves(x: jnp.ndarray, bits: int):
    """Split unsigned ints into exact 16-bit halves (f32-representable)."""
    n = (bits + 15) // 16
    return [((x >> jnp.array(16 * i, x.dtype)) &
             jnp.array(0xFFFF, x.dtype)).astype(jnp.float32)
            for i in range(n)]


def _from_halves(hs, dtype, bits: int):
    out = jnp.zeros(hs[0].shape, dtype)
    for i, h in enumerate(hs):
        out = out | (jnp.round(h).astype(dtype) << jnp.array(16 * i, dtype))
    return out


def _tile_partition(keys, *, shift: int, width: int):
    """Shared in-VMEM partition math: digits, per-digit run offsets, the
    (KPB, KPB) permutation matrix, and an ``apply`` closure that moves any
    payload through the MXU exactly (16-bit halves)."""
    r = 1 << width
    kpb = keys.shape[0]
    digit = ((keys >> jnp.array(shift, keys.dtype)) &
             jnp.array(r - 1, keys.dtype)).astype(jnp.int32)

    iota_r = jax.lax.broadcasted_iota(jnp.int32, (kpb, r), 1)
    onehot = (digit[:, None] == iota_r).astype(jnp.int32)      # (KPB, r)
    incl = jnp.cumsum(onehot, axis=0)
    excl_local = incl - onehot                                 # in-tile rank per digit
    hist = incl[-1]                                            # (r,)
    run_off = jnp.cumsum(hist) - hist                          # in-tile run starts

    # local destination of key i (digit-major slot) — gather-free via one-hot
    # (dtype pinned: under jax_enable_x64 an int32 sum would widen to int64)
    local_dest = jnp.sum(onehot * (run_off[None, :] + excl_local), axis=1,
                         dtype=jnp.int32)

    # permutation via MXU: M[j, i] = [local_dest[i] == j]
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (kpb, kpb), 0)
    perm = (iota_j == local_dest[None, :]).astype(jnp.float32)  # (KPB, KPB)

    def apply_perm(x, bits):
        hs = _halves(x, bits)
        out = [jax.lax.dot_general(perm, h[:, None], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)[:, 0]
               for h in hs]
        return _from_halves(out, x.dtype, bits)

    sdig = jax.lax.dot_general(perm, digit.astype(jnp.float32)[:, None],
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)[:, 0]
    sorted_digit = jnp.round(sdig).astype(jnp.int32)
    pos = jax.lax.broadcasted_iota(jnp.int32, (kpb,), 0)
    onehot_s = (sorted_digit[:, None] == iota_r).astype(jnp.int32)
    rank = pos - jnp.sum(onehot_s * run_off[None, :], axis=1, dtype=jnp.int32)
    return apply_perm, sorted_digit, rank, hist


def _multisplit_kernel(keys_ref, sorted_ref, digit_ref, rank_ref, hist_ref, *,
                       shift: int, width: int, key_bits: int):
    keys = keys_ref[0]                                    # (KPB,)
    apply_perm, sorted_digit, rank, hist = _tile_partition(
        keys, shift=shift, width=width)
    sorted_ref[0] = apply_perm(keys, key_bits)
    digit_ref[0] = sorted_digit
    rank_ref[0] = rank
    hist_ref[0] = hist


def _multisplit_kv_kernel(keys_ref, vals_ref, sorted_ref, vout_ref, digit_ref,
                          rank_ref, hist_ref, *, shift: int, width: int,
                          key_bits: int, val_bits: int):
    """Key-value variant (§4.6): the same in-VMEM permutation matrix moves the
    values, which is exactly the paper's 'reuse the stored offsets for the
    value pass' — here the MXU applies the permutation twice instead of the
    thread replaying its recorded offsets."""
    keys = keys_ref[0]
    vals = vals_ref[0]
    apply_perm, sorted_digit, rank, hist = _tile_partition(
        keys, shift=shift, width=width)
    sorted_ref[0] = apply_perm(keys, key_bits)
    vout_ref[0] = apply_perm(vals, val_bits)
    digit_ref[0] = sorted_digit
    rank_ref[0] = rank
    hist_ref[0] = hist


@functools.partial(jax.jit, static_argnames=("shift", "width", "key_bits",
                                             "val_bits", "interpret"))
def tile_multisplit_kv(keys: jnp.ndarray, vals: jnp.ndarray, shift: int,
                       width: int, key_bits: int, val_bits: int, *,
                       interpret: bool):
    """(T, KPB) keys + values -> digit-major (keys, values, digits, ranks,
    histograms) — the pairs path of the scatter (paper §4.6)."""
    t, kpb = keys.shape
    r = 1 << width
    return pl.pallas_call(
        functools.partial(_multisplit_kv_kernel, shift=shift, width=width,
                          key_bits=key_bits, val_bits=val_bits),
        grid=(t,),
        in_specs=[pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                  pl.BlockSpec((1, kpb), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                   pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                   pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                   pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                   pl.BlockSpec((1, r), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, kpb), keys.dtype),
                   jax.ShapeDtypeStruct((t, kpb), vals.dtype),
                   jax.ShapeDtypeStruct((t, kpb), jnp.int32),
                   jax.ShapeDtypeStruct((t, kpb), jnp.int32),
                   jax.ShapeDtypeStruct((t, r), jnp.int32)],
        interpret=interpret,
    )(keys, vals)


@functools.partial(jax.jit, static_argnames=("shift", "width", "key_bits",
                                             "interpret"))
def tile_multisplit(keys: jnp.ndarray, shift: int, width: int,
                    key_bits: int, *, interpret: bool):
    """(T, KPB) keys -> (digit-major keys, digits, in-run ranks, histograms).

    After this kernel the HBM scatter is r contiguous run-copies per tile
    (start = global offset of (tile, digit) from the scanned histograms,
    length = hist[tile, digit]).
    """
    t, kpb = keys.shape
    r = 1 << width
    return pl.pallas_call(
        functools.partial(_multisplit_kernel, shift=shift, width=width,
                          key_bits=key_bits),
        grid=(t,),
        in_specs=[pl.BlockSpec((1, kpb), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                   pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                   pl.BlockSpec((1, kpb), lambda i: (i, 0)),
                   pl.BlockSpec((1, r), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, kpb), keys.dtype),
                   jax.ShapeDtypeStruct((t, kpb), jnp.int32),
                   jax.ShapeDtypeStruct((t, kpb), jnp.int32),
                   jax.ShapeDtypeStruct((t, r), jnp.int32)],
        interpret=interpret,
    )(keys)
