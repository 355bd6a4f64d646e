"""Pallas TPU kernel: block-descriptor-driven histogram via scalar prefetch.

Paper §4.2: rather than one kernel launch per bucket, the GPU version launches
a *constant* number of kernels per pass and lets each thread block read its
{k_offs, k_count, b_id, b_offs} assignment from device memory.  The TPU
analogue is Pallas' scalar prefetch: the grid is the static block upper bound
(model I4) and the BlockSpec ``index_map`` *reads the assignment table* to
decide which input tile each grid step processes — data-dependent work
assignment with a single compiled kernel.

The general block-descriptor *generation* (segments plus done gaps, carry
resets, copy-through flags) lives in ``core.plan.make_region_blocks``, which
feeds ``kernels.fused`` — this module keeps the scalar-prefetch launch
pattern itself as the minimal tested exemplar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _assigned_hist_kernel(tile_idx_ref, valid_ref, keys_ref, hist_ref, *,
                          shift: int, width: int):
    g = pl.program_id(0)
    r = 1 << width
    keys = keys_ref[...]                                  # the assigned tile
    digit = ((keys >> jnp.array(shift, keys.dtype)) &
             jnp.array(r - 1, keys.dtype)).astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (keys.shape[1], r), 1)
    onehot = (digit.reshape(-1, 1) == iota).astype(jnp.int32)
    ones = jnp.ones((1, keys.shape[1]), jnp.int32)
    h = jax.lax.dot_general(ones, onehot, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    hist_ref[...] = h * valid_ref[g]                      # masked: padding rows of
    # the static-bound grid (I4) write zeros instead of branching


@functools.partial(jax.jit, static_argnames=("shift", "width", "interpret"))
def assigned_histogram(keys: jnp.ndarray, tile_idx: jnp.ndarray,
                       valid: jnp.ndarray, shift: int, width: int, *,
                       interpret: bool) -> jnp.ndarray:
    """Histogram of data-dependent tile assignments.

    keys: (T, KPB); tile_idx: (G,) int32 — which tile grid step g reads
    (the paper's k_offs in block units); valid: (G,) int32 {0,1}.
    Returns (G, 2^width) histograms, zero rows where invalid.
    """
    t, kpb = keys.shape
    g = tile_idx.shape[0]
    r = 1 << width
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g,),
        in_specs=[pl.BlockSpec((1, kpb), lambda i, idx, val: (idx[i], 0))],
        out_specs=pl.BlockSpec((1, r), lambda i, idx, val: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_assigned_hist_kernel, shift=shift, width=width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, r), jnp.int32),
        interpret=interpret,
    )(tile_idx, valid, keys)
