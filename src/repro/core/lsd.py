"""LSD radix sort baseline — the CUB/Merrill analogue (paper §3).

State-of-the-art GPU radix sorts are least-significant-digit-first with d = 4
or 5 bits per *stable* pass (CUB 1.5.1: d=5; CUB 1.6.4 appendix: up to d=7).
This module is the measured baseline the hybrid sort is compared against: the
pass structure (⌈k/d⌉ stable counting passes) is what produces the paper's
1.6–1.75x traffic ratio.

``lsd_sort`` routes through the same engine selector as ``hybrid_sort``:
``argsort``/``scan`` compute each pass's permutation in jnp; ``kernel`` runs
ONE fused Pallas launch per pass (``kernels.fused``) over donated ping-pong
buffers, with each pass's digit histogram fused out of the previous pass's
scatter (§4.3) — so the kernel engine reads the keys once and writes them
once per pass, plus a single prologue histogram sweep for pass 0.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bijection, hybrid, model, plan
from repro.core.ranks import (resolve_engine, resolve_interpret,
                              stable_partition_dest)
from repro.kernels import fused


@functools.partial(jax.jit, static_argnames=("d", "k", "engine", "kpb",
                                             "step_batch", "interpret", "lo"))
def _lsd_sort_bits(ukeys, vals, d: int, k: int, engine: str, kpb: int,
                   step_batch: int, interpret: bool, lo: int = 0):
    # [lo, k) is the live-bit window of the entropy-adaptive schedule: bits
    # outside it are globally constant, and a stable pass over a constant
    # digit is the identity permutation — eliding it changes nothing, not
    # even the permutation of equal keys.
    nd = model.num_digits(max(k - lo, 0), d)
    udt = ukeys.dtype
    n = ukeys.shape[0]

    if engine == "kernel":
        # LSD is the degenerate plan: one always-active segment covering
        # [0, n), no merging, ⌈k/d⌉ statically unrolled fused launches.
        r = 1 << d
        leaves, treedef = jax.tree.flatten(vals)
        (ck, cv), (ak, av) = fused.make_ping_pong(ukeys, leaves, kpb)
        base = jnp.zeros((1,), jnp.int32)
        size = jnp.full((1,), n, jnp.int32)
        blocks = plan.make_region_blocks(base, size, n, kpb,
                                         plan.max_region_blocks(n, kpb, 1),
                                         batch=step_batch)
        nsid = jnp.zeros((r,), jnp.int32)     # every sub-bucket -> segment 0
        w0 = min(d, max(k - lo, 1))
        seg_hist = fused.initial_histogram(ck, n, lo, w0, r, 1,
                                           interpret=interpret)
        for p in range(nd):
            base_excl = jnp.cumsum(seg_hist, axis=1) - seg_hist
            sc = plan.lsd_digit_window(p, k, d, lo=lo)
            nk, nv, hist_next = fused.fused_counting_pass(
                ck, cv, ak, av, sc, *blocks, base_excl, nsid,
                kpb=kpb, r=r, a_max=1, interpret=interpret)
            # flip: written buffers become current, old ones donate next
            ak, av, ck, cv = ck, cv, nk, nv
            seg_hist = hist_next.reshape(1, r)
        return (fused.unpad(ck, n, udt),
                jax.tree.unflatten(treedef, [fused.unpad(v, n) for v in cv]))

    def body(p, state):
        ukeys, vals = state
        shift = jnp.asarray(lo + p * d).astype(udt)
        # partial top digit: pass p covers bits [lo+p*d, min(lo+(p+1)*d, k))
        width = jnp.minimum(d, k - lo - p * d).astype(udt)
        mask = ((jnp.array(1, udt) << width) - 1).astype(udt)
        digit = ((ukeys >> shift) & mask).astype(jnp.int32)
        dest = stable_partition_dest(digit, 1 << d, engine=engine)
        ukeys = jnp.zeros_like(ukeys).at[dest].set(ukeys)
        vals = jax.tree.map(lambda v: jnp.zeros_like(v).at[dest].set(v), vals)
        return ukeys, vals

    ukeys, vals = lax.fori_loop(0, nd, body, (ukeys, vals))
    return ukeys, vals


def lsd_sort(keys: jnp.ndarray, values: Any = None, d: int = 5,
             engine: Optional[str] = None, kpb: int = 1024,
             step_batch: int = 8, interpret: Optional[bool] = None,
             adaptive: bool = True, return_passes: bool = False):
    """Stable LSD radix sort with ``d``-bit digits (default 5 — the CUB proxy).

    ``engine`` is resolved like ``hybrid_sort``'s (``argsort``/``scan``/
    ``kernel``/``auto``); ``kpb`` is the kernel engine's keys-per-block and
    ``step_batch`` its descriptor rows per fused-launch grid step
    (``plan.pack_region_blocks``).

    ``adaptive`` narrows the pass schedule to the statically live bit window
    of concrete keys (⌈k_eff/d⌉ passes); traced keys always get the full
    ⌈k/d⌉ schedule.  Bits outside the window are globally constant, so the
    elided passes were identity permutations — output and stability are
    unchanged.  ``return_passes`` appends the executed pass count (a Python
    int) to the return value.
    """
    if keys.ndim != 1:
        raise ValueError("lsd_sort expects a 1-D key array")
    interpret = resolve_interpret(interpret)
    engine = resolve_engine(engine)
    k = bijection.key_bits(keys.dtype)
    if keys.shape[0] == 0:
        out = keys if values is None else (keys, values)
        if return_passes:
            return (*((out,) if values is None else out), 0)
        return out
    lo, hi = 0, k
    if adaptive and not isinstance(keys, jax.core.Tracer):
        lo, hi = hybrid.live_bit_window(bijection.to_ordered_bits_np(
            np.asarray(keys)))
    ukeys = bijection.to_ordered_bits(keys)
    if engine == "kernel":
        fused.require_kernel_keys(ukeys.dtype, keys.dtype, interpret)
    vals = values if values is not None else ()
    ukeys, vals = _lsd_sort_bits(ukeys, vals, d, hi, engine, kpb, step_batch,
                                 interpret, lo=lo)
    out = bijection.from_ordered_bits(ukeys, keys.dtype)
    result = out if values is None else (out, vals)
    if return_passes:
        passes = model.num_digits(max(hi - lo, 0), d)
        return (*((result,) if values is None else result), passes)
    return result


# --- contract declaration (verified by repro.analysis; see analysis/contracts)
# The LSD driver unrolls its schedule: ⌈k/d⌉ fused launches + one prologue
# histogram, no device loop, every fused launch on the batched ⌈g_max/B⌉ grid.
ANALYSIS_CONTRACT = {
    "entry": "repro.core.lsd.lsd_sort",
    "census": {
        "launch_total": "passes + 1",
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
