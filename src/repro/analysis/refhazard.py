"""Intra-kernel ref-hazard pass over the Pallas bodies.

The fused/merge kernels write their outputs with *dynamic* scatters whose
destinations come from scalar-prefetched descriptor tables, and read their
inputs with *dynamic* block slices at table offsets.  Three things must hold
for the §4.3 "one read + one write sweep" accounting to be true and safe:

  1. scatter targets are write-only and written exactly once per element —
     no read-after-write inside a super-step, no double writes
     (:func:`check_kernel`, structural, on the kernel jaxpr),
  2. every dynamic-slice load fits its ref's block shape
     (:func:`check_kernel` bounds static components; the table-driven
     offsets are proven by 3.),
  3. the descriptor tables drive disjoint, exactly-covering index ranges —
     interval analysis on representative table instances generated by the
     real planners (:func:`check_fused_tables`, :func:`check_merge_tables`),
     so the per-grid-step writes tile the output exactly once and every
     block load ``[off, off + kpb)`` stays inside the padded buffer.

The bitonic class sorts use static block mappings instead of dynamic
scatters; their exactly-once property is block-mapping injectivity over the
grid (:func:`check_block_injectivity`).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import numpy as np
from jax.extend.core import Literal

from repro.analysis.trace import PallasSite, ref_events
from repro.kernels import fused


def _is_static(x) -> bool:
    return isinstance(x, (int, np.integer)) or isinstance(x, Literal)


def _static_val(x):
    return int(x.val) if isinstance(x, Literal) else int(x)


def _ref_shape(site: PallasSite, root: int):
    av = site.kernel_jaxpr.invars[root].aval
    av = getattr(av, "inner_aval", av)
    return tuple(int(d) for d in getattr(av, "shape", ()))


def check_kernel(site: PallasSite) -> List[str]:
    """Structural ref-hazard findings for one kernel body."""
    findings: List[str] = []
    events = ref_events(site.kernel_jaxpr)
    for root, evs in sorted(events.items()):
        kind, _ = site.classify_root(root)
        shape = _ref_shape(site, root)
        evs = sorted(evs, key=lambda e: e.order)
        label = f"{site.name}: ref {root} ({kind})"

        scatter_writes = [e for e in evs if e.kind == "swap" and e.scatter]
        gets = [e for e in evs if e.kind == "get"]

        # (1a) scatter outputs are write-only
        if scatter_writes and gets:
            findings.append(
                f"{label}: has {len(scatter_writes)} scatter write(s) AND "
                f"{len(gets)} read(s) — scatter targets must be write-only")
        # (1b) at most one scatter per ref per step (double write = WAW)
        if len(scatter_writes) > 1:
            findings.append(
                f"{label}: {len(scatter_writes)} scatter writes in one grid "
                f"step — write-after-write hazard")
        # (1c) read-after-(dynamic-)write within the super-step, on the
        # kernel's operands (VMEM/SMEM scratch is program-ordered staging)
        dirty_at = min((e.order for e in evs
                        if e.kind == "swap" and e.dynamic), default=None)
        if dirty_at is not None and kind != "scratch":
            late = [e for e in gets if e.order > dirty_at]
            if late:
                findings.append(
                    f"{label}: {len(late)} read(s) after a dynamic write in "
                    f"the same super-step — read-after-write hazard")

        # (2) static index components within the ref's block shape
        for e in evs:
            if not e.indexer:
                continue
            for nd in e.indexer:
                for axis, comp in enumerate(nd.indices):
                    dim = shape[axis] if axis < len(shape) else None
                    if dim is None:
                        continue
                    if hasattr(comp, "start"):               # Slice
                        sz = comp.size
                        if not _is_static(sz):
                            continue
                        sz = _static_val(sz)
                        if sz > dim:
                            findings.append(
                                f"{label}: dynamic slice of size {sz} on "
                                f"axis {axis} exceeds block extent {dim}")
                        if _is_static(comp.start):
                            st = _static_val(comp.start)
                            if st < 0 or st + sz > dim:
                                findings.append(
                                    f"{label}: static slice [{st}, {st + sz})"
                                    f" out of bounds for extent {dim}")
                    elif _is_static(comp):
                        iv = _static_val(comp)
                        if iv < 0 or iv >= dim:
                            findings.append(
                                f"{label}: static index {iv} out of bounds "
                                f"for extent {dim}")
    return findings


def _interval_findings(intervals, total: int, label: str,
                       cover: bool = True) -> List[str]:
    """Disjointness + exact-coverage findings for [start, end) intervals."""
    findings: List[str] = []
    ivs = sorted((int(a), int(b)) for a, b in intervals if b > a)
    covered = 0
    prev = (None, 0)
    for a, b in ivs:
        if prev[0] is not None and a < prev[1]:
            findings.append(
                f"{label}: write intervals overlap — [{prev[0]}, {prev[1]}) "
                f"and [{a}, {b})")
        covered += b - a
        prev = (a, b)
    if cover:
        lo = ivs[0][0] if ivs else 0
        hi = max((b for _, b in ivs), default=0)
        if not findings and (lo != 0 or hi != total or covered != total):
            findings.append(
                f"{label}: intervals cover [{lo}, {hi}) with {covered} "
                f"elements, expected exactly [0, {total})")
    return findings


def check_fused_tables(blocks, n: int, kpb: int, n_pad: int) -> List[str]:
    """Interval analysis on a fused-launch descriptor table instance.

    ``blocks`` is a ``plan.RegionBlocks`` (flat or packed); rows flatten in
    descriptor order.  Proves: (a) each row's block load — the
    ``fused.window_lines(kpb)`` 128-key lines from the line holding ``off``
    — fits the padded buffer, (b) rows group into regions at ``reset`` flags
    whose write intervals ``[first_offset, first_offset + Σcount)`` are
    pairwise disjoint and tile ``[0, n)`` exactly — the exactly-once scatter
    coverage of one fused pass.
    """
    seg, off, reset, cnt, act = (np.asarray(t).reshape(-1)
                                 for t in (blocks.seg, blocks.offset,
                                           blocks.reset, blocks.count,
                                           blocks.active))
    findings: List[str] = []
    window = fused.window_lines(kpb) * fused.LANES
    for g in range(off.shape[0]):
        lo = int(off[g]) - int(off[g]) % fused.LANES
        if int(off[g]) < 0 or lo + window > n_pad:
            findings.append(
                f"fused tables: row {g} loads [{lo}, {lo + window}) outside "
                f"padded buffer [0, {n_pad})")
    regions = []
    cur = None
    for g in range(off.shape[0]):
        if int(reset[g]) == 1:
            if cur is not None:
                regions.append(cur)
            cur = [int(off[g]), int(off[g])]
        if cur is None:
            findings.append(f"fused tables: row {g} precedes any reset row")
            cur = [int(off[g]), int(off[g])]
        c = int(cnt[g])
        if c:
            if int(off[g]) != cur[1]:
                findings.append(
                    f"fused tables: row {g} offset {int(off[g])} breaks its "
                    f"region's contiguity (expected {cur[1]})")
            cur[1] = int(off[g]) + c
    if cur is not None:
        regions.append(cur)
    findings.extend(_interval_findings(regions, n, "fused tables"))
    return findings


def check_merge_tables(out_off, out_cnt, win_start, win_take, *, kway: int,
                       tpb: int, n: int, buf_len: int) -> List[str]:
    """Interval analysis on one merge round's tile descriptor tables.

    Proves the per-tile write intervals ``[out_off, out_off + out_cnt)``
    are disjoint and tile ``[0, n)`` exactly, every window load
    ``[win_start, win_start + tpb)`` fits the run buffer, and each tile's
    live window lanes sum to its live output count (no element dropped or
    merged twice across tiles).
    """
    oo = np.asarray(out_off).reshape(-1)
    oc = np.asarray(out_cnt).reshape(-1)
    ws = np.asarray(win_start).reshape(-1, kway) \
        if np.asarray(win_start).size else np.zeros((0, kway), np.int32)
    wt = np.asarray(win_take).reshape(-1, kway) \
        if np.asarray(win_take).size else np.zeros((0, kway), np.int32)
    findings = _interval_findings(
        zip(oo.tolist(), (oo + oc).tolist()), n, "merge tables")
    for g in range(ws.shape[0]):
        for r in range(kway):
            s = int(ws[g, r])
            if s < 0 or s + tpb > buf_len:
                findings.append(
                    f"merge tables: tile {g} run {r} loads [{s}, {s + tpb}) "
                    f"outside buffer [0, {buf_len})")
            t = int(wt[g, r])
            if t < 0 or t > tpb:
                findings.append(
                    f"merge tables: tile {g} run {r} take {t} outside "
                    f"[0, {tpb}]")
        if g < oc.shape[0] and int(wt[g].sum()) != int(oc[g]):
            findings.append(
                f"merge tables: tile {g} window takes sum to "
                f"{int(wt[g].sum())} but writes {int(oc[g])} lanes")
    return findings


def check_block_injectivity(site: PallasSite,
                            max_points: int = 65536) -> List[str]:
    """Output block mappings visit distinct blocks across the grid.

    Whole-array blocks (constant index maps) are accumulator/scatter style
    and handled by the dynamic checks; everything else must map distinct
    grid points to distinct block indices — the static exactly-once write.
    """
    findings: List[str] = []
    grid = site.grid
    total = 1
    for g in grid:
        total *= g
    if total > max_points:
        return findings                       # census pins the grid anyway
    bms = site.block_mappings()
    out_bms = bms[-site.num_outputs:] if site.num_outputs else ()
    for j, bm in enumerate(out_bms):
        arr_shape = tuple(int(d) for d in bm.array_aval.shape)
        blk_shape = tuple(int(getattr(d, "block_size", d))
                          for d in bm.block_shape)
        if blk_shape == arr_shape:
            continue
        imj = bm.index_map_jaxpr
        seen: Dict = {}
        for flat in range(total):
            idxs, rem = [], flat
            for g in reversed(grid):
                idxs.append(rem % g)
                rem //= g
            idxs = tuple(reversed(idxs))
            vals = jax.core.eval_jaxpr(imj.jaxpr, imj.consts, *idxs)
            block = tuple(int(v) for v in vals)
            if block in seen:
                findings.append(
                    f"{site.name}: output {j} block mapping collides — grid "
                    f"points {seen[block]} and {idxs} both write block "
                    f"{block}")
                break
            seen[block] = idxs
    return findings


def sweep_kernels(sites: Sequence[PallasSite]) -> List[str]:
    """Run the structural kernel checks over every site of a trace."""
    findings: List[str] = []
    for s in sites:
        findings.extend(check_kernel(s))
        findings.extend(check_block_injectivity(s))
    return findings
