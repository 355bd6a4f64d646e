"""The contract registry: every public entry point, declaratively verified.

Each engine module co-locates a pure-data ``ANALYSIS_CONTRACT`` declaration
(census formulas, sort-free flag, donation counts, transfer formulas) next
to the code it constrains; this module binds those declarations to concrete
*trace recipes* — a representative input shape per entry point — and runs
every check against the traced jaxpr without executing anything.

A :class:`Contract` is (name, decl, make) where ``make() -> (fn, args,
params)``: ``fn(*args)`` is traced with ``jax.make_jaxpr`` and ``params``
is the symbolic-formula environment (passes, classes, n_pad, ...) built by
the exported ``*_params`` helpers — the same helpers the launch-census
tests use, so the tests and the analyzer can never drift apart.

``run_all()`` is the whole sweep (plus the descriptor-table interval
checks of :func:`table_checks`); ``python -m repro.analysis`` drives it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis import census as _census
from repro.analysis import donation as _donation
from repro.analysis import expr
from repro.analysis import refhazard
from repro.analysis import transfer as _transfer
from repro.analysis.trace import (collect_pallas_sites, collective_link_bytes,
                                  sort_primitive_count)
from repro.core import distributed as core_distributed
from repro.core import hybrid as core_hybrid
from repro.core import lsd as core_lsd
from repro.core import model, outofcore as core_outofcore, plan
from repro.core.hybrid import hybrid_sort, local_sort_classes
from repro.core.lsd import lsd_sort
from repro.core.outofcore import _sort_chunk, merge_round
from repro.core.segmented import capacity_dispatch, counting_partition
from repro.data import pipeline as data_pipeline
from repro.kernels import fused
from repro.kernels import merge as kmerge
from repro.models import moe as models_moe

# the launch-census test config: small thresholds so every structural
# feature (local-sort classes, multi-pass loop) appears at toy sizes
TCFG = model.SortConfig(d=8, kpb=64, local_threshold=48, merge_threshold=32)


# --------------------------------------------------------------------------
# symbolic-parameter builders (shared with tests/test_launch_count.py)

def hybrid_params(n: int, cfg: model.SortConfig, key_bits: int = 32,
                  key_bytes: int = 4, vals: int = 0,
                  val_bytes: int = 0) -> Dict[str, Any]:
    """Formula environment for the hybrid-sort contract at (n, cfg)."""
    a_max = model.max_active_buckets(n, cfg)
    return {
        "n": n,
        "classes": len(local_sort_classes(n, cfg)),
        "passes": model.num_digits(key_bits, cfg.d),
        "g_max": plan.max_region_blocks(n, cfg.kpb, a_max),
        "B": cfg.step_batch,
        "n_pad": fused.buffer_length(n, cfg.kpb),
        "kb": key_bytes, "vb": val_bytes, "vals": vals,
    }


def lsd_params(n: int, d: int, kpb: int, step_batch: int, key_bits: int = 32,
               key_bytes: int = 4, vals: int = 0,
               val_bytes: int = 0) -> Dict[str, Any]:
    """Formula environment for the LSD contract (unrolled, a_max = 1)."""
    return {
        "n": n,
        "passes": model.num_digits(key_bits, d),
        "g_max": plan.max_region_blocks(n, kpb, 1),
        "B": step_batch,
        "n_pad": fused.buffer_length(n, kpb),
        "kb": key_bytes, "vb": val_bytes, "vals": vals,
    }


def spp_params(m: int, num_buckets: int, kpb: int = 1024,
               step_batch: int = 8, id_bytes: int = 4) -> Dict[str, Any]:
    """Formula environment for one standalone counting pass
    (``plan.single_pass_partition`` and everything routed through it:
    ``counting_partition``, ``capacity_dispatch``, length bucketing).
    Mirrors the engine's kpb clamp; the iota permutation is the single
    int32 value leaf."""
    kpb_eff = max(8, min(kpb, 1 << (m - 1).bit_length()))
    return {
        "n": m,
        "passes": 1,
        "g_max": plan.max_region_blocks(m, kpb_eff, 1),
        "B": step_batch,
        "n_pad": fused.buffer_length(m, kpb_eff),
        "kb": id_bytes, "vb": 4, "vals": 1,
    }


def merge_params(lens, kway: int, tile: int, key_bytes: int = 4,
                 vals: int = 0, val_bytes: int = 0) -> Dict[str, Any]:
    """Formula environment for one k-way merge round over runs ``lens``."""
    n = int(sum(lens))
    return {
        "n": n, "kway": kway,
        "n_pad": fused.pad_length(n, tile),
        "kb": key_bytes, "vb": val_bytes, "vals": vals,
    }


def dist_params(P: int, n_local: int, chunks: int, attempts: int,
                cfg: model.SortConfig, oversample: int = 64,
                slack: float = 2.0, refine: int = 4, key_bytes: int = 4,
                leaves: int = 0, val_bytes: int = 0) -> Dict[str, Any]:
    """Formula environment for the distributed shard body.

    Re-derives the engine's static shapes: per-(source, dest) capacity
    (slack + 4σ headroom, chunk-capped) and the per-attempt gathered
    sample lengths ``samp[a] = chunks * m_a`` (the all_gather rows).
    """
    chunk = n_local // chunks
    base = slack * chunk / P
    cap = max(1, min(chunk, int(base + 4.0 * math.sqrt(max(base, 1.0)))))
    samp = []
    for a in range(attempts):
        s_a = oversample * (refine ** a)
        m = max(1, min(-(-s_a // chunks), chunk))
        samp.append(chunks * m)
    return {
        "P": P, "chunks": chunks, "attempts": attempts,
        "classes": len(local_sort_classes(chunk, cfg)),
        "cap": cap, "samp": samp,
        "kb": key_bytes, "vb": val_bytes, "leaves": leaves,
    }


def expected_census(name: str, params: Dict[str, Any]) -> Dict[str, Any]:
    """Evaluate a registered contract's census formulas at ``params``.

    The launch-census tests call this instead of re-stating the integers:
    one declaration, checked by the analyzer AND exercised by the tests.
    """
    decl = REGISTRY[name].decl["census"]
    return {
        "total": int(expr.evaluate(decl["launch_total"], params)),
        "while_bodies": [int(x) for x in
                         expr.evaluate(decl["while_body_launches"], params)],
    }


# --------------------------------------------------------------------------
# contract records and trace recipes

@dataclass(frozen=True)
class Contract:
    """One verified entry point: declaration + trace recipe."""
    name: str
    decl: Dict[str, Any]
    make: Callable[[], Tuple[Callable, tuple, Dict[str, Any]]]


def _abstract_mesh(n: int, name: str):
    return jax.sharding.AbstractMesh((n,), (name,))


def _mk_hybrid():
    n = 2048
    fn = lambda a: hybrid_sort(a, cfg=TCFG, engine="kernel")
    return fn, (jnp.zeros(n, jnp.uint32),), hybrid_params(n, TCFG)


def _mk_hybrid_kv():
    n = 1024
    fn = lambda a, b: hybrid_sort(a, b, cfg=TCFG, engine="kernel")
    return (fn, (jnp.zeros(n, jnp.uint32), jnp.zeros(n, jnp.int32)),
            hybrid_params(n, TCFG, vals=1, val_bytes=4))


def _mk_lsd():
    n, d, kpb, B = 2048, 8, 512, 4
    fn = lambda a: lsd_sort(a, d=d, engine="kernel", kpb=kpb, step_batch=B)
    return fn, (jnp.zeros(n, jnp.uint32),), lsd_params(n, d, kpb, B)


def _mk_spp():
    m, r = 1000, 8
    fn = lambda i: plan.single_pass_partition(i, r, engine="kernel")
    return fn, (jnp.zeros(m, jnp.int32),), spp_params(m, r)


def _mk_moe_dispatch():
    m, e, cap = 512, 8, 64
    fn = lambda i: capacity_dispatch(i, e, cap, engine="kernel")
    return fn, (jnp.zeros(m, jnp.int32),), spp_params(m, e)


def _mk_pipeline_bucketing():
    m, r = 600, 256
    fn = lambda i: counting_partition(i, r, engine="kernel")
    return fn, (jnp.zeros(m, jnp.int32),), spp_params(m, r)


def _mk_ooc_chunk_sort():
    n = 256
    fn = lambda a: _sort_chunk(a, (), TCFG, "kernel", True)
    return fn, (jnp.zeros(n, jnp.uint32),), hybrid_params(n, TCFG)


def _mk_ooc_merge_round():
    lens, kway, tile = (256,) * 4, 4, 64
    n = sum(lens)
    buf = fused.pad_length(n, tile)
    fn = lambda a, b: merge_round(a, (), b, (), lens=lens, kway=kway,
                                  tile=tile, n=n, interpret=True)
    return (fn, (jnp.zeros((buf,), jnp.uint32), jnp.zeros((buf,), jnp.uint32)),
            merge_params(lens, kway, tile))


def _mk_ooc_slab_sweep():
    # the §5 spill path: sentinel-pad an exact strip upload to the slab
    # buffer and run ONE merge-kernel sweep (mirrors the launch-count test)
    slab, tile, kway = 64, 16, 4
    buf = fused.pad_length(slab, tile)
    G = slab // tile
    sentinel = ~jnp.zeros((), jnp.uint32)

    def sweep(up_k, alt_k, off, cnt, ws, wt):
        slab_k = jnp.concatenate(
            [up_k, jnp.full((buf - up_k.shape[0],), sentinel, jnp.uint32)])
        return kmerge.kway_merge_round(slab_k, (), alt_k, (), off, cnt, ws,
                                       wt, kway=kway, tpb=tile, n=slab,
                                       interpret=True)

    args = (jnp.zeros((48,), jnp.uint32), jnp.full((buf,), sentinel),
            jnp.zeros((G,), jnp.int32), jnp.zeros((G,), jnp.int32),
            jnp.full((G * kway,), slab, jnp.int32),
            jnp.zeros((G * kway,), jnp.int32))
    return sweep, args, merge_params((slab,), kway, tile)


def _mk_distributed():
    P, n_local, chunks, attempts = 8, 512, 2, 2
    mesh = _abstract_mesh(P, "data")
    fn = core_distributed.make_distributed_sort(
        mesh, "data", cfg=TCFG, engine="kernel", num_chunks=chunks,
        max_attempts=attempts, oversample=64, slack=2.0, refine=4)
    return (fn, (jnp.zeros(P * n_local, jnp.uint32),),
            dist_params(P, n_local, chunks, attempts, TCFG))


CONTRACTS: List[Contract] = [
    Contract("hybrid_sort", core_hybrid.ANALYSIS_CONTRACT, _mk_hybrid),
    Contract("hybrid_sort_kv", core_hybrid.ANALYSIS_CONTRACT, _mk_hybrid_kv),
    Contract("lsd_sort", core_lsd.ANALYSIS_CONTRACT, _mk_lsd),
    Contract("single_pass_partition", plan.ANALYSIS_CONTRACT, _mk_spp),
    Contract("moe_dispatch", models_moe.ANALYSIS_CONTRACT, _mk_moe_dispatch),
    Contract("pipeline_bucketing", data_pipeline.ANALYSIS_CONTRACT,
             _mk_pipeline_bucketing),
    Contract("ooc_chunk_sort",
             core_outofcore.ANALYSIS_CONTRACTS["ooc_chunk_sort"],
             _mk_ooc_chunk_sort),
    Contract("ooc_merge_round",
             core_outofcore.ANALYSIS_CONTRACTS["ooc_merge_round"],
             _mk_ooc_merge_round),
    Contract("ooc_slab_sweep",
             core_outofcore.ANALYSIS_CONTRACTS["ooc_slab_sweep"],
             _mk_ooc_slab_sweep),
    Contract("distributed_shard", core_distributed.ANALYSIS_CONTRACT,
             _mk_distributed),
]
REGISTRY: Dict[str, Contract] = {c.name: c for c in CONTRACTS}


# --------------------------------------------------------------------------
# the runner

@dataclass
class ContractReport:
    """Per-contract findings, keyed by check name (empty lists = green)."""
    name: str
    checks: Dict[str, List[str]] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not any(self.checks.values())

    @property
    def findings(self) -> List[str]:
        return [f"{self.name}/{check}: {msg}"
                for check, msgs in self.checks.items() for msg in msgs]

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "ok": self.ok, "checks": self.checks}


def run_contract(contract: Contract) -> ContractReport:
    """Trace one entry point and run every declared check on the jaxpr."""
    fn, args, params = contract.make()
    jx = jax.make_jaxpr(fn)(*args)
    sites = collect_pallas_sites(jx)
    decl = contract.decl
    checks: Dict[str, List[str]] = {}

    if "census" in decl:
        checks["census"] = _census.check_census(jx, sites, decl["census"],
                                                params)
    if decl.get("sort_free"):
        nsorts = sort_primitive_count(jx)
        checks["sort_free"] = ([] if nsorts == 0 else
                               [f"{nsorts} sort primitive(s) in the trace of "
                                f"a sort-free entry point"])
    checks["donation"] = _donation.check_donation(sites, decl.get("donation"),
                                                  params)
    if "transfer" in decl:
        checks["transfer.hbm_bytes"] = _transfer.check_hbm_bytes(
            sites, decl["transfer"], params)
    if "link" in decl:
        checks["transfer.link_bytes"] = _transfer.check_link_bytes(
            collective_link_bytes(jx, params["P"]), decl["link"], params)
    checks["hazard"] = refhazard.sweep_kernels(sites)
    return ContractReport(contract.name, checks)


def table_checks() -> Dict[str, List[str]]:
    """Interval checks on descriptor-table instances from the real planners.

    The jaxpr-level hazard pass proves the kernels' access *shape*; these
    prove the scalar-prefetched tables driving them produce disjoint,
    exactly-covering ranges — fused region blocks, merge-path tiles, and
    host-spill strips.
    """
    out: Dict[str, List[str]] = {}

    m, kpb, B = 1000, 128, 4
    blocks = plan.make_region_blocks(
        jnp.zeros((1,), jnp.int32), jnp.full((1,), m, jnp.int32), m, kpb,
        plan.max_region_blocks(m, kpb, 1), batch=B)
    out["hazard.fused_tables"] = refhazard.check_fused_tables(
        blocks, m, kpb, fused.buffer_length(m, kpb))

    lens, kway, tile = (64, 48, 32, 16, 40), 4, 16
    n = int(sum(lens))
    buf = fused.pad_length(n, tile)
    sentinel = ~jnp.zeros((), jnp.uint32)
    keys = jnp.concatenate(
        [jnp.arange(l, dtype=jnp.uint32) for l in lens] +
        [jnp.full((buf - n,), sentinel)])
    tables = kmerge.merge_path_partition(keys, lens, kway, tile)
    out["hazard.merge_tables"] = refhazard.check_merge_tables(
        *tables, kway=kway, tpb=tile, n=n, buf_len=buf)

    runs = [np.arange(l, dtype=np.uint32) for l in (100, 37, 23)]
    tile, slab = 16, 32
    spill: List[str] = []
    for strip in kmerge.spill_group_plan(runs, 4, tile, slab):
        spill.extend(refhazard.check_merge_tables(
            *strip.tables, kway=4, tpb=tile, n=strip.out_len,
            buf_len=fused.pad_length(slab, tile)))
    out["hazard.spill_tables"] = spill
    return out


def run_all() -> List[ContractReport]:
    """The full sweep: every registered contract + the table instances."""
    reports = [run_contract(c) for c in CONTRACTS]
    reports.append(ContractReport("descriptor_tables", table_checks()))
    return reports
