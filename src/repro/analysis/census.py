"""Launch-census verification against symbolic contract formulas.

The structural headline of §4.3–§4.4 — ONE fused launch per counting pass,
one merge launch per round — is declared next to each engine as formulas in
(passes, rounds, classes, attempts, chunks) and verified here against the
actual trace: total ``pallas_call`` sites, per-while-body launch counts, and
optionally the batched fused-launch grid (⌈g_max/B⌉, the
``plan.pack_region_blocks`` contract).
"""
from __future__ import annotations

from typing import Dict, List

from repro.analysis import expr
from repro.analysis.trace import PallasSite
from repro.utils import hlo


def check_census(jaxpr, sites: List[PallasSite], decl: Dict,
                 params: Dict) -> List[str]:
    findings: List[str] = []
    got = hlo.launch_census(jaxpr)

    want_total = int(expr.evaluate(decl["launch_total"], params))
    if got["total"] != want_total:
        findings.append(
            f"launch total {got['total']} != declared "
            f"{decl['launch_total']!r} = {want_total}")

    want_while = [int(x)
                  for x in expr.evaluate(decl["while_body_launches"], params)]
    if list(got["while_bodies"]) != want_while:
        findings.append(
            f"while-body launches {got['while_bodies']} != declared "
            f"{decl['while_body_launches']!r} = {want_while}")

    # cross-check the site collector against utils.hlo (one impl per layer,
    # same count — a disagreement means the walker missed a context)
    if len(sites) != got["total"]:
        findings.append(
            f"site collector found {len(sites)} pallas sites but "
            f"utils.hlo counts {got['total']}")

    if "fused_grid" in decl:
        want_grid = int(expr.evaluate(decl["fused_grid"], params))
        fused = [s for s in sites if s.name == "fused_counting_pass"]
        if not fused:
            findings.append("fused_grid declared but no fused_counting_pass "
                            "site in trace")
        for s in fused:
            if s.grid != (want_grid,):
                findings.append(
                    f"{s.name}: grid {s.grid} != declared "
                    f"{decl['fused_grid']!r} = ({want_grid},)")
    return findings
