"""Donation audit: every ping-pong / slab buffer must alias, never copy.

The §4.4 in-place replacement is only real if the alternate buffers are
donated: ``input_output_aliases`` must map each full-length alternate
operand onto its output, and the kernel body must never read the donated
ref (its contents are garbage the moment the output writes begin).  A
dropped alias is *silent* — the program stays correct, XLA just
materialises a fresh buffer and copies, which doubles the §4.3 write
traffic.  This pass makes that failure loud:

  * declared check — each kernel listed in the contract's ``donation``
    mapping must carry exactly the declared number of alias pairs,
  * structural checks on every alias pair — operand/output avals match and
    the aliased operand has zero ``get``s in the kernel body,
  * the silent-copy sweep — any *unaliased* flat 1-D or whole-HBM
    (``memory_space=ANY``) output at least as large as the site's largest
    buffer operand, with an identically-shaped unaliased operand available
    to donate, is flagged (that is exactly the shape of a forgotten
    ping-pong alias; accumulator outputs and the tiled bitonic class tables
    don't trip it).
"""
from __future__ import annotations

from typing import Dict, List

from repro.analysis import expr
from repro.analysis.trace import PallasSite, ref_access_counts


def _nbytes(av) -> int:
    size = 1
    for d in av.shape:
        size *= int(d)
    return size * av.dtype.itemsize


def audit_site(site: PallasSite) -> List[str]:
    """Structural donation findings for one pallas site (empty = clean)."""
    findings: List[str] = []
    counts = ref_access_counts(site.kernel_jaxpr)

    for opi, outj in site.aliases.items():
        if opi >= len(site.in_avals) or outj >= len(site.out_avals):
            findings.append(f"{site.name}: alias ({opi}->{outj}) out of "
                            f"operand/result range")
            continue
        if site.in_avals[opi].shape != site.out_avals[outj].shape or \
                site.in_avals[opi].dtype != site.out_avals[outj].dtype:
            findings.append(
                f"{site.name}: alias ({opi}->{outj}) aval mismatch "
                f"{site.in_avals[opi]} vs {site.out_avals[outj]}")
        gets, _ = counts.get(site.root_of_operand(opi), (0, 0))
        if gets:
            findings.append(
                f"{site.name}: donated operand {opi} is read {gets}x in the "
                f"kernel body — donation invalidates its contents")

    # silent-copy sweep: unaliased full-size 1-D outputs with a donatable twin
    if site.num_inputs:
        buf_max = max(
            _nbytes(site.in_avals[i])
            for i in range(site.num_scalars,
                           site.num_scalars + site.num_inputs))
        aliased_ops = set(site.aliases)
        aliased_outs = set(site.aliases.values())
        for j, oav in enumerate(site.out_avals):
            if j in aliased_outs or not (len(oav.shape) == 1 or
                                         site.output_in_hbm(j)):
                continue
            if _nbytes(oav) < buf_max:
                continue
            gets, _ = counts.get(site.root_of_output(j), (0, 0))
            if gets:                # read-modify-write accumulator, not a
                continue            # ping-pong destination

            twin = any(
                site.in_avals[i].shape == oav.shape and
                site.in_avals[i].dtype == oav.dtype and i not in aliased_ops
                for i in range(site.num_scalars,
                               site.num_scalars + site.num_inputs))
            if twin:
                findings.append(
                    f"{site.name}: output {j} ({oav.dtype}{list(oav.shape)}) "
                    f"is a full-size buffer with an identically-shaped "
                    f"operand available but NO input_output_alias — the "
                    f"ping-pong buffer silently copies instead of aliasing")
    return findings


def check_donation(sites: List[PallasSite], decl: Dict[str, str],
                   params: Dict) -> List[str]:
    """Declared + structural donation audit over a trace's sites."""
    findings: List[str] = []
    expected = {k: int(expr.evaluate(f, params))
                for k, f in (decl or {}).items()}
    seen = {k: 0 for k in expected}
    for site in sites:
        findings.extend(audit_site(site))
        for kname, want in expected.items():
            if site.name == kname:
                seen[kname] += 1
                if len(site.aliases) != want:
                    findings.append(
                        f"{site.name}: expected {want} alias pair(s), "
                        f"found {len(site.aliases)}")
    for kname, n in seen.items():
        if n == 0:
            findings.append(
                f"declared donation kernel {kname!r} never appears in trace")
    return findings
