"""Analyzer tests: the full contract sweep is green, and every seeded
contract violation (mutation) is detected.

The mutations are the failure modes the analyzer exists to catch: an extra
launch smuggled into the pass loop, a dropped ping-pong alias, overlapping
or gappy scatter ranges, an out-of-bounds block load, a read-after-write in
a kernel body, a comparison sort hidden in an engine, an undeclared extra
HBM sweep, global-PRNG use, and an undonated dispatch.  Each mutation test
also carries the unmutated positive control, so a check that silently
flags everything (or nothing) fails here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.analysis import census, contracts, donation, lint, refhazard
from repro.analysis import expr, transfer
from repro.analysis.trace import (collect_pallas_sites, ref_access_counts,
                                  sort_primitive_count)


# --------------------------------------------------------------------------
# the green path

def test_full_contract_sweep_is_green():
    """Every registered entry point verifies against its declaration."""
    reports = contracts.run_all()
    bad = [f for r in reports for f in r.findings]
    assert not bad, "\n".join(bad)
    assert {r.name for r in reports} >= {
        "hybrid_sort", "hybrid_sort_kv", "lsd_sort", "single_pass_partition",
        "moe_dispatch", "pipeline_bucketing", "ooc_chunk_sort",
        "ooc_merge_round", "ooc_slab_sweep", "distributed_shard",
        "descriptor_tables"}


def test_repo_lint_is_green():
    import os
    import repro.analysis
    src_root = os.path.dirname(os.path.dirname(
        os.path.abspath(repro.analysis.__file__)))
    assert lint.run_lint(src_root) == []


def test_expected_census_matches_formulas():
    p = contracts.hybrid_params(2048, contracts.TCFG)
    got = contracts.expected_census("hybrid_sort", p)
    assert got["total"] == 2 + p["classes"]
    assert got["while_bodies"] == [1] * (1 + p["classes"])


def test_expr_evaluator_rejects_unsafe_forms():
    assert expr.evaluate("ceil_div(7, 2) + 1", {}) == 5
    assert expr.evaluate("[1] * chunks", {"chunks": 3}) == [1, 1, 1]
    for bad in ("__import__('os')", "(lambda: 1)()", "x.__class__",
                "open('/etc/passwd')"):
        with pytest.raises(expr.FormulaError):
            expr.evaluate(bad, {"x": 1})


# --------------------------------------------------------------------------
# mutation helpers: tiny pallas programs with seeded violations

def _noop(x):
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x)


def _pingpong(x, alt, donate):
    """Full-buffer rewrite through an alternate buffer — the ping-pong
    shape; ``donate=False`` is the dropped-alias mutation."""
    def kernel(x_ref, alt_ref, o_ref):
        o_ref[...] = x_ref[...] + 1

    kwargs = {"input_output_aliases": {1: 0}} if donate else {}
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True, **kwargs)(x, alt)


# M1: extra launch inside the pass loop's while body
def test_mutation_extra_launch_in_while_body():
    def prog(x):
        def body(c):
            i, v = c
            return i + 1, _noop(_noop(v))
        return jax.lax.while_loop(lambda c: c[0] < 3, body, (0, x))

    jx = jax.make_jaxpr(prog)(jnp.zeros(8, jnp.float32))
    sites = collect_pallas_sites(jx)
    decl = {"launch_total": "2", "while_body_launches": "[1]"}
    findings = census.check_census(jx, sites, decl, {})
    assert any("while-body launches" in f for f in findings), findings


# M2: extra top-level launch
def test_mutation_extra_toplevel_launch():
    jx = jax.make_jaxpr(lambda x: _noop(_noop(x)))(jnp.zeros(8, jnp.float32))
    sites = collect_pallas_sites(jx)
    ok = census.check_census(jx, sites,
                             {"launch_total": "2",
                              "while_body_launches": "[]"}, {})
    assert ok == []
    bad = census.check_census(jx, sites,
                              {"launch_total": "1",
                               "while_body_launches": "[]"}, {})
    assert any("launch total" in f for f in bad), bad


# M3: dropped ping-pong alias -> silent copy
def test_mutation_dropped_alias_silent_copy():
    x = jnp.zeros(64, jnp.uint32)
    for donate, nfind in ((True, 0), (False, 1)):
        jx = jax.make_jaxpr(
            lambda a, b, d=donate: _pingpong(a, b, d))(x, x)
        (site,) = collect_pallas_sites(jx)
        findings = donation.audit_site(site)
        assert len(findings) == nfind, (donate, findings)
        if not donate:
            assert "silently copies" in findings[0]
    # declared alias-count check catches it too
    jx = jax.make_jaxpr(lambda a, b: _pingpong(a, b, False))(x, x)
    sites = collect_pallas_sites(jx)
    bad = donation.check_donation(sites, {"kernel": "1"}, {})
    assert any("expected 1 alias pair" in f for f in bad), bad


# M4/M5: overlapping scatter ranges / coverage gap in the merge tables
def test_mutation_merge_table_overlap_and_gap():
    good = ([0, 16, 32, 48], [16, 16, 16, 16])
    overlap = ([0, 8, 32, 48], [16, 16, 16, 16])
    gap = ([0, 16, 40, 48], [16, 16, 8, 16])
    ws = np.zeros((16,), np.int32)
    wt_rows = np.zeros((4, 4), np.int32)

    def check(oo, oc):
        wt = wt_rows.copy()
        wt[:, 0] = oc
        return refhazard.check_merge_tables(
            np.array(oo, np.int32), np.array(oc, np.int32), ws,
            wt.reshape(-1), kway=4, tpb=16, n=64, buf_len=80)

    assert check(*good) == []
    assert any("overlap" in f for f in check(*overlap))
    assert any("expected exactly [0, 64)" in f for f in check(*gap))


# M10: block load overrunning the padded buffer
def test_mutation_fused_table_load_overrun():
    m, kpb, B = 1000, 128, 4
    import repro.core.plan as plan
    from repro.kernels.fused import pad_length
    blocks = plan.make_region_blocks(
        jnp.zeros((1,), jnp.int32), jnp.full((1,), m, jnp.int32), m, kpb,
        plan.max_region_blocks(m, kpb, 1), batch=B)
    n_pad = pad_length(m, kpb)
    assert refhazard.check_fused_tables(blocks, m, kpb, n_pad) == []
    # seed an offset past the pad: the load [off, off+kpb) escapes
    bad = blocks._replace(offset=blocks.offset.at[0, 0].set(n_pad - 1))
    findings = refhazard.check_fused_tables(bad, m, kpb, n_pad)
    assert any("outside padded buffer" in f for f in findings), findings


# M6: smuggled comparison sort
def test_mutation_smuggled_sort():
    jx = jax.make_jaxpr(lambda x: jnp.sort(x))(jnp.zeros(32, jnp.float32))
    assert sort_primitive_count(jx) == 1
    jx = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros(32, jnp.float32))
    assert sort_primitive_count(jx) == 0
    src = ("import jax.numpy as jnp\n"
           "def rank(keys):\n"
           "    return jnp.argsort(keys)\n")
    findings = lint.lint_source(src, "kernels/evil.py",
                                ["no-comparison-sort"])
    assert len(findings) == 1 and findings[0].rule == "no-comparison-sort"


# M7: global-PRNG use in the data layer
def test_mutation_global_prng():
    bad = ("import numpy as np\n"
           "def draw(n):\n"
           "    return np.random.randint(0, 5, n)\n")
    good = ("import numpy as np\n"
            "def draw(n, seed):\n"
            "    return np.random.default_rng(seed).integers(0, 5, n)\n")
    assert [f.rule for f in lint.lint_source(bad, "data/evil.py",
                                             ["no-global-prng"])] \
        == ["no-global-prng"]
    assert lint.lint_source(good, "data/fine.py", ["no-global-prng"]) == []


# M8: undeclared extra HBM sweep
def test_mutation_extra_sweep_changes_transfer_bytes():
    fn, args, params = contracts.REGISTRY["single_pass_partition"].make()
    jx = jax.make_jaxpr(fn)(*args)
    sites = collect_pallas_sites(jx)
    decl = contracts.REGISTRY["single_pass_partition"].decl["transfer"]
    assert transfer.check_hbm_bytes(sites, decl, params) == []
    # mutate the nominal schedule (an undeclared extra pass = extra sweeps)
    bad = dict(params)
    bad["passes"] = params["passes"] + 1
    findings = transfer.check_hbm_bytes(sites, decl, bad)
    assert any("HBM sweep bytes" in f for f in findings), findings


# M9: read-after-write on a scatter target inside a kernel body
def test_mutation_raw_hazard_in_kernel():
    def raw_kernel(i_ref, x_ref, o_ref):
        o_ref[pl.ds(i_ref[0], 4)] = x_ref[pl.ds(0, 4)]
        y = o_ref[pl.ds(i_ref[0], 4)]         # read-back of a dynamic write
        o_ref[pl.ds(4, 4)] = y + 1

    def prog(i, x):
        return pl.pallas_call(
            raw_kernel, out_shape=jax.ShapeDtypeStruct((16,), jnp.float32),
            interpret=True)(i, x)

    jx = jax.make_jaxpr(prog)(jnp.zeros(2, jnp.int32),
                              jnp.zeros(16, jnp.float32))
    (site,) = collect_pallas_sites(jx)
    findings = refhazard.check_kernel(site)
    assert any("read-after-write" in f for f in findings), findings


# M11: alt_* dispatch without donation (source level)
def test_mutation_undonated_dispatch_lint():
    bad = ("from jax.experimental import pallas as pl\n"
           "def sweep(src, alt_keys):\n"
           "    return pl.pallas_call(k, out_shape=o)(src, alt_keys)\n")
    good = ("from jax.experimental import pallas as pl\n"
            "def sweep(src, alt_keys):\n"
            "    return pl.pallas_call(k, out_shape=o,\n"
            "                          input_output_aliases={1: 0})(\n"
            "        src, alt_keys)\n")
    assert [f.rule for f in lint.lint_source(bad, "kernels/evil.py",
                                             ["undonated-dispatch"])] \
        == ["undonated-dispatch"]
    assert lint.lint_source(good, "kernels/fine.py",
                            ["undonated-dispatch"]) == []


# the trace layer itself: ref accounting sees through pl.when conds
def test_ref_access_counts_on_real_fused_kernel():
    fn, args, _ = contracts.REGISTRY["single_pass_partition"].make()
    jx = jax.make_jaxpr(fn)(*args)
    fused_sites = [s for s in collect_pallas_sites(jx)
                   if s.name == "fused_counting_pass"]
    assert fused_sites
    site = fused_sites[0]
    counts = ref_access_counts(site.kernel_jaxpr)
    # the donated alternate buffers are never read or written in the body
    for opi in site.aliases:
        assert counts.get(site.root_of_operand(opi), (0, 0)) == (0, 0)
    # the source key buffer is read (batch block loads), never written
    gets, swaps = counts[site.num_scalars]
    assert gets > 0 and swaps == 0
