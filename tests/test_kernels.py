"""Per-kernel interpret-mode validation against the pure-jnp oracles,
sweeping shapes, dtypes, digit positions and distributions."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import plan
from repro.kernels import fused, ref
from repro.kernels.histogram import radix_histogram
from repro.kernels.multisplit import tile_multisplit
from repro.kernels.bitonic import (bitonic_sort_rows, bitonic_sort_rows_kv,
                                   bitonic_sort_rows_stable)
from repro.kernels.assigned import assigned_histogram
from repro.kernels.ops import segmented_local_sort, tile_histogram_pass
from conftest import entropy_keys


@pytest.mark.parametrize("t,kpb", [(1, 256), (4, 512), (7, 1024)])
@pytest.mark.parametrize("shift,width", [(24, 8), (0, 8), (8, 5), (28, 4)])
def test_histogram_kernel(rng, t, kpb, shift, width):
    keys = jnp.asarray(rng.integers(0, 2**32, (t, kpb), dtype=np.uint32))
    got = radix_histogram(keys, shift, width, interpret=True)
    want = ref.radix_histogram_ref(keys, shift, width)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).sum() == t * kpb


@pytest.mark.parametrize("ands", [0, 3, 30])     # uniform .. near-constant
def test_histogram_kernel_skew(rng, ands):
    x = entropy_keys(rng, 4096, ands).reshape(4, 1024)
    got = radix_histogram(jnp.asarray(x), 24, 8, interpret=True)
    want = ref.radix_histogram_ref(jnp.asarray(x), 24, 8)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t,kpb", [(1, 128), (3, 256), (2, 512)])
@pytest.mark.parametrize("shift,width", [(24, 8), (0, 8), (16, 6)])
def test_multisplit_kernel(rng, t, kpb, shift, width):
    keys = jnp.asarray(rng.integers(0, 2**32, (t, kpb), dtype=np.uint32))
    sk, sd, rk, h = tile_multisplit(keys, shift, width, 32, interpret=True)
    rsk, rsd, rrk, rh = ref.tile_multisplit_ref(keys, shift, width)
    assert np.array_equal(np.asarray(h), np.asarray(rh))
    assert np.array_equal(np.asarray(sd), np.asarray(rsd))
    assert np.array_equal(np.asarray(rk), np.asarray(rrk))
    # key permutation: digit-major and a permutation of the input
    assert np.array_equal(np.sort(np.asarray(sk), axis=1),
                          np.sort(np.asarray(keys), axis=1))
    # within-tile stability: ref uses stable argsort; must match exactly
    assert np.array_equal(np.asarray(sk), np.asarray(rsk))


def test_multisplit_kernel_skewed(rng):
    x = entropy_keys(rng, 512, 8).reshape(2, 256)
    sk, sd, rk, h = tile_multisplit(jnp.asarray(x), 24, 8, 32, interpret=True)
    rsk, *_ = ref.tile_multisplit_ref(jnp.asarray(x), 24, 8)
    assert np.array_equal(np.asarray(sk), np.asarray(rsk))


@pytest.mark.parametrize("s,l", [(1, 64), (5, 128), (3, 1024)])
@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.float32])
def test_bitonic_kernel(rng, s, l, dtype):
    if np.issubdtype(dtype, np.floating):
        keys = rng.standard_normal((s, l)).astype(dtype)
    else:
        info = np.iinfo(dtype)
        keys = rng.integers(info.min, info.max, (s, l)).astype(dtype)
    got = bitonic_sort_rows(jnp.asarray(keys), interpret=True)
    assert np.array_equal(np.asarray(got), np.sort(keys, axis=1))


def test_bitonic_kv_kernel(rng):
    keys = rng.integers(0, 1000, (4, 256)).astype(np.uint32)   # duplicates
    vals = np.arange(4 * 256, dtype=np.int32).reshape(4, 256)
    ks, vs = bitonic_sort_rows_kv(jnp.asarray(keys), jnp.asarray(vals),
                                  interpret=True)
    ks, vs = np.asarray(ks), np.asarray(vs)
    assert np.array_equal(ks, np.sort(keys, axis=1))
    for i in range(4):                    # pair consistency, not stability
        assert np.array_equal(keys[i][vs[i] - i * 256], ks[i])


def test_assigned_histogram_scalar_prefetch(rng):
    keys = jnp.asarray(rng.integers(0, 2**32, (6, 256), dtype=np.uint32))
    # grid of 8 slots (static I4 bound), only 5 valid, out-of-order tiles
    tile_idx = jnp.asarray([3, 0, 5, 1, 4, 0, 0, 0], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 1, 1, 0, 0, 0], jnp.int32)
    got = np.asarray(assigned_histogram(keys, tile_idx, valid, 24, 8,
                                        interpret=True))
    want = np.asarray(ref.radix_histogram_ref(keys, 24, 8))
    for g in range(8):
        if valid[g]:
            assert np.array_equal(got[g], want[int(tile_idx[g])]), g
        else:
            assert got[g].sum() == 0


def test_tile_histogram_pass_total(rng):
    x = rng.integers(0, 2**32, 5000, dtype=np.uint32)
    hist, total = tile_histogram_pass(jnp.asarray(x), 24, 8, kpb=1024,
                                      interpret=True)
    want = np.bincount((x >> 24) & 0xFF, minlength=256)
    assert np.array_equal(np.asarray(total), want)


def test_bitonic_stable_kernel_sentinel_safe(rng):
    """(key, idx) lexicographic sort: stable under duplicates, and real
    all-ones keys (== the padding sentinel) sort before pads (idx = n)."""
    keys = np.array([[0xFFFFFFFF, 5, 0xFFFFFFFF, 1, 5, 0xFFFFFFFF, 0, 2]],
                    np.uint32)
    idx = np.arange(8, dtype=np.int32).reshape(1, 8)
    sk, si = bitonic_sort_rows_stable(jnp.asarray(keys), jnp.asarray(idx),
                                      interpret=True)
    order = np.argsort(keys[0], kind="stable")
    assert np.array_equal(np.asarray(sk)[0], keys[0][order])
    assert np.array_equal(np.asarray(si)[0], order)    # full stability


def test_segmented_local_sort_done_flags(rng):
    """Only flagged segments are sorted; sentinel-colliding keys stay exact."""
    n = 1000
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    x[10] = x[600] = 0xFFFFFFFF                        # collide with pad value
    starts = jnp.asarray([0, 300, 640], jnp.int32)
    sizes = jnp.asarray([300, 340, 360], jnp.int32)
    flags = jnp.asarray([True, False, True])
    (out,) = segmented_local_sort((jnp.asarray(x),), starts, sizes, flags,
                                  512, interpret=True)
    out = np.asarray(out)
    want = x.copy()
    want[:300] = np.sort(x[:300])
    want[640:] = np.sort(x[640:])
    assert np.array_equal(out, want)


def test_segmented_local_sort_size_classes(rng):
    """The size-classed plan sorts byte-identically to the single worst-case
    table, while binning each bucket into the narrowest power-of-two row
    that fits it (§4.2's local sort configurations)."""
    from repro.kernels.ops import local_sort_class_plan

    n = 2000
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    x[5] = x[900] = 0xFFFFFFFF                  # collide with the pad value
    # ragged bucket sizes spanning several classes, incl. 0/1/oversized gaps
    sizes_np = np.array([3, 1, 60, 0, 500, 17, 130, 33, 256, n], np.int32)
    starts_np = np.concatenate([[0], np.cumsum(sizes_np)[:-1]]).astype(np.int32)
    sizes_np[-1] = n - starts_np[-1]            # tail bucket fills the rest
    flags_np = np.array([1, 1, 1, 1, 1, 0, 1, 1, 1, 1], bool)
    starts, sizes = jnp.asarray(starts_np), jnp.asarray(sizes_np)
    flags = jnp.asarray(flags_np)
    row_len = 1024

    def run(**kw):
        return np.asarray(segmented_local_sort(
            (jnp.asarray(x),), starts, sizes, flags, row_len, interpret=True,
            **kw)[0])

    classes = local_sort_class_plan(n, row_len, s_max=len(sizes_np))
    got = run(classes=classes)
    ref = run()
    want = x.copy()
    for st_, sz, fl in zip(starts_np, sizes_np, flags_np):
        if fl and sz:
            want[st_:st_ + sz] = np.sort(x[st_:st_ + sz])
    assert np.array_equal(got, want)
    assert np.array_equal(got, ref)


# n = 8192 keys run tiles of 8192 lanes: 256 rows of class 0 (L = 32),
# 128 rows of class 1 (L = 64)
TILE_CASES = {
    # name: (segment sizes, flags or None for all set, tiles per class)
    "k_tiles": ([16] * 512, None, (2, 0)),
    "k_tiles_plus_one_row": ([15] * 512 + [16], None, (3, 0)),
    "empty_class": ([64] * 128, None, (0, 1)),
    "both_classes": ([20] * 300 + [40] * 50, None, (2, 1)),
    "every_class_empty": ([16] * 512, [False] * 512, (0, 0)),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_segmented_local_sort_tiles(rng, case):
    """Each class runs its occupied rows a tile at a time — whole tiles,
    one row over, an empty class, nothing flagged — and sorts every flagged
    segment stably, values riding along, whatever the tiling."""
    from repro.kernels.ops import (local_sort_class_plan,
                                   local_sort_tile_count,
                                   local_sort_tile_lanes)

    n, row_len = 8192, 64
    sizes_np, flags_np, per_class = TILE_CASES[case]
    starts_np = np.concatenate([[0], np.cumsum(sizes_np)]).astype(np.int32)
    sizes_np = np.append(sizes_np, n - starts_np[-1]).astype(np.int32)
    flags_np = np.append(np.ones(len(sizes_np) - 1, bool) if flags_np is None
                         else flags_np, False)        # unflagged tail
    classes = local_sort_class_plan(n, row_len, s_max=len(sizes_np))
    assert local_sort_tile_lanes(n, row_len) == 8192
    assert [l for l, _ in classes] == [32, 64]
    x = rng.integers(0, 1 << 12, n, dtype=np.uint32)  # many equal keys
    x[7] = 0xFFFFFFFF                                 # collides with the pad
    v = np.arange(n, dtype=np.int32)
    args = (jnp.asarray(starts_np), jnp.asarray(sizes_np),
            jnp.asarray(flags_np))
    k, vv = segmented_local_sort((jnp.asarray(x), jnp.asarray(v)), *args,
                                 row_len, interpret=True, classes=classes)
    want_k, want_v = x.copy(), v.copy()
    for st_, sz, fl in zip(starts_np, sizes_np, flags_np):
        if fl:
            order = st_ + np.argsort(x[st_:st_ + sz], kind="stable")
            want_k[st_:st_ + sz], want_v[st_:st_ + sz] = x[order], v[order]
    assert np.array_equal(np.asarray(k), want_k)
    assert np.array_equal(np.asarray(vv), want_v)
    assert int(local_sort_tile_count(args[1], args[2], n, row_len,
                                     classes)) == sum(per_class)


def test_local_sort_class_plan_bounds():
    """Class widths double from min_len to row_len; capacities are the
    static counting bounds (class 0: every segment slot; class i: at most
    n // (L/2 + 1) + 1 buckets can exceed half the width)."""
    from repro.kernels.ops import local_sort_class_plan

    plan_ = local_sort_class_plan(16384, 1024, s_max=341, min_len=32)
    widths = [l for l, _ in plan_]
    assert widths == [32, 64, 128, 256, 512, 1024]
    assert plan_[0][1] == 341                   # class 0: bounded by s_max
    for l, rows in plan_[1:]:
        assert rows == min(341, 16384 // (l // 2 + 1) + 1)
    # degenerate: row_len below min_len collapses to one legacy-shaped class
    assert local_sort_class_plan(100, 16, s_max=9) == ((16, 9),)


# ------------------- fused counting pass (kernels/fused.py) -----------------

def _run_fused(x, bounds, n, kpb, sc, nsid, a_max, r, vals=(), batch=None):
    """Drive one fused launch over explicit segment bounds; returns the new
    [0, n) key buffer, new value buffers and the fused next-pass histogram."""
    lo = int(sc[0])
    width = int(sc[1])
    base = jnp.asarray([b for b, _ in bounds] + [n] * (a_max - len(bounds)),
                       jnp.int32)
    size = jnp.asarray([s for _, s in bounds] + [0] * (a_max - len(bounds)),
                       jnp.int32)
    hist = np.zeros((a_max, r), np.int32)
    for i, (b, s) in enumerate(bounds):
        digs = (x[b:b + s] >> lo) & ((1 << width) - 1)
        hist[i, :] = np.bincount(digs, minlength=r)
    base_excl = (base[:, None] +
                 jnp.cumsum(jnp.asarray(hist), axis=1) - jnp.asarray(hist))
    blocks = plan.make_region_blocks(base, size, n, kpb,
                                     plan.max_region_blocks(n, kpb, a_max),
                                     batch=batch)
    (ck, cv), (ak, av) = fused.make_ping_pong(jnp.asarray(x), vals, kpb)
    nk, nv, hist_next = fused.fused_counting_pass(
        ck, cv, ak, av, jnp.asarray(sc, jnp.int32), *blocks, base_excl,
        jnp.asarray(nsid, jnp.int32), kpb=kpb, r=r, a_max=a_max,
        interpret=True)
    return (np.asarray(fused.unpad(nk, n)),
            tuple(np.asarray(fused.unpad(v, n)) for v in nv),
            np.asarray(hist_next).reshape(a_max, r))


@pytest.mark.parametrize("batch", [None, 1, 3, 8])
def test_fused_pass_partitions_segments_and_copies_gaps(rng, batch):
    """One launch partitions every active segment in place (stably, by the
    scalar-windowed digit) and copies the done gaps through untouched —
    identically for flat descriptor rows and packed (G', B) super-steps
    (including a non-dividing B with masked tail rows)."""
    n = 3000
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    bounds = [(0, 700), (1000, 1300)]       # gaps: [700,1000) and [2300,3000)
    out, _, _ = _run_fused(x, bounds, n, 256, [0, 8, 8, 8],
                           np.full(2 * 256, 2), a_max=2, r=256, batch=batch)
    want = x.copy()
    for b, s in bounds:
        seg = x[b:b + s]
        want[b:b + s] = seg[np.argsort(seg & 0xFF, kind="stable")]
    assert np.array_equal(out, want)
    assert np.array_equal(out[700:1000], x[700:1000])     # gap untouched


@pytest.mark.parametrize("batch", [None, 4])
def test_fused_pass_values_ride_and_next_histogram(rng, batch):
    """Values ride the same scatter (§4.6) and the launch returns the NEXT
    pass's digit histogram for the flagged sub-buckets (§4.3 fusion), under
    flat and packed descriptor tables alike."""
    n = 2048
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    v = np.arange(n, dtype=np.int32)
    bounds = [(0, 2048)]
    # flag the digit-3 sub-bucket as next-pass active row 0 (rest: done)
    nsid = np.full(256, 1, np.int32)
    nsid[3] = 0
    out, (ov,), hist_next = _run_fused(
        x, bounds, n, 256, [8, 8, 0, 8], nsid, a_max=1, r=256,
        vals=(jnp.asarray(v),), batch=batch)
    p = np.argsort((x >> 8) & 0xFF, kind="stable")
    assert np.array_equal(out, x[p])
    assert np.array_equal(ov, v[p])
    picked = x[((x >> 8) & 0xFF) == 3]
    assert np.array_equal(hist_next[0],
                          np.bincount(picked & 0xFF, minlength=256))


def test_fused_pass_empty_and_partial_segments(rng):
    """Zero-size descriptor rows contribute no blocks; partial-width digits
    and non-KPB-aligned segments are exact."""
    n = 500
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    bounds = [(0, 200), (350, 130)]
    out, _, _ = _run_fused(x, bounds, n, 64, [2, 5, 0, 2],
                           np.full(4 * 32, 4), a_max=4, r=32)
    want = x.copy()
    for b, s in bounds:
        seg = x[b:b + s]
        want[b:b + s] = seg[np.argsort((seg >> 2) & 31, kind="stable")]
    assert np.array_equal(out, want)


def test_fused_full_lsd_sort_composed(rng):
    """End-to-end: a complete LSD radix sort built ONLY from fused passes
    (one launch per pass, histogram carried across passes) matches np.sort."""
    from repro.core import lsd_sort
    x = rng.integers(0, 2**32, 3000, dtype=np.uint32)
    got = np.asarray(lsd_sort(jnp.asarray(x), d=8, engine="kernel", kpb=512))
    assert np.array_equal(np.sort(x), got)


def test_fused_msd_first_pass_matches_partition(rng):
    """The fused engine's MSD top-digit pass equals a stable partition by the
    top byte (same permutation, same stability)."""
    n = 2048
    x = rng.integers(0, 2**32, n, dtype=np.uint32)
    out, _, _ = _run_fused(x, [(0, n)], n, 256, [24, 8, 16, 8],
                           np.full(256, 1), a_max=1, r=256)
    want = x[np.argsort((x >> 24) & 0xFF, kind="stable")]
    assert np.array_equal(out, want)


def test_make_region_blocks_table():
    """Region table: gaps interleave actives, every position in exactly one
    block, carry resets at region firsts, copy blocks flagged inactive."""
    base = jnp.asarray([100, 400, 600, 600], jnp.int32)   # 2 padding rows
    size = jnp.asarray([150, 100, 0, 0], jnp.int32)
    n, kpb = 600, 100
    rb = plan.make_region_blocks(base, size, n, kpb,
                                 plan.max_region_blocks(n, kpb, 4))
    seg = np.asarray(rb.seg)
    off = np.asarray(rb.offset)
    cnt = np.asarray(rb.count)
    act = np.asarray(rb.active)
    rst = np.asarray(rb.reset)
    live = cnt > 0
    # every key position covered exactly once
    covered = np.zeros(n, np.int32)
    for o, c in zip(off[live], cnt[live]):
        covered[o:o + c] += 1
    assert np.array_equal(covered, np.ones(n, np.int32))
    # active blocks carry their compact segment id, copies carry a_max
    for o, c, s, a in zip(off[live], cnt[live], seg[live], act[live]):
        inside_active = any(b <= o < b + sz for b, sz in [(100, 150), (400, 100)])
        assert bool(a) == inside_active, (o, c)
        if a:
            assert s == (0 if o < 400 else 1)
        else:
            assert s == 4
    # carry resets exactly at each region's first block
    firsts = {0, 100, 250, 400, 500}        # gap0, act0 (2 blocks), gap1, act1, gap2
    assert set(off[live][rst[live] == 1].tolist()) == firsts