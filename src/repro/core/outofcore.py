"""Out-of-core pipelined sort: chunked device runs + streaming k-way merges.

The paper's second headline (§5, the 64 GB end-to-end result) sorts inputs
that exceed device memory with a chunk-sort-then-merge pipeline: the host
array streams to the device in chunks, every chunk is sorted on-device while
the next chunk's transfer is in flight, and the sorted runs are merged by a
device merge kernel.  ``oocsort`` is that pipeline in JAX terms, in two
device-memory regimes:

  1. the host-resident input (array or chunk iterator) is re-chunked into
     runs of ``chunk_elems`` keys (+ value slabs),
  2. chunk i+1 is staged with ``jax.device_put`` *before* chunk i's sort is
     consumed — JAX dispatch is asynchronous, so the upload and the sort
     overlap (double buffering, the §5 transfer/compute pipeline),
  3. each chunk is sorted by ``hybrid_sort`` — the fused single-launch
     counting-pass engine on donated ping-pong buffers (PR 1–2) — and mapped
     to order-preserving unsigned bits so runs merge bitwise,
  4. **device-resident merge** (default): ⌈log_K(runs)⌉ rounds of the
     merge-path kernel (``kernels.merge.kway_merge_round``) fuse K adjacent
     runs per group, ONE Pallas launch per round, ping-pong buffers donated
     between rounds — the whole flat run buffer lives on device,
  5. **host-spill streaming merge** (``spill_budget_bytes`` /
     ``device_slab_elems``): runs live *host-side* between rounds and every
     round streams its groups through a bounded handful of slab-sized
     device buffers (double-buffered sources, alternates and descriptor
     tables — ``_SLAB_FOOTPRINT`` models the worst case).  The
     merge-path co-ranks are computed from O(bits·K·log L) probed host
     elements per diagonal (``kernels.merge.host_coranks``), each group is cut into slab-sized
     strips of whole output tiles (``kernels.merge.spill_group_plan``), and
     strip i+1's ``device_put`` upload plus strip i−1's download are in
     flight while strip i's ``kway_merge_round`` launch runs — the chunk
     phase's double-buffering discipline extended with D2H, ONE Pallas
     launch per group-slab sweep.  Device memory stays bounded by the slab
     budget no matter how large the input,
  6. the merged keys map back to the key dtype and land on the host (the
     spill path inverts the bit bijection in numpy — no extra device trip).

Failure story (``core.faults``): every transfer and launch site above —
chunk uploads, sort launches, run downloads, strip uploads/downloads, merge
launches — runs through :func:`repro.core.faults.guarded`: an injectable
:class:`~repro.core.faults.FaultPolicy` (deterministic, seed-driven) plus a
bounded-retry :class:`~repro.core.faults.RetryPolicy`.  Host-resident runs
carry xxhash-style checksums recorded at each host crossing and verified
before consumption, so silent host-buffer corruption surfaces as
``ChecksumError`` instead of wrong output.  On exhausted retries the driver
walks a **degradation ladder** instead of crashing: halve the device slab
(floor ``tile``), then halve the merge fan-in ``kway`` (floor 2), then
re-chunk with halved ``chunk_elems`` — each rung re-validated against
``spill_budget_bytes`` so the device high-water gate still holds.  Rungs 1–2
leave the output byte-identical (the merge is grouping-invariant); the
re-chunk rung preserves key bytes always and KV bytes when keys are unique
(run boundaries move, so pair order across equal keys may change).  With
``checkpoint_dir`` the spill merge is **round-granular checkpointed**: after
each merge round the host-resident runs plus a manifest (round index, plan,
run lengths, checksums, fault-schedule state) publish atomically via
``repro.checkpoint.store``, and ``oocsort(resume_from=...)`` replays from
the last completed round byte-identically to an uninterrupted run.  A
detected corruption restores from the last checkpoint and continues.

Transfer accounting (§5, the table in ``repro.kernels``'s docstring): in the
device-resident regime every key crosses the host link exactly twice; in the
spill regime the chunk phase still crosses twice (staged up overlapped with
compute, runs gathered down overlapped with the next sort) and every spilled
merge round adds one up + one down crossing per key — ``2·N·b·(1 +
rounds_spilled)`` total, with leftover single-run groups carried host-side
for free.  Failed transfer attempts re-cross the link: their bytes are kept
out of the clean per-phase formulas and reported separately as
``OocStats.retry_link_bytes`` (so ``h2d + d2h == chunk_link + spill_link +
retry_link`` stays exact).  ``OocStats`` reports the per-phase link bytes
and the driver's device high-water mark (``device_high_water_bytes``), the
gate that fails if anyone re-materialises full runs on device.

Determinism: the merge breaks ties by (key, run, position) — in both
regimes, with strip boundaries cutting the *same* merge path the device
partition would — so runs of equal keys keep chunk order and the output is a
pure function of the input stream and the chunking — byte-identical across
engines, regimes, slab sizes and merge fan-ins, certified by the oocsort
parity wall.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.core import bijection, model
from repro.core.faults import (ChecksumError, FaultLedger, FaultPolicy,
                               RetriesExhausted, RetryPolicy, guarded,
                               tree_checksums)
from repro.core.hybrid import hybrid_sort
from repro.core.ranks import resolve_engine, resolve_interpret
from repro.kernels import merge as kmerge
from repro.kernels.fused import buffer_length, pad_length

# Modeled peak device working set, in units of one chunk / one slab payload.
# Chunk phase: staged chunks i and i+1, the sort's ping-pong pair (2x), the
# sorted run i, plus (spill) run i-1 with its working set still in flight —
# the ledger peaks under 10x one chunk's bytes, and the spill clamp starts
# from this reservation (then checks the engine-aware model below):
_CHUNK_FOOTPRINT = 12


def _chunk_working_bytes(chunk_elems: int, elem_bytes: int, cfg, engine,
                         key_dtype) -> int:
    """Modeled device working set of one chunk sort (its ping-pong pair).

    The kernel engine's donated ping-pong buffers are
    ``buffer_length(n, kpb)`` long — whole 128-key lines plus one block
    window — which dwarfs the raw chunk bytes
    for small chunks under a large ``kpb``; the jnp engines work in n-sized
    buffers.  Mirrors ``hybrid_sort``'s cfg/engine resolution so the spill
    budget clamp and the ledger charge what the sort actually allocates.
    """
    if resolve_engine(engine) == "kernel":
        kpb = (cfg or model.default_config(
            bijection.key_bits(key_dtype) // 8)).kpb
        return 2 * buffer_length(chunk_elems, kpb) * elem_bytes
    return 2 * chunk_elems * elem_bytes


def _chunk_peak_bytes(chunk_elems: int, elem_bytes: int, cfg, engine,
                      key_dtype) -> int:
    """Modeled chunk-phase peak: staged chunks i-1/i/i+1, sorted runs i-1/i,
    and two sort working sets in flight (the spill pipeline's worst case)."""
    return 5 * chunk_elems * elem_bytes + 2 * _chunk_working_bytes(
        chunk_elems, elem_bytes, cfg, engine, key_dtype)
# Spill merge phase: padded source slabs and alternate slabs for strips i-1,
# i and i+1 plus one exact upload in transit — under 7x one padded slab; the
# slab derivation starts from this reservation and then shrinks the slab
# until the modeled worst case (_spill_peak_bytes, which also counts the
# pad tile and the scalar-prefetch tables the reservation alone misses at
# small budgets) provably fits:
_SLAB_FOOTPRINT = 10


def _spill_peak_bytes(slab: int, tile: int, elem_bytes: int,
                      kway: int) -> int:
    """Modeled worst-case live device bytes of the strip stream.

    Dominates the ledger's peak: at most 5 padded slabs live at once (source
    and alternate for strips i-1 and i, plus strip i+1's source) — modeled
    as 6 — plus strip i+1's exact upload still in transit and three strips'
    scalar-prefetch table sets.
    """
    bufsize = slab + tile                       # pad_length for tile-aligned
    g = slab // tile
    table_bytes = (2 * g + 2 * g * kway) * np.dtype(np.int32).itemsize
    return (6 * bufsize + slab) * elem_bytes + 3 * table_bytes


class OocStats(NamedTuple):
    num_chunks: int      # sorted device runs the input was split into
    merge_rounds: int    # merge-kernel rounds executed (this process)
    chunk_elems: int     # device chunk capacity the plan used (post-ladder)
    h2d_bytes: int       # host->device payload bytes (incl. failed attempts)
    d2h_bytes: int       # device->host payload bytes (incl. failed attempts)
    device_high_water_bytes: int = 0   # driver's modeled peak device bytes
    chunk_link_bytes: int = 0   # chunk-phase crossings: 2·N·(b+v)
    spill_link_bytes: int = 0   # spill-round crossings: +2·N·(b+v) per round
    rounds_spilled: int = 0     # rounds streamed through host-side runs
    spill_slab_elems: int = 0   # device slab capacity (0: device-resident)
    retries: int = 0            # guarded ops re-attempted after a fault
    faults_injected: int = 0    # faults the FaultPolicy fired (all kinds)
    degradations: int = 0       # ladder rungs walked (slab/kway/re-chunk)
    checksum_failures: int = 0  # host-buffer corruptions detected
    rounds_checkpointed: int = 0  # merge rounds published to the store
    retry_link_bytes: int = 0   # extra link bytes of failed/aborted attempts
    chunk_passes_executed: int = 0  # counting passes the chunk sorts ran
                                    # (entropy-adaptive: <= nominal ⌈k/d⌉
                                    # per chunk; 0 on resumed runs — the
                                    # chunk phase ran in another process)


class _DeviceLedger:
    """Driver-side model of live device bytes (the high-water gate).

    Tracks the buffers the oocsort driver itself stages, allocates and
    releases (chunks, sort working sets, runs, slabs); the high-water mark is
    what the spill regression test pins under ``spill_budget_bytes``, so any
    change that re-materialises O(N) on device blows it up.  Recovery resets
    ``live`` to the pre-attempt level (an aborted attempt's buffers drop)
    while ``high`` keeps the true peak.
    """

    def __init__(self):
        self.live = 0
        self.high = 0

    def alloc(self, nbytes: int) -> None:
        self.live += int(nbytes)
        self.high = max(self.high, self.live)

    def free(self, nbytes: int) -> None:
        self.live -= int(nbytes)


def _as_stream(reader, values):
    """Normalise the input to a stream of (keys, values-or-None) pieces."""
    if hasattr(reader, "shape") and hasattr(reader, "dtype"):
        yield reader, values
        return
    if values is not None:
        raise ValueError("with an iterator reader, pass values inline as "
                         "(keys, values) tuples")
    for item in reader:
        if isinstance(item, tuple):
            yield item
        else:
            yield item, None


def _rechunk(stream, chunk_elems: int):
    """Re-cut a stream of (keys, values) pieces into device-sized chunks.

    Returns ``(chunks, treedef, key_dtype, empty_leaves)`` where each chunk
    is ``(keys, value_leaves)`` with ``len(keys) <= chunk_elems`` (only the
    last chunk may be short) and ``empty_leaves`` are zero-length prototypes
    of the value leaves.  Host-side only: pieces are numpy views/copies.
    Validation errors name the offending input chunk index so a bad piece
    deep inside a stream is findable.
    """
    buf_k, buf_v = [], []
    chunks = []
    treedef = None
    key_dtype = None
    empty_leaves = ()
    pending = 0

    def emit(upto):
        nonlocal buf_k, buf_v, pending
        k = np.concatenate(buf_k) if len(buf_k) > 1 else buf_k[0]
        vs = [np.concatenate(c) if len(c) > 1 else c[0] for c in buf_v]
        chunks.append((k[:upto], tuple(v[:upto] for v in vs)))
        buf_k = [k[upto:]] if upto < k.shape[0] else []
        buf_v = [[v[upto:]] for v in vs] if upto < k.shape[0] else \
            [[] for _ in vs]
        pending -= upto

    for ci, (keys, vals) in enumerate(stream):
        keys = np.asarray(keys)
        if keys.ndim != 1:
            raise ValueError(f"chunk {ci}: oocsort expects 1-D key chunks")
        leaves, td = jax.tree.flatten(vals)
        leaves = [np.asarray(v) for v in leaves]
        if treedef is None:
            treedef, key_dtype = td, keys.dtype
            empty_leaves = tuple(v[:0] for v in leaves)
            buf_v = [[] for _ in leaves]
        elif td != treedef:
            raise ValueError(f"chunk {ci}: inconsistent value structure "
                             f"across chunks ({td} vs {treedef})")
        if keys.dtype != key_dtype:
            raise ValueError(f"chunk {ci}: inconsistent key dtype across "
                             f"chunks: {keys.dtype} vs {key_dtype}")
        if any(v.dtype != p.dtype for v, p in zip(leaves, empty_leaves)):
            raise ValueError(f"chunk {ci}: inconsistent value dtypes across "
                             f"chunks")
        if any(v.ndim != 1 for v in leaves):
            raise ValueError(f"chunk {ci}: oocsort value leaves must be 1-D "
                             f"(the merge kernel moves flat per-key slabs)")
        if any(v.shape[0] != keys.shape[0] for v in leaves):
            raise ValueError(f"chunk {ci}: value leaves must match the key "
                             f"length")
        if keys.shape[0] == 0:
            continue
        buf_k.append(keys)
        for c, v in zip(buf_v, leaves):
            c.append(v)
        pending += keys.shape[0]
        while pending >= chunk_elems:
            emit(chunk_elems)
    if pending:
        emit(pending)
    return chunks, treedef, key_dtype, empty_leaves


def _split_chunks(chunks, chunk_elems: int):
    """Re-split host chunks to a smaller capacity (budget clamp / ladder)."""
    out = []
    for k, vs in chunks:
        for o in range(0, k.shape[0], chunk_elems):
            out.append((k[o:o + chunk_elems],
                        tuple(v[o:o + chunk_elems] for v in vs)))
    return out


def _chunk_nbytes(chunk) -> int:
    return chunk[0].nbytes + sum(v.nbytes for v in chunk[1])


@functools.partial(jax.jit, static_argnames=("cfg", "engine", "interpret"))
def _sort_chunk(keys, leaves, cfg, engine, interpret):
    """Sort one staged chunk; emit the run as order-preserving unsigned bits.

    The trailing element is the executed counting-pass count of the chunk
    sort (entropy-adaptive schedules execute <= the nominal ⌈k/d⌉).
    """
    if leaves:
        sk, sv, st = hybrid_sort(keys, leaves, cfg=cfg, engine=engine,
                                 interpret=interpret, return_stats=True)
    else:
        sk, st = hybrid_sort(keys, cfg=cfg, engine=engine,
                             interpret=interpret, return_stats=True)
        sv = ()
    return bijection.to_ordered_bits(sk), sv, st.counting_passes


@functools.partial(jax.jit, static_argnames=("lens", "kway", "tile", "n",
                                             "interpret"),
                   donate_argnums=(2, 3))
def merge_round(src_keys, src_vals, alt_keys, alt_vals, *, lens, kway: int,
                tile: int, n: int, interpret: bool):
    """One k-way merge round: diagonal partition + ONE merge-kernel launch.

    ``lens`` is the static tuple of current run lengths; groups of up to
    ``kway`` adjacent runs merge into one run each.  The partition tables are
    sort-free binary searches; the data movement is the single
    ``kway_merge_round`` launch (the per-round census gate).  The alternate
    buffers are donated.
    """
    tables = kmerge.merge_path_partition(src_keys, lens, kway, tile)
    return kmerge.kway_merge_round(src_keys, src_vals, alt_keys, alt_vals,
                                   *tables, kway=kway, tpb=tile, n=n,
                                   interpret=interpret)


class _Job(NamedTuple):
    """One slab strip of one merge group, with its host source/target runs."""
    strip: kmerge.SpillStrip
    kruns: list           # host key runs of the group (np, unsigned bits)
    vruns: list           # host value runs: per run a tuple of leaves
    mk: np.ndarray        # merged host key run being assembled
    mv: Tuple[np.ndarray, ...]


class _RechunkEscalation(Exception):
    """The merge ladder's last rung: restart the pipeline with smaller chunks.

    Raised by the spill merge loop once slab and kway are already at their
    floors; the driver's outer attempt loop catches it, halves
    ``chunk_elems``, re-splits the host chunks and reruns.  Carries the
    :class:`RetriesExhausted` that exhausted the ladder for re-raising when
    re-chunking is impossible (min chunk size, or a resumed run with no
    chunks to re-split).
    """

    def __init__(self, cause: RetriesExhausted):
        super().__init__(str(cause))
        self.cause = cause


def _verify_runs(keys_h, vals_h, checksums) -> None:
    """Verify every host run against its recorded checksums (pre-consume)."""
    for i, (k, vs) in enumerate(zip(keys_h, vals_h)):
        if tree_checksums((k,) + tuple(vs)) != tuple(checksums[i]):
            raise ChecksumError(
                f"host run {i} no longer matches its recorded checksum "
                f"(corrupted while host-resident or in transit)")


def _run_checksums(keys_h, vals_h):
    return [tree_checksums((k,) + tuple(vs))
            for k, vs in zip(keys_h, vals_h)]


def _flat_run_arrays(keys_h, vals_h):
    out = list(keys_h)
    for vs in vals_h:
        out.extend(vs)
    return out


# --------------------- round-granular checkpointing -------------------------

def _dictkey(keystr: str) -> str:
    # jax keystr for a dict entry is "['name']"
    return keystr[2:-2]


def _save_round_checkpoint(directory: str, round_idx: int, keys_h, vals_h,
                           checksums, meta: dict, keep: int = 3) -> None:
    """Publish one merge round atomically via ``repro.checkpoint.store``.

    The tree is a flat dict — run key buffers ``k####``, value leaves
    ``v####_#`` and a JSON ``meta`` leaf (round index, merge plan, run
    lengths, per-run checksums, fault-schedule state) — so a resuming
    process can rebuild everything via ``store.restore_blind`` with no live
    pytree to mirror.  Host crossings: zero — the runs already live
    host-side in the spill regime; the cost is disk only.
    """
    meta = dict(meta, round=round_idx,
                run_lens=[int(k.shape[0]) for k in keys_h],
                checksums=[list(cs) for cs in checksums])
    tree = {"meta": np.frombuffer(json.dumps(meta).encode(), np.uint8)}
    for i, k in enumerate(keys_h):
        tree[f"k{i:04d}"] = k
        for j, v in enumerate(vals_h[i]):
            tree[f"v{i:04d}_{j}"] = v
    store.save_checkpoint(directory, round_idx, tree, keep=keep)


def _load_round_checkpoint(directory: str, round_idx: Optional[int] = None):
    """Load the newest (or a specific) checkpointed round.

    Returns ``(meta, keys_h, vals_h)`` with writable host arrays, after
    re-verifying the oocsort-level checksums on top of the store's own
    content hashes.
    """
    if round_idx is None:
        round_idx = store.latest_step(directory)
        if round_idx is None:
            raise ValueError(f"resume_from={directory!r}: no checkpointed "
                             f"rounds found")
    flat = {_dictkey(p): a
            for p, a in store.restore_blind(directory, round_idx).items()}
    meta = json.loads(bytes(flat.pop("meta")))
    nruns = len(meta["run_lens"])
    nleaves = meta["num_leaves"]
    keys_h = [np.array(flat[f"k{i:04d}"]) for i in range(nruns)]
    vals_h = [tuple(np.array(flat[f"v{i:04d}_{j}"]) for j in range(nleaves))
              for i in range(nruns)]
    _verify_runs(keys_h, vals_h, meta["checksums"])
    return meta, keys_h, vals_h


# --------------------- chunk phase ------------------------------------------

def _chunk_phase(chunks, *, spill, cfg, engine, interpret, key_dtype,
                 elem_bytes, ledger, faults, retry, faultlog, acct,
                 make_writable):
    """Double-buffered chunk staging + sorts, §5's upload/sort overlap.

    Every ``device_put`` goes through the ``chunk_upload`` fault site, every
    sort through ``sort_launch``, and (spill regime) every run download
    through ``run_download``.  ``acct`` accumulates the phase's clean link
    bytes so an aborted attempt can fold them into the retry ledger.
    Returns ``(runs, passes)``: the runs — device-resident ``(keys,
    leaves)`` pairs, or host numpy pairs in the spill regime — plus the
    per-chunk executed counting-pass counts (device scalars).
    """
    num_chunks = len(chunks)

    def upload(chunk, nbytes):
        out = guarded("chunk_upload", jax.device_put, chunk, policy=faults,
                      retry=retry, ledger=faultlog, cost_bytes=nbytes,
                      direction="h2d")
        ledger.alloc(nbytes)
        acct["up"] += nbytes
        return out

    def land(p):
        run, nbytes, held = p

        def download():
            k = np.asarray(run[0])
            vs = tuple(np.asarray(v) for v in run[1])
            if make_writable:
                k = k if k.flags.writeable else np.array(k)
                vs = tuple(v if v.flags.writeable else np.array(v)
                           for v in vs)
            return k, vs

        out = guarded("run_download", download, policy=faults, retry=retry,
                      ledger=faultlog, cost_bytes=nbytes, direction="d2h")
        acct["down"] += nbytes
        ledger.free(held)
        return out

    staged_bytes = _chunk_nbytes(chunks[0])
    staged = upload(chunks[0], staged_bytes)
    runs = []
    passes = []
    pending = None     # spill: (device run, run bytes, working bytes) to D2H
    for i in range(num_chunks):
        nxt = nxt_bytes = None
        if i + 1 < num_chunks:
            nxt_bytes = _chunk_nbytes(chunks[i + 1])
            nxt = upload(chunks[i + 1], nxt_bytes)       # stage i+1 ...
        ws = _chunk_working_bytes(chunks[i][0].shape[0], elem_bytes, cfg,
                                  engine, key_dtype)
        ledger.alloc(ws)                                 # sort ping-pong model
        run = guarded("sort_launch", _sort_chunk, *staged, cfg, engine,
                      interpret, policy=faults, retry=retry,
                      ledger=faultlog)                   # ... sort i
        passes.append(run[2])
        run = run[:2]
        ledger.alloc(staged_bytes)                       # the sorted run
        if spill:
            if pending is not None:                      # ... download run i-1
                runs.append(land(pending))
            pending = (run, staged_bytes, 2 * staged_bytes + ws)
        else:
            runs.append(run)
            ledger.free(staged_bytes + ws)               # staged + working set
        staged, staged_bytes = nxt, nxt_bytes
    if spill:
        runs.append(land(pending))
    return runs, passes


# --------------------- host-spill streaming merge ---------------------------

def _spill_round(keys_h, vals_h, *, kway: int, tile: int, slab: int,
                 interpret: bool, ledger: _DeviceLedger, faults, retry,
                 faultlog: FaultLedger, elem_bytes: int, acct: dict):
    """ONE host-spilled merge round: stream every group through device slabs.

    ``keys_h``/``vals_h`` are the host-resident sorted runs (unsigned bits).
    The round plans slab-sized strips for every multi-run group (single-run
    leftovers carry over host-side for free), then streams the strip list
    with the chunk phase's double-buffering discipline extended with D2H:
    strip i+1's upload and strip i−1's download are in flight while strip
    i's ``kway_merge_round`` launch runs.  Strip uploads, merge launches
    and strip downloads are guarded fault sites; ``acct`` accumulates the
    round's clean link bytes so an aborted round folds into the retry
    ledger.  Returns the next round's ``(keys, values)`` run lists; the
    device footprint never exceeds a handful of slabs (see
    ``_SLAB_FOOTPRINT``), which is what makes the §5 beyond-device-memory
    claim literal.
    """
    udtype = keys_h[0].dtype
    sentinel = udtype.type(~np.zeros((), udtype))
    bufsize = pad_length(slab, tile)

    next_k, next_v, jobs = [], [], []
    for grp in kmerge.merge_groups(list(range(len(keys_h))), kway):
        if len(grp) == 1:               # leftover run: carried for free
            next_k.append(keys_h[grp[0]])
            next_v.append(vals_h[grp[0]])
            continue
        kruns = [keys_h[j] for j in grp]
        vruns = [vals_h[j] for j in grp]
        glen = sum(r.shape[0] for r in kruns)
        mk = np.empty(glen, udtype)
        mv = tuple(np.empty(glen, v.dtype) for v in vruns[0])
        next_k.append(mk)
        next_v.append(mv)
        for strip in kmerge.spill_group_plan(kruns, kway, tile, slab):
            jobs.append(_Job(strip, kruns, vruns, mk, mv))

    def stage(job):
        strip, kruns, vruns = job.strip, job.kruns, job.vruns
        K = len(kruns)
        wins = [slice(strip.win_lo[r], strip.win_lo[r] + strip.win_len[r])
                for r in range(K)]
        up_k = np.concatenate([kruns[r][wins[r]] for r in range(K)])
        up_v = tuple(np.concatenate([vruns[r][li][wins[r]]
                                     for r in range(K)])
                     for li in range(len(vruns[0])))
        up_bytes = up_k.nbytes + sum(v.nbytes for v in up_v)

        def upload():
            dk = jax.device_put(up_k)
            dv = tuple(jax.device_put(v) for v in up_v)
            ts = tuple(jnp.asarray(t) for t in strip.tables)
            return dk, dv, ts

        dev_k, dev_v, tabs = guarded("slab_upload", upload, policy=faults,
                                     retry=retry, ledger=faultlog,
                                     cost_bytes=up_bytes, direction="h2d")
        ledger.alloc(up_bytes)
        acct["up"] += up_bytes
        tab_bytes = sum(t.nbytes for t in strip.tables)
        ledger.alloc(tab_bytes)
        # pad the exact upload out to the fixed slab (sentinel keys, zero
        # values) so every strip of a round shares one kernel signature
        pad = bufsize - strip.out_len
        slab_k = jnp.concatenate([dev_k, jnp.full((pad,), sentinel, udtype)])
        slab_v = tuple(jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
                       for v in dev_v)
        slab_bytes = slab_k.nbytes + sum(v.nbytes for v in slab_v)
        ledger.alloc(slab_bytes)
        ledger.free(up_bytes)
        return slab_k, slab_v, tabs, slab_bytes + tab_bytes

    def launch(staged):
        slab_k, slab_v, tabs, held = staged

        def fire():
            alt_k = jnp.full((bufsize,), sentinel, udtype)
            alt_v = tuple(jnp.zeros((bufsize,), v.dtype) for v in slab_v)
            ab = alt_k.nbytes + sum(v.nbytes for v in alt_v)
            return kmerge.kway_merge_round(
                slab_k, slab_v, alt_k, alt_v, *tabs, kway=kway, tpb=tile,
                n=slab, interpret=interpret), ab

        (out_k, out_v), alt_bytes = guarded(
            "merge_launch", fire, policy=faults, retry=retry, ledger=faultlog)
        ledger.alloc(alt_bytes)
        return out_k, out_v, held + alt_bytes

    def collect(launched, job):
        out_k, out_v, held = launched
        lo, sl = job.strip.out_lo, job.strip.out_len

        def download():
            kb = np.asarray(out_k[:sl])
            return kb, [np.asarray(v[:sl]) for v in out_v]

        kb, vbs = guarded("slab_download", download, policy=faults,
                          retry=retry, ledger=faultlog,
                          cost_bytes=sl * elem_bytes, direction="d2h")
        job.mk[lo:lo + sl] = kb
        down = kb.nbytes
        for li, vb in enumerate(vbs):
            job.mv[li][lo:lo + sl] = vb
            down += vb.nbytes
        acct["down"] += down
        ledger.free(held)

    staged = stage(jobs[0])
    prev = None
    for i, job in enumerate(jobs):
        nxt = stage(jobs[i + 1]) if i + 1 < len(jobs) else None      # up i+1
        launched = launch(staged)                                    # run i
        if prev is not None:
            collect(*prev)                                           # down i-1
        prev = (launched, job)
        staged = nxt
    collect(*prev)
    return next_k, next_v


def _merge_spilled(keys_h, vals_h, *, round_idx: int, kway: int, tile: int,
                   slab: int, budget: Optional[int], elem_bytes: int,
                   interpret: bool, ledger: _DeviceLedger, faults, retry,
                   faultlog: FaultLedger, checkpoint_dir: Optional[str],
                   checkpoint_every: int, meta_base: dict,
                   checksums=None, save_incoming: bool = True,
                   checksummed: bool = True):
    """The spill merge's round loop: verify → merge → checksum → checkpoint.

    Owns the merge half of the degradation ladder (slab halving to the
    ``tile`` floor, then kway halving to 2 — both output-byte-preserving;
    the re-chunk rung escalates via :class:`_RechunkEscalation`) and the
    recovery path for detected host corruption (restore the last published
    round and continue).  Returns ``(keys, vals, rounds_done, up, down,
    kway, slab)``.
    """
    up_total = down_total = 0
    rounds_done = 0
    if checksums is None and checksummed:
        checksums = _run_checksums(keys_h, vals_h)
    last_ckpt = None

    def save(idx):
        nonlocal last_ckpt
        _save_round_checkpoint(
            checkpoint_dir, idx, keys_h, vals_h, checksums,
            dict(meta_base, kway=kway, tile=tile, slab=slab,
                 fault_state=faults.state() if faults is not None else {}))
        faultlog.rounds_checkpointed += 1
        last_ckpt = idx

    if checkpoint_dir is not None:
        if save_incoming:
            save(round_idx)        # round-0 / adopted-state checkpoint
        else:
            last_ckpt = round_idx  # resumed from this very round
    if faults is not None and len(keys_h) > 1:
        faults.maybe_corrupt(_flat_run_arrays(keys_h, vals_h))

    while len(keys_h) > 1:
        live0 = ledger.live
        acct = {"up": 0, "down": 0}
        try:
            if checksummed:
                _verify_runs(keys_h, vals_h, checksums)
            nk, nv = _spill_round(
                keys_h, vals_h, kway=kway, tile=tile, slab=slab,
                interpret=interpret, ledger=ledger, faults=faults,
                retry=retry, faultlog=faultlog, elem_bytes=elem_bytes,
                acct=acct)
        except ChecksumError:
            faultlog.checksum_failures += 1
            ledger.live = live0
            faultlog.retry_h2d_bytes += acct["up"]
            faultlog.retry_d2h_bytes += acct["down"]
            if last_ckpt is None:
                raise
            meta, keys_h, vals_h = _load_round_checkpoint(
                checkpoint_dir, last_ckpt)
            checksums = [tuple(cs) for cs in meta["checksums"]]
            continue
        except RetriesExhausted as e:
            ledger.live = live0
            faultlog.retry_h2d_bytes += acct["up"]
            faultlog.retry_d2h_bytes += acct["down"]
            if slab > tile:                       # rung 1: halve the slab
                slab = max(tile, (slab // 2) - ((slab // 2) % tile))
                assert budget is None or _spill_peak_bytes(
                    slab, tile, elem_bytes, kway) <= budget
            elif kway > 2:                        # rung 2: halve the fan-in
                kway = max(2, kway // 2)
            else:                                 # rung 3: re-chunk smaller
                raise _RechunkEscalation(e)
            faultlog.degradations += 1
            continue
        up_total += acct["up"]
        down_total += acct["down"]
        keys_h, vals_h = nk, nv
        round_idx += 1
        rounds_done += 1
        if checksummed:
            checksums = _run_checksums(keys_h, vals_h)
        if checkpoint_dir is not None and len(keys_h) > 1 and \
                round_idx % checkpoint_every == 0:
            save(round_idx)
        if faults is not None and len(keys_h) > 1:
            faults.maybe_corrupt(_flat_run_arrays(keys_h, vals_h))
    return (keys_h[0], vals_h[0], rounds_done, up_total, down_total,
            kway, slab)


def oocsort(reader, chunk_elems: int, values: Any = None,
            cfg: Optional[model.SortConfig] = None,
            engine: Optional[str] = None, interpret: Optional[bool] = None,
            kway: int = 4, tile: int = 256, return_stats: bool = False,
            spill_budget_bytes: Optional[int] = None,
            device_slab_elems: Optional[int] = None,
            faults: Optional[FaultPolicy] = None,
            retry: Optional[RetryPolicy] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 1,
            resume_from: Optional[str] = None,
            values_like: Any = None,
            compress: bool = False):
    """Sort a host-resident array (or chunk stream) larger than one device run.

    ``reader`` is a 1-D numpy array, an iterable of 1-D key chunks (all of
    one dtype), or an iterable of ``(keys, values)`` chunk tuples;
    ``values`` (array-input only) is a 1-D array or pytree of 1-D arrays
    permuted alongside the keys (flat per-key slabs — the merge kernel's
    payload layout).  The
    input is cut into runs of ``chunk_elems`` keys; each run is sorted
    on-device by ``hybrid_sort`` (``cfg``/``engine`` as there) while the next
    chunk's ``jax.device_put`` is in flight, and the runs are merged by
    ⌈log_``kway``⌉ rounds of the merge-path kernel, one Pallas launch per
    round on donated ping-pong buffers.

    Setting ``spill_budget_bytes`` (a hard device-byte budget) and/or
    ``device_slab_elems`` (an explicit slab capacity, floored to a multiple
    of ``tile``) switches the merge phase to the **host-spill streaming**
    regime: runs live host-side between rounds and every round streams
    through a bounded handful of slab-sized device buffers (double-buffered
    uploads/downloads, one kernel launch per slab sweep; worst case modeled
    by ``_spill_peak_bytes``), so device memory stays bounded by the budget
    no matter how large the input.  When a budget is given,
    ``chunk_elems`` is clamped so the chunk phase fits it too, and the
    returned ``OocStats.device_high_water_bytes`` stays under it.

    Resilience (``core.faults``): pass ``faults`` (a deterministic
    :class:`FaultPolicy`) and ``retry`` (a :class:`RetryPolicy`, default 3
    bounded retries with capped backoff) to run every transfer and launch
    site through fault injection + retries; exhausted retries walk the
    degradation ladder (slab → kway → re-chunk) instead of crashing.  With
    any of ``faults``/``retry``/``checkpoint_dir`` set, host-resident runs
    are checksummed at each crossing and verified before consumption.
    ``checkpoint_dir`` (spill regime only) publishes the runs + a manifest
    after every ``checkpoint_every``-th merge round;
    ``oocsort(None, 0, resume_from=dir)`` resumes from the newest published
    round and replays to a byte-identical result, adopting the plan (kway/
    tile/slab/dtype) recorded in the manifest.  On resume, pass
    ``values_like`` (a structure prototype) to get the value pytree back in
    its original shape; otherwise a single value leaf is returned bare and
    multiple leaves as a tuple.

    ``compress=True`` packs the keys' live bits into the smallest unsigned
    carrier **host-side** before anything touches the device (bit positions
    constant across the whole input contribute no ordering information;
    ``core.bijection.CompressionPlan``).  Every downstream byte count —
    chunk uploads, sort ping-pong, merge slabs, spill budgets, checkpoint
    manifests — then moves b_eff-sized keys, and uint64 inputs with <= 32
    live bits sort without ``jax_enable_x64``.  The output is decoded back
    to the original dtype and is byte-identical to the uncompressed sort.

    Returns host numpy arrays: ``sorted_keys``, or ``(sorted_keys,
    permuted_values)`` when values were given; append an :class:`OocStats`
    when ``return_stats``.  Pair movement is consistent but — like
    ``hybrid_sort`` — not stable across equal keys *within* a chunk; across
    chunks the merge keeps run order (ties break by run index).
    """
    if checkpoint_every < 1:
        raise ValueError("checkpoint_every must be >= 1")
    interpret = resolve_interpret(interpret)
    faultlog = FaultLedger()
    ledger = _DeviceLedger()

    # --- resume: the checkpoint manifest is the plan -----------------------
    if resume_from is not None:
        return _resume(resume_from, spill_budget_bytes=spill_budget_bytes,
                       interpret=interpret, faults=faults, retry=retry,
                       checkpoint_dir=checkpoint_dir,
                       checkpoint_every=checkpoint_every,
                       values_like=values_like, return_stats=return_stats,
                       faultlog=faultlog, ledger=ledger)

    if chunk_elems < 1:
        raise ValueError("chunk_elems must be >= 1")
    if kway < 2:
        raise ValueError("kway must be >= 2")
    if tile < 8:
        raise ValueError("tile must be >= 8")
    spill = spill_budget_bytes is not None or device_slab_elems is not None
    if spill_budget_bytes is not None and spill_budget_bytes < 1:
        raise ValueError("spill_budget_bytes must be >= 1")
    if checkpoint_dir is not None and not spill:
        raise ValueError(
            "checkpoint_dir requires the host-spill regime (set "
            "spill_budget_bytes or device_slab_elems): round-granular "
            "checkpoints publish host-resident runs, which only exist there")

    chunks, treedef, key_dtype, empty_leaves = _rechunk(
        _as_stream(reader, values), chunk_elems)
    had_values = treedef is not None and treedef.num_leaves > 0

    def finish(keys_np, leaves_np, stats):
        out = (keys_np,) if not had_values else \
            (keys_np, jax.tree.unflatten(treedef, list(leaves_np)))
        if return_stats:
            out = out + (stats,)
        return out[0] if len(out) == 1 else out

    if key_dtype is None:
        raise ValueError("empty iterator reader: yield at least one "
                         "(possibly empty) chunk to fix the dtype")

    # --- compressed-key mode: pack live bits host-side ---------------------
    # The global OR/AND reduce over the ordered bits of every chunk finds the
    # bit positions that vary anywhere in the input; packing them out is
    # order-preserving (two distinct keys first differ at a live bit), so the
    # whole pipeline sorts/merges the packed carrier and only the two decode
    # sites below see the original dtype again.  Done before the spill plan
    # so elem_bytes, slab sizing and the chunk clamp all model packed keys.
    orig_key_dtype = key_dtype
    cplan = None
    if compress and chunks:
        bits = bijection.key_bits(key_dtype)
        orv, andv = 0, (1 << bits) - 1
        for ckeys, _ in chunks:
            ub = bijection.to_ordered_bits_np(ckeys)
            if ub.size:
                orv |= int(np.bitwise_or.reduce(ub))
                andv &= int(np.bitwise_and.reduce(ub))
        mask = orv ^ andv
        cplan = bijection.CompressionPlan(mask=mask, dead=andv & ~mask,
                                          source_bits=bits)
        chunks = [(bijection.pack_ordered_bits_np(
                       bijection.to_ordered_bits_np(ckeys), cplan), vs)
                  for ckeys, vs in chunks]
        key_dtype = np.dtype(bijection.packed_carrier_dtype(cplan))

    def decode_np(ubits):
        if cplan is not None:
            ubits = bijection.unpack_ordered_bits_np(ubits, cplan)
        return bijection.from_ordered_bits_np(ubits, orig_key_dtype)

    # --- spill plan: slab capacity + chunk clamp from the device budget ----
    # (validated before the empty-input return so a misconfigured slab or
    # budget fails input-independently, not just on the first non-empty run)
    elem_bytes = np.dtype(key_dtype).itemsize + \
        sum(v.dtype.itemsize for v in empty_leaves)
    slab = 0
    if spill:
        slab = device_slab_elems
        if slab is not None:
            slab -= slab % tile
            if slab < tile:
                raise ValueError("device_slab_elems must be >= tile")
        if spill_budget_bytes is not None:
            # the real constraint is the modeled worst case (incl. pad tile
            # + descriptor tables, which matter at tight budgets): start
            # from the explicit slab, or the footprint reservation when
            # deriving, and shrink tile by tile until the peak provably fits
            if slab is None:
                slab = spill_budget_bytes // (_SLAB_FOOTPRINT * elem_bytes)
                slab -= slab % tile
            while slab >= tile and _spill_peak_bytes(
                    slab, tile, elem_bytes, kway) > spill_budget_bytes:
                slab -= tile
            if slab < tile:
                raise ValueError(
                    f"spill_budget_bytes={spill_budget_bytes} too small: "
                    f"need >= "
                    f"{_spill_peak_bytes(tile, tile, elem_bytes, kway)} "
                    f"for tile={tile} (worst-case stream of one-tile slabs)")
            # largest chunk whose engine-aware peak (kernel chunks allocate
            # buffer_length(n, kpb)-sized ping-pong pairs) fits the budget
            peak = lambda c: _chunk_peak_bytes(c, elem_bytes, cfg, engine,
                                               key_dtype)
            if peak(1) > spill_budget_bytes:
                raise ValueError(
                    f"spill_budget_bytes={spill_budget_bytes} too small for "
                    f"the chunk phase: even a 1-element chunk sort models "
                    f"{peak(1)} device bytes (engine "
                    f"{resolve_engine(engine)!r}; the kernel engine pads to "
                    f"a cfg.kpb block window — pass a smaller-kpb cfg)")
            lo = 1
            hi = max(1, spill_budget_bytes // (_CHUNK_FOOTPRINT * elem_bytes))
            while peak(hi) <= spill_budget_bytes and hi < chunk_elems:
                hi = min(2 * hi, chunk_elems)    # the reservation start is
                # conservative for the jnp engines; grow to the model's edge
            while lo < hi:
                mid = (lo + hi + 1) // 2
                lo, hi = (mid, hi) if peak(mid) <= spill_budget_bytes \
                    else (lo, mid - 1)
            if lo < chunk_elems:
                chunk_elems = lo
                chunks = _split_chunks(chunks, chunk_elems)

    if not chunks:
        stats = OocStats(0, 0, chunk_elems, 0, 0,
                         spill_slab_elems=slab if spill else 0)
        return finish(np.empty((0,), key_dtype), empty_leaves, stats)

    k = bijection.key_bits(key_dtype)
    if k > 32 and not jax.config.jax_enable_x64:
        raise RuntimeError("64-bit keys require jax_enable_x64")
    if not jax.config.jax_enable_x64:
        # leaf dtypes are chunk-uniform (_rechunk), so one chunk decides;
        # device_put would otherwise silently truncate 64-bit payloads
        for v in chunks[0][1]:
            if v.dtype.itemsize > 4:
                raise RuntimeError(
                    f"64-bit value leaves ({v.dtype}) require "
                    "jax_enable_x64")

    n = sum(c[0].shape[0] for c in chunks)
    make_writable = faults is not None and faults.corrupts
    meta_base = {"key_dtype": np.dtype(key_dtype).str, "n": n,
                 "num_leaves": len(empty_leaves),
                 "value_dtypes": [v.dtype.str for v in empty_leaves]}
    if cplan is not None:
        # manifest runs hold PACKED bits; record the plan so resume decodes
        meta_base["compress"] = {"mask": cplan.mask, "dead": cplan.dead,
                                 "source_bits": cplan.source_bits,
                                 "orig_dtype": np.dtype(orig_key_dtype).str}

    # --- attempt loop: the degradation ladder's restart point --------------
    # Each attempt runs the chunk phase and the merge phase under the current
    # (chunk_elems, kway, slab) plan.  Merge-internal rungs (slab, kway) are
    # walked inside _merge_spilled without restarting; chunk-phase failures
    # and the ladder's re-chunk rung land here and restart with smaller
    # chunks (ledger.live resets, the high-water mark and fault counters
    # persist — the fault schedule never replays).
    while True:
        ledger.live = 0
        num_chunks = len(chunks)
        lens = [c[0].shape[0] for c in chunks]
        acct = {"up": 0, "down": 0}

        def _abort_attempt():
            ledger.live = 0
            faultlog.retry_h2d_bytes += acct["up"]
            faultlog.retry_d2h_bytes += acct["down"]

        def _rechunk_smaller():
            nonlocal chunk_elems, chunks
            if chunk_elems <= 1:
                return False
            chunk_elems = max(1, chunk_elems // 2)
            chunks = _split_chunks(chunks, chunk_elems)
            faultlog.degradations += 1
            return True

        # --- chunk phase: double-buffered staging --------------------------
        try:
            runs, cpasses = _chunk_phase(
                chunks, spill=spill, cfg=cfg, engine=engine,
                interpret=interpret, key_dtype=key_dtype,
                elem_bytes=elem_bytes, ledger=ledger, faults=faults,
                retry=retry, faultlog=faultlog, acct=acct,
                make_writable=make_writable)
        except RetriesExhausted:
            _abort_attempt()
            if not _rechunk_smaller():
                raise
            continue
        chunk_up, chunk_down = acct["up"], acct["down"]

        # --- merge phase ----------------------------------------------------
        rounds = 0
        spill_up = spill_down = 0
        if spill:
            meta = dict(meta_base, num_chunks=num_chunks,
                        chunk_elems=chunk_elems)
            try:
                if num_chunks == 1:
                    keys_h, vals_h = runs[0]
                else:
                    (keys_h, vals_h, rounds, spill_up, spill_down, kway,
                     slab) = _merge_spilled(
                        [r[0] for r in runs], [r[1] for r in runs],
                        round_idx=0, kway=kway, tile=tile, slab=slab,
                        budget=spill_budget_bytes, elem_bytes=elem_bytes,
                        interpret=interpret, ledger=ledger, faults=faults,
                        retry=retry, faultlog=faultlog,
                        checkpoint_dir=checkpoint_dir,
                        checkpoint_every=checkpoint_every,
                        meta_base=meta,
                        checksummed=(faults is not None or retry is not None
                                     or checkpoint_dir is not None))
            except _RechunkEscalation as esc:
                _abort_attempt()
                if not _rechunk_smaller():
                    raise esc.cause
                continue
            keys_np = decode_np(keys_h)
            leaves_np = tuple(vals_h)
        else:
            try:
                if num_chunks == 1:
                    ck, cv = runs[0]     # single run: no marshalling/merge
                else:
                    # the padded current/alternate buffers follow
                    # fused.make_ping_pong's contract (sentinel key pad, zero
                    # value pad), built inline so run marshalling is a single
                    # concatenate — one fewer sweep than padding a
                    # pre-concatenated copy
                    udtype = runs[0][0].dtype
                    n_pad = pad_length(n, tile)
                    sentinel = ~jnp.zeros((), udtype)
                    ck = jnp.concatenate(
                        [r[0] for r in runs] +
                        [jnp.full((n_pad - n,), sentinel, udtype)])
                    num_leaves = len(runs[0][1])
                    cv = tuple(
                        jnp.concatenate(
                            [r[1][i] for r in runs] +
                            [jnp.zeros((n_pad - n,), runs[0][1][i].dtype)])
                        for i in range(num_leaves))
                    av = tuple(jnp.zeros_like(v) for v in cv)
                    ledger.alloc(2 * n_pad * elem_bytes)  # flat ping-pong pair
                    ledger.free(n * elem_bytes)   # per-run buffers release
                    del runs    # the merge phase's footprint is the two
                    # flat ping-pong buffers only — the very footprint the
                    # spill regime replaces with bounded slabs (the host
                    # chunks stay live only while a fault policy may demand
                    # a re-chunk restart)
                    if faults is None:
                        del chunks
                    mlens = list(lens)
                    ak = jnp.full_like(ck, sentinel)
                    while len(mlens) > 1:
                        nk, nv = guarded(
                            "merge_launch", merge_round, ck, cv, ak, av,
                            policy=faults, retry=retry, ledger=faultlog,
                            lens=tuple(mlens), kway=kway, tile=tile, n=n,
                            interpret=interpret)
                        ak, av = ck, cv      # old current donates next round
                        ck, cv = nk, nv
                        mlens = [sum(g)
                                 for g in kmerge.merge_groups(mlens, kway)]
                        rounds += 1

                def gather():
                    if cplan is None:
                        kn = np.asarray(
                            bijection.from_ordered_bits(ck[:n], key_dtype))
                    else:
                        kn = decode_np(np.asarray(ck[:n]))
                    return kn, tuple(np.asarray(v[:n]) for v in cv)

                keys_np, leaves_np = guarded(
                    "run_download", gather, policy=faults, retry=retry,
                    ledger=faultlog, cost_bytes=n * elem_bytes,
                    direction="d2h")
                # the link carried the PACKED carrier; decode is host-side
                acct["down"] += n * np.dtype(key_dtype).itemsize + \
                    sum(v.nbytes for v in leaves_np)
                chunk_down = acct["down"]
            except RetriesExhausted:
                # non-spill recovery: the device runs were donated away, so
                # every rung restarts the attempt — kway first, then re-chunk
                _abort_attempt()
                if kway > 2:
                    kway = max(2, kway // 2)
                    faultlog.degradations += 1
                    continue
                if not _rechunk_smaller():
                    raise
                continue
        break

    h2d = chunk_up + spill_up + faultlog.retry_h2d_bytes
    d2h = chunk_down + spill_down + faultlog.retry_d2h_bytes
    stats = OocStats(
        len(lens), rounds, chunk_elems, h2d, d2h,
        device_high_water_bytes=ledger.high,
        chunk_link_bytes=chunk_up + chunk_down,
        spill_link_bytes=spill_up + spill_down,
        rounds_spilled=rounds if spill else 0,
        spill_slab_elems=slab,
        retries=faultlog.retries,
        faults_injected=faultlog.faults_injected,
        degradations=faultlog.degradations,
        checksum_failures=faultlog.checksum_failures,
        rounds_checkpointed=faultlog.rounds_checkpointed,
        retry_link_bytes=faultlog.retry_link_bytes,
        chunk_passes_executed=sum(int(p) for p in cpasses))
    return finish(keys_np, leaves_np, stats)


def _resume(resume_from: str, *, spill_budget_bytes, interpret, faults,
            retry, checkpoint_dir, checkpoint_every, values_like,
            return_stats, faultlog: FaultLedger, ledger: _DeviceLedger):
    """Replay an interrupted spill-merge from its newest published round.

    Adopts the plan recorded in the manifest (kway/tile/slab/key dtype) so
    the remaining rounds are byte-identical to the uninterrupted run's.
    Stats cover only the work done by this process (the chunk phase ran in
    the interrupted one).  Continued checkpointing: pass ``checkpoint_dir``
    — same directory to extend the existing sequence, a different one to
    re-publish the adopted state there first.
    """
    meta, keys_h, vals_h = _load_round_checkpoint(resume_from)
    kway, tile, slab = meta["kway"], meta["tile"], meta["slab"]
    key_dtype = np.dtype(meta["key_dtype"])    # packed carrier if compressed
    comp = meta.get("compress")
    cplan = None
    out_dtype = key_dtype
    if comp is not None:
        cplan = bijection.CompressionPlan(mask=int(comp["mask"]),
                                          dead=int(comp["dead"]),
                                          source_bits=int(comp["source_bits"]))
        out_dtype = np.dtype(comp["orig_dtype"])
    n = meta["n"]
    elem_bytes = key_dtype.itemsize + \
        sum(np.dtype(d).itemsize for d in meta["value_dtypes"])
    if spill_budget_bytes is not None and _spill_peak_bytes(
            slab, tile, elem_bytes, kway) > spill_budget_bytes:
        raise ValueError(
            f"resume_from plan (slab={slab}, kway={kway}, tile={tile}) "
            f"models a peak above spill_budget_bytes={spill_budget_bytes}; "
            f"resume with the original budget or none")
    if bijection.key_bits(key_dtype) > 32 and not jax.config.jax_enable_x64:
        raise RuntimeError("64-bit keys require jax_enable_x64")
    if faults is not None and meta.get("fault_state"):
        faults.load_state(meta["fault_state"])
    same_dir = checkpoint_dir is not None and \
        os.path.abspath(checkpoint_dir) == os.path.abspath(resume_from)
    try:
        keys_h0, vals_h0, rounds, up, down, kway, slab = _merge_spilled(
            keys_h, vals_h, round_idx=meta["round"], kway=kway, tile=tile,
            slab=slab, budget=spill_budget_bytes, elem_bytes=elem_bytes,
            interpret=interpret, ledger=ledger, faults=faults, retry=retry,
            faultlog=faultlog, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            meta_base={k: meta[k] for k in
                       ("key_dtype", "n", "num_leaves", "value_dtypes",
                        "num_chunks", "chunk_elems", "compress")
                       if k in meta},
            checksums=[tuple(cs) for cs in meta["checksums"]],
            save_incoming=not same_dir)
    except _RechunkEscalation as esc:
        raise esc.cause      # no host chunks to re-split in a resumed run

    if cplan is not None:
        keys_h0 = bijection.unpack_ordered_bits_np(keys_h0, cplan)
    keys_np = bijection.from_ordered_bits_np(keys_h0, out_dtype)
    leaves_np = tuple(vals_h0)
    nl = meta["num_leaves"]
    if nl == 0:
        out = (keys_np,)
    elif values_like is not None:
        td = jax.tree.flatten(values_like)[1]
        if td.num_leaves != nl:
            raise ValueError(f"values_like has {td.num_leaves} leaves; the "
                             f"checkpoint recorded {nl}")
        out = (keys_np, jax.tree.unflatten(td, list(leaves_np)))
    elif nl == 1:
        out = (keys_np, leaves_np[0])
    else:
        out = (keys_np, leaves_np)
    if return_stats:
        stats = OocStats(
            meta["num_chunks"], rounds, meta["chunk_elems"],
            up + faultlog.retry_h2d_bytes, down + faultlog.retry_d2h_bytes,
            device_high_water_bytes=ledger.high,
            chunk_link_bytes=0,
            spill_link_bytes=up + down,
            rounds_spilled=rounds,
            spill_slab_elems=slab,
            retries=faultlog.retries,
            faults_injected=faultlog.faults_injected,
            degradations=faultlog.degradations,
            checksum_failures=faultlog.checksum_failures,
            rounds_checkpointed=faultlog.rounds_checkpointed,
            retry_link_bytes=faultlog.retry_link_bytes)
        out = out + (stats,)
    return out[0] if len(out) == 1 else out


# --- contract declarations (verified by repro.analysis; see analysis/contracts)
# §5 census + transfer tables: a chunk sort inherits the hybrid contract at
# chunk size (pass loop plus one tile loop per local-sort class); a device merge round and a spill slab sweep are each ONE
# kway_merge_round launch moving exactly one read + one write sweep of the
# (pad_length-sized) run/slab buffer.
ANALYSIS_CONTRACTS = {
    "ooc_chunk_sort": {
        "entry": "repro.core.outofcore._sort_chunk",
        "census": {"launch_total": "2 + classes",
                   "while_body_launches": "[1] * (1 + classes)"},
        "sort_free": True,
        "donation": {"fused_counting_pass": "1 + vals"},
        "transfer": {
            "sweep_kernels": ["radix_histogram_total", "fused_counting_pass"],
            "bytes": "(2 * passes + 1) * n_pad * kb"
                     " + 2 * passes * n_pad * vb",
        },
    },
    "ooc_merge_round": {
        "entry": "repro.core.outofcore.merge_round",
        "census": {"launch_total": "1", "while_body_launches": "[]"},
        "sort_free": True,
        "donation": {"_kway_merge_kernel": "1 + vals"},
        "transfer": {
            "sweep_kernels": ["_kway_merge_kernel"],
            "bytes": "2 * n_pad * kb + 2 * n_pad * vb",
        },
    },
    "ooc_slab_sweep": {
        "entry": "repro.kernels.merge.kway_merge_round",
        "census": {"launch_total": "1", "while_body_launches": "[]"},
        "sort_free": True,
        "donation": {"_kway_merge_kernel": "1 + vals"},
        "transfer": {
            "sweep_kernels": ["_kway_merge_kernel"],
            "bytes": "2 * n_pad * kb + 2 * n_pad * vb",
        },
    },
}
