"""Pallas TPU kernels for the hybrid radix sort's compute hot spots.

fused       — ONE launch per counting pass: block-descriptor partition +
              coalesced scatter of pass i fused with the digit histogram of
              pass i+1, on donated ping-pong buffers (§4.2–§4.4)
merge       — ONE launch per k-way merge round (§5): merge-path diagonal
              partition of K sorted runs per output tile (per-run-pair
              searchsorted co-ranks inside the tile), coalesced merge
              writes with KV payloads, donated ping-pong buffers — the
              device half of ``core.outofcore``'s pipelined sort; plus the
              host-side partition math (``host_coranks``,
              ``spill_group_plan``) that cuts groups of host-spilled runs
              into device-slab-sized strips, ONE launch per slab sweep
histogram   — one-hot MXU contraction histogram (§4.3's atomics, TPU-native)
multisplit  — in-VMEM tile partition + write combining (§4.4 / Fig. 3); the
              fused pass's per-block partition math, kept as the standalone
              per-tile kernel and oracle
bitonic     — VMEM local sort (§4.1's local sort; CUB BlockRadixSort analogue)
assigned    — the scalar-prefetch launch exemplar (§4.2 constant-invocation
              trick; descriptor *generation* lives in core.plan)
ops         — local-sort / histogram drivers (the fused counting passes moved
              to ``fused``; the per-bucket multi-launch drivers are retired)
ref         — pure-jnp oracles

Memory-transfer accounting (paper §4.3–§4.4, the roofline target that
``bench/roofline.py`` measures the counting passes against): one *unfused* counting pass over n keys of b bytes moves
``2R + 1W`` key sweeps (histogram read + scatter read + scatter write) =
3·n·b bytes; values add ``1R + 1W`` = 2·n·v.  The fused pass moves
``1R + 1W`` = 2·n·b (+ 2·n·v) because pass i+1's histogram is computed while
pass i's scatter still holds the keys — a 1.5x per-pass key-traffic
reduction, and the whole sort pays exactly one extra 1R prologue sweep
(pass 0's histogram).  A full k-bit hybrid sort therefore moves at most
``(2·⌈k/d⌉ + 1)·n·b`` key bytes versus ``3·⌈k/d⌉·n·b`` unfused and versus
``3·⌈k/5⌉·n·b`` for the CUB-style LSD baseline — the paper's 1.6–1.75x
traffic headline.  Bookkeeping arrays (M2–M5 of §4.5) are O(n/∂̂ · r) and do
not change the leading term.
[verified-by: ``repro.analysis`` contracts ``hybrid_sort`` /
``hybrid_sort_kv`` / ``lsd_sort`` / ``single_pass_partition``, checks
``transfer.hbm_bytes`` (the formula above, re-derived from traced operand
shapes), ``census`` (one launch per pass) and ``donation`` (the ping-pong
aliases the 1R+1W claim depends on); ``python -m repro.analysis``]

Entropy-adaptive row (``core.hybrid`` adaptive schedule + ``core.bijection``
compressed keys): only *executed* passes move bytes — statically dead bits
shrink the nominal schedule to ⌈k_eff/d⌉ over the live window, the fused
launch's free next-pass histogram elides single-occupied-digit passes with
no launch at all, and opt-in key compression shrinks b itself to the packed
carrier b_eff (uint64 → uint32 when ≤ 32 bits are live).  The adaptive
bound is therefore

    ``(2·p_exec + 1)·n·b_eff``  key bytes,  ``p_exec ≤ ⌈k_eff/d⌉ ≤ ⌈k/d⌉``

with equality on full-entropy keys (zero overhead: the skip predicate reads
the histogram the fused pass already produced) and p_exec → 1 on clustered
/ shared-prefix keys.  Executed-vs-nominal counts are census-gated (one
``pallas_call`` per *executed* pass — elided passes launch nothing;
tests/test_adaptive.py) and reported per call by ``SortStats`` (the chip
benchmark, ``bench/``, checks its traced pass launches against
``SortStats.counting_passes``).

Out-of-core transfer accounting (§5; no chip benchmark cell yet): for
N keys in C = ⌈N/chunk⌉ device-sized chunks merged K ways per round, per
key of b bytes (values: v bytes):

| phase                       | host-link bytes | device sweeps (R+W)        |
|-----------------------------|-----------------|----------------------------|
| chunk staging (device_put)  | 1·(b+v)         | —  (overlapped with sorts) |
| chunk sorts (fused engine)  | —               | (2·⌈k/d⌉ + 1)·b + 2·⌈k/d⌉·v|
| run marshalling (concat +   | —               | 3·(b+v)  (1R + 2W, once)   |
|   alternate-buffer fill)    |                 |                            |
| merge rounds (merge kernel) | —               | 2·⌈log_K C⌉·(b+v)          |
| spill rounds (host-resident | 2·(b+v) each    | 2·(b+v) each (slab-sized   |
|   runs, slab-streamed merge)|                 | buffers only)              |
| result gather               | 1·(b+v)         | —  (spill: runs gathered   |
|                             |                 |    during the chunk phase) |

Device-resident regime (rows 1–4 + gather): every key crosses the host link
exactly twice regardless of C (the §5 pipeline hides the upload behind the
previous chunk's sort), and each merge round reads and writes the whole run
buffer once — one ``pallas_call`` per round, ⌈log_K C⌉ rounds.  The chunk
sorts inherit the adaptive bound above (⌈k/d⌉ → p_exec per chunk, totalled
in ``OocStats.chunk_passes_executed``), and ``oocsort(compress=True)``
replaces b with the packed carrier b_eff in EVERY row — link bytes, slab
sizing and spill budgets included — before any key crosses the link.  Host-spill
regime (``oocsort(spill_budget_bytes=...)``): run marshalling and the flat
merge buffers disappear — runs live host-side between rounds, every spilled
round streams each multi-run group through fixed device slabs (strip i+1's
upload and strip i−1's download in flight around strip i's launch, one
``pallas_call`` per group-slab sweep), and total host crossings are
``2·N·(b+v)·(1 + rounds_spilled)`` — leftover single-run groups carry over
host-side for free, and device bytes stay bounded by the budget
(``OocStats.device_high_water_bytes``) no matter how large N grows, which
is what makes the §5 beyond-device-memory claim literal.  The merge-path
diagonal searches add O(tiles · K · log chunk) gathered (host-spill:
probed) elements and O(G·K) int32 descriptor uploads per strip, sub-leading
for any real tile size.  Until a chip cell measures it, the tracked proxy
is the structural census (``utils.hlo.launch_census``).
[verified-by: contracts ``ooc_chunk_sort`` / ``ooc_merge_round`` /
``ooc_slab_sweep`` — ``transfer.hbm_bytes`` pins the 2·(b+v) device sweep
per merge/slab row, ``census`` the one-launch-per-round gate, and the
``descriptor_tables`` report proves the merge-path/spill tables write
disjoint, exactly-covering output ranges]

Distributed-exchange accounting (``core.distributed``; the chip benchmark's
``kv32_uniform.dist4`` cell times the ``exchange`` scope): for n_local keys of b bytes (+ v payload bytes) per
shard over P shards, per *executed* exchange attempt (attempts ledgered in
``DistStats.exchange_attempts``; re-samples replay every row below):

| exchange phase                  | ICI wire bytes per shard              |
|---------------------------------|---------------------------------------|
| splitter sample (all_gather)    | s·b·(P−1)  (s = oversample·refine^a)  |
| key exchange (all_to_all)       | 2·n_local·b·(P−1)/P·slack  (1 send +  |
|                                 |   1 receive crossing per key, padded) |
| payload exchange (all_to_all)   | 2·n_local·v·(P−1)/P·slack  per leaf   |
| count exchange + overflow psum  | O(P)·4  (sub-leading)                 |

Device sweeps stay the single-shard tables above at n_local/C per chunk:
the local chunk sorts pay the fused/adaptive bound, each attempt's shard
bucketing is ONE fused counting pass (2·n_local·b sweeps), and the finish
is one high-fan-in multiway merge (2·n_local·b·⌈log2(C·P)⌉ searchsorted
sweeps) plus one 2-bucket compaction pass.  The sample term is what the
oversampling ratio trades: s·P·b gathered bytes buy splitter rank error
≈ n_local·P/(s·P) keys, so doubling s halves the skew the slack capacity
must absorb — the ≤ 2x clustered-skew gate in
tests/test_distributed_property.py pins the quality side, and
``utils.hlo.collective_bytes`` reads the wire side off the lowered HLO.
[verified-by: contract ``distributed_shard`` — ``transfer.link_bytes``
re-derives every row from the collective-primitive result shapes in the
traced shard body (per-kind site counts included) and diffs them against
the declared formula in ``core.distributed.ANALYSIS_CONTRACT``]

Failure & recovery accounting (``core.faults``, the fault-replay wall in
tests/test_faults.py): resilience must not silently bend the tables above,
so its costs are ledgered separately and the clean formulas stay exact.

  * Retries — a failed *transfer* attempt still crossed the link before it
    was declared lost (the worst-case model), so each transient fault at a
    transfer site re-pays that site's payload bytes; failed *launches*
    re-pay nothing on the link.  The extra bytes accumulate in
    ``OocStats.retry_link_bytes`` (split h2d/d2h internally), never in the
    per-phase columns, giving the test-asserted identity::

        h2d_bytes + d2h_bytes ==
            chunk_link_bytes + spill_link_bytes + retry_link_bytes

    and with F_s transient faults at transfer site s of payload p_s the
    overhead is exactly ``retry_link_bytes == Σ_s F_s · p_s``.
  * Checksums — ``host_checksum`` runs host-side over buffers already
    resident there: 0 extra link bytes, one O(run) host sweep per crossing
    (fault-free overhead is pure host CPU).
  * Checkpoints — round-granular checkpoints publish *host-resident* runs
    to disk: 0 extra link bytes (``rounds_checkpointed`` rounds pay
    ≈ Σ run bytes + manifest to the store, not to the device link).
  * Degradation ladder — slab and kway rungs re-plan the *same* round, so a
    completed round still moves exactly ``2·N·(b+v)`` clean link bytes (the
    aborted round's partial crossings fold into ``retry_link_bytes``);
    re-chunking restarts the chunk phase, whose aborted crossings fold in
    the same way.  Every rung is re-validated against
    ``spill_budget_bytes``, so ``device_high_water_bytes`` stays gated.
  * Census — ``guarded`` is host code around the same jitted callables and
    a retry re-invokes the same compiled function, so the per-round /
    per-slab-sweep launch census is identical with and without a policy.
"""
from repro.kernels.histogram import radix_histogram
from repro.kernels.multisplit import tile_multisplit, tile_multisplit_kv
from repro.kernels.bitonic import (bitonic_sort_rows, bitonic_sort_rows_kv,
                                   bitonic_sort_rows_stable)
from repro.kernels.assigned import assigned_histogram
from repro.kernels.fused import (fused_counting_pass, initial_histogram,
                                 make_ping_pong, pad_length)
from repro.kernels.merge import (host_coranks, kway_merge_round,
                                 merge_path_partition, num_merge_rounds,
                                 spill_group_plan)
from repro.kernels.ops import (apply_run_copies, kernel_local_sort,
                               local_sort_class_plan, segmented_local_sort,
                               tile_histogram_pass)

__all__ = [
    "radix_histogram", "tile_multisplit", "tile_multisplit_kv",
    "bitonic_sort_rows", "bitonic_sort_rows_kv", "bitonic_sort_rows_stable",
    "assigned_histogram",
    "fused_counting_pass", "initial_histogram", "make_ping_pong", "pad_length",
    "host_coranks", "kway_merge_round", "merge_path_partition",
    "num_merge_rounds", "spill_group_plan",
    "apply_run_copies", "kernel_local_sort", "local_sort_class_plan",
    "segmented_local_sort", "tile_histogram_pass",
]
