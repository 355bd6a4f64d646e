"""Data pipeline: restart-exact synthetic LM stream + sort-based bucketing.

Restart-exactness is the fault-tolerance contract: batch t is a pure function
of (seed, t), so resuming from a checkpoint at step t replays the identical
stream with no pipeline state to persist — counter-based PRNG keys, the same
pattern large-scale deterministic loaders use.

Length bucketing runs explicit d=8 counting passes through
``core.segmented.counting_partition`` — the same engine-selected partition
primitive as MoE dispatch and the distributed sort's shard step
(``core.plan.single_pass_partition``; fused Pallas kernel under interpret
mode, XLA stable sort on compiled hardware until the Mosaic lowering lands).
Corpora larger than one device run route through the §5 out-of-core
pipeline instead (``ooc_chunk_elems``): shard-sized batches are ordered by
``core.outofcore.oocsort`` — chunked device sorts under double-buffered
staging plus the streaming k-way merge — so bucketing scales past device
memory with the same packing contract.  Corpora whose *runs* no longer fit
device memory additionally set ``ooc_spill_budget_bytes``: the merge phase
then streams host-resident runs through budget-bounded device slabs (the
§5 beyond-device-memory regime), still the same packing contract.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.segmented import counting_partition


@dataclasses.dataclass
class SyntheticLMData:
    """Deterministic synthetic token stream: batch(step) is pure in (seed, step)."""
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    num_patches: int = 0          # vlm stub: also emit patch embeddings
    d_model: int = 0

    def batch(self, step: int) -> Dict[str, jnp.ndarray]:
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        # zipfian-ish token marginals: realistic softmax targets, cheap to make
        k1, k2 = jax.random.split(key)
        u = jax.random.uniform(k1, (self.global_batch, self.seq_len),
                               minval=1e-6, maxval=1.0)
        tokens = jnp.clip((self.vocab ** u - 1.0).astype(jnp.int32),
                          0, self.vocab - 1)
        out = {"tokens": tokens}
        if self.num_patches:
            out["patches"] = jax.random.normal(
                k2, (self.global_batch, self.num_patches, self.d_model),
                jnp.float32) * 0.02
        return out

    def __iter__(self) -> Iterator[Dict[str, jnp.ndarray]]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1


def length_bucketed_batches(lengths: np.ndarray, batch_tokens: int,
                            engine: Optional[str] = None,
                            ooc_chunk_elems: Optional[int] = None,
                            ooc_spill_budget_bytes: Optional[int] = None,
                            ooc_device_slab_elems: Optional[int] = None,
                            ooc_fault_policy=None,
                            ooc_retry_policy=None,
                            ooc_checkpoint_dir: Optional[str] = None,
                            dist_mesh=None,
                            dist_axis: str = "data"):
    """Order documents by length via two LSD counting passes, then pack.

    The ordering is an explicit LSD radix sort on the shared engine-selected
    partition primitive: chained d=8 ``counting_partition`` passes, one per
    occupied length byte (typical 16-bit lengths: two passes).  Corpora that
    exceed one device run set ``ooc_chunk_elems``: the order then comes from
    the §5 out-of-core pipeline (``core.outofcore.oocsort`` with the doc
    indices as the value payload — chunk sorts overlapped with staging, then
    streaming k-way merge rounds).  ``ooc_spill_budget_bytes`` /
    ``ooc_device_slab_elems`` pass through to ``oocsort``'s host-spill
    streaming merge, bounding device bytes for corpora whose sorted runs
    exceed device memory.  ``ooc_fault_policy`` / ``ooc_retry_policy`` /
    ``ooc_checkpoint_dir`` pass through to ``oocsort``'s resilience layer
    (``core.faults``): the bucketing order inherits bounded retries, the
    degradation ladder and round-granular checkpointing, so a multi-round
    corpus sort that dies mid-merge resumes instead of restarting — the
    same restart-exactness posture as the token stream itself.

    Corpora sharded across a device mesh route through the §5 distributed
    exchange instead (``dist_mesh=``, exclusive with the ooc route): doc
    indices ride ``core.distributed.make_distributed_sort`` as the value
    payload over the ``dist_axis`` mesh axis (sample-sort splitters, one
    fused counting pass per shard, capacity-padded all_to_all, bounded
    splitter-refinement retries on overflow), and the per-shard valid
    prefixes concatenate back into the global order.
    Returns (order, bucket_bounds):
    ``order`` is the sorted document order (longest-with-longest minimises
    padding waste), bounds delimit batches of at most ``batch_tokens``
    padded tokens.
    """
    lengths = np.asarray(lengths, np.uint32)
    if ooc_chunk_elems is None and (ooc_spill_budget_bytes is not None or
                                    ooc_device_slab_elems is not None):
        raise ValueError("ooc spill options require ooc_chunk_elems (the "
                         "spill regime is part of the out-of-core route)")
    if ooc_chunk_elems is None and (ooc_fault_policy is not None or
                                    ooc_retry_policy is not None or
                                    ooc_checkpoint_dir is not None):
        raise ValueError("ooc fault/retry/checkpoint options require "
                         "ooc_chunk_elems (resilience wraps the "
                         "out-of-core route)")
    if dist_mesh is not None and ooc_chunk_elems is not None:
        raise ValueError("dist_mesh and ooc_chunk_elems are exclusive "
                         "routes (mesh-sharded vs host-chunked ordering)")
    if dist_mesh is not None:
        from repro.core.distributed import make_distributed_sort, valid_concat
        nshards = dist_mesh.shape[dist_axis]
        n = lengths.shape[0]
        pad = (-n) % nshards
        # sentinel-pad to a shardable length; pads sort last and are dropped
        # below by index, so a real 0xFFFFFFFF length still buckets correctly
        keys = np.concatenate(
            [lengths, np.full(pad, np.uint32(0xFFFFFFFF), np.uint32)])
        idx = np.arange(n + pad, dtype=np.int32)
        # tiny shards: full-fan exchange capacity (slack = nshards caps each
        # cell at the whole chunk) so a small corpus can never overflow on
        # per-cell noise; the memory cost is n·nshards elements, trivial at
        # this scale, and large shards keep the sampled-splitter default
        n_local = (n + pad) // nshards
        slack = float(nshards) if n_local < 1024 else 2.0
        fn = jax.jit(make_distributed_sort(dist_mesh, dist_axis,
                                           slack=slack, engine=engine))
        out, order_out, stats = fn(jnp.asarray(keys), jnp.asarray(idx))
        if bool(np.asarray(stats.overflow).any()):
            raise RuntimeError("distributed length bucketing overflowed its "
                               "exchange capacity after splitter-refinement "
                               "retries (raise slack= or oversample=)")
        sorted_all = valid_concat(out, stats.valid)
        order_all = valid_concat(order_out, stats.valid)
        keep = order_all < n
        sorted_len, order = sorted_all[keep], order_all[keep]
    elif ooc_chunk_elems is not None:
        from repro.core.outofcore import oocsort
        sorted_len, order = oocsort(
            lengths, ooc_chunk_elems, engine=engine,
            values=np.arange(lengths.shape[0], dtype=np.int32),
            spill_budget_bytes=ooc_spill_budget_bytes,
            device_slab_elems=ooc_device_slab_elems,
            faults=ooc_fault_policy, retry=ooc_retry_policy,
            checkpoint_dir=ooc_checkpoint_dir)
    else:
        # host-side: only as many passes as the longest document needs
        max_len = int(lengths.max()) if lengths.size else 0
        npasses = max(1, (max_len.bit_length() + 7) // 8)
        x = lengths.copy()
        order = np.arange(lengths.shape[0], dtype=np.int32)
        for p in range(npasses):  # stable LSD, least-significant byte first
            ids = jnp.asarray(((x >> (8 * p)) & 0xFF).astype(np.int32))
            perm = np.asarray(counting_partition(ids, 256,
                                                 engine=engine).perm)
            x = x[perm]
            order = order[perm]
        sorted_len = x

    bounds = [0]
    cur_max = 0
    cur_n = 0
    for i, ln in enumerate(sorted_len):
        cand_max = max(cur_max, int(ln))
        if cur_n and cand_max * (cur_n + 1) > batch_tokens:
            bounds.append(i)
            cur_max, cur_n = int(ln), 1
        else:
            cur_max, cur_n = cand_max, cur_n + 1
    bounds.append(len(sorted_len))
    return order, bounds


# --- contract declaration (verified by repro.analysis; see analysis/contracts)
# Length bucketing partitions ids into 256 buckets with ONE counting pass
# (prologue histogram + fused launch), iota payload as the value leaf — the
# data-pipeline consumer of the same partition primitive.
ANALYSIS_CONTRACT = {
    "entry": "repro.core.segmented.counting_partition",
    "census": {
        "launch_total": "2",
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
