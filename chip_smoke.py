"""Chip smoke test: the hybrid radix sort's kernel path, end to end, on a TPU.

Runs the public entry points once at a size a database user would call real
and checks every result against NumPy:

  * phase A — 2^26 uniform uint32 keys with uint32 values (the paper's
    8-byte records, 512 MiB) through ``hybrid_sort(keys, values)``,
  * phase B — 2^26 uint32 keys of Thearling "ands3" skew (multiple passes,
    R3 merging, several local-sort size classes) through ``hybrid_sort``,
  * phase C — MoE token dispatch as the model runs it: ``capacity_dispatch``
    under ``jax.vmap`` over 4 token groups of 8,192 tokens x top-8 of
    qwen3's 128 experts, checked against the ``argsort`` engine.

For each phase the jitted program is lowered and compiled first; it must
contain Mosaic kernels (``tpu_custom_call``) and no comparison sort, so the
result provably comes from the paper's kernel path.  Phase A also times one
call of the ``argsort`` engine and of ``jax.lax.sort`` on its data, for
reference (informational, not a benchmark).

``--chips 4`` runs only the distributed path instead: ``make_distributed_sort``
over a mesh of four chips, 4 x 2^24 uint32 keys with uint32 values, checked
against ``np.sort`` through ``valid_concat``.

Data comes from ``--seed``.  The script exits non-zero without a result when
JAX finds no TPU or any phase fails; on success its last line is one JSON
object naming the device.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


N_KEYS = 1 << 26              # keys per one-chip phase (512 MiB of pairs)
N_KEYS_PER_CHIP = 1 << 24     # keys per chip with --chips 4


def _say(msg: str) -> None:
    print(msg, flush=True)


def _check_pairs(keys_in, keys_out, vals_out, label: str) -> None:
    """Keys byte-identical to np.sort; values a permutation carrying keys."""
    want = np.sort(keys_in)
    if keys_out.tobytes() != want.tobytes():
        bad = int(np.argmax(keys_out != want))
        raise AssertionError(f"{label}: keys differ from np.sort first at "
                             f"index {bad}")
    if vals_out is None:
        return
    n = keys_in.shape[0]
    seen = np.zeros(n, bool)
    seen[vals_out] = True
    if vals_out.shape[0] != n or not seen.all():
        raise AssertionError(f"{label}: values are not a permutation")
    if not np.array_equal(keys_in[vals_out], keys_out):
        raise AssertionError(f"{label}: a value left its key")


def _sort_phase(label: str, keys_np, vals_np, device) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import hybrid, hybrid_sort, model
    from repro.utils import hlo

    n = keys_np.shape[0]
    keys = jax.device_put(jnp.asarray(keys_np), device)
    vals = (None if vals_np is None
            else jax.device_put(jnp.asarray(vals_np), device))

    def sort(k, v):
        return hybrid_sort(k, v, return_stats=True)

    t0 = time.perf_counter()
    lowered = jax.jit(sort).lower(keys, vals)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    kernels = text.count("tpu_custom_call")
    sorts = hlo.sort_op_count(lowered.as_text())
    assert kernels > 0, f"{label}: no Mosaic kernel in the compiled sort"
    assert sorts == 0, f"{label}: {sorts} comparison sort(s) in the program"

    t0 = time.perf_counter()
    out = compiled(keys, vals)
    jax.block_until_ready(out)
    wall_s = time.perf_counter() - t0

    out_keys, out_vals, stats = ((out[0], None, out[1]) if vals is None
                                 else out)
    cfg = model.default_config(4)
    _say(f"{label}: n={n} counting_passes={int(stats.counting_passes)} "
         f"elided_passes={int(stats.elided_passes)} "
         f"local_sort_classes={hybrid.local_sort_classes(n, cfg)}")
    _say(f"{label}: compile_s={compile_s:.3f} wall_s={wall_s:.6f} "
         f"(compiled call; informational, not a benchmark) "
         f"tpu_custom_calls={kernels} sort_ops={sorts}")
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    _say(f"{label}: peak_device_bytes={peak}")
    _check_pairs(keys_np, np.asarray(out_keys),
                 None if out_vals is None else np.asarray(out_vals), label)
    _say(f"{label}: OK (byte-identical to np.sort)")


def _reference_times(keys_np, vals_np, device) -> None:
    """One timed call each of the argsort engine and of ``jax.lax.sort`` on
    phase A's data (compiled first; informational, not a benchmark)."""
    import jax
    import jax.numpy as jnp
    from repro.core import hybrid_sort

    keys = jax.device_put(jnp.asarray(keys_np), device)
    vals = jax.device_put(jnp.asarray(vals_np), device)
    refs = {
        "hybrid_sort(engine='argsort')":
            lambda k, v: hybrid_sort(k, v, engine="argsort"),
        "jax.lax.sort": lambda k, v: jax.lax.sort((k, v), num_keys=1),
    }
    for name, fn in refs.items():
        compiled = jax.jit(fn).lower(keys, vals).compile()
        t0 = time.perf_counter()
        out = compiled(keys, vals)
        jax.block_until_ready(out)
        wall_s = time.perf_counter() - t0
        _check_pairs(keys_np, np.asarray(out[0]), np.asarray(out[1]),
                     f"A reference {name}")
        del out
        _say(f"A reference {name}: wall_s={wall_s:.6f} (one compiled call; "
             f"informational, not a benchmark)")


def _moe_phase(seed: int, device) -> None:
    """Vmapped MoE dispatch on the kernel engine against the argsort one."""
    import jax
    import jax.numpy as jnp
    from repro.core.segmented import capacity_dispatch
    from repro.utils import hlo

    groups, tokens, top_k, experts = 4, 8192, 8, 128
    capacity = int(1.25 * tokens * top_k / experts)
    rng = np.random.default_rng(seed)
    ids = jax.device_put(jnp.asarray(rng.integers(
        0, experts, (groups, tokens * top_k), dtype=np.int32)), device)

    def dispatch(engine):
        return jax.jit(jax.vmap(lambda i: capacity_dispatch(
            i, experts, capacity, engine=engine)))

    lowered = dispatch(None).lower(ids)
    compiled = lowered.compile()
    kernels = compiled.as_text().count("tpu_custom_call")
    sorts = hlo.sort_op_count(lowered.as_text())
    _say(f"C moe dispatch: {groups} groups x {tokens * top_k} ids, "
         f"{experts} experts, capacity {capacity}: "
         f"tpu_custom_calls={kernels} sort_ops={sorts}")
    assert kernels > 0, "C moe dispatch: no Mosaic kernel in the program"
    assert sorts == 0, f"C moe dispatch: {sorts} comparison sort(s)"
    got = compiled(ids)
    want = dispatch("argsort")(ids)
    for name, a, b in zip(got._fields, got, want):
        if np.asarray(a).tobytes() != np.asarray(b).tobytes():
            raise AssertionError(f"C moe dispatch: {name} differs from the "
                                 f"argsort engine")
    _say("C moe dispatch: OK (byte-identical to the argsort engine)")


def _dist_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import make_distributed_sort, valid_concat
    from repro.utils import hlo

    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, JAX sees "
                           f"{len(devices)}")
    mesh = Mesh(np.array(devices), ("data",))
    n = 4 * N_KEYS_PER_CHIP
    rng = np.random.default_rng(seed)
    keys_np = rng.integers(0, 2**32, n, dtype=np.uint32)
    vals_np = np.arange(n, dtype=np.uint32)
    shard = NamedSharding(mesh, P("data"))
    keys = jax.device_put(keys_np, shard)
    vals = jax.device_put(vals_np, shard)
    placed = sorted(d.id for d in keys.sharding.device_set)
    assert len(placed) == 4, f"keys placed on devices {placed}"
    _say(f"dist: keys sharded over devices {placed}, "
         f"per-shard {[s.data.shape[0] for s in keys.addressable_shards]}")

    fn = jax.jit(make_distributed_sort(mesh, "data"))
    t0 = time.perf_counter()
    lowered = fn.lower(keys, vals)
    compiled = lowered.compile()
    kernels = compiled.as_text().count("tpu_custom_call")
    sorts = hlo.sort_op_count(lowered.as_text())
    _say(f"dist: compile_s={time.perf_counter() - t0:.3f} "
         f"tpu_custom_calls={kernels} sort_ops={sorts}")
    assert kernels > 0, "dist: no Mosaic kernel in the compiled sort"
    assert sorts == 0, f"dist: {sorts} comparison sort(s) in the program"
    t0 = time.perf_counter()
    out_k, out_v, stats = compiled(keys, vals)
    jax.block_until_ready(out_k)
    _say(f"dist: wall_s={time.perf_counter() - t0:.6f} (informational) "
         f"attempts={np.asarray(stats.exchange_attempts).tolist()} "
         f"valid={np.asarray(stats.valid).tolist()}")
    out_dev = sorted(d.id for d in out_k.sharding.device_set)
    assert len(out_dev) == 4, f"output on devices {out_dev}"
    ks = valid_concat(out_k, stats.valid)
    vs = valid_concat(out_v, stats.valid)
    _check_pairs(keys_np, ks, vs, "dist")
    _say("dist: OK (valid_concat byte-identical to np.sort)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    from repro.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    _say(f"device: {dev.device_kind} x{len(jax.devices())}")

    if args.chips == 4:
        _dist_phase(args.seed)
    else:
        from repro.data.distributions import entropy_keys
        rng = np.random.default_rng(args.seed)
        keys = rng.integers(0, 2**32, N_KEYS, dtype=np.uint32)
        vals = np.arange(N_KEYS, dtype=np.uint32)
        _sort_phase("A uniform kv", keys, vals, dev)
        _reference_times(keys, vals, dev)
        _sort_phase("B ands3", entropy_keys(rng, N_KEYS, 3), None, dev)
        _moe_phase(args.seed, dev)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
