import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh) cell.

For each cell this driver:
  1. builds ShapeDtypeStruct stand-ins for params/optimizer/batch/cache
     (zero allocation — the full configs exist only as shapes),
  2. jits the real step (train_step / prefill serve_step / decode serve_step)
     with explicit in_shardings from launch/sharding.py,
  3. ``.lower().compile()`` against the production mesh (16x16 single-pod and
     2x16x16 multi-pod),
  4. prints ``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs,
     bytes) and parses the partitioned HLO for per-chip collective wire bytes,
  5. writes a JSON artifact (the chip benchmark, ``bench/``, does not
     read it).

Usage:
  python -m repro.launch.dryrun --arch internlm2_1_8b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config, SHAPES, shapes_for
from repro.launch.mesh import make_production_mesh, data_axes, data_shards
from repro.launch import sharding as shd
from repro.models import init_params, init_cache, prefill, decode_step
from repro.optim import get_optimizer
from repro.train import make_train_step, TrainState
from repro.utils.hlo import collective_bytes, collective_counts
from repro.utils.roofline import Roofline, model_flops


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(cfg, shape_cfg, mesh):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape_cfg.global_batch, shape_cfg.seq_len
    if shape_cfg.kind in ("train", "prefill"):
        text = s - (cfg.num_patches if cfg.frontend == "vision_patches" else 0)
        batch = {"tokens": jax.ShapeDtypeStruct((b, text), jnp.int32)}
        if cfg.frontend == "vision_patches":
            batch["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.num_patches, cfg.d_model), jnp.float32)
        return batch
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    cache = jax.eval_shape(lambda: init_cache(cfg, b, s))
    return {"token": token, "cache": cache}


def _prepare(arch: str, shape_name: str, mesh):
    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, dispatch_groups=data_shards(mesh))
    shape_cfg = SHAPES[shape_name]
    return cfg, shape_cfg


def _clip_layers(cfg, n: int):
    """Same config with n UNROLLED layers (for the two-point cost fit —
    XLA's cost model skips while-loop bodies, so the fit lowerings unroll)."""
    globals_ = tuple(g for g in cfg.global_attn_layers if g < n)
    return dataclasses.replace(cfg, n_layers=n, global_attn_layers=globals_,
                               scan_unroll=True)


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str,
               step_override: str = None, cfg_override=None,
               fit_layers: bool = True):
    """Lower + compile one cell; returns the artifact dict.

    XLA's cost_analysis counts a while-loop (scan) body ONCE regardless of the
    trip count, so FLOPs/bytes/collectives are extrapolated linearly from two
    extra lowerings at n_layers=1 and n_layers=2 (cost(L) = a + b*L);
    memory_analysis comes from the real-depth program.
    """
    cfg, shape_cfg = _prepare(arch, shape_name, mesh)
    if cfg_override:
        cfg = cfg_override(cfg)
    step_kind = step_override or ("train" if shape_cfg.kind == "train" else
                                  "prefill" if shape_cfg.kind == "prefill"
                                  else "decode")
    chips = mesh.devices.size
    dp = data_axes(mesh)

    def _lower(c):
        params_shape = jax.eval_shape(
            lambda: init_params(c, jax.random.PRNGKey(0)))
        pshard = shd.param_shardings(params_shape, c, mesh)
        if step_kind == "train":
            opt = get_optimizer(c.optimizer)
            opt_shape = jax.eval_shape(opt.init, params_shape)
            oshard = shd.param_shardings(opt_shape, c, mesh)
            state_sds = TrainState(params_shape, opt_shape,
                                   jax.ShapeDtypeStruct((), jnp.int32))
            state_shard = TrainState(pshard, oshard, NamedSharding(mesh, P()))
            batch_sds = input_specs(c, shape_cfg, mesh)
            bshard = shd.to_shardings(shd.batch_specs(c, mesh, shape_cfg), mesh)

            from repro.models import loss_fn
            from repro.optim import clip_by_global_norm, cosine_schedule
            lr_fn = cosine_schedule(3e-4, 100, 10_000)

            def step_fn(state, batch):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: loss_fn(p, c, batch, remat=c.remat),
                    has_aux=True)(state.params)
                grads, gnorm = clip_by_global_norm(grads, 1.0)
                new_p, new_o = opt.update(grads, state.opt_state, state.params,
                                          lr_fn(state.step))
                return TrainState(new_p, new_o, state.step + 1), loss

            fn = jax.jit(step_fn, in_shardings=(state_shard, bshard),
                         donate_argnums=(0,))
            return fn.lower(state_sds, batch_sds)
        if step_kind == "prefill":
            batch_sds = input_specs(c, shape_cfg, mesh)
            bshard = shd.to_shardings(shd.batch_specs(c, mesh, shape_cfg), mesh)
            cshard = shd.to_shardings(
                shd.cache_specs(c, mesh, shape_cfg.global_batch,
                                shape_cfg.seq_len), mesh)
            v_ok = c.padded_vocab % mesh.shape["model"] == 0
            lshard = NamedSharding(mesh, P(
                dp if shape_cfg.global_batch % data_shards(mesh) == 0 else None,
                None, "model" if v_ok else None))

            def serve_prefill(params, batch):
                return prefill(params, c, batch, remat=c.remat)

            fn = jax.jit(serve_prefill, in_shardings=(pshard, bshard),
                         out_shardings=(lshard, cshard))
            return fn.lower(params_shape, batch_sds)
        # decode
        spec = input_specs(c, shape_cfg, mesh)
        cshard = shd.to_shardings(
            shd.cache_specs(c, mesh, shape_cfg.global_batch,
                            shape_cfg.seq_len), mesh)
        tshard = NamedSharding(
            mesh, P(dp, None) if shape_cfg.global_batch % data_shards(mesh) == 0
            else P())

        def serve_decode(params, token, cache):
            return decode_step(params, c, token, cache)

        fn = jax.jit(serve_decode, in_shardings=(pshard, tshard, cshard),
                     donate_argnums=(2,))
        return fn.lower(params_shape, spec["token"], spec["cache"])

    def _costs(compiled_exe):
        ca_ = compiled_exe.cost_analysis()
        if isinstance(ca_, list):
            ca_ = ca_[0]
        hlo_ = compiled_exe.as_text()
        coll_ = collective_bytes(hlo_, chips)
        return (float(ca_.get("flops", 0.0)),
                float(ca_.get("bytes accessed", 0.0)),
                float(coll_.get("total", 0.0)), coll_, collective_counts(hlo_))

    t0 = time.time()
    with jax.set_mesh(mesh):          # binds in-model sharding constraints
        lowered = _lower(cfg)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    flops0, bytes0, cbytes0, coll, counts = _costs(compiled)

    if fit_layers and cfg.n_layers > 2:
        # two-point fit: cost(L) = a + b*L (scan body counted once by XLA).
        # Slopes are clamped at 0 — GSPMD occasionally picks different
        # strategies for the two small lowers (flagged as degenerate).
        with jax.set_mesh(mesh):
            f1, b1, c1, _, _ = _costs(_lower(_clip_layers(cfg, 1)).compile())
            f2, b2, c2, _, _ = _costs(_lower(_clip_layers(cfg, 2)).compile())
        l = cfg.n_layers
        flops = max(f1 + max(f2 - f1, 0.0) * (l - 1), flops0)
        hbytes = max(b1 + max(b2 - b1, 0.0) * (l - 1), bytes0)
        cbytes = max(c1 + max(c2 - c1, 0.0) * (l - 1), cbytes0)
        fit = {"flops_l1": f1, "flops_l2": f2, "raw_flops": flops0,
               "raw_bytes": bytes0, "raw_coll": cbytes0,
               "degenerate": bool(f2 < f1 or b2 < b1 or c2 < c1)}
    else:
        flops, hbytes, cbytes = flops0, bytes0, cbytes0
        fit = {}

    mem_total = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
                 mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    rl = Roofline(
        arch=arch, shape=shape_name, step=step_kind, mesh=mesh_name,
        chips=chips,
        flops_per_chip=flops,
        hbm_bytes_per_chip=hbytes,
        coll_bytes_per_chip=cbytes,
        model_flops_global=model_flops(cfg, shape_cfg),
        mem_per_chip=float(max(mem_total, 0)),
    )
    art = {
        **rl.row(),
        "lower_s": t_lower, "compile_s": t_compile, "layer_fit": fit,
        "collective_bytes": coll, "collective_counts": counts,
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "ok": True,
    }
    print(f"[dryrun] {mesh_name}/{arch}/{shape_name}/{step_kind}: "
          f"mem={art['mem_per_chip_gib']:.2f} GiB/chip "
          f"t_comp={rl.t_compute*1e3:.2f}ms t_mem={rl.t_memory*1e3:.2f}ms "
          f"t_coll={rl.t_collective*1e3:.2f}ms -> {rl.bottleneck} "
          f"(compile {t_compile:.1f}s)")
    print(f"[dryrun]   memory_analysis: {mem}")
    return art


def run_cells(archs, shapes, meshes, out_dir, cfg_override=None):
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
        for arch in archs:
            cfg = get_config(arch)
            wanted = shapes or list(SHAPES)       # all 4 => 40 cells/mesh
            for shape_name in wanted:
                if (shape_name == "long_500k"
                        and not cfg.supports_long_context):
                    results.append({"arch": arch, "shape": shape_name,
                                    "mesh": mesh_name, "ok": False,
                                    "skipped": "full-attention arch: 524k dense"
                                               " KV decode is the quadratic"
                                               " regime this shape excludes"})
                    continue
                tag = f"{mesh_name}_{arch}_{shape_name}"
                try:
                    art = lower_cell(arch, shape_name, mesh, mesh_name,
                                     cfg_override=cfg_override)
                except Exception as e:   # a failure here is a bug — record it
                    traceback.print_exc()
                    art = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "ok": False, "error": f"{type(e).__name__}: {e}"}
                results.append(art)
                with open(os.path.join(out_dir, tag + ".json"), "w") as f:
                    json.dump(art, f, indent=2, default=str)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(results, f, indent=2, default=str)
    bad = [r for r in results if not r.get("ok") and "skipped" not in r]
    print(f"[dryrun] {len(results)} cells, {len(bad)} failures")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="beyond-paper config: flash attention everywhere")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = None if (args.all or not args.shape) else [args.shape]
    override = None
    if args.optimized:
        override = lambda c: dataclasses.replace(c, attention_impl="flash")
    run_cells(archs, shapes, meshes, args.out, cfg_override=override)


if __name__ == "__main__":
    main()
