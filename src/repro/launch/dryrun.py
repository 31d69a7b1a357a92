import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, partitions, and compiles on the production mesh —
without touching real hardware.  See the module-leading XLA_FLAGS line:
512 placeholder host devices, set before ANY jax import.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # 40 baselines
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Per run: compiled.memory_analysis() (fits?), cost_analysis() (FLOPs/bytes),
collective bytes parsed from partitioned HLO, and the three roofline terms.
Records are appended to benchmarks/results/dryrun.jsonl.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import get_config, ARCH_IDS, ALIASES
from repro.core import llm_a3c
from repro.distributed import ctx, sharding
from repro.kernels import dispatch
from repro.launch import hlo_analysis, traffic
from repro.launch import specs as specs_mod
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import model as M
from repro.optim import optimizers as opt_mod

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "results")


def _mem_summary(compiled):
    try:
        ma = compiled.memory_analysis()
        return {
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "peak_bytes": getattr(ma, "peak_memory_in_bytes", None)
                if hasattr(ma, "peak_memory_in_bytes") else None,
            "generated_code_bytes":
                getattr(ma, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def lower_case(arch: str, shape_id: str, *, multi_pod: bool = False,
               fsdp: bool = True, mode: str = "sync",
               verbose: bool = True) -> dict:
    """Lower + compile one (arch x shape x mesh).  mode: sync | delayed."""
    cfg = get_config(arch)
    cfg = specs_mod.maybe_long_variant(cfg, shape_id)
    if shape_id == "long_500k" and \
            specs_mod.LONG_DECODE.get(get_config(arch).name) is None:
        return {"arch": arch, "shape": shape_id, "status": "skipped",
                "reason": "enc-dec / full attention (DESIGN.md §4)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    kind, in_specs = specs_mod.input_specs(cfg, shape_id)
    bsz = specs_mod.INPUT_SHAPES[shape_id]["batch"]

    p_specs = specs_mod.params_specs(cfg)
    p_shard = sharding.param_shardings(cfg, mesh, p_specs, fsdp=fsdp)
    rules = sharding.activation_rules(mesh, batch_size=bsz, cfg=cfg)

    t0 = time.time()
    # install the mesh as the kernel-dispatch target: backend resolution
    # keys off the mesh's device platform (the lowering target), and the
    # dispatcher shard_maps the Pallas kernels over (data, heads)
    dispatch.clear_decision_log()
    with jax.set_mesh(mesh), ctx.use_mesh(mesh), \
            ctx.sharding_rules(rules):
        if kind == "train" and mode == "delayed":
            # T3: paper-faithful pod-scale asynchrony — each pod updates a
            # local replica for H steps, merging on the 'pod' axis.
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import delayed_sync
            assert multi_pod, "delayed mode needs the pod axis"
            n_pods = mesh.shape["pod"]
            opt = opt_mod.shared_rmsprop()

            def prepend_pod(sh):
                # the pod axis becomes the replica-group dim: strip it from
                # any inner (FSDP) spec entries before prepending
                def strip(a):
                    if isinstance(a, tuple):
                        t = tuple(x for x in a if x != "pod")
                        return t if len(t) > 1 else (t[0] if t else None)
                    return None if a == "pod" else a
                spec = tuple(strip(a) for a in tuple(sh.spec))
                return NamedSharding(mesh, P(*(("pod",) + spec)))

            pg_specs = jax.eval_shape(
                lambda t: delayed_sync.replicate(t, n_pods), p_specs)
            pg_shard = jax.tree.map(prepend_pod, p_shard)
            og_specs = jax.eval_shape(
                lambda t: delayed_sync.replicate(t, n_pods),
                jax.eval_shape(opt.init, p_specs))
            og_shard = {"g": pg_shard}
            # per-pod batch shard: group dim on 'pod', batch dim on 'data'
            bg_specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((n_pods, bsz // n_pods)
                                               + x.shape[1:], x.dtype)
                if x.shape[0] == bsz else
                jax.ShapeDtypeStruct((n_pods,) + x.shape, x.dtype),
                in_specs)
            inner = sharding.batch_shardings(mesh, in_specs,
                                             batch_size=bsz)

            def pod_batch_shard(sh, leaf):
                spec = tuple(sh.spec)
                # replace the ('pod','data') batch spec with 'data' and
                # prepend 'pod' for the group dim
                spec = tuple(("data",) if a == ("pod", "data") else a
                             for a in spec)
                return NamedSharding(mesh, P(*(("pod",) + spec)))

            bg_shard = jax.tree.map(pod_batch_shard, inner, in_specs)
            ds_step = delayed_sync.make_delayed_train_step(
                cfg, opt, n_groups=n_pods, merge_interval=8)
            lowered = jax.jit(
                ds_step,
                in_shardings=(pg_shard, og_shard, bg_shard, None),
                out_shardings=(pg_shard, og_shard, None),
            ).lower(pg_specs, og_specs, bg_specs,
                    jax.ShapeDtypeStruct((), jnp.int32))
        elif kind == "train":
            opt = opt_mod.shared_rmsprop()
            opt_specs = jax.eval_shape(opt.init, p_specs)
            opt_shard = {"g": p_shard}
            b_shard = sharding.batch_shardings(mesh, in_specs,
                                               batch_size=bsz)
            step_spec = jax.ShapeDtypeStruct((), jnp.int32)
            train_step = llm_a3c.make_train_step(cfg, opt)
            lowered = jax.jit(
                train_step,
                in_shardings=(p_shard, opt_shard, b_shard, None),
                out_shardings=(p_shard, opt_shard, None),
            ).lower(p_specs, opt_specs, in_specs, step_spec)
        elif kind == "prefill":
            b_shard = sharding.batch_shardings(mesh, in_specs,
                                               batch_size=bsz)

            def prefill(params, batch):
                out = M.forward(cfg, params, batch)
                # serving prefill returns ONLY the next-token logits; XLA
                # narrows the vocab matmul to the last position (without
                # this, whisper's replicated odd-vocab logits peak at
                # >100GB/device)
                return {"logits": out["logits"][:, -1],
                        "value": out.get("value",
                                         out["logits"][:, -1, :1])[:, -1]}

            lowered = jax.jit(
                prefill, in_shardings=(p_shard, b_shard),
            ).lower(p_specs, in_specs)
        else:  # decode
            serve_step = llm_a3c.make_serve_step(cfg)
            b_shard = sharding.batch_shardings(mesh, in_specs["batch"],
                                               batch_size=bsz)
            c_shard = sharding.cache_shardings(cfg, mesh, in_specs["cache"],
                                               batch_size=bsz)
            # serving replicas store bf16 weights sharded over `model` only
            # (no FSDP): removes the per-token f32 weight gathers
            # (perf iter #6)
            p_serve_specs = jax.eval_shape(
                lambda t: M.cast_params(cfg, t), p_specs)
            p_serve_shard = sharding.param_shardings(cfg, mesh,
                                                     p_serve_specs,
                                                     fsdp=False)
            dec_rules = {**rules,
                         **sharding.decode_rules(cfg, mesh, batch_size=bsz)}
            with ctx.sharding_rules(dec_rules):
                lowered = jax.jit(
                    serve_step,
                    in_shardings=(p_serve_shard, c_shard, b_shard, None,
                                  None),
                    out_shardings=(None, None, c_shard),
                ).lower(p_serve_specs, in_specs["cache"], in_specs["batch"],
                        in_specs["pos"], in_specs["key"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    mem = _mem_summary(compiled)
    hlo_text = compiled.as_text()
    weighted = hlo_analysis.weighted_totals(hlo_text)
    coll = {k: weighted[k] for k in ("all-gather", "all-reduce",
                                     "reduce-scatter", "all-to-all",
                                     "collective-permute", "total")}
    n = cfg.param_count()
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = bsz * specs_mod.INPUT_SHAPES[shape_id]["seq"]
        model_flops = 6 * n_active * tokens
    elif kind == "prefill":
        tokens = bsz * specs_mod.INPUT_SHAPES[shape_id]["seq"]
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * bsz
    hbm = traffic.hbm_bytes(cfg, shape_id, kind, n_chips)
    # dot shapes in the partitioned module are per-device slices, so the
    # weighted flops are already per-chip; scale to whole-program for the
    # MODEL_FLOPS ratio.
    hlo_flops = weighted["flops"] * n_chips
    terms = hlo_analysis.roofline_terms(
        hlo_flops=hlo_flops, hbm_bytes=hbm, collective_total=coll["total"],
        n_chips=n_chips, peak_flops=PEAK_FLOPS_BF16,
        hbm_bw=HBM_BW, ici_bw=ICI_BW)
    rec = {
        "arch": arch, "variant": cfg.name, "shape": shape_id, "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16", "mode": mode,
        "status": "ok", "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
        "params": n, "active_params": n_active,
        "hlo_flops": hlo_flops,
        "xla_cost_flops_unweighted": float(cost.get("flops", 0.0)),
        "hbm_bytes_per_chip": hbm,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / hlo_flops
                               if hlo_flops else None),
        "collective_bytes": coll,
        "memory": mem,
        "roofline": terms,
        # which kernels this lowering picked, and why any call fell back
        "kernel_dispatch": hlo_analysis.kernel_dispatch_summary(),
    }
    if verbose:
        print(json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="arch id (e.g. qwen2-72b); default: all")
    ap.add_argument("--shape", default=None,
                    choices=list(specs_mod.INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--mode", default="sync", choices=["sync", "delayed"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ALIASES)
    shapes = [args.shape] if args.shape else list(specs_mod.INPUT_SHAPES)

    os.makedirs(RESULTS, exist_ok=True)
    out_path = args.out or os.path.join(RESULTS, "dryrun.jsonl")
    results = []
    for arch in archs:
        for shape in shapes:
            print(f"=== {arch} x {shape} "
                  f"({'2x16x16' if args.multi_pod else '16x16'}) ===",
                  flush=True)
            try:
                rec = lower_case(arch, shape, multi_pod=args.multi_pod,
                                 fsdp=not args.no_fsdp, mode=args.mode)
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if args.multi_pod else "16x16",
                       "status": "error", "error": str(e)[:2000]}
            results.append(rec)
            with open(out_path, "a") as f:
                f.write(json.dumps(rec, default=str) + "\n")
    ok = sum(r["status"] == "ok" for r in results)
    skipped = sum(r["status"] == "skipped" for r in results)
    print(f"\n{ok} ok / {skipped} skipped / "
          f"{len(results) - ok - skipped} failed of {len(results)}")
    return 0 if ok + skipped == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
