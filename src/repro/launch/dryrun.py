import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the right step (train_step / prefill / serve_step) against
     ShapeDtypeStruct inputs with the runtime's shardings,
  3. compiles (the pass/fail gate: sharding mismatches, OOM-at-compile and
     unsupported collectives all fail here),
  4. records memory_analysis / cost_analysis / the while-aware text
     analysis (launch.hloanalysis) and the three roofline terms,
  5. writes one JSON per cell into experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax

from ..configs import SHAPES, all_archs, get_config, supports_shape
from ..configs.base import ModelConfig, ShapeConfig
from ..optim import AdamWConfig
from ..runtime import (
    ShardRules,
    batch_shardings,
    cache_shardings,
    param_shardings,
)
from ..runtime.actshard import mesh_constrainer, use_constrainer
from .hloanalysis import HBM_BW, ICI_BW, PEAK_FLOPS, analyze
from .mesh import auto_mesh, make_production_mesh
from .steps import (
    cache_specs,
    input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)

HBM_PER_CHIP = 16 * 1024**3  # v5e: 16 GiB


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6*N*D (train) / 2*N*D (fwd-only), N = active params (MoE-aware)."""
    n = cfg.param_counts()["active"]
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n * d
    return 2.0 * n * shape.global_batch  # decode: one token per row


def lower_cell(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh,
    rules: Optional[ShardRules] = None,
    donate: bool = True,
):
    """Returns (lowered, jitted, specs) for one cell."""
    rules = rules or ShardRules()
    with use_constrainer(mesh_constrainer(mesh, rules, shape.global_batch)):
        return _lower_cell_inner(cfg, shape, mesh, rules, donate)


def _lower_cell_inner(cfg, shape, mesh, rules, donate):
    specs = input_specs(cfg, shape)
    psh = param_shardings(specs["params"], cfg, mesh, rules)
    if shape.kind == "train":
        # ZeRO over the pod axis: optimizer state and gradients shard over
        # ("pod", fsdp) on the multi-pod mesh — grads reduce-scatter across
        # pods instead of all-reduce, opt state is never replicated.
        opt_rules = rules
        if "pod" in mesh.axis_names and isinstance(rules.fsdp, str):
            opt_rules = dataclasses.replace(rules, fsdp=("pod", rules.fsdp))
        osh = param_shardings(specs["opt_state"], cfg, mesh, opt_rules)
        gsh = param_shardings(specs["params"], cfg, mesh, opt_rules)
        bsh = batch_shardings(
            specs["batch"], mesh, rules, global_batch=shape.global_batch
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..runtime.sharding import batch_pspec

        bspec = batch_pspec(mesh, rules, shape.global_batch // max(cfg.microbatch, 1))

        def micro_sharding_fn(tree):
            def c(x):
                spec = P(None, *(list(bspec) + [None] * (x.ndim - 2)))
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, spec)
                )
            return jax.tree.map(c, tree)

        step = make_train_step(
            cfg, AdamWConfig(moments=cfg.opt_moments), grad_shardings=gsh,
            micro_sharding_fn=micro_sharding_fn if cfg.microbatch > 1 else None,
        )
        jitted = jax.jit(
            step,
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1) if donate else (),
        )
        lowered = jitted.lower(specs["params"], specs["opt_state"], specs["batch"])
    elif shape.kind == "prefill":
        bsh = batch_shardings(
            specs["batch"], mesh, rules, global_batch=shape.global_batch
        )
        csh_out = cache_shardings(
            cache_specs(cfg, shape.global_batch, shape.seq_len), cfg, mesh, rules
        )
        step = make_prefill_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(psh, bsh),
            out_shardings=(None, csh_out),
        )
        lowered = jitted.lower(specs["params"], specs["batch"])
    else:  # decode
        csh = cache_shardings(specs["cache"], cfg, mesh, rules)
        tsh = batch_shardings(
            specs["tokens"], mesh, rules, global_batch=shape.global_batch
        )
        step = make_serve_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(psh, csh, tsh),
            out_shardings=(tsh, csh),
            donate_argnums=(1,) if donate else (),
        )
        lowered = jitted.lower(specs["params"], specs["cache"], specs["tokens"])
    return lowered, jitted, specs


def _parse_overrides(pairs):
    """["k=v", ...] -> dict with literal-ish coercion."""
    out = {}
    for kv in pairs or []:
        k, v = kv.split("=", 1)
        if v in ("true", "True"):
            v = True
        elif v in ("false", "False"):
            v = False
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        out[k] = v
    return out


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    rules: Optional[ShardRules] = None,
    scan: Optional[bool] = None,
    out_dir: str = "experiments/dryrun",
    tag: str = "",
    cfg_overrides: Optional[Dict] = None,
    mesh_shape: Optional[tuple] = None,
) -> Dict:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, reason = supports_shape(cfg, shape)
    result: Dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
        "status": "skipped", "reason": reason,
    }
    if not ok:
        _write(result, out_dir)
        return result

    # scan-over-layers: small HLO, while-aware analyzer keeps costs exact
    if scan is None:
        scan = cfg.family == "lm" and shape.kind == "train"
    cfg = dataclasses.replace(cfg, scan_layers=scan)

    if mesh_shape is not None:  # hillclimb: re-factor the 256 chips
        axes = ("pod", "data", "model")[-len(mesh_shape):]
        mesh = auto_mesh(mesh_shape, axes)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        lowered, jitted, specs = lower_cell(cfg, shape, mesh, rules)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        result.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-2000:])
        _write(result, out_dir)
        return result

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    rep = analyze(compiled.as_text())

    per_dev_bytes = (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes
    )
    terms = {
        "t_compute": rep.flops / PEAK_FLOPS,
        "t_memory": rep.hbm_bytes / HBM_BW,
        "t_collective": rep.collective_bytes / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_flops_global = rep.flops * n_chips
    result.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        scan_layers=scan,
        memory={
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_bytes": per_dev_bytes,
            "hbm_frac": per_dev_bytes / HBM_PER_CHIP,
            "fits": bool(per_dev_bytes <= HBM_PER_CHIP),
        },
        xla_cost_analysis={
            "flops": ca.get("flops"),
            "bytes_accessed": ca.get("bytes accessed"),
        },
        hlo={**rep.as_dict()},
        roofline={
            **terms,
            "dominant": dominant,
            "model_flops_global": mf,
            "hlo_flops_global": hlo_flops_global,
            "useful_ratio": mf / hlo_flops_global if hlo_flops_global else None,
            "step_time_bound_s": max(terms.values()),
            "mfu_bound": mf / (max(terms.values()) * n_chips * PEAK_FLOPS)
            if max(terms.values()) > 0 else None,
        },
    )
    _write(result, out_dir)
    return result


def _write(result: Dict, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"_{result['tag']}" if result.get("tag") else ""
    fn = f"{result['arch']}_{result['shape']}_{result['mesh']}{tag}.json"
    with open(os.path.join(out_dir, fn), "w") as f:
        json.dump(result, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--scan", default=None, choices=[None, "on", "off"])
    ap.add_argument("--seq-sharded", action="store_true")
    ap.add_argument("--no-ep", action="store_true")
    ap.add_argument("--no-kv-heads", action="store_true")
    ap.add_argument("--set", nargs="*", default=None, metavar="K=V",
                    help="ModelConfig overrides, e.g. remat_policy=dots")
    ap.add_argument("--rules", nargs="*", default=None, metavar="K=V",
                    help="ShardRules overrides, e.g. batch=pod,data,model")
    ap.add_argument("--mesh-shape", default=None,
                    help="re-factor chips, e.g. 32,8 (hillclimb)")
    args = ap.parse_args()

    rules = ShardRules(
        expert_parallel=not args.no_ep,
        kv_head_sharded=not args.no_kv_heads,
        seq_sharded_acts=args.seq_sharded,
    )
    rule_over = _parse_overrides(args.rules)
    if "batch" in rule_over:
        rule_over["batch"] = tuple(rule_over["batch"].split(","))
    if rule_over:
        rules = dataclasses.replace(rules, **rule_over)
    cfg_over = _parse_overrides(args.set)
    mesh_shape = tuple(int(x) for x in args.mesh_shape.split(",")) if args.mesh_shape else None
    scan = None if args.scan is None else (args.scan == "on")
    archs = all_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                r = run_cell(arch, shape, mk, rules, scan, args.out, args.tag,
                             cfg_overrides=cfg_over, mesh_shape=mesh_shape)
                line = f"{arch:28s} {shape:12s} {mk:6s} {r['status']:8s}"
                if r["status"] == "ok":
                    rf = r["roofline"]
                    line += (
                        f" compile={r['compile_s']:7.1f}s"
                        f" mem/dev={r['memory']['per_device_bytes']/2**30:6.2f}GiB"
                        f" dom={rf['dominant'][2:]:10s}"
                        f" t=({rf['t_compute']*1e3:8.3f},{rf['t_memory']*1e3:8.3f},"
                        f"{rf['t_collective']*1e3:8.3f})ms"
                    )
                elif r["status"] == "FAILED":
                    line += " " + r.get("error", "")[:90]
                else:
                    line += " " + r.get("reason", "")[:70]
                print(line, flush=True)


if __name__ == "__main__":
    main()
