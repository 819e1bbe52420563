import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract memory / cost / collective statistics for the roofline analysis.

The two lines above MUST stay the first statements in this module: jax locks
the device count at first backend init, and the production meshes need 512
placeholder host devices. Nothing else in the repo sets this flag (tests and
benchmarks see the real single CPU device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import functools
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.approx import ApproxConfig
from repro.launch import roofline as R
from repro.launch.mesh import batch_axes, make_production_mesh, mesh_tag
from repro.launch.specs import cache_specs, input_specs, params_specs, state_specs
from repro.parallel.sharding import (
    batch_pspecs,
    cache_pspecs,
    param_shardings,
    prune_pspec,
)
from repro.serve.engine import prefill_step, serve_step
from repro.train import optim as O
from repro.train.loop import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def _named(mesh, pspec_tree, shape_tree):
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def cell_supported(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    """None if runnable; otherwise the documented skip reason."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return "quadratic attention at 524k ctx: skipped for pure full-attention archs (DESIGN.md)"
    return None


def auto_microbatch(cfg: ModelConfig, shape: ShapeConfig, mesh, budget_bytes=4e9) -> int:
    """Grad-accumulation split keeping the per-device remat carry stack
    (L x B_mb/dp x S x d bf16) under ~4 GB."""
    dp = 1
    for a in batch_axes(mesh):
        dp *= int(mesh.shape[a])
    per_seq = cfg.num_layers * shape.seq_len * cfg.d_model * 2
    budget_seqs = max(1, int(budget_bytes // max(per_seq, 1)))
    b_per_dev = max(1, shape.global_batch // dp)
    mb = 1
    while b_per_dev // mb > budget_seqs and mb < b_per_dev:
        mb *= 2
    return mb


def build_lowerable(cfg: ModelConfig, shape: ShapeConfig, mesh, opt_cfg: O.OptConfig,
                    *, microbatch: Optional[int] = None,
                    frozen_weights: bool = False,
                    grad_compression: bool = False):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    binputs = input_specs(cfg, shape)
    bspec = batch_pspecs(cfg, mesh, shape.kind)
    bshard = {
        k: NamedSharding(mesh, prune_pspec(mesh, bspec.get(k, P()), binputs[k].shape))
        for k in binputs
    }

    if shape.kind == "train":
        sspecs = state_specs(cfg, opt_cfg)
        psh = param_shardings(cfg, sspecs["params"], mesh)
        ssh = {"params": psh, "opt": O.opt_state_shardings(opt_cfg, psh, mesh)}
        if grad_compression:
            from repro.train.loop import init_state  # structure only

            sspecs = dict(sspecs)
            sspecs["grad_err"] = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), sspecs["params"]
            )
            ssh = dict(ssh)
            ssh["grad_err"] = psh
        if microbatch is None:
            microbatch = auto_microbatch(cfg, shape, mesh)
        fn = make_train_step(cfg, opt_cfg, microbatch=microbatch,
                             grad_compression=grad_compression)
        jfn = jax.jit(fn, in_shardings=(ssh, bshard), donate_argnums=(0,))
        return jfn, (sspecs, binputs)

    pspecs = params_specs(cfg, frozen=frozen_weights and cfg.approx.is_quantized)
    psh = param_shardings(cfg, pspecs, mesh)

    if shape.kind == "prefill":
        fn = functools.partial(prefill_step, cfg)
        jfn = jax.jit(fn, in_shardings=(psh, bshard))
        return jfn, (pspecs, binputs)

    # decode
    cspecs = cache_specs(cfg, shape)
    csh = cache_pspecs(cfg, mesh, cspecs)
    lens = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    lsh = NamedSharding(mesh, prune_pspec(mesh, P(batch_axes(mesh)), lens.shape))
    fn = functools.partial(serve_step, cfg)
    jfn = jax.jit(fn, in_shardings=(psh, csh, bshard, lsh), donate_argnums=(1,))
    return jfn, (pspecs, cspecs, binputs, lens)


def _measure(cfg, shape, mesh, opt_cfg, *, microbatch, frozen_weights=False,
             grad_compression=False):
    """Lower+compile one variant; return (flops, bytes, wire)/device + times."""
    t0 = time.time()
    with jax.set_mesh(mesh):
        jfn, args = build_lowerable(cfg, shape, mesh, opt_cfg, microbatch=microbatch,
                                    frozen_weights=frozen_weights,
                                    grad_compression=grad_compression)
        lowered = jfn.lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = R.parse_collectives(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": R.estimate_hbm_bytes(hlo),
        "bytes_raw": float(cost.get("bytes accessed", 0.0)),
        "wire": coll.total_bytes,
        "wire_by_op": coll.per_op,
        "coll_counts": coll.counts,
        "wall_s": time.time() - t0,
        "compiled": compiled,
    }


def extract_costs(cfg: ModelConfig, shape: ShapeConfig, mesh, opt_cfg,
                  *, frozen_weights: bool = False, grad_compression: bool = False,
                  microbatch_override: Optional[int] = None) -> Dict[str, Any]:
    """Two-point extrapolated per-device costs.

    HLO cost analysis counts while-loop bodies ONCE, so the production
    lowering (layer-scan x microbatch-scan x chunk-scans) undercounts. We
    therefore lower two UNROLLED reduced-depth variants (1 and 2 layer
    units, chunk scans disabled, experts unrolled, microbatch=1 at the
    per-microbatch batch size) and extrapolate linearly in depth:

        cost(L) = fixed + units(L) * per_unit     (exact: depth-linear HLO)
        total   = n_microbatches * cost(L_full)

    Collective bytes and HBM bytes extrapolate the same way.
    """
    unit = cfg.attn_every if cfg.family == "hybrid" else 1
    mb = auto_microbatch(cfg, shape, mesh) if shape.kind == "train" else 1
    if microbatch_override is not None:
        mb = microbatch_override
    b_mb = max(1, shape.global_batch // mb)
    small = dict(
        scan_layers=False,
        unroll_experts=True,
        q_chunk=shape.seq_len if shape.kind != "decode" else cfg.q_chunk,
        ssm_chunk=shape.seq_len if shape.kind != "decode" else cfg.ssm_chunk,
    )
    cfg1 = dataclasses.replace(cfg, num_layers=unit, **small)
    cfg2 = dataclasses.replace(cfg, num_layers=2 * unit, **small)
    shape_mb = dataclasses.replace(shape, global_batch=b_mb)
    m1 = _measure(cfg1, shape_mb, mesh, opt_cfg, microbatch=1,
                  frozen_weights=frozen_weights, grad_compression=grad_compression)
    m2 = _measure(cfg2, shape_mb, mesh, opt_cfg, microbatch=1,
                  frozen_weights=frozen_weights, grad_compression=grad_compression)
    n_units = cfg.num_layers // unit
    out: Dict[str, Any] = {"microbatches": mb, "n_units": n_units}
    for key in ("flops", "bytes", "bytes_raw", "wire"):
        per_unit = m2[key] - m1[key]
        fixed = m1[key] - per_unit
        out[key] = mb * (fixed + n_units * per_unit)
        out[f"{key}_per_unit"] = per_unit
        out[f"{key}_fixed"] = fixed
    out["wire_by_op"] = {
        k: m1["wire_by_op"][k]
        + (m2["wire_by_op"][k] - m1["wire_by_op"][k]) * (n_units - 1)
        for k in m1["wire_by_op"]
    }
    out["cost_extraction_wall_s"] = m1["wall_s"] + m2["wall_s"]
    return out


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool,
    approx_mode: str = "lowrank",
    multiplier: str = "mul8x8_2",
    act_qmax: int = 255,
    w_qmax: int = 255,
    opt_kind: str = "adamw",
    print_analysis: bool = True,
    compute_costs: bool = True,
    frozen_weights: bool = False,
    grad_compression: bool = False,
    microbatch_override: Optional[int] = None,
    cfg_overrides: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    cfg = get_config(arch)
    cfg = dataclasses.replace(
        cfg,
        approx=ApproxConfig(
            multiplier=multiplier, mode=approx_mode, act_qmax=act_qmax, w_qmax=w_qmax
        ),
        **(cfg_overrides or {}),
    )
    shape = SHAPES[shape_name]
    skip = cell_supported(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "skipped": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    opt_cfg = O.OptConfig(kind=opt_kind)

    # 1) production lowering: proves shardability + gives per-device memory
    t0 = time.time()
    with jax.set_mesh(mesh):
        jfn, args = build_lowerable(cfg, shape, mesh, opt_cfg,
                                    frozen_weights=frozen_weights,
                                    grad_compression=grad_compression,
                                    microbatch=microbatch_override)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    n_dev = mesh.devices.size

    # 2) cost extraction: two-point unrolled extrapolation (scan bodies are
    #    counted once by HLO cost analysis — see extract_costs docstring).
    #    The roofline table is single-pod only (assignment); the multi-pod
    #    pass proves the "pod"-axis sharding compiles (--no-costs).
    if not compute_costs:
        result: Dict[str, Any] = {
            "arch": arch, "shape": shape_name, "mesh": mesh_tag(mesh),
            "n_devices": mesh.devices.size, "approx_mode": approx_mode,
            "multiplier": multiplier, "kind": shape.kind,
            "lower_s": t_lower, "compile_s": t_compile,
            "compiled_ok": True,
        }
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes"):
                try:
                    result[attr] = int(getattr(mem, attr))
                except Exception:
                    pass
        if print_analysis:
            print(f"== {arch} {shape_name} mesh={result['mesh']} compile-only ==")
            print("memory_analysis:", mem)
        return result

    costs = extract_costs(cfg, shape, mesh, opt_cfg, frozen_weights=frozen_weights,
                          grad_compression=grad_compression,
                          microbatch_override=microbatch_override)
    flops_dev = costs["flops"]
    bytes_dev = costs["bytes"]
    wire_dev = costs["wire"]

    # model flops: 6*N*D train, 2*N*D forward-only
    n_params = cfg.active_param_count() if cfg.family == "moe" else cfg.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = (6 if shape.kind == "train" else 2) * n_params * tokens

    terms = R.roofline_terms(
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        wire_bytes_per_device=wire_dev,
        n_devices=n_dev,
        model_flops_global=float(mf),
    )

    result: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag(mesh),
        "n_devices": n_dev,
        "approx_mode": approx_mode,
        "multiplier": multiplier,
        "act_qmax": act_qmax,
        "w_qmax": w_qmax,
        "kind": shape.kind,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "params": n_params,
        "tokens": tokens,
        "microbatches": costs["microbatches"],
        "collectives": {"bytes_per_device_by_op": costs["wire_by_op"]},
        "cost_extraction_wall_s": costs["cost_extraction_wall_s"],
        "cost_breakdown": {
            k: costs[k]
            for k in costs
            if k.endswith(("_per_unit", "_fixed")) or k in ("bytes_raw", "n_units")
        },
        **terms,
    }
    if mem is not None:
        for attr in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            try:
                result[attr] = int(getattr(mem, attr))
            except Exception:
                pass

    if print_analysis:
        print(f"== {arch} {shape_name} mesh={result['mesh']} mode={approx_mode} ==")
        print("memory_analysis:", mem)
        print("cost_analysis flops/device: %.3e  bytes/device: %.3e" % (flops_dev, bytes_dev))
        print(
            "roofline: compute %.4fs  memory %.4fs  collective %.4fs  -> %s-bound"
            % (terms["t_compute_s"], terms["t_memory_s"], terms["t_collective_s"], terms["bound"])
        )
        print(
            "useful-flop fraction %.3f  roofline fraction %.4f"
            % (terms["useful_flop_fraction"], terms.get("roofline_fraction", 0.0))
        )
    return result


def cell_list(archs, shapes):
    for a in archs:
        cfg = get_config(a)
        for s in shapes:
            yield a, s


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--approx-mode", default="lowrank",
                    choices=["float", "exact_quant", "lut", "lowrank", "pallas"])
    ap.add_argument("--multiplier", default="mul8x8_2")
    ap.add_argument("--act-qmax", type=int, default=255)
    ap.add_argument("--w-qmax", type=int, default=255)
    ap.add_argument("--out", default=os.environ.get("DRYRUN_OUT", "results/dryrun"))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-costs", action="store_true",
                    help="compile-only (shardability proof; used for multi-pod)")
    args = ap.parse_args(argv)

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for arch, shape in cell_list(archs, shapes):
        for mp in meshes:
            tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}__{args.approx_mode}"
            if args.act_qmax != 255 or args.w_qmax != 255:
                tag += f"__a{args.act_qmax}w{args.w_qmax}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                print("cached:", tag)
                continue
            try:
                res = run_cell(
                    arch, shape, multi_pod=mp, approx_mode=args.approx_mode,
                    multiplier=args.multiplier, act_qmax=args.act_qmax,
                    w_qmax=args.w_qmax, compute_costs=not args.no_costs,
                )
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append((tag, repr(e)))
                continue
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            print("wrote:", path)

    if failures:
        print("\nFAILED CELLS:")
        for t, e in failures:
            print(" ", t, e)
        sys.exit(1)
    print("\nall requested cells passed")


if __name__ == "__main__":
    main()
