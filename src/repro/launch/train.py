"""Cluster training launcher: pjit + sharded state + checkpoint/restart +
fault monitoring. On real pods each host runs this under its own process
(jax.distributed.initialize); in the container it runs on the local device
mesh. The dry-run (dryrun.py) is the 512-device rehearsal of exactly the
jit/sharding construction used here.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --reduced --steps 20 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, reduced_config
from repro.core.approx import ApproxConfig
from repro.data.synthetic import token_batches
from repro.launch.mesh import batch_axes, make_mesh
from repro.parallel.sharding import batch_pspecs, param_shardings, prune_pspec
from repro.train import optim as O
from repro.train.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.train.fault import PreemptionGuard, StragglerMonitor, run_with_restarts
from repro.train.loop import init_state, make_train_step
from repro.launch.specs import state_specs


def make_mesh_from_args(spec: str):
    devs = np.array(jax.devices())
    if spec == "auto":
        return make_mesh((len(devs), 1), ("data", "model"))
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    return make_mesh(dims, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true", help="CPU-sized variant")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="auto", help='"auto" or e.g. "16x16"')
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--multiplier", default="mul8x8_2")
    ap.add_argument("--mode", default="lowrank")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--max-restarts", type=int, default=2)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    cfg = dataclasses.replace(
        cfg, approx=ApproxConfig(multiplier=args.multiplier, mode=args.mode, band_reg=1e-4)
    )
    mesh = make_mesh_from_args(args.mesh)
    opt = O.OptConfig(lr=3e-4, total_steps=args.steps)

    def job(attempt: int):
        state = init_state(cfg, opt, jax.random.PRNGKey(0),
                           grad_compression=args.grad_compression)
        start = 0
        if latest_step(args.ckpt) is not None:
            state, start = restore_checkpoint(args.ckpt, jax.eval_shape(lambda: state))
            print(f"[attempt {attempt}] resumed at step {start}")

        with jax.set_mesh(mesh):
            psh = param_shardings(cfg, state["params"], mesh)
            ssh = {"params": psh, "opt": O.opt_state_shardings(opt, psh, mesh)}
            if "grad_err" in state:
                ssh["grad_err"] = psh
            state = jax.tree.map(
                lambda a, s: jax.device_put(a, s), state, ssh
            )
            bspec = batch_pspecs(cfg, mesh, "train")
            step_fn = jax.jit(
                make_train_step(cfg, opt, microbatch=args.microbatch,
                                grad_compression=args.grad_compression),
                donate_argnums=(0,),
            )
            mon = StragglerMonitor(threshold=3.0)
            batches = token_batches(cfg.vocab_size, args.batch, args.seq, seed=start)
            with PreemptionGuard() as guard:
                for i in range(start, args.steps):
                    toks, labels = next(batches)
                    batch = {
                        "tokens": jax.device_put(
                            jnp.asarray(toks),
                            NamedSharding(mesh, prune_pspec(mesh, bspec["tokens"], toks.shape)),
                        ),
                        "labels": jnp.asarray(labels),
                    }
                    t0 = time.perf_counter()
                    state, m = step_fn(state, batch)
                    jax.block_until_ready(m["loss"])
                    mon.record(i, time.perf_counter() - t0)
                    if i % 10 == 0:
                        print(f"step {i:4d} loss {float(m['loss']):.4f} "
                              f"gnorm {float(m['grad_norm']):.3f}")
                    if (i + 1) % args.ckpt_every == 0 or guard.should_stop:
                        save_checkpoint(args.ckpt, i + 1, state, keep=3)
                        if guard.should_stop:
                            print("preempted: checkpoint flushed")
                            return state
        save_checkpoint(args.ckpt, args.steps, state, keep=3)
        return state

    run_with_restarts(job, max_restarts=args.max_restarts,
                      on_restart=lambda a, e: print(f"restart {a} after {e!r}"))
    print("training complete")


if __name__ == "__main__":
    main()
