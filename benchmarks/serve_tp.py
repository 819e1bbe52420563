"""Tensor-parallel serving: per-device KV bytes and attention FLOPs vs tp.

Every arm serves the SAME trace through ``repro.serve.scheduler
.ServeSession`` (paged layout, greedy, identical buckets/pool/slots); the
oracle arm runs with no mesh and each tp arm runs under a ``(tp,)``-device
``"model"`` mesh (params Megatron-split by the ``param_pspec`` rules, the
paged pool sharded along the KV-head dim by ``cache_pspecs(layout=
"paged")``).  The claims this bench pins (ISSUE PR-8, all asserted):

* **parity** — greedy tokens bit-identical to the no-mesh oracle, and
  tick-for-tick schedule parity (same tick count for the same trace:
  sharding changes WHERE bytes live, never what the scheduler decides);
* **zero recompiles** after warmup on every arm (jit caches keyed on
  operand shardings — the warmup normalization must cover them all);
* **1/tp scaling** — measured per-device KV-pool bytes
  (``pool_bytes_per_device``: ``Sharding.shard_shape`` over the pool
  leaves) and analytic per-device attention FLOPs per full-window decode
  tick (``4 * slots * (H/tp) * hd * max_len * layers``: QK^T + AV at 2
  FLOPs/MAC, heads split over the mesh) both scale exactly as ``1/tp``.

Runs on CPU by forcing 8 host devices — XLA_FLAGS is set before jax is
imported, so this module must NOT import jax at the top.

    PYTHONPATH=src python benchmarks/serve_tp.py
    PYTHONPATH=src python benchmarks/serve_tp.py --smoke --out /tmp/b.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

BUCKETS = (8, 16, 32)
NEW_CHOICES = (4, 8, 12, 16)
MAX_LEN = 64
BLOCK_SIZE = 8
NUM_BLOCKS = 64
TPS = (1, 2, 4)
FORCED_DEVICES = 8


def _ensure_devices():
    """Force a multi-device CPU before jax initializes (no-op if the flag —
    or a real multi-device backend — is already present)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={FORCED_DEVICES}"
        ).strip()


def _tiny_cfg():
    from repro.configs import get_config, reduced_config

    # head counts divisible by every tp arm (4 KV heads / tp=4 -> 1 per shard)
    return dataclasses.replace(
        reduced_config(get_config("granite-3-2b")),
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=512, remat=False, q_chunk=64, dtype="float32",
    )


def build_trace(n: int, vocab: int, seed: int = 0, max_new: int | None = None):
    rng = np.random.default_rng(seed)
    choices = [c for c in NEW_CHOICES if max_new is None or c <= max_new]
    trace, t = [], 0
    for _ in range(n):
        t += int(rng.poisson(1.0))
        prompt = rng.integers(0, vocab,
                              int(rng.integers(2, BUCKETS[-1] + 1))).astype(np.int32)
        trace.append((prompt, int(choices[rng.integers(len(choices))]), t))
    return trace


def attn_flops_per_tick_per_device(cfg, num_slots: int, tp: int) -> int:
    """Analytic decode-attention FLOPs per device for one full-window tick:
    QK^T and AV are each ``2 * hd`` FLOPs per (query head, key) pair, each
    shard holds ``H/tp`` query heads, and the attended window is bounded by
    ``max_len`` rows of the block pool."""
    return 4 * num_slots * (cfg.num_heads // tp) * cfg.head_dim * MAX_LEN \
        * cfg.num_layers


def run_arm(cfg, params, trace, *, tp: int | None, num_slots: int = 4):
    """Warm pass (compiles every program under this arm's mesh), then a
    timed fresh-session pass.  Returns (tok/s, results, session, recompiles,
    seconds, live per-device pool bytes)."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.serve import cache as C
    from repro.serve.scheduler import ServeSession, scheduler_compile_stats

    mesh = None if tp is None else make_mesh((tp,), ("model",))

    def serve():
        sess = ServeSession(
            cfg, params, num_slots=num_slots, max_len=MAX_LEN,
            prompt_buckets=BUCKETS, cache_layout="paged",
            block_size=BLOCK_SIZE, num_blocks=NUM_BLOCKS, mesh=mesh,
        )
        sess.warmup()
        for p, n, t in trace:
            sess.submit(p, max_new=n, arrival=t)
        sess.run()
        return sess

    warm = serve()
    before = scheduler_compile_stats()
    t0 = time.perf_counter()
    sess = serve()
    dt = time.perf_counter() - t0
    recompiles = sum(scheduler_compile_stats().values()) - sum(before.values())
    useful = sum(len(r.tokens) for r in sess.results.values())
    del warm
    return (useful / dt, sess.results, sess, recompiles, dt,
            C.pool_bytes_per_device(sess.cache))


def bench(requests: int = 32, num_slots: int = 4, seed: int = 0,
          max_new: int | None = None):
    _ensure_devices()
    import jax

    from repro.models.transformer import init_params
    from repro.serve.scheduler import SchedulerStats, _resolve_cache_donation

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = build_trace(requests, cfg.vocab_size, seed=seed, max_new=max_new)

    base_tps, base_res, base_sess, base_rc, base_dt, base_bytes = run_arm(
        cfg, params, trace, tp=None, num_slots=num_slots)
    base_st = base_sess.stats
    base_flops = attn_flops_per_tick_per_device(cfg, num_slots, 1)

    mismatches = 0
    recompiles = base_rc
    schedule_divergence = 0
    arms = []
    tps = [t for t in TPS if t <= jax.device_count()]
    for tp in tps:
        tok_s, res, sess, rc, dt, pool_bytes = run_arm(
            cfg, params, trace, tp=tp, num_slots=num_slots)
        st = sess.stats
        mismatches += sum(
            not np.array_equal(base_res[rid].tokens, res[rid].tokens)
            for rid in base_res)
        recompiles += rc
        schedule_divergence += int(st.ticks != base_st.ticks)
        flops = attn_flops_per_tick_per_device(cfg, num_slots, tp)
        arms.append({
            "tp": tp,
            "devices": st.devices,
            "tok_s": round(tok_s, 1),
            "ticks": st.ticks,
            "seconds": round(dt, 4),
            "kv_pool_bytes_per_device": pool_bytes,
            "kv_bytes_ratio_vs_tp1": round(pool_bytes / base_bytes, 6),
            "peak_block_bytes_per_device": st.peak_block_bytes_per_device,
            "attn_flops_per_tick_per_device": flops,
            "attn_flops_ratio_vs_tp1": round(flops / base_flops, 6),
        })
    return {
        "bench": "serve_tp",
        "requests": requests,
        "seed": seed,
        "prompt_buckets": list(BUCKETS),
        "max_new_choices": [c for c in NEW_CHOICES
                            if max_new is None or c <= max_new],
        "max_len": MAX_LEN,
        "block_size": BLOCK_SIZE,
        "num_blocks": NUM_BLOCKS,
        "num_slots": num_slots,
        "devices_visible": jax.device_count(),
        "cache_donation": list(_resolve_cache_donation()),
        "useful_tokens": sum(len(r.tokens) for r in base_res.values()),
        "oracle_tok_s": round(base_tps, 1),
        "oracle_ticks": base_st.ticks,
        "oracle_kv_pool_bytes_per_device": base_bytes,
        "arms": arms,
        "token_mismatches": mismatches,
        "schedule_divergence": schedule_divergence,
        "recompiles_after_warmup": recompiles,
        "field_docs": dict(SchedulerStats.DOCS),
    }


def run(requests: int = 32):
    """benchmarks/run.py entry: (name, us_per_call, derived) rows."""
    r = bench(requests=requests)
    rows = []
    for arm in r["arms"]:
        rows.append((
            f"serve/tp{arm['tp']}",
            1e6 / arm["tok_s"],
            f"{arm['tok_s']} tok/s, {arm['kv_pool_bytes_per_device']} "
            f"KV B/dev ({arm['kv_bytes_ratio_vs_tp1']}x tp1), "
            f"mismatches={r['token_mismatches']}",
        ))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="miniature trace: exercises every oracle without "
                         "the full request count (CI gate for the harness)")
    ap.add_argument("--out", default="BENCH_serve_tp.json")
    args = ap.parse_args()
    max_new = None
    if args.smoke:
        args.requests = min(args.requests, 8)
        max_new = 8
    r = bench(requests=args.requests, num_slots=args.num_slots,
              seed=args.seed, max_new=max_new)
    with open(args.out, "w") as f:
        json.dump(r, f, indent=2)
        f.write("\n")
    print(json.dumps({k: v for k, v in r.items() if k != "field_docs"},
                     indent=2))
    failures = []
    if r["token_mismatches"]:
        failures.append(
            f"{r['token_mismatches']} request outputs differ from the "
            "no-mesh oracle — TP broke greedy-token parity")
    if r["schedule_divergence"]:
        failures.append(
            f"{r['schedule_divergence']} arms diverged from the oracle tick "
            "schedule")
    if r["recompiles_after_warmup"]:
        failures.append(
            f"{r['recompiles_after_warmup']} recompiles after warmup")
    for arm in r["arms"]:
        want = 1.0 / arm["tp"]
        if arm["kv_bytes_ratio_vs_tp1"] != want:
            failures.append(
                f"tp={arm['tp']}: KV bytes/device ratio "
                f"{arm['kv_bytes_ratio_vs_tp1']} != {want}")
        if arm["attn_flops_ratio_vs_tp1"] != want:
            failures.append(
                f"tp={arm['tp']}: attention FLOPs/device ratio "
                f"{arm['attn_flops_ratio_vs_tp1']} != {want}")
    if failures:
        raise SystemExit("serve_tp bench FAILED: " + "; ".join(failures))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
