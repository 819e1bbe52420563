"""Paged decode-attention: Pallas block-pool kernel vs the XLA block gather.

Two levels, one JSON artifact (``BENCH_attn_paged.json``):

* **kernel micro** — the attention compute alone (projections excluded from
  both arms), sweeping (B, context, block_size): the gather arm is the
  pure-JAX clamp-gather-mask math (``kernels.paged_attention.ref``, jit'd),
  the kernel arm is ``paged_attention_pallas``.  Off-TPU the kernel runs
  through the Pallas **interpreter**, so its wall clock measures the
  interpreter, not the hardware — the honest cross-platform metric is the
  analytic HBM KV traffic each arm implies, reported per call;
* **serve level** — the same mixed-length Poisson trace served through
  ``ServeSession(cache_layout="paged")`` under both ``attn_impl`` arms,
  with the exactness oracles asserted (bit-identical greedy tokens across
  arms, zero recompiles after warmup) and the per-tick KV traffic
  *instrumented from the live session*: the gather arm materializes the
  full ``(num_slots, W*block_size, Hkv, hd)`` transient per layer per
  decode step regardless of how short the resident contexts are, while the
  kernel reads exactly the blocks holding valid positions.  The headline
  ``hbm_bytes_ratio`` (gathered / in-place, mean over decode ticks) is
  therefore >= ``W * block_size / mean_context`` by construction — the
  table-width-vs-actual-context waste the kernel eliminates.

CPU wall-clock swings ~2x under contention (docs/serving.md §Benchmarks):
run timed benches alone; the byte accounting is deterministic either way.

    PYTHONPATH=src python benchmarks/attn_paged_kernel.py
    PYTHONPATH=src python benchmarks/attn_paged_kernel.py --requests 48
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

BUCKETS = (4, 8, 16)
NEW_CHOICES = (2, 4, 4, 8, 16, 48)
MAX_LEN = 64
BLOCK_SIZE = 8

FIELD_DOCS = {
    "micro": "per-(B, context, block_size) attention-only rows; *_us are "
             "post-compile medians (pallas arm interpreted off-TPU — see "
             "interpret_mode), *_kv_bytes are the analytic per-call KV "
             "reads each arm implies",
    "gathered_kv_bytes": "bytes the gather arm moves per call: the full "
                         "B x W x block_size x Hkv x hd K+V transient, "
                         "independent of the actual contexts",
    "inplace_kv_bytes": "bytes the kernel arm reads per call: only blocks "
                        "holding >= 1 valid position (sentinel/empty "
                        "blocks skipped by predicate)",
    "hbm_gathered_bytes_per_tick": "serve level: mean bytes/decode-tick of "
                                   "the per-layer K+V block gather the "
                                   "gather impl materializes (instrumented "
                                   "at the dispatch boundary, so same-step "
                                   "admissions are included)",
    "hbm_inplace_bytes_per_tick": "serve level: mean bytes/decode-tick the "
                                  "kernel reads for the same dispatches — "
                                  "blocks holding valid positions only "
                                  "(sentinel steps re-map to the last held "
                                  "block, so they issue no extra DMA)",
    "hbm_bytes_ratio": "gathered / in-place (the per-tick KV traffic the "
                       "kernel eliminates); >= table_width * block_size / "
                       "mean_context by construction",
    "floor_ratio": "table_width * block_size / mean_context — the lower "
                   "bound hbm_bytes_ratio must clear (equality iff every "
                   "slot were always occupied)",
    "mean_active": "mean resident requests per decode tick",
    "mean_context": "mean block-rounded context per resident request "
                    "(KV positions actually read by the kernel)",
    "token_mismatches": "requests whose greedy tokens differ between "
                        "attn_impl arms (must be 0)",
    "recompiles_after_warmup": "compile-count delta across the timed "
                               "pallas run (must be 0)",
    "interpret_mode": "True when the Pallas arm ran through the "
                      "interpreter (any non-TPU backend) — wall clocks "
                      "then measure the interpreter, trust the byte "
                      "fields",
}


def _tiny_cfg():
    from repro.configs import get_config, reduced_config
    from repro.serve.engine import resolve_execution_mode

    return dataclasses.replace(
        reduced_config(get_config("granite-3-2b")),
        num_layers=4, d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
        d_ff=512, vocab_size=1024, remat=False, q_chunk=64, dtype="float32",
        approx=resolve_execution_mode("exact"),
    )


def _time_med(fn, *args, reps: int = 5) -> float:
    """Median post-compile microseconds per call."""
    jax.block_until_ready(fn(*args))                     # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def micro_rows(seed: int = 0):
    """Attention-only sweep: each row builds a pool + tables whose rows sit
    at mixed contexts around ``context``, then times both arms."""
    from repro.kernels.paged_attention import (
        paged_attention_pallas,
        paged_attention_ref,
    )

    H, n_kv, hd = 4, 2, 64
    item = 4                                             # f32 pool
    rng = np.random.default_rng(seed)
    rows = []
    for B, context, bs in [(2, 24, 4), (2, 24, 8), (8, 24, 8),
                           (8, 56, 8), (4, 56, 4), (8, 40, 16)]:
        W = MAX_LEN // bs
        num_blocks = B * W
        q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(B, n_kv, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(B, n_kv, hd)), jnp.float32)
        # a one-layer pool, (L, blocks, block_size, Hkv * hd)
        kp = jnp.asarray(rng.normal(size=(1, num_blocks, bs, n_kv * hd)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(1, num_blocks, bs, n_kv * hd)), jnp.float32)
        cur = rng.integers(context // 2, context, (B,)).astype(np.int32)
        tbl = np.full((B, W), num_blocks, np.int32)
        free = list(range(num_blocks))
        for b in range(B):
            need = int(cur[b]) // bs + 1
            tbl[b, :need] = [free.pop() for _ in range(need)]
        tbl = jnp.asarray(tbl)
        curj = jnp.asarray(cur)

        ref_fn = jax.jit(functools.partial(paged_attention_ref, block_size=bs))
        pal_fn = functools.partial(paged_attention_pallas, block_size=bs)
        args = (q, kn, vn, kp, vp, tbl, curj, jnp.int32(0))
        np.testing.assert_allclose(
            np.asarray(pal_fn(*args)), np.asarray(ref_fn(*args)),
            rtol=2e-5, atol=2e-5,
        )
        kv_row = n_kv * hd * item * 2                    # K + V, one position
        valid_blocks = int(sum(c // bs + 1 for c in cur))
        rows.append({
            "B": B, "context": context, "block_size": bs, "table_width": W,
            "gather_us": round(_time_med(ref_fn, *args), 1),
            "pallas_us": round(_time_med(pal_fn, *args), 1),
            "gathered_kv_bytes": B * W * bs * kv_row,
            "inplace_kv_bytes": valid_blocks * bs * kv_row,
            "bytes_ratio": round(B * W / valid_blocks, 3),
        })
    return rows


def build_trace(n: int, vocab: int, seed: int = 0, rate: float = 1.0):
    rng = np.random.default_rng(seed)
    trace, t = [], 0
    for _ in range(n):
        t += int(rng.poisson(rate))
        plen = int(rng.integers(2, BUCKETS[-1] + 1))
        trace.append((
            rng.integers(0, vocab, plen).astype(np.int32),
            int(NEW_CHOICES[rng.integers(len(NEW_CHOICES))]),
            t,
        ))
    return trace


class _DispatchSpy:
    """Wraps the scheduler's decode-tick entry point to record the exact
    ``active``/``cur_len`` operands of every dispatched chunk — the rows the
    tick actually attends, including requests admitted earlier in the SAME
    ``step()`` (snapshotting around ``step()`` would miss them: the sync
    loop admits before it decodes).  Forwards ``_cache_size`` so the
    recompile accounting sees through the wrapper."""

    def __init__(self, inner):
        self.inner = inner
        self.dispatches = []                     # (active mask, cur_len)

    def __call__(self, **kw):
        self.dispatches.append(
            (np.asarray(kw["active"]).copy(), np.asarray(kw["cur_len"]).copy())
        )
        return self.inner(**kw)

    def _cache_size(self):
        return self.inner._cache_size()


def serve_arm(cfg, params, trace, *, attn_impl: str, num_slots: int = 6):
    """Sync-loop serve pass (steps_per_tick=1 so one dispatch == one tick):
    returns (tok/s, results, recompiles, and per-tick
    [gathered_bytes, inplace_bytes, n_active, context_rows])."""
    from repro.serve import scheduler as S

    def make():
        sess = S.ServeSession(
            cfg, params, num_slots=num_slots, max_len=MAX_LEN,
            prompt_buckets=BUCKETS, cache_layout="paged",
            block_size=BLOCK_SIZE, loop="sync", steps_per_tick=1,
            attn_impl=attn_impl,
        )
        for p, n, t in trace:
            sess.submit(p, max_new=n, arrival=t)
        return sess

    warm = make()
    warm.run()
    warm.warmup()
    before = S.scheduler_compile_stats()

    sess = make()
    spy = _DispatchSpy(S._decode_tick_jit)
    S._decode_tick_jit = spy
    try:
        t0 = time.perf_counter()
        sess.run()
        dt = time.perf_counter() - t0
    finally:
        S._decode_tick_jit = spy.inner
    recompiles = sum(S.scheduler_compile_stats().values()) - sum(before.values())
    useful = sum(len(r.tokens) for r in sess.results.values())

    # bytes one KV position costs across K + V and every layer
    kv_row = cfg.num_kv_heads * cfg.head_dim * \
        jnp.dtype(sess.cache_dtype).itemsize * 2 * cfg.num_layers
    W = sess.table_width
    ticks = []
    for active, cur_len in spy.dispatches:
        # this chunk attended positions [0, cur_len] per active row: the
        # gather impl materializes every table row in full, the kernel
        # reads only blocks holding >= 1 valid position
        rows = sum(
            (int(cur_len[i]) // BLOCK_SIZE + 1) * BLOCK_SIZE
            for i in np.flatnonzero(active)
        )
        ticks.append((
            num_slots * W * BLOCK_SIZE * kv_row,     # gathered bytes
            rows * kv_row,                            # in-place bytes
            int(active.sum()),
            rows,
        ))
    return useful / dt, sess.results, recompiles, ticks


def bench(requests: int = 48, seed: int = 0):
    from repro.kernels.interpret import default_interpret
    from repro.models.transformer import init_params

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = build_trace(requests, cfg.vocab_size, seed=seed)

    g_tps, g_res, _, ticks = serve_arm(cfg, params, trace, attn_impl="gather")
    p_tps, p_res, recompiles, _ = serve_arm(cfg, params, trace, attn_impl="pallas")

    mismatches = sum(
        not np.array_equal(g_res[rid].tokens, p_res[rid].tokens)
        for rid in g_res
    )
    gathered = float(np.mean([t[0] for t in ticks]))
    inplace = float(np.mean([t[1] for t in ticks]))
    mean_active = float(np.mean([t[2] for t in ticks]))
    mean_rows = float(np.mean([t[3] for t in ticks]))
    # mean resident context per active row (block-rounded KV positions)
    mean_context = mean_rows / mean_active
    W = MAX_LEN // BLOCK_SIZE
    interpret = default_interpret()
    return {
        "bench": "attn_paged_kernel",
        "requests": requests,
        "seed": seed,
        "prompt_buckets": list(BUCKETS),
        "max_new_choices": list(NEW_CHOICES),
        "max_len": MAX_LEN,
        "block_size": BLOCK_SIZE,
        "table_width": W,
        "interpret_mode": interpret,
        "micro": micro_rows(seed),
        "serve_gather_tok_s": round(g_tps, 1),
        "serve_pallas_tok_s": round(p_tps, 1),
        "hbm_gathered_bytes_per_tick": int(gathered),
        "hbm_inplace_bytes_per_tick": int(inplace),
        "hbm_bytes_ratio": round(gathered / inplace, 3),
        "mean_active": round(mean_active, 2),
        "mean_context": round(mean_context, 1),
        "floor_ratio": round(W * BLOCK_SIZE / mean_context, 3),
        "token_mismatches": mismatches,
        "recompiles_after_warmup": recompiles,
        "field_docs": dict(FIELD_DOCS),
    }


def run(requests: int = 32):
    """benchmarks/run.py entry: (name, us_per_call, derived) rows."""
    r = bench(requests=requests)
    return [
        ("serve/attn_paged_gather", 1e6 / r["serve_gather_tok_s"],
         f"{r['serve_gather_tok_s']} tok/s"),
        ("serve/attn_paged_pallas", 1e6 / r["serve_pallas_tok_s"],
         f"{r['serve_pallas_tok_s']} tok/s (interpret={r['interpret_mode']})"),
        ("serve/attn_paged_hbm_ratio", 0.0,
         f"{r['hbm_bytes_ratio']}x KV traffic eliminated, "
         f"mismatches={r['token_mismatches']}"),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_attn_paged.json")
    args = ap.parse_args()
    r = bench(requests=args.requests, seed=args.seed)
    with open(args.out, "w") as f:
        json.dump(r, f, indent=2)
        f.write("\n")
    print(json.dumps({k: v for k, v in r.items() if k != "field_docs"}, indent=2))
    # exactness oracles fail the run (CI gates on this); perf floors warn
    if r["token_mismatches"]:
        raise SystemExit(
            f"FAIL: {r['token_mismatches']} requests differ between impls")
    if r["recompiles_after_warmup"]:
        raise SystemExit(
            f"FAIL: {r['recompiles_after_warmup']} recompiles after warmup")
    if r["hbm_bytes_ratio"] < r["floor_ratio"]:
        print(f"WARNING: hbm_bytes_ratio {r['hbm_bytes_ratio']} below the "
              f"W*block_size/context floor {r['floor_ratio']}")


if __name__ == "__main__":
    main()
