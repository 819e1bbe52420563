"""Smoke run of the serving path on a TPU: granite-3-2b at its published
widths and full depth (40 layers, d 2048, 32/8 heads of dim 64, d_ff 8192,
vocab 49155), random weights from a seed.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # four chips: tensor-parallel serving only

One process, these phases; any failure exits non-zero before the result line:

1. Device check: the JAX backend must be a TPU and ``REPRO_FORCE_INTERPRET``
   must be unset — no phase may carry on on the CPU or in the interpreter.
2. Kernels at granite widths, compiled for the chip (``tpu_custom_call`` in
   the compiled text): ``approx_matmul`` bit-exact against the multiplier
   LUT oracle, ``paged_attention`` against its exact-softmax oracle.
3. Serving through ``ServeSession`` (paged cache, async loop, Pallas paged
   attention): once in ``exact`` mode (bf16 weights) and once in ``approx``
   mode (frozen uint8 codes through the Pallas ``approx_matmul``).  Every
   request must complete with its tokens and nothing may compile after
   warmup; the first decode step's logits must be finite and agree between
   ``attn_impl="pallas"`` and ``"gather"``.

With ``--tp N`` only the tensor-parallel phase runs: the same trace served
on an ``(N,)`` ``"model"`` mesh and on one chip of the same host, compared
by first-decode-step logits and per-device KV bytes.

Times printed are smoke numbers, not benchmark results.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# importing these starts no backend: that happens at the first device call
from repro.configs import get_config  # noqa: E402
from repro.core.multipliers import mul8x8_table  # noqa: E402
from repro.kernels.approx_matmul.ops import approx_matmul_pallas  # noqa: E402
from repro.kernels.approx_matmul.ref import approx_matmul_ref  # noqa: E402
from repro.kernels.paged_attention import (  # noqa: E402
    paged_attention_pallas,
    paged_attention_ref,
)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.transformer import (  # noqa: E402
    forward,
    init_paged_cache,
    init_params,
    paged_decode_step,
)
from repro.serve.cache import scatter_prompt_blocks  # noqa: E402
from repro.serve.engine import freeze_params, resolve_execution_mode  # noqa: E402
from repro.serve.scheduler import ServeSession, scheduler_compile_stats  # noqa: E402

SEED = 0
MODEL = "granite-3-2b"
NUM_SLOTS = 4
N_REQUESTS = 8
PROMPT_LENS = (64, 512)       # inclusive range of prompt lengths
BUCKETS = (512,)
MAX_NEW = 32
BLOCK_SIZE = 16
MAX_LEN = 544                 # largest bucket + MAX_NEW, a BLOCK_SIZE multiple

# paged_attention vs its f32 oracle (run at "highest" matmul precision): the
# kernel's bf16 output rounds at 2^-9 relative and its in-kernel f32 dots may
# round operands to bf16 once; |out| stays below ~4 for N(0, 1) values, so
# both together stay under 2e-2 absolute.
PAGED_ATTN_ATOL = 2e-2
# first decode step logits, pallas vs gather attention (and mesh vs one
# chip), as max |a - b| / max |b| over the real vocabulary.  Bitwise parity
# is not the contract.  exact: the two paths sum the softmax in a different
# order and the gather path rounds its softmax weights to the bf16 cache
# dtype; 40 bf16 layers carry that roundoff to the logits.  approx: each
# layer re-quantizes its input to uint8 codes with one scale per tensor, so
# that roundoff also flips codes by one step (1/255 of the tensor's range).
LOGIT_RTOL = {"exact": 3e-2, "approx": 1e-1}


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- phase 1 -----------------------------------------------------------------


def device_check(n_chips: int) -> dict:
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        raise SmokeFailure(
            "REPRO_FORCE_INTERPRET is set: the Pallas kernels would run in "
            "the interpreter, not on the chip"
        )
    backend = jax.default_backend()
    check(backend == "tpu", f"JAX backend is {backend!r}, not 'tpu'")
    devs = jax.devices()
    check(len(devs) >= n_chips, f"{n_chips} chips needed, {len(devs)} found")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    return dev


# -- phase 2 -----------------------------------------------------------------


def compile_on_chip(fn, *args):
    """Compile ``fn`` for ``args`` and require a Mosaic kernel in it."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{fn}: no tpu_custom_call in the compiled program — the kernel "
          "did not lower for the chip")
    return compiled


def check_approx_matmul(key, M: int, K: int, N: int, multiplier: str) -> None:
    ka, kb = jax.random.split(key)
    a = jax.random.randint(ka, (M, K), 0, 256, jnp.int32).astype(jnp.uint8)
    b = jax.random.randint(kb, (K, N), 0, 256, jnp.int32).astype(jnp.uint8)
    kernel = compile_on_chip(functools.partial(
        approx_matmul_pallas, multiplier=multiplier, interpret=False), a, b)
    got = kernel(a, b)
    # the oracle gathers (M, block_k, N) LUT entries per scan step: keep
    # that transient near 2^26 elements
    block_k = min(512, max(1, 2**26 // (M * N)))
    lut = jnp.asarray(mul8x8_table(multiplier))
    want = jax.jit(approx_matmul_ref, static_argnames="block_k")(
        a, b, lut, block_k=block_k)
    bad = int(jnp.sum(got != want))
    check(bad == 0, f"approx_matmul {multiplier} M={M} K={K} N={N}: {bad} "
          "entries differ from the LUT oracle")
    print(f"kernel approx_matmul {multiplier} M={M} K={K} N={N}: bit-exact "
          f"vs LUT oracle (max |out| {int(jnp.max(jnp.abs(want)))})", flush=True)


def check_paged_attention(key, cfg) -> None:
    B, H, n_kv, hd = 8, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W = MAX_LEN // BLOCK_SIZE
    num_blocks = B * W
    ks = jax.random.split(key, 7)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, H, hd), dt)
    k_new = jax.random.normal(ks[1], (B, n_kv, hd), dt)
    v_new = jax.random.normal(ks[2], (B, n_kv, hd), dt)
    k_pool = jax.random.normal(ks[3], (num_blocks, BLOCK_SIZE, n_kv, hd), dt)
    v_pool = jax.random.normal(ks[4], (num_blocks, BLOCK_SIZE, n_kv, hd), dt)
    cur_len = jax.random.randint(ks[5], (B,), 0, MAX_LEN, jnp.int32)
    # each row holds its blocks up to cur_len in a shuffled pool; later
    # entries are sentinels, and the last row holds none (an idle slot)
    table = jax.random.permutation(ks[6], num_blocks).reshape(B, W).astype(jnp.int32)
    w = jnp.arange(W, dtype=jnp.int32)[None, :]
    table = jnp.where(w * BLOCK_SIZE <= cur_len[:, None], table, num_blocks)
    table = table.at[B - 1].set(num_blocks)
    args = (q, k_new, v_new, k_pool, v_pool, table, cur_len)
    kernel = compile_on_chip(functools.partial(
        paged_attention_pallas, block_size=BLOCK_SIZE, interpret=False), *args)
    got = np.asarray(kernel(*args).astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(functools.partial(
            paged_attention_ref, block_size=BLOCK_SIZE))(*args))
    err = float(np.max(np.abs(got - want)))
    check(np.isfinite(got).all(), "paged_attention: non-finite outputs")
    check(err <= PAGED_ATTN_ATOL, f"paged_attention: max |kernel - oracle| "
          f"{err:.3e} > {PAGED_ATTN_ATOL:.0e}")
    check(not got[B - 1].any(), "paged_attention: an idle row is not zero")
    print(f"kernel paged_attention B={B} H={H} Hkv={n_kv} hd={hd} "
          f"bs={BLOCK_SIZE} bf16: max |kernel - oracle| {err:.3e} "
          f"(tolerance {PAGED_ATTN_ATOL:.0e})", flush=True)


def check_kernels(cfg) -> None:
    key = jax.random.PRNGKey(SEED)
    for M in (8, 512):     # a decode row block and a prefill row block
        check_approx_matmul(jax.random.fold_in(key, M), M, cfg.d_model,
                            cfg.d_ff, "mul8x8_2")
    check_paged_attention(jax.random.fold_in(key, 1), cfg)


# -- phase 3 -----------------------------------------------------------------


def make_prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    lo, hi = PROMPT_LENS
    lens = rng.integers(lo, hi + 1, N_REQUESTS)
    lens[0], lens[-1] = lo, hi
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def serve(cfg, params, prompts, label: str, mesh=None):
    """Serve ``prompts`` through one ServeSession; returns (results, session)."""
    sess = ServeSession(
        cfg, params, num_slots=NUM_SLOTS, max_len=MAX_LEN,
        prompt_buckets=BUCKETS, cache_dtype=jnp.bfloat16,
        cache_layout="paged", block_size=BLOCK_SIZE, loop="async",
        attn_impl="pallas", seed=SEED, mesh=mesh,
    )
    t0 = time.perf_counter()
    sess.warmup()
    warm_s = time.perf_counter() - t0
    before = scheduler_compile_stats()
    for i, p in enumerate(prompts):
        sess.submit(p, max_new=MAX_NEW, req_id=i)
    t0 = time.perf_counter()
    res = sess.run()
    wall_s = time.perf_counter() - t0
    after = scheduler_compile_stats()
    recompiled = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    check(not recompiled, f"{label}: programs compiled after warmup: {recompiled}")
    check(sorted(res) == list(range(len(prompts))),
          f"{label}: completed {sorted(res)} of {len(prompts)} requests")
    for i, r in res.items():
        check(len(r.tokens) == MAX_NEW and r.finish_reason == "length",
              f"{label}: request {i} ended {r.finish_reason!r} with "
              f"{len(r.tokens)} of {MAX_NEW} tokens")
        check(bool(((r.tokens >= 0) & (r.tokens < cfg.vocab_size)).all()),
              f"{label}: request {i} emitted a token outside the vocabulary")
    n_tok = sum(len(r.tokens) for r in res.values())
    print(f"smoke {label}: warmup {warm_s:.1f} s, {len(res)} requests, "
          f"{n_tok} tokens in {wall_s:.2f} s wall "
          "(smoke numbers, not benchmark results)", flush=True)
    sess.close()
    return res, sess


@functools.partial(jax.jit, static_argnames=("cfg", "attn_impl"))
def _first_decode(cfg, params, tokens, lens, attn_impl):
    """Fused prefill into a fresh paged pool, then one paged decode step of
    the greedy first token: (prefill last logits, step logits)."""
    B, S = tokens.shape
    W = MAX_LEN // BLOCK_SIZE
    cache = init_paged_cache(cfg, B * W, BLOCK_SIZE, jnp.bfloat16)
    logits, _, kvs = forward(cfg, params, {"tokens": tokens}, return_kv=True)
    last = jnp.take_along_axis(logits, (lens - 1)[:, None, None], axis=1)[:, 0]
    table = jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
    cache = scatter_prompt_blocks(cache, kvs, table[:, : S // BLOCK_SIZE],
                                  BLOCK_SIZE)
    w = jnp.arange(W, dtype=jnp.int32)[None, :]
    table = jnp.where(w * BLOCK_SIZE <= lens[:, None], table, B * W)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    step, _ = paged_decode_step(
        cfg, params, cache, {"tokens": tok[:, None]}, lens, table,
        block_size=BLOCK_SIZE, attn_impl=attn_impl,
    )
    V = cfg.vocab_size
    return last[:, :V], step[:, 0, :V]


def first_decode_logits(cfg, params, prompts, attn_impl: str):
    tokens = np.zeros((len(prompts), BUCKETS[-1]), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, : len(p)] = p
    lens = np.array([len(p) for p in prompts], np.int32)
    last, step = _first_decode(cfg, params, tokens, lens, attn_impl=attn_impl)
    return np.asarray(last), np.asarray(step)


def compare_logits(label: str, got, want, tol: float) -> None:
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          f"{label}: non-finite logits")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    rms = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    print(f"logits {label}: max|diff|/max|ref| {rel:.3e} (tolerance "
          f"{tol:.0e}), rms rel {rms:.3e}, argmax agreement {agree:.3f}",
          flush=True)
    check(rel <= tol, f"{label}: max|diff|/max|ref| {rel:.3e} > {tol:.0e}")


def check_mode(cfg, params, prompts, mode: str) -> None:
    """Serve the trace in ``mode``, then hold the first decode step's logits
    through the Pallas kernel against the gather path."""
    serve(cfg, params, prompts, mode)
    prefill, step_pallas = first_decode_logits(cfg, params, prompts, "pallas")
    _, step_gather = first_decode_logits(cfg, params, prompts, "gather")
    check(np.isfinite(prefill).all(), f"{mode}: non-finite prefill logits")
    compare_logits(f"{mode} first decode step pallas vs gather",
                   step_pallas, step_gather, LOGIT_RTOL[mode])


def granite_config(mode: str):
    return dataclasses.replace(
        get_config(MODEL), param_dtype="bfloat16",
        approx=resolve_execution_mode(mode, "mul8x8_2"),
    )


def init_weights(cfg):
    # jitted so the f32 draws fuse into their bf16 casts: eager init would
    # hold the whole f32 tree (about 10.5 GB) at once
    params = jax.jit(init_params, static_argnums=0)(cfg, jax.random.PRNGKey(SEED))
    return jax.block_until_ready(params)


def run_single_chip() -> None:
    exact = granite_config("exact")
    check_kernels(exact)
    prompts = make_prompts(exact.vocab_size)
    params = init_weights(exact)
    check_mode(exact, params, prompts, "exact")
    approx = granite_config("approx")
    # frozen uint8 codes replace the bf16 weights; jitted so quantization
    # fuses instead of holding f32 copies of each stacked weight
    frozen = jax.block_until_ready(
        jax.jit(freeze_params, static_argnums=0)(approx, params))
    del params
    check_mode(approx, frozen, prompts, "approx")


# -- tensor-parallel phase (--tp N) -----------------------------------------


def _spans(tree, n: int) -> bool:
    return all(len(x.sharding.device_set) == n for x in jax.tree.leaves(tree))


def _bytes_per_device(tree) -> int:
    return sum(int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def run_tensor_parallel(tp: int) -> None:
    cfg = granite_config("exact")
    prompts = make_prompts(cfg.vocab_size)
    params = init_weights(cfg)
    res1, sess1 = serve(cfg, params, prompts, "exact one chip")
    kv1 = sess1.stats.peak_block_bytes_per_device
    del sess1
    _, step1 = first_decode_logits(cfg, params, prompts, "pallas")

    mesh = make_mesh((tp,), ("model",))
    res_tp, sess = serve(cfg, params, prompts, f"exact tp={tp}", mesh=mesh)
    check(_spans(sess.params, tp), f"tp={tp}: a parameter is not placed on "
          f"all {tp} devices")
    check(_spans(sess.cache, tp), f"tp={tp}: the KV pool is not placed on "
          f"all {tp} devices")
    p_all, p_dev = _bytes_per_device(params), _bytes_per_device(sess.params)
    check(p_dev < p_all / 2, f"tp={tp}: params not split ({p_dev} of {p_all} "
          "bytes per device)")
    kv_tp = sess.stats.peak_block_bytes_per_device
    print(f"tp={tp}: params {p_dev} B/device of {p_all} B; peak KV "
          f"{kv_tp} B/device vs {kv1} B on one chip", flush=True)
    check(kv_tp * tp == kv1, f"tp={tp}: peak KV bytes/device {kv_tp} is not "
          f"1/{tp} of the one-chip {kv1}")
    with jax.set_mesh(mesh):
        _, step_tp = first_decode_logits(cfg, sess.params, prompts, "pallas")
    compare_logits(f"first decode step tp={tp} vs one chip", step_tp, step1,
                   LOGIT_RTOL["exact"])
    same = sum(int(np.array_equal(res1[i].tokens, res_tp[i].tokens)) for i in res1)
    print(f"tp={tp}: {same} of {len(res1)} requests emitted identical tokens "
          "(informational: psum order differs from one chip)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=0,
                    help="run only the tensor-parallel phase on this many chips")
    args = ap.parse_args(argv)
    try:
        dev = device_check(max(1, args.tp))
        if args.tp:
            run_tensor_parallel(args.tp)
        else:
            run_single_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
