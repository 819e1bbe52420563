"""Batched serving engine: fused prefill + single-jit ``lax.scan`` decode.

The engine compiles ONE program per (config, generation-shape) pair:

* **prefill** seeds the whole KV cache in one fused full-sequence pass
  (``forward(..., return_kv=True)`` + ``seed_cache``) instead of S0
  teacher-forced decode dispatches; SSM/hybrid families (whose caches carry
  conv/ssm state, not K/V) transparently fall back to a scan-based
  teacher-forced prefill — still inside the same jit;
* **decode** runs ``max_new`` steps under ``lax.scan`` over a
  ``GenerationState`` carry, so serving costs one dispatch per request
  instead of one per token;
* **sampling** is configured by a static ``SamplingConfig`` (greedy,
  temperature, top-k, stop-on-eos via masking — finished rows emit
  ``eos_id`` and keep shapes static);
* **execution mode** comes from ``ModelConfig.approx``:
  ``resolve_execution_mode`` maps the serving-level names (``exact`` /
  ``exact_quant`` / ``approx`` / ``approx_lowrank``) onto the paper's
  multiplier pipeline, with ``approx`` dispatching every projection matmul
  to the Pallas approximate-matmul kernel (interpret mode off-TPU);
* ``freeze_params`` pre-quantizes matmul weights to uint8 ``QWeight``s so
  quantized serving skips per-step weight calibration.

``prefill_step`` / ``serve_step`` are the functions the dry-run lowers for
the inference shapes (prefill_32k lowers ``prefill_step``; decode_32k /
long_500k lower ``serve_step`` — one new token against a seq_len cache).

``greedy_generate`` keeps its historical signature as a thin wrapper over
``generate``; ``greedy_generate_legacy`` preserves the original per-token
Python loop as the parity/throughput baseline (tests/test_engine.py,
benchmarks/kernel_bench.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.approx import ApproxConfig, prequantize_tree
from repro.models.transformer import (
    decode_step,
    forward,
    init_cache,
    seed_cache,
)

__all__ = [
    "SamplingConfig",
    "GenerationState",
    "generate",
    "greedy_generate",
    "greedy_generate_legacy",
    "prefill_step",
    "serve_step",
    "select_token",
    "resolve_execution_mode",
    "draft_config",
    "freeze_params",
    "EXECUTION_MODES",
]


# ---------------------------------------------------------------------------
# Execution modes (serving-level names for the paper's multiplier pipeline)
# ---------------------------------------------------------------------------

EXECUTION_MODES = ("exact", "exact_quant", "approx", "approx_lowrank", "approx_msr")


def resolve_execution_mode(
    mode: str, multiplier: str = "mul8x8_2", *, act_per_row: bool = False
) -> ApproxConfig:
    """Map a serving execution mode onto an ``ApproxConfig``.

    exact          float matmuls (baseline)
    exact_quant    uint8 affine quantization, exact integer matmul
    approx         named approximate multiplier through the fused Pallas
                   kernel (interpret mode off-TPU — bit-exact to the LUT)
    approx_lowrank same semantics via the XLA low-rank path (fast on CPU)
    approx_msr     the fixed-shift MSR truncation family through the same
                   Pallas kernel (default rung ``mul8x8_msr4`` unless an
                   ``mul8x8_msr*`` name is passed) — the cheapest rung of
                   the serving quality ladder

    ``act_per_row`` selects per-row (per-token) activation scales so a
    row's outputs never depend on its batch neighbours — mixed-tier
    serving relies on this for bit-identical per-request parity.
    """
    if mode == "exact":
        return ApproxConfig(mode="float")
    if mode == "exact_quant":
        return ApproxConfig(multiplier="exact", mode="exact_quant",
                            act_per_row=act_per_row)
    if mode == "approx":
        return ApproxConfig(multiplier=multiplier, mode="pallas",
                            act_per_row=act_per_row)
    if mode == "approx_lowrank":
        return ApproxConfig(multiplier=multiplier, mode="lowrank",
                            act_per_row=act_per_row)
    if mode == "approx_msr":
        from repro.core.multipliers import MSR_SPECS

        msr = multiplier if multiplier in MSR_SPECS else "mul8x8_msr4"
        return ApproxConfig(multiplier=msr, mode="pallas",
                            act_per_row=act_per_row)
    raise ValueError(f"execution mode {mode!r} not in {EXECUTION_MODES}")


def draft_config(cfg: ModelConfig, draft_mode: str,
                 multiplier: str = "mul8x8_2") -> ModelConfig:
    """The self-speculative DRAFT model's config: the verifier's ``cfg``
    with only ``approx`` swapped for ``draft_mode``'s execution pipeline.

    This is the whole parameter dispatch of self-speculative decoding —
    draft and verifier share every weight; what differs is which multiplier
    path the projection matmuls route through (``layers.dense`` reads
    ``cfg.approx`` at trace time, so the swap costs one extra compiled
    decode program and zero extra parameter memory).  The accept rate the
    scheduler then measures is a live end-to-end readout of the paper's
    error-rate claim for ``multiplier``.

    ``draft_mode`` may be any execution mode, including ``"exact"`` (a
    self-test: the draft then *is* the verifier and every token must be
    accepted).  The returned config is hashable and therefore usable as a
    static jit argument, same as ``cfg`` itself."""
    return dataclasses.replace(
        cfg, approx=resolve_execution_mode(draft_mode, multiplier)
    )


def freeze_params(cfg: ModelConfig, params):
    """Pre-quantize matmul weights to frozen uint8 ``QWeight``s for serving
    (1 byte/element weight reads, no per-step weight calibration). No-op for
    float execution."""
    if not cfg.approx.is_quantized:
        return params
    return prequantize_tree(params, cfg.approx)


# ---------------------------------------------------------------------------
# Dry-run entry points (unchanged shapes)
# ---------------------------------------------------------------------------


def prefill_step(cfg: ModelConfig, params, batch) -> jax.Array:
    """Full-sequence forward (logits only; cache seeding is fused into the
    layer scan — see ``generate``'s fused prefill — here prefill cost is what
    we measure)."""
    logits, _ = forward(cfg, params, batch)
    return logits


def serve_step(
    cfg: ModelConfig,
    params,
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    cur_len: jax.Array,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step: (B,1) token (or embedding) -> (B,1,V) logits + cache."""
    return decode_step(cfg, params, cache, batch, cur_len)


_serve_step_jit = jax.jit(serve_step, static_argnums=(0,))


# ---------------------------------------------------------------------------
# Generation API
# ---------------------------------------------------------------------------


class SamplingConfig(NamedTuple):
    """Static sampling parameters (part of the jit cache key).

    temperature <= 0 selects greedy argmax; top_k == 0 disables top-k
    filtering; eos_id < 0 disables stop-on-eos."""

    temperature: float = 0.0
    top_k: int = 0
    eos_id: int = -1


class GenerationState(NamedTuple):
    """The scan carry of the decode loop."""

    cache: Any                 # transformer.init_cache pytree
    cur_len: jax.Array         # (B,) int32 — next cache write position
    last_token: jax.Array      # (B,) int32 — token to feed next step
    done: jax.Array            # (B,) bool — row hit eos (masking, not exit)
    rng: jax.Array             # PRNG key threaded through sampling


@jax.named_scope("sample")
def select_token(logits: jax.Array, sampling: SamplingConfig, rng) -> jax.Array:
    """(B, V) logits -> (B,) int32 next tokens under the static sampling
    config (python branches are resolved at trace time)."""
    if sampling.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.float32(sampling.temperature)
    if sampling.top_k > 0:
        kth = jax.lax.top_k(scaled, sampling.top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)


# historical private name (tests/test_engine.py pokes it directly)
_select_token = select_token


def _prefill_fused(cfg: ModelConfig, params, prompt_tokens, cache):
    """One full-sequence pass: last-position logits + fully seeded KV cache."""
    logits, _, kvs = forward(cfg, params, {"tokens": prompt_tokens}, return_kv=True)
    return logits[:, -1, :], seed_cache(cfg, cache, kvs)


def _prefill_decode(cfg: ModelConfig, params, prompt_tokens, cache):
    """Teacher-forced prefill as a scan over prompt positions (SSM/hybrid
    caches, or when bit-identical parity with step-wise decode is wanted)."""
    B, _ = prompt_tokens.shape
    Vp = cfg.padded_vocab

    def body(carry, tok):
        cache, cur, _ = carry
        logits, cache = decode_step(cfg, params, cache, {"tokens": tok[:, None]}, cur)
        return (cache, cur + 1, logits[:, 0, :]), None

    init = (cache, jnp.zeros((B,), jnp.int32), jnp.zeros((B, Vp), jnp.float32))
    (cache, _, last_logits), _ = jax.lax.scan(body, init, prompt_tokens.T)
    return last_logits, cache


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new", "max_len", "sampling", "prefill_mode", "cache_dtype"),
)
def _generate_jit(
    cfg: ModelConfig,
    params,
    prompt_tokens: jax.Array,
    rng: jax.Array,
    *,
    max_new: int,
    max_len: int,
    sampling: SamplingConfig,
    prefill_mode: str,
    cache_dtype,
) -> Tuple[jax.Array, jax.Array]:
    B, S0 = prompt_tokens.shape
    cache = init_cache(cfg, B, max_len, jnp.dtype(cache_dtype))
    if prefill_mode == "fused":
        last_logits, cache = _prefill_fused(cfg, params, prompt_tokens, cache)
    else:
        last_logits, cache = _prefill_decode(cfg, params, prompt_tokens, cache)

    eos = sampling.eos_id
    rng, k0 = jax.random.split(rng)
    tok0 = _select_token(last_logits, sampling, k0)
    done0 = (tok0 == eos) if eos >= 0 else jnp.zeros((B,), bool)
    state = GenerationState(
        cache=cache,
        cur_len=jnp.full((B,), S0, jnp.int32),
        last_token=tok0,
        done=done0,
        rng=rng,
    )

    def step(state: GenerationState, _):
        logits, cache = decode_step(
            cfg, params, state.cache, {"tokens": state.last_token[:, None]}, state.cur_len
        )
        rng, sub = jax.random.split(state.rng)
        tok = _select_token(logits[:, 0, :], sampling, sub)
        if eos >= 0:
            tok = jnp.where(state.done, jnp.int32(eos), tok)
            done = state.done | (tok == eos)
        else:
            done = state.done
        return GenerationState(cache, state.cur_len + 1, tok, done, rng), tok

    if max_new > 1:
        state, rest = jax.lax.scan(step, state, None, length=max_new - 1)
        new_tokens = jnp.concatenate([tok0[:, None], rest.swapaxes(0, 1)], axis=1)
    else:
        new_tokens = tok0[:, None]
    return jnp.concatenate([prompt_tokens, new_tokens], axis=1), state.done


def generate(
    cfg: ModelConfig,
    params,
    prompt_tokens: jax.Array,          # (B, S0) int32
    *,
    max_new: int = 16,
    sampling: Optional[SamplingConfig] = None,
    max_len: Optional[int] = None,
    cache_dtype=jnp.float32,
    rng: Optional[jax.Array] = None,
    prefill_mode: str = "fused",       # fused | decode
) -> jax.Array:
    """Batched generation in a single compiled program.

    Returns (B, S0 + max_new) int32 tokens (prompt included); rows that hit
    ``sampling.eos_id`` are padded with it. ``prefill_mode="decode"``
    teacher-forces the prompt through the decode path (required for
    SSM/hybrid caches — selected automatically — and used by the parity
    tests); ``"fused"`` seeds the KV cache in one full-sequence pass.
    """
    if not cfg.embed_input:
        raise ValueError(f"{cfg.name}: token generation requires an embed-input arch")
    if prefill_mode not in ("fused", "decode"):
        raise ValueError(f"prefill_mode {prefill_mode!r} not in ('fused', 'decode')")
    sampling = sampling if sampling is not None else SamplingConfig()
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if max_new < 1:
        raise ValueError(f"max_new must be >= 1, got {max_new}")
    B, S0 = prompt_tokens.shape
    max_len = max_len or (S0 + max_new)
    if max_len < S0 + max_new:
        # decode writes clamp at max_len-1 under jit and would silently
        # overwrite the last cache slot — fail loudly instead
        raise ValueError(f"max_len={max_len} < prompt_len + max_new = {S0 + max_new}")
    if cfg.family in ("ssm", "hybrid"):
        prefill_mode = "decode"
    tokens, _ = _generate_jit(
        cfg,
        params,
        prompt_tokens,
        rng,
        max_new=max_new,
        max_len=max_len,
        sampling=sampling,
        prefill_mode=prefill_mode,
        cache_dtype=jnp.dtype(cache_dtype).name,
    )
    return tokens


def greedy_generate(
    cfg: ModelConfig,
    params,
    prompt_tokens: jax.Array,        # (B, S0) int32 (embed_input archs)
    *,
    max_new: int = 16,
    max_len: Optional[int] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """Historical entry point: batched greedy decoding (now scan-based)."""
    return generate(
        cfg,
        params,
        prompt_tokens,
        max_new=max_new,
        max_len=max_len,
        cache_dtype=dtype,
    )


def greedy_generate_legacy(
    cfg: ModelConfig,
    params,
    prompt_tokens: jax.Array,
    *,
    max_new: int = 16,
    max_len: Optional[int] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """The original per-token Python loop (one dispatch per token,
    teacher-forced prefill through the decode path). Kept as the parity
    oracle and throughput baseline for the scan engine."""
    B, S0 = prompt_tokens.shape
    max_len = max_len or (S0 + max_new)
    cache = init_cache(cfg, B, max_len, dtype)

    # module-level jit so repeat calls (benchmarks) reuse the compile cache
    step = functools.partial(_serve_step_jit, cfg)

    cur = jnp.zeros((B,), jnp.int32)
    last = None
    for i in range(S0):
        last, cache = step(params, cache, {"tokens": prompt_tokens[:, i : i + 1]}, cur)
        cur = cur + 1
    out = [prompt_tokens]
    tok = jnp.argmax(last[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for _ in range(max_new - 1):
        out.append(tok)
        last, cache = step(params, cache, {"tokens": tok}, cur)
        cur = cur + 1
        tok = jnp.argmax(last[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out.append(tok)
    return jnp.concatenate(out, axis=1)
