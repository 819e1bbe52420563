"""Continuous-batching serve scheduler over a fixed pool of decode slots.

The PR-1 engine (``repro.serve.engine.generate``) serves one fixed batch of
same-length requests end-to-end: every request in the batch pays for the
longest prompt and the largest ``max_new``.  ``ServeSession`` instead keeps
a pool of ``num_slots`` decode slots hot and refills each slot from a
request queue the moment its occupant finishes (EOS or max-token), so the
approximate-multiplier matmuls stay saturated instead of idling behind the
longest request.

Two **cache layouts** share the session (``cache_layout=``):

* ``"slots"`` — every request reserves a worst-case ``max_len`` KV stripe
  for its lifetime (the PR-2 engine, kept as the parity oracle);
* ``"paged"`` — K/V live in a global ``BlockPool`` of fixed-size blocks
  and each request holds only the blocks its actual context occupies,
  recorded in a fixed-width per-slot block table.  Admission allocates
  ``ceil(prompt_len / block_size)`` blocks, decode appends one block only
  when a request's context crosses a block boundary, and completion frees
  every held block immediately — so mixed-context traffic shares HBM
  instead of stranding it, and ``num_slots`` (decode width) decouples from
  memory.  Admission reserves each request's worst case
  (``ceil((prompt_len + max_new - 1) / block_size)`` blocks) against the
  pool, which makes mid-decode block appends infallible: no preemption
  path is ever needed.  Greedy float outputs are bit-identical to the slot
  layout (and to standalone ``generate``) — masked block-gather garbage
  receives softmax probability exactly 0.0.

Everything runs under **fixed compiled shapes**:

* ONE decode program per (config, sampling, num_slots, max_len [, layout])
  — a single ``decode_step`` / ``paged_decode_step`` over the pooled cache
  each tick, all slots at once; block-table *contents* are traced data, so
  no context layout recompiles;
* ONE prefill program per prompt-length *bucket* (``PromptBuckets``):
  every admission in a tick shares a single batched (width ``num_slots``)
  fused ``forward(return_kv=True)`` pass that seeds the freed slots' KV rows
  and samples each first token (SSM/hybrid families fall back to a masked
  teacher-forced scan inside the same jit); unadmitted rows degenerate to
  exact no-ops (``cache.scatter_rows`` where-gather for slots, dropped
  sentinel-block scatters for paged), and the other slots' rows are
  untouched.

No request pattern (arrival order, prompt length, max_new mix) triggers a
recompile after ``warmup()`` — asserted by ``compile_stats`` deltas in
tests/test_scheduler.py.

Two **host loops** drive those programs (``loop=``):

* ``"sync"`` — the PR-3 tick loop, kept as the parity baseline: each
  ``step()`` admits, dispatches one decode chunk, and immediately blocks on
  the chunk's tokens before doing any bookkeeping, so host scheduling and
  device compute strictly alternate;
* ``"async"`` (default) — a **double-buffered pipeline**: ``step()``
  dispatches decode chunk *N+1* (and any admits) *before* blocking on chunk
  *N*'s token transfer.  The decode carry (``last_token`` and the per-slot
  PRNG keys) stays device-resident between chunks and admissions merge
  their first sampled tokens into it with a fixed-shape scatter
  (``cache.merge_admit_carry``), so no host sync sits between dispatches —
  queue management, admission decisions, and ``_finish`` bookkeeping all
  overlap device compute.  The price is one chunk of lag on *observing*
  completions: a request that finishes inside the in-flight chunk decodes
  one extra garbage chunk before the host sees it (discarded, counted as
  idle — the same overshoot discipline as ``steps_per_tick``).  Length
  completions never pay that lag: **predictive early turnover** releases a
  row whose in-flight chunk provably finishes it by length (an eos can
  only finish it sooner), so a successor admits into the slot before the
  harvest and the async schedule matches the sync loop tick-for-tick.
  Greedy float outputs remain bit-identical to the sync loop and to
  standalone ``generate``: each row's math depends only on its own
  carry/cache state, which both loops feed identically.  On accelerators
  every cache-consuming program additionally donates its cache operand
  (each cache future is consumed exactly once by the next dispatch), so
  the pipeline rebuilds the pooled cache in place instead of doubling HBM
  traffic; on CPU donation is deliberately off — see
  ``_resolve_cache_donation``.

**Prefill/decode interleaving** rate-limits admission so a burst of long
prompts cannot starve resident decodes: with ``prefill_decode_ratio=R``,
each ``step()`` admits at most ``R * n_active * steps_per_tick`` bucketed
prompt tokens (``prefill_token_budget=B`` is the flat-budget variant); the
queue head is deferred — never skipped — when it exceeds the remaining
budget, and admission is unthrottled while no decode is resident (nothing
to starve, and the queue must drain).  ``SchedulerStats`` surfaces the
policy: ``prefill_stall_ticks`` counts steps that deferred an admissible
request, ``max_decode_gap_ticks`` is the starvation gauge (worst
device-work gap between a resident request's consecutive accepted tokens,
bounded by ``steps_per_tick + ceil(R * steps_per_tick)`` under the ratio
policy — the carry-based work accounting makes that bound exact), and
``overlap_fraction`` reports how much of the wall clock the async loop hid
host work behind device compute.

Sampling is per-request deterministic: each request gets
``fold_in(session_key, req_id)`` and each sampled token position folds in
its cache position, so a request's output is independent of which slot it
lands in and of what else is in flight (bit-exact under float execution;
quantized modes couple batch rows through the dynamic per-tensor activation
scale, so there parity is statistical, not bitwise).

Execution modes: the session serves whatever ``cfg.approx`` selects —
``exact`` / ``exact_quant`` / ``approx`` (Pallas kernel) /
``approx_lowrank`` / ``approx_msr`` — and accepts ``freeze_params``
QWeight trees.

**Quality tiers** (``tiers=("exact", "approx", "approx_msr")``) instead
route each REQUEST through its own execution mode: one compiled decode
program per ladder rung, dispatched per step for the rungs holding active
rows, with the other tiers' rows made write-inert exactly the way released
rows already are (sentinel block tables / out-of-bounds ``cur_len``).  A
request's rung is frozen at admission — ``submit(..., tier=...)`` names the
requested rung, and the **load shedder** (``shed_queue_depth`` /
``shed_gap_ticks``) may demote new admissions further down the ladder while
the session is overloaded, restoring with hysteresis
(``shed_hold_steps``).  Per-rung configs use per-row activation scales
(``act_per_row``), so every request's greedy output is bit-identical to a
single-mode oracle session of its effective rung regardless of what else
shares the batch.  Tier sessions take raw float params (the rungs disagree
about quantization, so ``freeze_params`` trees cannot be shared).
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import os
import time
from collections import deque
from typing import Any, ClassVar, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.attention import ATTN_IMPLS
from repro.models.transformer import (
    decode_step,
    forward,
    init_cache,
    init_paged_cache,
    paged_chunk_prefill_step,
    paged_decode_step,
    paged_verify_step,
)
from repro.parallel.sharding import constrain as _sh_constrain
from repro.parallel.sharding import mesh_axis_size as _mesh_axis_size
from repro.serve import cache as C
from repro.serve.engine import (
    EXECUTION_MODES,
    SamplingConfig,
    draft_config,
    resolve_execution_mode,
    select_token,
)

__all__ = [
    "Request",
    "CompletedRequest",
    "SchedulerStats",
    "ServeSession",
    "scheduler_compile_stats",
    "ATTN_IMPLS",
    "CACHE_LAYOUTS",
    "ADMISSION_POLICIES",
    "SERVE_LOOPS",
]

CACHE_LAYOUTS = ("slots", "paged")
ADMISSION_POLICIES = ("priority", "fifo", "sjf")
SERVE_LOOPS = ("async", "sync")

# host-side profiler spans (``serve.*``): about a microsecond each while no
# profiler runs; inside a trace they land on the device's clock
_span = jax.profiler.TraceAnnotation

def _resolve_cache_donation() -> Tuple[str, ...]:
    """Donate the cache operand of every cache-consuming program so the
    pooled KV is rebuilt IN PLACE instead of copied per dispatch — sound
    because the loop hands each cache future to exactly one next dispatch,
    and warmup() chains its outputs the same way.  Default ON for
    accelerators (the ROADMAP cache-donation item: cuts HBM traffic and
    halves peak pool memory) but OFF on CPU: XLA CPU honors aliasing
    (measured ~40x on a pool-sized ``.at[].set``), yet donating there makes
    the chunk execute effectively inline with its dispatch, which
    serializes the very host/device overlap the async loop exists to
    create (measured: both loops' overlap_fraction -> ~0.99 and the async
    win -> ~1.0x with CPU donation on; the pool copy it avoids is
    negligible at bench scale).  ``REPRO_SERVE_DONATE=0|1`` overrides the
    per-backend default.  Resolved lazily (first program call, via
    ``_LazyJit``) so importing this module never initializes the jax
    backend and the decision reads the platform the application actually
    configured."""
    env = os.environ.get("REPRO_SERVE_DONATE", "")
    if env == "1":
        return ("cache",)
    if env == "0":
        return ()
    return ("cache",) if jax.default_backend() != "cpu" else ()


def _pin_pool(cache, n_kv: int):
    """Pin the paged pool's placement at program outputs: block contents
    shard the head-major last dim over ``"model"`` when ``n_kv`` (the
    config's KV head count) divides it, and replicate otherwise — exactly
    ``cache_pspecs(layout="paged")``.  Without the pin, jit is free to pick
    a different output sharding than the input's, and the NEXT dispatch of
    the same program would see changed operand placements — one recompile
    per flip.  ``constrain`` degrades to a no-op off-mesh, so single-device
    serving is untouched."""
    ax = "model" if n_kv % _mesh_axis_size("model") == 0 else None
    spec = (None, None, None, ax)
    return dict(
        cache,
        k=_sh_constrain(cache["k"], spec),
        v=_sh_constrain(cache["v"], spec),
    )


class _LazyJit:
    """Defer ``jax.jit`` wrapping to the first call.  Keeps module import
    free of backend initialization and lets the donation decision see the
    configured platform; exposes ``_cache_size`` like a real jit so the
    compile-count plumbing is unchanged (0 before the first call — no
    programs exist yet)."""

    def __init__(self, build):
        self._build = build
        self._fn = None

    def __call__(self, *args, **kwargs):
        if self._fn is None:
            self._fn = self._build()
        return self._fn(*args, **kwargs)

    def _cache_size(self) -> int:
        if self._fn is None:
            return 0
        get = getattr(self._fn, "_cache_size", None)
        return int(get()) if callable(get) else -1


# ---------------------------------------------------------------------------
# Compiled programs (module-level lazy jits: cfg/sampling static, shared
# cache, cache operand donated per _resolve_cache_donation)
# ---------------------------------------------------------------------------


def _decode_tick(
    cfg: ModelConfig,
    params,
    cache,
    last_token: jax.Array,     # (N,) int32
    cur_len: jax.Array,        # (N,) int32
    active: jax.Array,         # (N,) bool
    slot_keys: jax.Array,      # (N, 2) uint32 per-request PRNG keys
    tables: Optional[jax.Array] = None,   # (N, W) int32 — paged layout only
    *,
    sampling: SamplingConfig,
    steps: int = 1,
    block_size: int = 0,
    attn_impl: str = "gather",
):
    """``steps`` decode steps across all slots in one dispatch (decode
    chunk).  Inactive slots compute garbage into their own rows only (masked
    out here and overwritten at next admit; under the paged layout their
    all-sentinel table rows drop the writes entirely).  Rows that finish
    mid-chunk (eos here, max-token on the host) overshoot at most
    ``steps - 1`` positions; the host discards the extra tokens.  Overshoot
    cache writes go through per-row ``.at[...].set`` scatters, whose
    out-of-bounds updates are dropped (unlike ``dynamic_update_slice``,
    which CLAMPS — do not swap the write path without rechecking this); the
    hard guarantee, though, is ``submit``'s ``prompt_len + max_new <=
    max_len`` bound: no attending row ever reads a position an overshooting
    row could have written.  ``tables is None`` selects the slot layout at
    trace time — both layouts share this entry point, so the compile-count
    recompile checks cover them uniformly.

    Returns ``(cache, toks, last_token)``: the final ``last_token`` carry is
    a device array the async loop feeds straight into the next chunk's
    dispatch, which is what lets chunk N+1 launch before chunk N's tokens
    ever reach the host (the sync loop ignores it and rebuilds the value
    from the fetched tokens — same numbers, same program)."""

    def one(carry, _):
        cache, last_token, cur_len, done = carry
        if tables is None:
            logits, cache = decode_step(
                cfg, params, cache, {"tokens": last_token[:, None]}, cur_len
            )
        else:
            logits, cache = paged_decode_step(
                cfg, params, cache, {"tokens": last_token[:, None]}, cur_len,
                tables, block_size=block_size, attn_impl=attn_impl,
            )
        with jax.named_scope("sample"):
            # the sampled token lands at position cur_len + 1 -> unique,
            # slot- and schedule-independent key per token
            keys = jax.vmap(jax.random.fold_in)(slot_keys, cur_len + 1)
            toks = jax.vmap(
                lambda l, k: select_token(l[None], sampling, k)[0]
            )(logits[:, 0, :], keys)
            if sampling.eos_id >= 0:
                toks = jnp.where(done, jnp.int32(sampling.eos_id), toks)
                done = done | (toks == sampling.eos_id)
            toks = jnp.where(active, toks, 0)
            last_token = jnp.where(active, toks, last_token)
        return (cache, last_token, cur_len + active, done), toks

    carry = (cache, last_token, cur_len, jnp.zeros_like(active))
    (cache, last_token, _, _), toks = jax.lax.scan(one, carry, None, length=steps)
    if tables is not None:
        cache = _pin_pool(cache, cfg.num_kv_heads)
    # only the sampled tokens (and the tiny carry) replicate back to the
    # host loop — logits/activations stay sharded inside the program
    toks = _sh_constrain(toks, (None, None))
    last_token = _sh_constrain(last_token, (None,))
    return cache, toks, last_token          # toks: (steps, N)


_decode_tick_jit = _LazyJit(lambda: jax.jit(
    _decode_tick,
    static_argnames=("cfg", "sampling", "steps", "block_size", "attn_impl"),
    donate_argnames=_resolve_cache_donation(),
))


def _spec_tick(
    cfg: ModelConfig,
    draft_cfg: ModelConfig,
    params,
    cache,
    last_token: jax.Array,     # (N,) int32
    cur_len: jax.Array,        # (N,) int32 — position of last_token
    active: jax.Array,         # (N,) bool
    slot_keys: jax.Array,      # (N, 2) uint32 per-request PRNG keys
    tables: jax.Array,         # (N, W) int32 — spec decode is paged-only
    *,
    sampling: SamplingConfig,
    draft_k: int,
    block_size: int,
    attn_impl: str,
):
    """One self-speculative work tick: ``draft_k`` decode steps through the
    approximate draft path (``draft_cfg`` differs from ``cfg`` only in
    ``cfg.approx`` — same params, zero extra weights), then ONE exact
    verify pass over the K+1 positions [last accepted token; K drafts],
    accepting per row the longest draft prefix that matches the exact
    sampler plus the verifier's correction token.

    Exactness by construction, for ANY sampling config: the verify step
    replays the sequential decode's per-position instruction sequence
    (``paged_verify_attention``), and the positional ``fold_in(slot_key,
    position)`` key schedule makes the exact token at position ``p`` a
    function of the prefix alone — a token is only accepted when its whole
    prefix matched, so accepted tokens are bit-identical to the
    non-speculative oracle.  The draft's only power is over *which*
    positions get verified, i.e. throughput, never content.

    Cache discipline: the draft scan writes approximate K/V at positions
    ``c .. c+K-1`` and the verify pass overwrites ``c .. c+K`` with exact
    K/V; positions past the accept point hold wrong-token K/V but sit
    beyond the new ``cur_len`` and are rewritten by the next tick's draft
    or verify before any attention horizon reaches them (the same
    masked-overshoot discipline as ``_decode_tick``; sentinel table
    entries drop writes past a row's allocation).

    Returns ``(cache, toks, n_acc, last_token, cur_len)``: ``toks`` is
    (K+1, N) with each row's accepted tokens in ``toks[:n_acc[row], row]``
    (zeros past them), ``n_acc`` is (N,) in 1..K+1 for live rows / 0 for
    inactive ones, and the carries advance per row by its own ``n_acc`` —
    the async loop feeds them straight into the next dispatch."""
    S = draft_k + 1

    def one(carry, _):
        cache, tok, pos = carry
        logits, cache = paged_decode_step(
            draft_cfg, params, cache, {"tokens": tok[:, None]}, pos,
            tables, block_size=block_size, attn_impl=attn_impl,
        )
        # the draft samples with the SAME positional keys as the verifier,
        # so a perfect draft (draft_mode="exact") accepts every token
        keys = jax.vmap(jax.random.fold_in)(slot_keys, pos + 1)
        nxt = jax.vmap(lambda l, k: select_token(l[None], sampling, k)[0])(
            logits[:, 0, :], keys
        )
        nxt = jnp.where(active, nxt, 0)
        return (cache, nxt, pos + active), nxt

    (cache, _, _), drafts = jax.lax.scan(
        one, (cache, last_token, cur_len), None, length=draft_k
    )
    drafts = drafts.T                                # (N, K)

    tokens_in = jnp.concatenate([last_token[:, None], drafts], axis=1)
    logits, cache = paged_verify_step(
        cfg, params, cache, {"tokens": tokens_in}, cur_len, tables,
        block_size=block_size,
    )
    # exact token at position cur_len + j + 1, for j = 0..K
    pos = cur_len[:, None] + 1 + jnp.arange(S, dtype=cur_len.dtype)[None, :]
    keys = jax.vmap(
        lambda k, p: jax.vmap(jax.random.fold_in, in_axes=(None, 0))(k, p)
    )(slot_keys, pos)
    exact = jax.vmap(jax.vmap(
        lambda l, k: select_token(l[None], sampling, k)[0]
    ))(logits, keys)                                 # (N, K+1)

    # longest matching draft prefix m -> emit those m tokens plus the
    # verifier's correction token exact[m]
    match = (exact[:, :draft_k] == drafts).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1) + 1
    if sampling.eos_id >= 0:
        # never emit past the first exact eos (the oracle stops there)
        is_eos = exact == sampling.eos_id
        first = jnp.where(
            jnp.any(is_eos, axis=1), jnp.argmax(is_eos, axis=1), S
        )
        n_acc = jnp.minimum(n_acc, first + 1)
    n_acc = jnp.where(active, n_acc, 0)
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    toks = jnp.where((idx < n_acc[:, None]) & active[:, None], exact, 0)
    new_last = jnp.take_along_axis(
        exact, jnp.maximum(n_acc - 1, 0)[:, None], axis=1
    )[:, 0]
    last_token = jnp.where(active, new_last, last_token)
    max_pos = tables.shape[1] * block_size - 1       # == max_len - 1
    cur_len = jnp.where(
        active, jnp.minimum(cur_len + n_acc, max_pos), cur_len
    )
    cache = _pin_pool(cache, cfg.num_kv_heads)
    toks = _sh_constrain(toks.T, (None, None))
    n_acc = _sh_constrain(n_acc, (None,))
    last_token = _sh_constrain(last_token, (None,))
    cur_len = _sh_constrain(cur_len, (None,))
    return cache, toks, n_acc, last_token, cur_len       # toks: (K+1, N)


_spec_tick_jit = _LazyJit(lambda: jax.jit(
    _spec_tick,
    static_argnames=(
        "cfg", "draft_cfg", "sampling", "draft_k", "block_size", "attn_impl"
    ),
    donate_argnames=_resolve_cache_donation(),
))


def _request_keys(base_key, req_ids):
    """(A,) request ids -> (A, 2) per-request PRNG keys (computed in-jit so
    admission costs no extra host dispatches)."""
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(base_key, req_ids)


def _first_tokens(last_logits, req_keys, prompt_lens, sampling: SamplingConfig):
    """(A, V) last-position logits -> (A,) first sampled tokens under the
    per-request fold_in key schedule (position == prompt_len)."""
    keys = jax.vmap(jax.random.fold_in)(req_keys, prompt_lens)
    return jax.vmap(lambda l, k: select_token(l[None], sampling, k)[0])(
        last_logits, keys
    )


_scatter_rows = C.scatter_rows


def _admit_fused(
    cfg: ModelConfig,
    params,
    cache,
    prompts: jax.Array,        # (A, S_bucket) int32, right-padded
    prompt_lens: jax.Array,    # (A,) int32
    slots: jax.Array,          # (A,) int32 — a permutation of range(num_slots)
    valid: jax.Array,          # (A,) bool — rows actually being admitted
    req_ids: jax.Array,        # (A,) int32
    base_key: jax.Array,       # (2,) uint32 session key
    *,
    sampling: SamplingConfig,
):
    """Batched fused prefill-on-admit (attention families): ONE
    full-sequence pass prefills every admission of this tick, seeds their
    slots' KV rows [0, S_bucket), and samples each first token.  Compiled
    once per bucket size; invalid rows are no-ops (see ``_scatter_rows``),
    so 1..A admissions share the program."""
    logits, _, kvs = forward(cfg, params, {"tokens": prompts}, return_kv=True)
    last = jnp.take_along_axis(
        logits, (prompt_lens - 1)[:, None, None], axis=1
    )[:, 0, :]
    k, v = kvs                                  # (L, A, S_bucket, Hkv, hd)
    Sb = prompts.shape[1]
    cache = dict(
        cache,
        k=_scatter_rows(cache["k"], k, slots, valid, s_cap=Sb),
        v=_scatter_rows(cache["v"], v, slots, valid, s_cap=Sb),
    )
    req_keys = _request_keys(base_key, req_ids)
    return cache, _first_tokens(last, req_keys, prompt_lens, sampling), req_keys


_admit_fused_jit = _LazyJit(lambda: jax.jit(
    _admit_fused, static_argnames=("cfg", "sampling"),
    donate_argnames=_resolve_cache_donation(),
))


def _admit_decode(
    cfg: ModelConfig,
    params,
    cache,
    prompts: jax.Array,        # (A, S_bucket) int32, right-padded
    prompt_lens: jax.Array,    # (A,) int32
    slots: jax.Array,          # (A,) int32 — a permutation of range(num_slots)
    valid: jax.Array,          # (A,) bool
    req_ids: jax.Array,        # (A,) int32
    base_key: jax.Array,       # (2,) uint32 session key
    *,
    sampling: SamplingConfig,
    max_len: int,
    cache_dtype: str,
):
    """Batched teacher-forced prefill-on-admit for SSM/hybrid caches
    (conv/ssm state has no fused seeding path): scan the bucket positions on
    a fresh batch-A cache, freezing each row's state updates past its own
    prompt_len, then scatter the rows into their slots."""
    A, Sb = prompts.shape
    slot_cache = init_cache(cfg, A, max_len, jnp.dtype(cache_dtype))

    def body(carry, xs):
        cache_c, last = carry
        t, toks = xs
        logits, new_cache = decode_step(
            cfg, params, cache_c, {"tokens": toks[:, None]},
            jnp.full((A,), t, jnp.int32),
        )
        take = t < prompt_lens                   # (A,) per-row freeze
        cache_c = jax.tree.map(
            lambda n, o: jnp.where(
                take.reshape((1, A) + (1,) * (n.ndim - 2)), n, o
            ),
            new_cache,
            cache_c,
        )
        last = jnp.where((t == prompt_lens - 1)[:, None], logits[:, 0, :], last)
        return (cache_c, last), None

    init = (slot_cache, jnp.zeros((A, cfg.padded_vocab), jnp.float32))
    (slot_cache, last), _ = jax.lax.scan(
        body, init, (jnp.arange(Sb, dtype=jnp.int32), prompts.T)
    )
    cache = jax.tree.map(
        lambda full, part: _scatter_rows(full, part, slots, valid), cache, slot_cache
    )
    req_keys = _request_keys(base_key, req_ids)
    return cache, _first_tokens(last, req_keys, prompt_lens, sampling), req_keys


_admit_decode_jit = _LazyJit(lambda: jax.jit(
    _admit_decode,
    static_argnames=("cfg", "sampling", "max_len", "cache_dtype"),
    donate_argnames=_resolve_cache_donation(),
))


def _admit_fused_paged(
    cfg: ModelConfig,
    params,
    cache,
    prompts: jax.Array,        # (A, S_bucket) int32, right-padded
    prompt_lens: jax.Array,    # (A,) int32
    block_ids: jax.Array,      # (A, ceil(S_bucket/block_size)) int32
    req_ids: jax.Array,        # (A,) int32
    base_key: jax.Array,       # (2,) uint32 session key
    *,
    sampling: SamplingConfig,
    block_size: int,
):
    """Batched fused prefill-on-admit against the paged cache: ONE
    full-sequence pass prefills every admission of this tick, scatters each
    row's K/V into its allocated blocks, and samples each first token.
    Unallocated / padding-row entries of ``block_ids`` hold the sentinel
    ``num_blocks`` and are dropped by the scatter — no ``valid`` mask is
    needed, and 1..A admissions share the program (compiled once per
    (admit width, bucket))."""
    logits, _, kvs = forward(cfg, params, {"tokens": prompts}, return_kv=True)
    last = jnp.take_along_axis(
        logits, (prompt_lens - 1)[:, None, None], axis=1
    )[:, 0, :]
    cache = _pin_pool(
        C.scatter_prompt_blocks(cache, kvs, block_ids, block_size),
        cfg.num_kv_heads,
    )
    req_keys = _request_keys(base_key, req_ids)
    tok0s = _sh_constrain(
        _first_tokens(last, req_keys, prompt_lens, sampling), (None,)
    )
    return cache, tok0s, _sh_constrain(req_keys, (None, None))


_admit_fused_paged_jit = _LazyJit(lambda: jax.jit(
    _admit_fused_paged, static_argnames=("cfg", "sampling", "block_size"),
    donate_argnames=_resolve_cache_donation(),
))


def _prefill_chunk(
    cfg: ModelConfig,
    params,
    cache,
    tokens: jax.Array,         # (A, C_bucket) int32, right-padded chunk tokens
    starts: jax.Array,         # (A,) int32 prefill cursor (position of tokens[:, 0])
    chunk_lens: jax.Array,     # (A,) int32 real tokens this chunk
    tables: jax.Array,         # (A, W) int32 per-row block tables, sentinel-tailed
    req_ids: jax.Array,        # (A,) int32
    base_key: jax.Array,       # (2,) uint32 session key
    *,
    sampling: SamplingConfig,
    block_size: int,
):
    """Chunked prefill: teacher-force one chunk of each row's prompt into
    the paged pool at positions ``[starts, starts + chunk_lens)``, reading
    the already-written prefix through the block table (see
    ``paged_chunk_prefill_step`` — bit-identical to the fused one-shot
    prefill by construction).  Padding rows carry all-sentinel tables, so
    their writes are dropped like ``_admit_fused_paged``'s; no ``valid``
    mask is needed and 1..A chunks share the program (compiled once per
    (admit width, chunk bucket) — the same ``{1,2,4,...} x buckets``
    program set as the one-shot path, so chunking adds no shapes).

    ``tok0s`` is each row's first sampled token *assuming this is its final
    chunk*: the key folds in ``starts + chunk_lens``, which equals the
    effective prompt length exactly when the chunk completes the prompt —
    the same positional key the one-shot path folds — and is garbage the
    host ignores for non-final chunks."""
    logits, cache = paged_chunk_prefill_step(
        cfg, params, cache, {"tokens": tokens}, starts, tables,
        block_size=block_size,
    )
    last = jnp.take_along_axis(
        logits, (chunk_lens - 1)[:, None, None], axis=1
    )[:, 0, :]
    cache = _pin_pool(cache, cfg.num_kv_heads)
    req_keys = _request_keys(base_key, req_ids)
    tok0s = _sh_constrain(
        _first_tokens(last, req_keys, starts + chunk_lens, sampling), (None,)
    )
    return cache, tok0s, _sh_constrain(req_keys, (None, None))


_prefill_chunk_jit = _LazyJit(lambda: jax.jit(
    _prefill_chunk, static_argnames=("cfg", "sampling", "block_size"),
    donate_argnames=_resolve_cache_donation(),
))


def _evict(cache, slot: jax.Array):
    return C.evict_slot(cache, slot)


_evict_jit = _LazyJit(lambda: jax.jit(
    _evict, donate_argnames=_resolve_cache_donation(),
))


def _copy_block(cache, src: jax.Array, dst: jax.Array, *, n_kv: int):
    """Copy-on-write fork (see ``cache.copy_block``): src/dst are traced, so
    one compiled program forks any block pair; warmed by ``warmup()`` when
    prefix sharing is on so the first real fork never compiles.  The copy is
    head-local under TP (each shard copies its own Hkv/tp slice), so the
    pool pin adds no traffic."""
    return _pin_pool(C.copy_block(cache, src, dst), n_kv)


_copy_block_jit = _LazyJit(lambda: jax.jit(
    _copy_block, static_argnames=("n_kv",),
    donate_argnames=_resolve_cache_donation(),
))


def _admit_merge(
    last_token: jax.Array,     # (N,) int32 device-resident decode carry
    slot_keys: jax.Array,      # (N, 2) uint32 per-request PRNG keys
    slots: jax.Array,          # (A,) int32 — distinct slot ids
    tok0s: jax.Array,          # (A,) int32 first sampled tokens (admit output)
    keys: jax.Array,           # (A, 2) uint32 per-request keys (admit output)
    valid: jax.Array,          # (A,) bool — rows actually admitted
):
    """Async loop: merge an admission batch's first tokens and PRNG keys into
    the device-resident decode carry (see ``cache.merge_admit_carry``).
    ``tok0s``/``keys`` are usually still in-flight futures of an admit
    program — composing here instead of on the host is what keeps the
    pipeline free of syncs between dispatches."""
    lt, sk = C.merge_admit_carry(last_token, slot_keys, slots, tok0s, keys, valid)
    return _sh_constrain(lt, (None,)), _sh_constrain(sk, (None, None))


_admit_merge_jit = _LazyJit(lambda: jax.jit(_admit_merge))


def _spec_merge_len(
    cur_len: jax.Array,        # (N,) int32 device-resident length carry
    slots: jax.Array,          # (A,) int32 — distinct slot ids
    lens: jax.Array,           # (A,) int32 admitted prompt lengths
    valid: jax.Array,          # (A,) bool — rows actually admitted
):
    """Async speculative loop: merge an admission batch's prompt lengths
    into the device-resident ``cur_len`` carry (see ``cache.merge_spec_len``
    — spec rows advance by data-dependent accepted counts, so the async
    loop keeps ``cur_len`` on device next to the token carry)."""
    return _sh_constrain(C.merge_spec_len(cur_len, slots, lens, valid), (None,))


_spec_merge_len_jit = _LazyJit(lambda: jax.jit(_spec_merge_len))

# TP placement normalizers (warmup only): pass session state through tiny
# jitted pins so every program's warmup operands carry exactly the sharding
# representation their serving-time operands will have — outputs of GSPMD
# programs under the mesh — instead of the ctor's device_put shardings.
# Without this, the FIRST program compiled against each state piece would
# key on the device_put sharding and recompile once at its first real
# dispatch.
_pin_carry_jit = _LazyJit(
    lambda: jax.jit(lambda x: _sh_constrain(x, (None,) * x.ndim))
)
_pin_pool_jit = _LazyJit(
    lambda: jax.jit(_pin_pool, static_argnames=("n_kv",))
)


def _jit_cache_size(fn) -> int:
    """Compiled-program count of a jitted callable. ``_cache_size`` is a
    private jax attribute (stable across 0.4.x); fall back to a sentinel
    rather than crash serving if a jax upgrade drops it — the
    zero-recompile tests compare these values, so a sentinel keeps the
    deltas zero and surfaces the API break via the recorded -1."""
    get = getattr(fn, "_cache_size", None)
    return int(get()) if callable(get) else -1


def scheduler_compile_stats() -> Dict[str, int]:
    """Compiled-program counts of the scheduler's jit entry points.  A trace
    that triggers zero recompiles leaves every count unchanged."""
    return {
        "decode_tick": _jit_cache_size(_decode_tick_jit),
        "spec_tick": _jit_cache_size(_spec_tick_jit),
        "spec_merge_len": _jit_cache_size(_spec_merge_len_jit),
        "admit_fused": _jit_cache_size(_admit_fused_jit),
        "admit_decode": _jit_cache_size(_admit_decode_jit),
        "admit_paged": _jit_cache_size(_admit_fused_paged_jit),
        "prefill_chunk": _jit_cache_size(_prefill_chunk_jit),
        "admit_merge": _jit_cache_size(_admit_merge_jit),
        "evict": _jit_cache_size(_evict_jit),
        "copy_block": _jit_cache_size(_copy_block_jit),
    }


# ---------------------------------------------------------------------------
# Requests / results / stats
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. ``arrival`` is in scheduler ticks (one decode
    step == one tick); ``priority`` orders admission (lower first, FIFO
    within a class); ``tier`` names the requested quality-ladder rung
    (``None`` = the session's best rung; tier sessions only)."""

    req_id: int
    prompt: np.ndarray          # (S0,) int32
    max_new: int
    priority: int = 0
    arrival: int = 0
    tier: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class CompletedRequest:
    req_id: int
    prompt: np.ndarray
    tokens: np.ndarray          # generated tokens (first token included)
    finish_reason: str          # "eos" | "length"
    admitted_tick: int
    finished_tick: int
    # quality tiers: the EFFECTIVE rung the request was served at (requested
    # rung, possibly demoted by the load shedder); "" when tiers are off
    tier: str = ""
    # time-to-first-token in scheduler ticks since arrival (the same sample
    # appended to SchedulerStats.ttft_ticks — kept per-request here so
    # benches can split TTFT by request class); -1 if never recorded
    ttft: int = -1

    @property
    def full_sequence(self) -> np.ndarray:
        return np.concatenate([self.prompt, self.tokens])


@dataclasses.dataclass
class SchedulerStats:
    """Serve-session counters and gauges.

    Every field and derived property is documented in :data:`DOCS` (one line
    per metric, asserted complete by ``tests/test_docs.py``) so the metric
    names the serve benchmarks emit into their ``BENCH_*.json`` artifacts
    are self-describing — benches embed ``SchedulerStats.DOCS`` under a
    ``"field_docs"`` key.

    Two clocks appear below.  *Scheduler ticks* (``ticks``, the latency
    lists) count executed decode steps only — one decode step across all
    slots == one tick, admission is free — and are the unit of ``Request
    .arrival``.  *Work ticks* (``work_ticks``, ``max_decode_gap_ticks``)
    additionally charge each admission its prefill cost, normalized to
    decode widths (``ceil(bucketed prompt tokens / num_slots)``), so they
    approximate device occupancy and make prefill-induced decode starvation
    measurable deterministically (no wall-clock flakiness)."""

    DOCS: ClassVar[Dict[str, str]] = {
        "ticks": "decode ticks executed (1 tick = one decode step across "
                 "all slots; steps_per_tick of them per decode chunk)",
        "busy_slot_steps": "slot-steps that produced an accepted token "
                           "(sum over chunks of accepted tokens)",
        "idle_slot_steps": "slot-steps wasted: empty slots, mid-chunk "
                           "overshoot, and async garbage chunks "
                           "(ticks * num_slots - busy_slot_steps)",
        "admitted": "requests admitted (prefilled into a slot)",
        "completed": "requests finished (eos or length)",
        "generated_tokens": "tokens accepted across all requests, "
                            "including each request's admit-time first token",
        "admit_calls": "batched prefill dispatches (one per admission "
                       "batch, covering 1..num_slots requests)",
        "prefills": "prompt-bucket size -> prefill dispatches charged at "
                    "that bucket (each request's OWN effective-prompt "
                    "bucket — replayed preemption victims count at their "
                    "longer replay bucket — not the admit batch's padding "
                    "bucket; under chunked prefill every CHUNK counts at "
                    "its own chunk bucket, so one long request contributes "
                    "several entries)",
        "peak_active": "max concurrently-resident requests",
        "peak_blocks_in_use": "paged layout: max KV pool blocks held at "
                              "once",
        "ttft_ticks": "per-request time-to-first-token in scheduler ticks "
                      "since the request's arrival (queue wait + prefill), "
                      "appended at admit — under chunked prefill, at the "
                      "FINAL chunk's dispatch, when the first token is "
                      "actually sampled",
        "latency_ticks": "per-request total latency in scheduler ticks "
                         "since arrival, appended at finish",
        "prefill_tokens": "bucketed prompt tokens admitted (the device "
                          "prefill work the interleaving budget meters; "
                          "excludes admit-width padding rows)",
        "work_ticks": "device-work clock: decode steps + prefill charged "
                      "at bucketed tokens / num_slots, integerized "
                      "through a carry so rounding never compounds",
        "prefill_stall_ticks": "scheduler steps where the interleaving "
                               "budget deferred an otherwise-admissible "
                               "request (slots and memory both fit)",
        "max_decode_gap_ticks": "starvation gauge: worst work-tick gap "
                                "between a resident request's consecutive "
                                "accepted tokens (<= steps_per_tick + "
                                "ceil(prefill_decode_ratio * "
                                "steps_per_tick) under the ratio policy; "
                                "chunked prefill tightens the per-item "
                                "budget overshoot from one prompt bucket "
                                "to one chunk — docs/serving.md)",
        "host_block_s": "wall seconds the host spent blocked on device "
                        "token transfers (np.asarray of chunk outputs)",
        "wall_s": "wall seconds spent inside step() in total",
        "slot_utilization": "busy_slot_steps / (busy + idle): fraction of "
                            "decode capacity that produced accepted tokens",
        "ttft_p50": "median time-to-first-token, scheduler ticks",
        "ttft_p95": "95th-percentile time-to-first-token, scheduler ticks",
        "latency_p50": "median request latency, scheduler ticks",
        "latency_p95": "95th-percentile request latency, scheduler ticks",
        "overlap_fraction": "1 - host_block_s / wall_s: fraction of step() "
                            "wall time NOT spent blocked on the device — "
                            "the async loop's pipelining win (sync loop "
                            "reports its serial block share for contrast); "
                            "clamped to [0, 1] because the two timers nest "
                            "imperfectly (a block timed inside a step can "
                            "skew the raw ratio past either end)",
        "prefix_hit_blocks": "prefix sharing: prompt blocks admitted by "
                             "pointing the block table at an already-"
                             "resident shared block instead of acquiring "
                             "and prefill-writing a new one",
        "cow_forks": "prefix sharing: copy-on-write forks — a request "
                     "about to write into a block it shares acquired a "
                     "private copy via copy_block first",
        "preemptions": "preemption: resident requests evicted to free "
                       "blocks for another row's append/fork; the victim "
                       "re-enters the ready queue and replays from its "
                       "accepted tokens (bit-identical under the "
                       "positional key schedule)",
        "attn_impl": "paged decode-attention implementation the session's "
                     "decode program compiled: 'gather' (XLA block gather, "
                     "the oracle) or 'pallas' (in-place block-pool kernel)",
        "draft_tokens": "speculative decoding: tokens proposed by the "
                        "approximate draft path (draft_k per live row per "
                        "verify)",
        "accepted_tokens": "speculative decoding: drafted tokens the exact "
                           "verifier accepted — excludes the correction "
                           "token every verify emits, so accepted == "
                           "drafted means a perfect draft",
        "verify_calls": "speculative decoding: per-row exact verify "
                        "passes (one per live row per spec tick)",
        "accept_rate": "speculative decoding: accepted_tokens / "
                       "draft_tokens — the live end-to-end readout of the "
                       "draft multiplier's error rate (0.0 when spec "
                       "decode is off)",
        "tp": "tensor-parallel degree: size of the session mesh's "
              "'model' axis (1 for single-device serving)",
        "devices": "devices the session mesh spans (1 off-mesh)",
        "peak_block_bytes_per_device": "paged layout: KV pool bytes "
                                       "resident on EACH device for the "
                                       "peak_blocks_in_use blocks — the "
                                       "pool shards along the KV-head dim "
                                       "under TP, so this scales as 1/tp "
                                       "at equal block counts",
        "draft_k_current": "speculative decoding: the draft window the "
                           "NEXT spec tick will dispatch — equals the "
                           "configured draft_k unless dynamic_draft_k "
                           "shrank/regrew it on the rolling accept rate",
        "draft_k_shrinks": "speculative decoding: times dynamic_draft_k "
                           "halved the draft window (rolling accept rate "
                           "below break-even 1/draft_cost_ratio)",
        "draft_k_grows": "speculative decoding: times dynamic_draft_k "
                         "re-grew the draft window (rolling accept rate "
                         "back at/above break-even)",
        "tier_demotions": "quality tiers: times the load shedder raised "
                          "the shed level (new admissions demoted one rung "
                          "further down the tier ladder)",
        "tier_restorations": "quality tiers: times the shedder lowered the "
                             "shed level after shed_hold_steps consecutive "
                             "healthy steps (the hysteresis window clears "
                             "on every level change)",
        "shed_level": "quality tiers: current shed level — new admissions "
                      "serve at ladder rung max(requested, shed_level); "
                      "0 = no shedding in effect",
        "active_per_tier": "quality tiers: currently-resident requests per "
                           "EFFECTIVE ladder rung (the rung each request "
                           "was admitted at, post-shedding); empty when "
                           "tiers are off",
        "prefill_chunks": "chunked prefill: partial-prompt chunk rows "
                          "dispatched (each long request contributes "
                          "ceil(effective_prompt / prefill_chunk) rows; 0 "
                          "when chunking is off or every prompt fits one "
                          "chunk)",
    }

    ticks: int = 0
    busy_slot_steps: int = 0
    idle_slot_steps: int = 0
    admitted: int = 0
    completed: int = 0
    generated_tokens: int = 0
    admit_calls: int = 0
    prefills: Dict[int, int] = dataclasses.field(default_factory=dict)
    peak_active: int = 0
    peak_blocks_in_use: int = 0
    ttft_ticks: List[int] = dataclasses.field(default_factory=list)
    latency_ticks: List[int] = dataclasses.field(default_factory=list)
    prefill_tokens: int = 0
    work_ticks: int = 0
    prefill_stall_ticks: int = 0
    max_decode_gap_ticks: int = 0
    host_block_s: float = 0.0
    wall_s: float = 0.0
    prefix_hit_blocks: int = 0
    cow_forks: int = 0
    preemptions: int = 0
    attn_impl: str = "gather"
    draft_tokens: int = 0
    accepted_tokens: int = 0
    verify_calls: int = 0
    tp: int = 1
    devices: int = 1
    peak_block_bytes_per_device: int = 0
    draft_k_current: int = 0
    draft_k_shrinks: int = 0
    draft_k_grows: int = 0
    tier_demotions: int = 0
    tier_restorations: int = 0
    shed_level: int = 0
    active_per_tier: Dict[str, int] = dataclasses.field(default_factory=dict)
    prefill_chunks: int = 0

    @property
    def accept_rate(self) -> float:
        if not self.draft_tokens:
            return 0.0
        return self.accepted_tokens / self.draft_tokens

    @property
    def slot_utilization(self) -> float:
        cap = self.busy_slot_steps + self.idle_slot_steps
        return self.busy_slot_steps / cap if cap else 0.0

    @property
    def overlap_fraction(self) -> float:
        if not self.wall_s:
            return 0.0
        return min(1.0, max(0.0, 1.0 - self.host_block_s / self.wall_s))

    @staticmethod
    def _pct(xs: List[int], q: float) -> float:
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

    # time-to-first-token (queue wait + prefill) and total latency, both in
    # ticks relative to the request's arrival tick
    @property
    def ttft_p50(self) -> float:
        return self._pct(self.ttft_ticks, 50)

    @property
    def ttft_p95(self) -> float:
        return self._pct(self.ttft_ticks, 95)

    @property
    def latency_p50(self) -> float:
        return self._pct(self.latency_ticks, 50)

    @property
    def latency_p95(self) -> float:
        return self._pct(self.latency_ticks, 95)


@dataclasses.dataclass
class _ActiveSlot:
    req: Request
    slot: int
    tokens: List[int]
    admitted_tick: int
    # set by _finish; the async loop uses it to skip chunk tokens of rows
    # whose completion was discovered after their last chunk was dispatched
    done: bool = False
    # slot/blocks already freed (predictive early turnover — the async loop
    # releases a row whose in-flight chunk provably completes it by length,
    # so a successor can refill the slot before the harvest)
    released: bool = False
    # evicted mid-decode to free blocks for another row; the request is back
    # in the ready queue and will replay from its accepted tokens — every
    # token this state still has in flight is discarded (replay regenerates
    # it bit-identically under the positional key schedule)
    preempted: bool = False
    # async loop: admit-time first token dispatched but not yet harvested
    # (re-admitted rows have non-empty `tokens` while it is still pending,
    # so emptiness can no longer stand in for this)
    pending_first: bool = False
    # quality tiers: the effective ladder rung this request decodes under,
    # frozen at admission (preemption replays re-admit at the same rung so
    # the replay stays bit-identical)
    tier_idx: int = 0
    # chunked prefill: the resident-but-still-prefilling cursor.  A chunked
    # row holds its slot and grows its block table chunk by chunk;
    # `prefill_pos` counts effective-prompt tokens already dispatched and
    # `eff_prompt` caches the effective prompt (prompt + replayed accepted
    # tokens).  One-shot admits leave both at 0/None, so `prefilling` is
    # False for every non-chunked row.
    prefill_pos: int = 0
    prefill_len: int = 0
    eff_prompt: Optional[np.ndarray] = None
    # per-request TTFT sample (ticks since arrival), -1 until the first
    # token is dispatched — survives preemption via the resume snapshot so
    # each request is sampled exactly once
    ttft: int = -1

    @property
    def prefilling(self) -> bool:
        return self.prefill_pos < self.prefill_len


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unharvested decode chunk (async loop).

    ``states`` snapshots ``self._active`` at dispatch time: only those rows
    may accept this chunk's tokens (rows admitted later first appear in the
    *next* chunk).  ``work_end`` is the work-tick clock just after this
    chunk's steps were charged — the emission time used by the starvation
    gauge."""

    toks: Any                  # (steps, N) device future; quality tiers: a
                               # tuple of per-rung futures (disjoint row
                               # masks — merged by elementwise sum at harvest)
    steps: int
    states: List[Optional[_ActiveSlot]]
    work_end: int
    # speculative chunks only: (N,) device future of per-row accepted
    # counts (the chunk's rows advanced unevenly — see _spec_tick)
    n_acc: Any = None
    # speculative chunks only: the draft window THIS chunk was dispatched
    # with (dynamic_draft_k may change _draft_k_eff before the harvest)
    draft_k: int = 0


# ---------------------------------------------------------------------------
# ServeSession
# ---------------------------------------------------------------------------


class ServeSession:
    """Continuous-batching serving over a slot pool (see module docstring).

    >>> sess = ServeSession(cfg, params, num_slots=8, max_len=256)
    >>> sess.submit(prompt_ids, max_new=64)
    >>> results = sess.run()          # {req_id: CompletedRequest}

    ``cache_layout="paged"`` swaps the per-slot ``max_len`` KV stripes for a
    global ``BlockPool`` of ``num_blocks`` blocks of ``block_size`` KV rows:
    ``num_slots`` then bounds decode *width* only, and memory admission is
    governed by each request's worst-case block reservation.  The default
    ``num_blocks`` matches the slot layout's HBM exactly
    (``num_slots * max_len / block_size``); raise ``num_slots`` (or lower
    ``num_blocks``) to oversubscribe.  ``policy`` orders the ready queue:
    ``"priority"`` (the ``Request.priority`` classes, FIFO within a class —
    the default, and plain FIFO when priorities are untouched), ``"fifo"``
    (ignore priorities), or ``"sjf"`` — shortest job first on
    ``max_new + bucketed prompt len``, which minimizes mean latency on a
    drain tail.

    ``loop="async"`` (default) runs the double-buffered pipeline —
    ``step()`` dispatches the next decode chunk before blocking on the
    previous one's tokens, keeping the decode carry device-resident; pass
    ``loop="sync"`` for the PR-3 strictly-alternating loop (the parity
    baseline ``benchmarks/serve_async.py`` measures against).
    ``attn_impl`` selects the paged decode-attention path: ``"gather"``
    (XLA clamp-gather-mask, the exact oracle) or ``"pallas"`` (the
    ``kernels.paged_attention`` in-place block-pool kernel; interpret mode
    off-TPU).  ``prefill_decode_ratio`` / ``prefill_token_budget`` bound the bucketed
    prompt tokens each ``step()`` may admit while decodes are resident
    (``ratio * n_active * steps_per_tick`` resp. a flat budget), so a burst
    of long prompts spreads over several steps instead of stalling every
    resident decode behind one giant prefill train.

    ``spec_decode=True`` turns each work tick into SELF-speculative
    decoding (paged layout, ``steps_per_tick=1`` only): ``draft_k`` decode
    steps through the approximate draft path (``draft_mode`` x
    ``draft_multiplier`` — the same weights with only ``cfg.approx``
    swapped, see ``engine.draft_config``), then one exact verify pass that
    accepts each row's longest matching draft prefix plus a correction
    token.  Accepted outputs are bit-identical to the non-speculative
    session under float execution BY CONSTRUCTION (see ``_spec_tick``), so
    ``stats.accept_rate`` is a pure throughput readout of the draft
    multiplier's error rate — the paper's claim, measured end-to-end.
    Rows advance unevenly (1..draft_k+1 tokens per tick), which is why the
    async loop keeps a device-resident length carry next to the token
    carry.  ``close()`` flushes the in-flight chunk and seals the session:
    later ``submit``/``step`` raise ``RuntimeError``.

    ``tiers=("exact", "approx", "approx_msr")`` turns on per-request
    quality-tier routing (attention families; mutually exclusive with
    ``spec_decode``): each rung gets its own compiled decode/prefill
    programs (the session cfg with only ``cfg.approx`` swapped —
    ``tier_multiplier`` names the approximate design, MSR rungs default to
    ``mul8x8_msr4``), ``submit(..., tier=...)`` picks a request's rung, and
    every step dispatches one decode chunk per rung holding active rows.
    ``warmup()`` compiles the full rung x width x bucket program set, so no
    tier mix recompiles.  ``shed_queue_depth`` / ``shed_gap_ticks`` arm the
    load shedder: breaches demote NEW admissions one rung down the ladder
    (resident requests never switch rungs — a request's output is
    bit-identical to a single-mode oracle of its effective rung), and
    recovery restores one rung after ``shed_hold_steps`` consecutive steps
    below ``shed_restore_fraction`` of the thresholds."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        num_slots: int = 4,
        max_len: int = 256,
        prompt_buckets: Sequence[int] = (8, 16, 32, 64),
        sampling: Optional[SamplingConfig] = None,
        cache_dtype=jnp.float32,
        seed: int = 0,
        zero_on_evict: bool = False,
        steps_per_tick: int = 1,
        cache_layout: str = "slots",
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        policy: str = "priority",
        loop: str = "async",
        prefill_decode_ratio: Optional[float] = None,
        prefill_token_budget: Optional[int] = None,
        chunked_prefill: bool = False,
        prefill_chunk: Optional[int] = None,
        attn_impl: str = "gather",
        pad_id: int = 0,
        prefix_sharing: bool = False,
        preemption: bool = False,
        spec_decode: bool = False,
        draft_k: int = 4,
        draft_mode: str = "approx",
        draft_multiplier: str = "mul8x8_2",
        dynamic_draft_k: bool = False,
        draft_cost_ratio: float = 4.0,
        draft_window: int = 32,
        tiers: Optional[Sequence[str]] = None,
        tier_multiplier: str = "mul8x8_2",
        shed_queue_depth: Optional[int] = None,
        shed_gap_ticks: Optional[int] = None,
        shed_hold_steps: int = 8,
        shed_restore_fraction: float = 0.5,
        mesh=None,
        tp_axis: str = "model",
    ):
        if not cfg.embed_input:
            raise ValueError(f"{cfg.name}: token serving requires an embed-input arch")
        if cache_layout not in CACHE_LAYOUTS:
            raise ValueError(f"cache_layout {cache_layout!r} not in {CACHE_LAYOUTS}")
        if policy not in ADMISSION_POLICIES:
            raise ValueError(f"policy {policy!r} not in {ADMISSION_POLICIES}")
        if loop not in SERVE_LOOPS:
            raise ValueError(f"loop {loop!r} not in {SERVE_LOOPS}")
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(f"attn_impl {attn_impl!r} not in {ATTN_IMPLS}")
        if attn_impl != "gather" and cache_layout != "paged":
            raise ValueError(
                f"attn_impl {attn_impl!r} requires cache_layout='paged' — "
                "the slot layout has no block table to walk"
            )
        if prefill_decode_ratio is not None and prefill_token_budget is not None:
            raise ValueError(
                "prefill_decode_ratio and prefill_token_budget are alternative "
                "interleaving policies — set at most one"
            )
        if prefill_decode_ratio is not None and prefill_decode_ratio <= 0:
            raise ValueError(
                f"prefill_decode_ratio must be > 0, got {prefill_decode_ratio}"
            )
        if prefill_token_budget is not None and prefill_token_budget < 1:
            raise ValueError(
                f"prefill_token_budget must be >= 1, got {prefill_token_budget}"
            )
        if (prefix_sharing or preemption) and cache_layout != "paged":
            raise ValueError(
                "prefix_sharing/preemption operate on the shared BlockPool — "
                'they require cache_layout="paged"'
            )
        if spec_decode:
            if cache_layout != "paged":
                raise ValueError(
                    "spec_decode verifies drafted positions against the "
                    'block pool — it requires cache_layout="paged"'
                )
            if steps_per_tick != 1:
                raise ValueError(
                    "spec_decode replaces the decode chunk with draft_k "
                    "drafts + one verify per tick — steps_per_tick must "
                    f"stay 1, got {steps_per_tick}"
                )
            if draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got {draft_k}")
            if cfg.family == "moe":
                raise ValueError(
                    "spec_decode requires a dense attention family: moe "
                    "routing is capacity-coupled across the token batch, "
                    "so a batched verify would route differently than "
                    "sequential decode and lose the exactness contract"
                )
        if dynamic_draft_k:
            if not spec_decode:
                raise ValueError("dynamic_draft_k requires spec_decode=True")
            if draft_cost_ratio <= 1.0:
                raise ValueError(
                    "draft_cost_ratio is verify-work / draft-step-work and "
                    f"must be > 1 (break-even accept rate is its inverse), "
                    f"got {draft_cost_ratio}"
                )
            if draft_window < 1:
                raise ValueError(f"draft_window must be >= 1, got {draft_window}")
        if tiers is not None:
            tiers = tuple(tiers)
            if not tiers:
                raise ValueError("tiers must name at least one execution mode")
            if len(set(tiers)) != len(tiers):
                raise ValueError(f"tiers contains duplicate rungs: {tiers}")
            for t in tiers:
                if t not in EXECUTION_MODES:
                    raise ValueError(
                        f"tier {t!r} not in execution modes {EXECUTION_MODES}"
                    )
            if spec_decode:
                raise ValueError(
                    "tiers and spec_decode both repurpose the per-dispatch "
                    "cfg.approx execution routing — set at most one"
                )
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    "quality tiers dispatch one decode program per rung and "
                    "rely on positional KV writes to keep the other rungs' "
                    f"rows untouched — {cfg.family} carries non-positional "
                    "conv/ssm state, so tier serving requires an attention "
                    "family"
                )
        shed_on = shed_queue_depth is not None or shed_gap_ticks is not None
        if shed_on:
            if tiers is None or len(tiers) < 2:
                raise ValueError(
                    "load shedding demotes admissions down the quality "
                    "ladder — it requires tiers with >= 2 rungs"
                )
            if shed_queue_depth is not None and shed_queue_depth < 1:
                raise ValueError(
                    f"shed_queue_depth must be >= 1, got {shed_queue_depth}"
                )
            if shed_gap_ticks is not None and shed_gap_ticks < 1:
                raise ValueError(
                    f"shed_gap_ticks must be >= 1, got {shed_gap_ticks}"
                )
            if shed_hold_steps < 1:
                raise ValueError(
                    f"shed_hold_steps must be >= 1, got {shed_hold_steps}"
                )
            if not 0.0 < shed_restore_fraction <= 1.0:
                raise ValueError(
                    "shed_restore_fraction must be in (0, 1], got "
                    f"{shed_restore_fraction}"
                )
        if mesh is not None:
            if tp_axis != "model":
                raise ValueError(
                    f"tp_axis must be 'model' (param_pspec/cache_pspecs key "
                    f"their TP rules on it), got {tp_axis!r}"
                )
            if tp_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh has no {tp_axis!r} axis (axes: {mesh.axis_names})"
                )
            if cache_layout != "paged":
                raise ValueError(
                    "mesh serving shards the paged BlockPool along the "
                    'KV-head dim — it requires cache_layout="paged"'
                )
        self.cfg = cfg
        self.params = params
        self.sampling = sampling if sampling is not None else SamplingConfig()
        self.max_len = int(max_len)
        self.layout = cache_layout
        self.policy = policy
        self.loop = loop
        self.attn_impl = attn_impl
        self.pad_id = int(pad_id)
        self.prefix_sharing = bool(prefix_sharing)
        self.preempt = bool(preemption)
        self.prefill_decode_ratio = prefill_decode_ratio
        self.prefill_token_budget = prefill_token_budget
        self.spec = bool(spec_decode)
        self.draft_k = int(draft_k)
        self.dynamic_draft = bool(dynamic_draft_k)
        self.draft_cost_ratio = float(draft_cost_ratio)
        self.draft_window = int(draft_window)
        # halving ladder draft_k -> 1: the rungs dynamic_draft_k may visit.
        # draft_k is a STATIC jit arg, so warmup() compiles every rung and
        # adaptation never compiles mid-trace.
        ks: List[int] = []
        k = max(1, self.draft_k)
        while True:
            ks.append(k)
            if k == 1:
                break
            k //= 2
        self._draft_ks: Tuple[int, ...] = tuple(ks)
        self._draft_k_eff = self.draft_k
        # rolling (drafted, accepted) pairs over the last draft_window live
        # rows; cleared on every rung change so each rung re-measures a full
        # window before the next decision
        self._accept_hist: deque = deque(maxlen=self.draft_window)
        self.draft_mode = draft_mode if self.spec else None
        # the draft model IS the session model with only cfg.approx swapped
        # (shared weights; one extra compiled decode program) — see
        # engine.draft_config
        self.draft_cfg = (
            draft_config(cfg, draft_mode, draft_multiplier) if self.spec
            else None
        )
        # -- quality tiers ----------------------------------------------------
        # One ModelConfig per ladder rung: the session cfg with only `approx`
        # swapped (the draft_config pattern — shared weights, one compiled
        # decode program per rung).  act_per_row=True makes each batch row's
        # quantized math independent of its neighbours, which is what makes
        # a mixed-tier batch bit-identical per request to a single-mode
        # oracle session of its rung.
        self.tiers: Optional[Tuple[str, ...]] = tiers
        self.tier_multiplier = tier_multiplier
        self._tier_cfgs: Tuple[ModelConfig, ...] = (
            tuple(
                dataclasses.replace(
                    cfg,
                    approx=resolve_execution_mode(
                        t, tier_multiplier, act_per_row=True
                    ),
                )
                for t in tiers
            )
            if tiers is not None else ()
        )
        self._shed_on = shed_on
        self.shed_queue_depth = shed_queue_depth
        self.shed_gap_ticks = shed_gap_ticks
        self.shed_hold_steps = int(shed_hold_steps)
        self.shed_restore_fraction = float(shed_restore_fraction)
        self._shed_level = 0
        # consecutive healthy steps toward a restore; cleared on every shed-
        # level change and on every unhealthy step (the hysteresis window)
        self._shed_ok_steps = 0
        self._tier_active_counts: List[int] = [0] * (len(tiers) if tiers else 0)
        self.buckets = C.PromptBuckets(prompt_buckets)
        if self.buckets.max_size > self.max_len:
            raise ValueError(
                f"largest prompt bucket {self.buckets.max_size} > max_len {self.max_len}"
            )
        # -- chunked prefill --------------------------------------------------
        # Split one prompt's prefill into prefill_chunk-wide chunks dispatched
        # across successive scheduler steps (resumed through _prefilling), so
        # a long prompt never monopolizes a tick and the interleaving budget
        # meters chunks, not whole buckets.  v1 composes with preemption (a
        # replayed victim's long recompute is itself chunked) but not with
        # the features below — each gated with its reason.
        if prefill_chunk is not None and not chunked_prefill:
            raise ValueError("prefill_chunk requires chunked_prefill=True")
        if chunked_prefill:
            if cache_layout != "paged":
                raise ValueError(
                    "chunked prefill resumes a partially-written block table "
                    'across ticks — it requires cache_layout="paged" (the '
                    "slot layout has no sentinel-tailed table to grow)"
                )
            if cfg.family == "moe":
                raise ValueError(
                    "chunked prefill teacher-forces chunk tokens through a "
                    "batched pass; moe routing is capacity-coupled across "
                    "the token batch, so chunks would route differently "
                    "than the fused prefill oracle and lose the exactness "
                    "contract"
                )
            if spec_decode:
                raise ValueError(
                    "chunked prefill and spec_decode both repurpose the "
                    "multi-position verify pass with different per-tick "
                    "schedules — composing them is a ROADMAP follow-on; "
                    "set at most one"
                )
            if tiers is not None:
                raise ValueError(
                    "chunked prefill dispatches chunk batches outside the "
                    "per-rung admit grouping — composing it with quality "
                    "tiers is a ROADMAP follow-on"
                )
            if prefix_sharing:
                raise ValueError(
                    "prefix sharing publishes prompt blocks at admission, "
                    "but a chunk-prefilled block is written ticks after its "
                    "table entry exists — a sharer could map it before its "
                    "K/V lands; publish-at-chunk-boundary is a ROADMAP "
                    "follow-on"
                )
            if prefill_chunk is None:
                prefill_chunk = self.buckets.max_size
            if prefill_chunk not in self.buckets.sizes:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} must be one of the "
                    f"prompt buckets {self.buckets.sizes} — chunk widths are "
                    "drawn from the bucket set so the compiled program set "
                    "stays (admit widths x buckets)"
                )
        self.chunked = bool(chunked_prefill)
        self.prefill_chunk = int(prefill_chunk) if chunked_prefill else 0
        self.pool = C.SlotPool(num_slots)
        self.num_slots = num_slots
        self.cache_dtype = jnp.dtype(cache_dtype).name
        self.zero_on_evict = zero_on_evict
        if steps_per_tick < 1:
            raise ValueError(f"steps_per_tick must be >= 1, got {steps_per_tick}")
        # decode-chunk size: dispatches amortize steps_per_tick-fold, rows
        # finishing mid-chunk waste <= steps_per_tick - 1 slot-steps each
        self.steps_per_tick = int(steps_per_tick)
        # SSM/hybrid caches carry conv/ssm state -> masked teacher-forced admit
        self.prefill_mode = "decode" if cfg.family in ("ssm", "hybrid") else "fused"

        if cache_layout == "paged":
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    f"{cfg.family} decode state is O(1) per request (no KV "
                    "sequence axis) — there is nothing to page; use "
                    'cache_layout="slots"'
                )
            if zero_on_evict:
                raise ValueError(
                    "zero_on_evict applies to the slot layout only (freed "
                    "blocks are invisible until re-seeded by their next owner)"
                )
            if block_size < 1:
                raise ValueError(f"block_size must be >= 1, got {block_size}")
            if self.max_len % block_size:
                raise ValueError(
                    f"max_len {self.max_len} must be a multiple of "
                    f"block_size {block_size} (fixed-width block tables)"
                )
            self.block_size = int(block_size)
            self.table_width = self.max_len // self.block_size
            if num_blocks is None:
                num_blocks = num_slots * self.table_width    # == slot-layout HBM
            self.blocks = C.BlockPool(num_blocks)
            self.num_blocks = int(num_blocks)
            self.cache = init_paged_cache(
                cfg, self.num_blocks, self.block_size, jnp.dtype(cache_dtype)
            )
            # per-slot block table (sentinel == num_blocks -> writes dropped),
            # held physical blocks, and not-yet-held worst-case reservation
            self._tables = np.full(
                (num_slots, self.table_width), self.num_blocks, np.int32
            )
            self._held: List[List[int]] = [[] for _ in range(num_slots)]
            self._future = np.zeros((num_slots,), np.int64)
            self._reserved_total = 0           # future blocks across all rows
            # prefix sharing: content -> physical block; the scheduler takes
            # one pool ref per published block on the cache's behalf
            self._prefix = C.PrefixCache() if self.prefix_sharing else None
            # preemption: req_id -> (accepted tokens, original admit tick,
            # effective tier rung), consumed when the victim re-admits and
            # replays (at the SAME rung — the replay must be bit-identical)
            self._preempt_resume: Dict[int, Tuple[List[int], int, int]] = {}
        else:
            self._prefix = None
            self._preempt_resume = {}
            self.cache = init_cache(cfg, num_slots, self.max_len, jnp.dtype(cache_dtype))

        # -- tensor parallelism ----------------------------------------------
        # Shard params by the param_pspec rules (Megatron column/row split)
        # and the paged pool along the KV-head dim (cache_pspecs paged
        # layout); all program dispatches then run under ``jax.set_mesh`` (see
        # _mesh_ctx) so constrain() sees the mesh at trace time.
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp = int(mesh.shape[tp_axis]) if mesh is not None else 1
        if mesh is not None:
            from repro.parallel.sharding import cache_pspecs, param_shardings

            if attn_impl == "pallas":
                from repro.kernels.paged_attention import validate_tp_heads

                validate_tp_heads(cfg.num_heads, cfg.num_kv_heads, self.tp)
            self.params = jax.device_put(
                self.params, param_shardings(cfg, self.params, mesh)
            )
            self.cache = jax.device_put(
                self.cache, cache_pspecs(cfg, mesh, self.cache, layout="paged")
            )
        # per-device bytes of ONE pool block (0 for the slot layout): the
        # peak_block_bytes_per_device gauge and the bench's 1/tp KV-bytes
        # claim both read it
        self._block_bytes_dev = (
            C.pool_bytes_per_device(self.cache) // self.num_blocks
            if self.layout == "paged" else 0
        )

        self._last_token = np.zeros((num_slots,), np.int32)
        self._cur_len = np.zeros((num_slots,), np.int32)
        self._slot_keys = np.zeros((num_slots, 2), np.uint32)
        # quality tiers: each slot occupant's effective ladder rung (valid
        # only where a slot is occupied — per-rung dispatch masks on it)
        self._slot_tier = np.zeros((num_slots,), np.int32)
        self._base_key = jax.random.PRNGKey(seed)

        self._active: List[Optional[_ActiveSlot]] = [None] * num_slots
        # future arrivals: heap of (arrival, submit seq, req) — submit pushes
        # in O(log n) and _pull_arrivals pops in O(log n), replacing the
        # per-submit sort + O(n) list.pop(0) that made long traces O(n^2);
        # the seq tiebreak reproduces the old stable-sort admission order
        self._pending: List[Tuple[int, int, Request]] = []
        self._ready: List[Tuple[int, int, Request]] = []  # heap (policy key, seq)
        self._seq = 0
        self._next_id = 0
        self.clock = 0
        self.stats = SchedulerStats(
            attn_impl=attn_impl,
            tp=self.tp,
            devices=int(mesh.size) if mesh is not None else 1,
            draft_k_current=self.draft_k if self.spec else 0,
        )
        self._completed: Dict[int, CompletedRequest] = {}
        self._just_finished: List[int] = []     # drained by each step()
        # -- async pipeline state --------------------------------------------
        self._closed = False
        self._inflight: Optional[_Inflight] = None
        # device-resident decode carry: the async loop never fetches these,
        # it chains chunk outputs and admit merges into the next dispatch
        self._lt_dev: jax.Array = jnp.zeros((num_slots,), jnp.int32)
        self._sk_dev: jax.Array = jnp.zeros((num_slots, 2), jnp.uint32)
        # speculative async loop: rows advance by data-dependent accepted
        # counts, so cur_len joins the device carry (_cl_dev); the host
        # keeps _cur_len as a conservative UPPER bound (every live row
        # charged the full draft_k + 1 at dispatch, reconciled at harvest)
        # for block allocation, and _cl_true as the truth through the last
        # harvested chunk (the CoW guard's lower bound)
        self._cl_dev: jax.Array = jnp.zeros((num_slots,), jnp.int32)
        self._cl_true = np.zeros((num_slots,), np.int32)
        # admissions dispatched since the last harvest: their first sampled
        # tokens are fetched together with the next chunk's tokens
        self._pending_tok0: List[Tuple[List[_ActiveSlot], Any]] = []
        # work-tick of each slot occupant's latest accepted token (gauge)
        self._last_emit_work = np.zeros((num_slots,), np.int64)
        # prefill-token residue below one work tick (carried, not ceil'd)
        self._prefill_carry = 0
        # chunked prefill: resident rows whose prompt is still being written,
        # FIFO between the arrival heap and the decoding set — each step
        # resumes their next chunk (budget permitting) BEFORE admitting new
        # work, so in-flight prefills finish first and bound their own TTFT
        self._prefilling: List[_ActiveSlot] = []

    # -- queue ---------------------------------------------------------------

    def submit(
        self,
        prompt,
        max_new: int,
        *,
        req_id: Optional[int] = None,
        priority: int = 0,
        arrival: int = 0,
        tier: Optional[str] = None,
    ) -> int:
        """Queue one request; returns its id. ``arrival`` in ticks.
        ``tier`` names the requested quality-ladder rung (tier sessions
        only; ``None`` = the session's best rung) — the load shedder may
        still demote the EFFECTIVE rung at admission time.

        Every shape constraint is validated HERE, naming the request — a
        request that can never be admitted must fail at submit, not deep
        inside an admission tick.  A sealed session (after ``close()``)
        refuses loudly rather than queueing work that will never run."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        rid = self._next_id if req_id is None else req_id
        if self._closed:
            raise RuntimeError(
                f"request {rid}: submitted after close() — the session is "
                "sealed and its pipeline flushed; create a new ServeSession"
            )
        if tier is not None:
            if self.tiers is None:
                raise ValueError(
                    f"request {rid}: tier={tier!r} on a session without a "
                    "quality ladder — construct ServeSession(tiers=(...))"
                )
            if tier not in self.tiers:
                raise ValueError(
                    f"request {rid}: tier {tier!r} not in session tiers "
                    f"{self.tiers}"
                )
        if prompt.size < 1:
            raise ValueError(f"request {rid}: empty prompt")
        if max_new < 1:
            raise ValueError(f"request {rid}: max_new must be >= 1, got {max_new}")
        if prompt.size > self.buckets.max_size and not self.chunked:
            raise ValueError(
                f"request {rid}: prompt_len {prompt.size} exceeds the largest "
                f"prompt bucket {self.buckets.max_size} (buckets "
                f"{self.buckets.sizes}) — split the prompt, widen the "
                "buckets, or enable chunked_prefill"
            )
        # strict `>`: the exact-fill boundary prompt_len + max_new == max_len
        # IS admissible — the last cache write lands at position
        # prompt_len + max_new - 2 <= max_len - 2 (the final token is
        # sampled, never written; see _worst_blocks) and decode's cur_len
        # clamp at max_len - 1 is never binding before the row finishes.
        # Pinned for both layouts by tests/test_scheduler.py
        # (test_exact_fill_boundary_admits_and_completes).
        if prompt.size > self.buckets.max_size:
            # chunked-only admission: no single bucket pads this prompt —
            # every chunk pads to its own chunk bucket and writes stay
            # within the prompt's blocks, so only the raw context binds
            if prompt.size + max_new > self.max_len:
                raise ValueError(
                    f"request {rid}: prompt_len {prompt.size} + max_new "
                    f"{max_new} exceeds cache max_len {self.max_len}"
                )
        else:
            bucket = self.buckets.bucket(prompt.size)
            if max(bucket, prompt.size + max_new) > self.max_len:
                raise ValueError(
                    f"request {rid}: prompt_len {prompt.size} + max_new "
                    f"{max_new} (bucket {bucket}) exceeds cache max_len "
                    f"{self.max_len}"
                )
        if self.layout == "paged":
            worst = self._worst_blocks(prompt.size, max_new)
            if worst > self.num_blocks:
                raise ValueError(
                    f"request {rid}: worst-case context needs {worst} blocks "
                    f"but the pool only has {self.num_blocks} — it could "
                    "never be admitted"
                )
            if (self.prefix_sharing and not self.preempt
                    and prompt.size % self.block_size
                    and worst + 1 > self.num_blocks):
                raise ValueError(
                    f"request {rid}: prefix sharing reserves {worst} + 1 "
                    "blocks (worst case + the partial tail's potential "
                    f"copy-on-write fork) but the pool only has "
                    f"{self.num_blocks} — it could never be admitted"
                )
            # with chunking the replay prompt needs no single bucket — its
            # chunks each pad to a chunk bucket, like any long prompt
            if (self.preempt and not self.chunked
                    and prompt.size + max_new - 1 > self.buckets.max_size):
                raise ValueError(
                    f"request {rid}: preemption replays prompt + accepted "
                    f"tokens through the bucketed prefill — its replay "
                    f"prompt can reach {prompt.size + max_new - 1} tokens, "
                    f"exceeding the largest prompt bucket "
                    f"{self.buckets.max_size}; widen the buckets or lower "
                    "max_new"
                )
        if req_id is None:
            req_id = rid
        elif (
            req_id in self._completed
            or any(r.req_id == req_id for _, _, r in self._pending)
            or any(r.req_id == req_id for _, _, r in self._ready)
            or any(s is not None and s.req.req_id == req_id for s in self._active)
        ):
            raise ValueError(f"req_id {req_id} already in use")
        self._next_id = max(self._next_id, req_id) + 1
        req = Request(req_id, prompt, int(max_new), int(priority), int(arrival),
                      tier)
        if req.arrival > self.clock:
            heapq.heappush(self._pending, (req.arrival, self._seq, req))
            self._seq += 1
        else:
            self._push_ready(req)
        return req_id

    def submit_all(self, requests: Sequence[Request]) -> None:
        for r in requests:
            self.submit(r.prompt, r.max_new, req_id=r.req_id,
                        priority=r.priority, arrival=r.arrival, tier=r.tier)

    def _ready_key(self, req: Request, eff_len: Optional[int] = None) -> int:
        """Admission-order key under the session policy (ties broken FIFO by
        submission sequence).  SJF ranks the EFFECTIVE prompt: a preempted
        request re-admits by replaying prompt + accepted tokens through the
        prefill, so its cost is the longer replay prompt, not the original
        ``req.prompt`` (``_pick_victim`` passes the would-be replay length
        of a still-resident row the same way)."""
        if self.policy == "sjf":
            # shortest job first: expected residency = generation budget +
            # bucketed prefill cost (summed over chunks when chunking)
            if eff_len is None:
                eff_len = int(self._eff_prompt(req).size)
            return req.max_new + self._prefill_cost(eff_len)
        if self.policy == "fifo":
            return 0
        return req.priority

    def _push_ready(self, req: Request) -> None:
        heapq.heappush(self._ready, (self._ready_key(req), self._seq, req))
        self._seq += 1

    # -- admission -----------------------------------------------------------

    def _worst_blocks(self, prompt_len: int, max_new: int) -> int:
        """Blocks the request could ever hold: its last cache write lands at
        position ``prompt_len + max_new - 2`` (token ``t`` of ``max_new`` is
        written at ``prompt_len + t - 2``; the final sampled token is output,
        never written), and prefill occupies ``[0, prompt_len)`` — bucket
        right-padding past the last prompt block is dropped, never stored."""
        return -(-(prompt_len + max_new - 1) // self.block_size)

    # -- chunked-prefill planning --------------------------------------------

    def _chunks_prefill(self, eff_len: int) -> bool:
        """Whether a prompt of this effective length takes the chunked path.
        Prompts that fit one chunk keep the one-shot ``_admit_many`` path —
        identical dispatch to an unchunked session, which is what lets the
        parity oracle share every short-prompt program."""
        return self.chunked and eff_len > self.prefill_chunk

    def _chunk_spans(self, eff_len: int) -> List[int]:
        """Deterministic host-side chunk plan: ``prefill_chunk``-wide spans
        plus a remainder.  Each span dispatches at its own bucket
        (``bucket(span) <= prefill_chunk``), so every chunk shape is already
        in the warmed (admit width x bucket) program set."""
        spans, pos = [], 0
        while pos < eff_len:
            s = min(self.prefill_chunk, eff_len - pos)
            spans.append(s)
            pos += s
        return spans

    def _prefill_cost(self, eff_len: int) -> int:
        """Bucketed prefill tokens this prompt will charge in total — the
        one-shot bucket, or the sum of chunk buckets when chunking (used by
        SJF ranking and victim selection; safe past ``buckets.max_size``,
        where ``bucket()`` itself would raise)."""
        if not self._chunks_prefill(eff_len):
            return self.buckets.bucket(eff_len)
        return sum(self.buckets.bucket(s) for s in self._chunk_spans(eff_len))

    def _head_charge(self, eff_len: int) -> int:
        """Tokens the interleaving budget charges when this request admits
        THIS step: the first chunk's bucket on the chunked path (later
        chunks are charged step by step from the resume queue), else the
        whole one-shot bucket."""
        if self._chunks_prefill(eff_len):
            return self.prefill_chunk
        return self.buckets.bucket(eff_len)

    # -- prefix sharing / preemption helpers ---------------------------------

    def _eff_prompt(self, req: Request) -> np.ndarray:
        """The prompt a (re-)admission actually prefills: the original
        prompt, extended by the accepted tokens snapshotted at preemption —
        recompute-based re-admission replays the victim as a longer prompt,
        and the positional fold_in key schedule makes the replayed samples
        bit-identical to the uninterrupted run."""
        resume = self._preempt_resume.get(req.req_id)
        if resume is None:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(resume[0], np.int32)]
        ).astype(np.int32)

    def _reclaimable_blocks(self) -> int:
        """Published blocks held ONLY by the prefix cache (refcount 1):
        evictable on demand, so admission may count them as free."""
        if self._prefix is None:
            return 0
        return sum(1 for b in self._prefix.lru_blocks()
                   if self.blocks.refcount(b) == 1)

    def _reclaim_cache_block(self) -> bool:
        """Evict the least-recently-used cache-only published block back to
        the free heap.  Returns False when every published block is still
        shared with a resident request (nothing to reclaim)."""
        if self._prefix is None:
            return False
        for b in self._prefix.lru_blocks():
            if self.blocks.refcount(b) == 1:
                self._prefix.drop_block(b)
                self.blocks.release(b)          # the cache's own reference
                return True
        return False

    def _pick_victim(self, excl_slot: int) -> Optional[_ActiveSlot]:
        """Preemption victim: the least-important resident row — highest
        policy key (lowest priority class), then youngest admit, then
        highest req_id — excluding the row that needs the block."""
        best = None
        best_key = None
        for state in self._active:
            if (state is None or state.done or state.released
                    or state.preempted or state.slot == excl_slot):
                continue
            # a victim re-admits by replaying prompt + accepted tokens, so
            # rank it on that replay length (what SJF would charge it)
            key = (self._ready_key(
                       state.req,
                       eff_len=state.req.prompt.size + len(state.tokens),
                   ),
                   state.admitted_tick,
                   state.req.req_id)
            if best_key is None or key > best_key:
                best, best_key = state, key
        return best

    def _preempt(self, state: _ActiveSlot) -> None:
        """Evict ``state`` mid-decode: snapshot its accepted tokens for
        replay, free its private blocks (shared ones just decref — the
        zeroed table row makes any in-flight writes sentinel-dropped), and
        push the original request back on the ready queue."""
        state.preempted = True
        # the 4th element carries the first-token latency across the
        # eviction: ttft is counted exactly once per request, and a
        # mid-prefill victim (chunked path, ttft still unsampled) gets its
        # ttft at the REPLAY's final chunk instead
        self._preempt_resume[state.req.req_id] = (
            list(state.tokens), state.admitted_tick, state.tier_idx,
            state.ttft,
        )
        self._release_resources(state)
        self._push_ready(state.req)
        self.stats.preemptions += 1

    def _acquire_block(self, requesting_slot: int) -> int:
        """One block for ``requesting_slot``, escalating: free heap ->
        reclaim a cache-only published block -> (preemption on) evict the
        least-important other resident row, repeating until a block frees.
        Deadlock-free: submit bounds every request's worst case at
        ``num_blocks``, so once every other row is evicted and every
        cache-only block reclaimed, the pool can always fund the requester's
        next block."""
        b = self.blocks.acquire()
        if b is not None:
            return b
        while self._reclaim_cache_block():
            b = self.blocks.acquire()
            if b is not None:
                return b
        if not self.preempt:
            raise AssertionError("block append failed despite reservation")
        while True:
            victim = self._pick_victim(requesting_slot)
            if victim is None:
                raise AssertionError(
                    "block pool exhausted with no victim left — submit's "
                    "worst-case bound should make this unreachable"
                )
            self._preempt(victim)
            while self._reclaim_cache_block():
                pass
            b = self.blocks.acquire()
            if b is not None:
                return b

    def _admit_block(self) -> int:
        """One block for an admission row.  Never preempts: admission was
        gated on ``free + reclaimable`` (preemption) or the reservation
        (without), so free-heap + cache reclaim must always fund it."""
        b = self.blocks.acquire()
        while b is None and self._reclaim_cache_block():
            b = self.blocks.acquire()
        assert b is not None, "admission admitted an unfundable request"
        return b

    def _cow_guard(self, slot: int, state: _ActiveSlot, idx: int) -> None:
        """Copy-on-write: before a chunk writes into held block ``idx``,
        make that block privately owned and unpublished.  Publication is
        dropped first (the content is about to diverge from its key); if
        the block is still shared with another request after that, fork it
        through ``copy_block`` into a private copy.  ``_chunk_inputs``
        passes the block holding ``cur_len`` (the only pre-existing block a
        non-speculative chunk can touch — later positions land in freshly
        acquired private blocks) or, speculatively, every block index the
        chunk's write span could reach; guarding a privately held index is
        a no-op."""
        held = self._held[slot]
        if idx >= len(held):
            return                          # next write opens a fresh block
        b = held[idx]
        if self._prefix is not None and self._prefix.holds_block(b):
            self._prefix.drop_block(b)
            self.blocks.release(b)          # the cache's reference
        if self.blocks.refcount(b) <= 1:
            return                          # sole owner: write in place
        nb = self._acquire_block(slot)
        self.cache = _copy_block_jit(
            self.cache, np.int32(b), np.int32(nb), n_kv=self.cfg.num_kv_heads
        )
        self.blocks.release(b)              # this row's shared reference
        held[idx] = nb
        self._tables[slot, idx] = nb
        self.stats.cow_forks += 1
        if not self.preempt:
            # the fork consumes the +1 reserve _admit_many added for a
            # shared tail, keeping appends infallible without preemption
            self._future[slot] -= 1
            self._reserved_total -= 1

    def _admit_width(self, n: int) -> int:
        """Admission rows are width-bucketed to powers of two (capped at
        ``num_slots``) so small admissions don't pay a full-width prefill:
        the compiled-program set stays {1, 2, 4, ...} x prompt buckets."""
        w = 1
        while w < n:
            w <<= 1
        return min(w, self.num_slots)

    def _admit_many(self, reqs: List[Request], tier_idx: int = 0) -> None:
        """Admit up to ``num_slots`` requests with ONE prefill dispatch: all
        prompts pad to the largest needed bucket, the row count pads to the
        admit-width bucket, and padding rows are no-ops — so the compiled
        program depends only on (admit width, prompt bucket).  Under the
        paged layout each request additionally acquires its prompt's blocks
        (``ceil(prompt_len / block_size)`` — proportional to the *actual*
        context, not the bucket or ``max_len``), converting that much of the
        reservation ``step`` took out when it popped the request.  On a tier
        session every request of the batch shares the effective rung
        ``tier_idx`` (``_admit_phase`` groups by rung) and prefills under
        that rung's config — the prompt KV must be seeded by the same
        execution mode its decode runs."""
        assert 0 < len(reqs) <= self.pool.free_count
        acfg = self._tier_cfgs[tier_idx] if self.tiers is not None else self.cfg
        A = self._admit_width(len(reqs))
        effs = [self._eff_prompt(r) for r in reqs]   # replay prompt if resumed
        bucket = max(self.buckets.bucket(e.size) for e in effs)
        # right-pad with the model's real pad id: token 0 can be a meaningful
        # vocab entry, and the masked teacher-forced ssm/hybrid prefill rows
        # see the pad positions before their per-row freeze
        prompts = np.full((A, bucket), self.pad_id, np.int32)
        prompt_lens = np.ones((A,), np.int32)
        valid = np.zeros((A,), bool)
        req_ids = np.zeros((A,), np.int32)
        row_slot = [self.pool.acquire() for _ in reqs]
        for i, req in enumerate(reqs):
            plen = effs[i].size
            prompts[i, :plen] = effs[i]
            prompt_lens[i] = plen
            valid[i] = True
            req_ids[i] = req.req_id
        # valid rows -> their acquired slots; padding rows -> distinct other
        # slot ids, keeping `slots` collision-free (deterministic scatter,
        # and the no-op rows rewrite rows they gathered — see _scatter_rows
        # and merge_admit_carry)
        rest = [s for s in range(self.num_slots) if s not in row_slot]
        slots = np.asarray((row_slot + rest)[:A], np.int32)
        if self.layout == "paged":
            nb = -(-bucket // self.block_size)
            block_ids = np.full((A, nb), self.num_blocks, np.int32)
            bs = self.block_size
            for i, req in enumerate(reqs):
                slot = row_slot[i]
                eff = effs[i]
                plen = int(eff.size)
                ninit = -(-plen // bs)
                held: List[int] = []
                self._tables[slot, :] = self.num_blocks
                if self._prefix is not None:
                    # rolling-key walk over the prompt's blocks: a hit maps
                    # the table entry at the already-resident block and
                    # leaves block_ids at the sentinel, so the (still full-
                    # shape) prefill dispatch's writes for that span are
                    # dropped; a miss acquires, writes, and publishes.
                    # Publishing happens host-side before the next request
                    # of this batch is processed, so batch-mates share too
                    # (the single dispatch writes each block exactly once —
                    # the one non-sentinel row).  Quality tiers: a block's
                    # K/V is rung-specific (it was prefilled under one
                    # rung's execution mode), so each rung chains from its
                    # OWN root — distinct negative roots never collide with
                    # interned kids (>= 0), keeping the rung chains disjoint
                    parent = C.PrefixCache.ROOT - tier_idx
                    for j in range(ninit):
                        toks = eff[j * bs:min((j + 1) * bs, plen)]
                        kid = self._prefix.key(parent, toks)
                        parent = kid
                        hit = self._prefix.lookup(kid)
                        if hit is not None:
                            self.blocks.share(hit)
                            held.append(hit)
                            self.stats.prefix_hit_blocks += 1
                        else:
                            b = self._admit_block()
                            block_ids[i, j] = b
                            held.append(b)
                            self.blocks.share(b)    # the cache's reference
                            self._prefix.insert(kid, b)
                else:
                    for j in range(ninit):
                        b = self._admit_block()
                        block_ids[i, j] = b
                        held.append(b)
                self._held[slot] = held
                self._tables[slot, :ninit] = held
                if not self.preempt:
                    # a partial tail under sharing is (or may become)
                    # published/shared: its eventual copy-on-write fork
                    # consumes one reserved block, pre-funded by
                    # _pop_admissible's +1 (see _cow_guard)
                    fork_reserve = int(
                        self._prefix is not None and plen % bs != 0
                    )
                    self._future[slot] = (
                        self._worst_blocks(req.prompt.size, req.max_new)
                        - ninit + fork_reserve
                    )
                    self._reserved_total -= ninit      # reservation -> held
            self.cache, tok0s, req_keys = _admit_fused_paged_jit(
                cfg=acfg, params=self.params, cache=self.cache,
                prompts=prompts, prompt_lens=prompt_lens, block_ids=block_ids,
                req_ids=req_ids, base_key=self._base_key,
                sampling=self.sampling, block_size=self.block_size,
            )
            self.stats.peak_blocks_in_use = max(
                self.stats.peak_blocks_in_use, self.blocks.busy_count
            )
            self.stats.peak_block_bytes_per_device = (
                self.stats.peak_blocks_in_use * self._block_bytes_dev
            )
        else:
            if self.prefill_mode == "fused":
                self.cache, tok0s, req_keys = _admit_fused_jit(
                    cfg=acfg, params=self.params, cache=self.cache,
                    prompts=prompts, prompt_lens=prompt_lens, slots=slots,
                    valid=valid, req_ids=req_ids, base_key=self._base_key,
                    sampling=self.sampling,
                )
            else:
                self.cache, tok0s, req_keys = _admit_decode_jit(
                    cfg=acfg, params=self.params, cache=self.cache,
                    prompts=prompts, prompt_lens=prompt_lens, slots=slots,
                    valid=valid, req_ids=req_ids, base_key=self._base_key,
                    sampling=self.sampling,
                    max_len=self.max_len, cache_dtype=self.cache_dtype,
                )
        self.stats.admit_calls += 1
        # charge the EFFECTIVE prompts: a replayed preemption victim
        # prefills prompt + accepted tokens, not its original prompt —
        # charging req.prompt here undercounted prefill_tokens/work_ticks
        # (and so the starvation gauge) after every preemption, and it is
        # the per-request effective bucket, not the batch-max padding
        # bucket, that _pop_admissible meters against the budget
        tok_sum = 0
        for e in effs:
            b = self.buckets.bucket(e.size)
            self.stats.prefills[b] = self.stats.prefills.get(b, 0) + 1
            tok_sum += b
        self.stats.prefill_tokens += tok_sum
        # prefill device work in decode-width-normalized ticks (the unit of
        # the starvation gauge); padding rows are a constant-factor artifact
        # the budget already ignores, so charge the metered tokens.  The
        # integer carry keeps rounding from compounding across admission
        # batches — that is what makes the documented gap bound
        # steps_per_tick + ceil(R * steps_per_tick) provable (a per-batch
        # ceil could overcharge a step by one tick per batch)
        self._prefill_carry += tok_sum
        self.stats.work_ticks += self._prefill_carry // self.num_slots
        self._prefill_carry %= self.num_slots

        if self.loop == "async":
            # no host sync: merge the admit program's (still in-flight)
            # first tokens + keys into the device-resident decode carry so
            # these rows join the next dispatched chunk; their tok0s are
            # fetched at the next harvest (eos/max_new==1 finishes are then
            # discovered one chunk late — the garbage chunk is discarded)
            self._lt_dev, self._sk_dev = _admit_merge_jit(
                self._lt_dev, self._sk_dev, slots, tok0s, req_keys, valid
            )
            if self.spec:
                # the length carry lives on device too (rows advance by
                # data-dependent accepted counts) — same fixed-shape merge
                self._cl_dev = _spec_merge_len_jit(
                    self._cl_dev, slots, prompt_lens, valid
                )
            states: List[_ActiveSlot] = []
            for i, req in enumerate(reqs):
                slot = row_slot[i]
                self._cur_len[slot] = int(prompt_lens[i])
                self._cl_true[slot] = int(prompt_lens[i])
                self._last_emit_work[slot] = self.stats.work_ticks
                resume = self._preempt_resume.pop(req.req_id, None)
                if resume is None:
                    self.stats.admitted += 1
                    state = _ActiveSlot(req, slot, [], self.clock,
                                        tier_idx=tier_idx)
                    state.ttft = self.clock - req.arrival
                    self.stats.ttft_ticks.append(state.ttft)
                else:
                    # re-admission after preemption: the request keeps its
                    # accepted tokens and original admit tick — admitted/
                    # ttft were already counted at first admit (a chunked
                    # victim evicted mid-prefill carries ttft < 0 and
                    # samples it now, on the replay that reaches a token)
                    state = _ActiveSlot(req, slot, list(resume[0]), resume[1],
                                        tier_idx=tier_idx)
                    state.ttft = resume[3]
                    if state.ttft < 0:
                        state.ttft = self.clock - req.arrival
                        self.stats.ttft_ticks.append(state.ttft)
                state.pending_first = True
                self._slot_tier[slot] = tier_idx
                self._bump_tier_gauge(tier_idx, +1)
                self._active[slot] = state
                states.append(state)
            # row indices into tok0s travel with the states: a chunked
            # dispatch merges only its FINAL rows, so the harvest needs to
            # know which tok0 row belongs to which state
            self._pending_tok0.append((states, tok0s, list(range(len(states)))))
            return

        # the sync loop blocks here until the prefill program completes —
        # time it as host_block_s so overlap_fraction stays comparable with
        # the async loop (whose tok0 fetches are timed in _harvest)
        with self._host_blocked():
            tok0s = np.asarray(tok0s)
            req_keys = np.asarray(req_keys, np.uint32)
        eos = self.sampling.eos_id
        for i, req in enumerate(reqs):
            slot, tok0 = row_slot[i], int(tok0s[i])
            self._last_token[slot] = tok0
            self._cur_len[slot] = int(prompt_lens[i])
            self._slot_keys[slot] = req_keys[i]
            self._last_emit_work[slot] = self.stats.work_ticks
            resume = self._preempt_resume.pop(req.req_id, None)
            if resume is None:
                self.stats.admitted += 1
                state = _ActiveSlot(req, slot, [tok0], self.clock,
                                    tier_idx=tier_idx)
                state.ttft = self.clock - req.arrival
                self.stats.ttft_ticks.append(state.ttft)
            else:
                state = _ActiveSlot(req, slot, list(resume[0]) + [tok0],
                                    resume[1], tier_idx=tier_idx)
                state.ttft = resume[3]
                if state.ttft < 0:
                    state.ttft = self.clock - req.arrival
                    self.stats.ttft_ticks.append(state.ttft)
            self._slot_tier[slot] = tier_idx
            self._bump_tier_gauge(tier_idx, +1)
            self.stats.generated_tokens += 1
            if len(state.tokens) >= req.max_new or (eos >= 0 and tok0 == eos):
                self._finish(state, "eos" if (eos >= 0 and tok0 == eos) else "length")
            else:
                self._active[slot] = state

    def _bump_tier_gauge(self, tier_idx: int, delta: int) -> None:
        """Maintain the ``active_per_tier`` residency gauge (tier sessions
        only): +1 at each admission, -1 at each release — exactly-once by
        the same ``state.released`` discipline as the resources."""
        if self.tiers is None:
            return
        self._tier_active_counts[tier_idx] += delta
        self.stats.active_per_tier = {
            t: int(c) for t, c in zip(self.tiers, self._tier_active_counts)
        }

    def _release_resources(self, state: _ActiveSlot) -> None:
        """Free ``state``'s slot — and under the paged layout every held
        block plus the unused remainder of its worst-case reservation —
        exactly once (``state.released`` guards the double-call when a
        predictively released row is later finished at harvest).  Stale
        cache contents are invisible: a slot stripe / block re-enters
        attention only after its next owner's prefill/decode writes
        overwrite the exposed positions."""
        state.released = True
        self._bump_tier_gauge(state.tier_idx, -1)
        if state.prefilling:
            # a mid-prefill victim leaves the resume queue with its slot —
            # the replay restarts the chunk plan from position 0
            self._prefilling = [s for s in self._prefilling if s is not state]
        if self._active[state.slot] is state:   # a successor may already own it
            self._active[state.slot] = None
        self.pool.release(state.slot)
        if self.layout == "paged":
            slot = state.slot
            self.blocks.release_many(self._held[slot])
            self._held[slot] = []
            self._tables[slot, :] = self.num_blocks
            self._reserved_total -= int(self._future[slot])
            self._future[slot] = 0
        elif self.zero_on_evict:
            self.cache = _evict_jit(self.cache, np.int32(state.slot))

    def _finish(self, state: _ActiveSlot, reason: str) -> None:
        state.done = True
        if not state.released:
            self._release_resources(state)
        self.stats.completed += 1
        self.stats.latency_ticks.append(self.clock - state.req.arrival)
        self._just_finished.append(state.req.req_id)
        self._completed[state.req.req_id] = CompletedRequest(
            req_id=state.req.req_id,
            prompt=state.req.prompt,
            tokens=np.asarray(state.tokens, np.int32),
            finish_reason=reason,
            admitted_tick=state.admitted_tick,
            finished_tick=self.clock,
            tier=self.tiers[state.tier_idx] if self.tiers is not None else "",
            ttft=state.ttft,
        )

    def _ensure_blocks(self, slot: int, hi: int) -> None:
        """Paged layout: append blocks to ``slot``'s table until it covers
        cache position ``hi`` (a no-op when already covered — a request only
        pays a pool op when its context actually crosses a block boundary).
        Without preemption the admission reservation makes the acquire
        infallible; with it, ``_acquire_block`` reclaims published blocks
        and evicts other rows until the pool funds the append."""
        held = self._held[slot]
        while len(held) * self.block_size <= hi:
            b = self._acquire_block(slot)
            self._tables[slot, len(held)] = b
            held.append(b)
            if not self.preempt:
                self._future[slot] -= 1
                self._reserved_total -= 1

    # -- stepping ------------------------------------------------------------

    def _pull_arrivals(self) -> None:
        while self._pending and self._pending[0][0] <= self.clock:
            self._push_ready(heapq.heappop(self._pending)[2])

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self._active)

    @property
    def n_decoding(self) -> int:
        """Resident rows actually decoding — mid-prefill rows (chunked
        path) hold a slot but join no decode chunk, so they neither starve
        nor scale the interleaving budget."""
        return sum(
            s is not None and not s.prefilling for s in self._active
        )

    @property
    def drained(self) -> bool:
        return not (
            self._pending or self._ready or self.n_active or self._inflight
        )

    def _drain_finished(self) -> List[CompletedRequest]:
        with _span("serve.accept"):
            done = [self._completed[i] for i in self._just_finished]
            self._just_finished.clear()
            return done

    @contextlib.contextmanager
    def _host_blocked(self):
        """Block on device-to-host token transfers inside: timed into
        ``host_block_s`` and spanned as ``serve.harvest_wait``."""
        tb = time.perf_counter()
        with _span("serve.harvest_wait"):
            yield
        self.stats.host_block_s += time.perf_counter() - tb

    def _prefill_budget(self) -> float:
        """Bucketed prompt tokens this step may admit under the interleaving
        policy.  Unlimited when no policy is set, and unlimited while no
        decode is resident — there is nothing to starve, and the queue must
        be able to drain (a head whose bucket exceeds the per-step budget
        therefore waits at most until the resident decodes finish)."""
        if self.prefill_decode_ratio is None and self.prefill_token_budget is None:
            return float("inf")
        if self.n_decoding == 0:
            return float("inf")
        if self.prefill_token_budget is not None:
            return float(self.prefill_token_budget)
        return self.prefill_decode_ratio * self.n_decoding * self.steps_per_tick

    def _pop_admissible(
        self, budget: float = float("inf")
    ) -> Tuple[List[Request], float, bool]:
        """Pop ready requests that fit the free slots, (paged) the block
        pool, and the prefill-token ``budget``.  Memory admission is
        reservation-based: a request is popped only if its worst-case block
        count fits what the pool can still promise (``free - reserved``),
        and that worst case is reserved on the spot — which is exactly what
        makes mid-decode appends and the no-preemption guarantee sound.  The
        queue head blocks admission when it doesn't fit (no skip-ahead):
        policy order is preserved and a big request cannot be starved by a
        stream of small ones.  Returns ``(batch, remaining budget, stalled)``
        where ``stalled`` means the head was deferred by the budget alone
        (slots and memory both had room)."""
        batch: List[Request] = []
        stalled = False
        pending_need = 0
        reclaimable = (
            self._reclaimable_blocks()
            if self.layout == "paged" and self.preempt else 0
        )
        while self._ready and len(batch) < self.pool.free_count:
            req = self._ready[0][2]
            eff_len = req.prompt.size
            worst = 0
            if self.layout == "paged":
                eff_len = int(self._eff_prompt(req).size)
                if self.preempt:
                    # oversubscription: admit on the *immediate* prompt need
                    # (prefix hits only shrink it; cache-only published
                    # blocks count as free because reclaim evicts them on
                    # demand) — mid-decode appends are funded by reclaim and
                    # preemption instead of a worst-case reservation.  A
                    # chunked admission's immediate need is its FIRST
                    # chunk's blocks; later chunks append like decode does
                    head = (
                        min(eff_len, self.prefill_chunk)
                        if self._chunks_prefill(eff_len) else eff_len
                    )
                    need = -(-head // self.block_size)
                    if pending_need + need > (
                        self.blocks.free_count + reclaimable
                    ):
                        break
                else:
                    worst = self._worst_blocks(req.prompt.size, req.max_new)
                    if self._prefix is not None and eff_len % self.block_size:
                        # +1 pre-funds the partial tail's potential copy-on-
                        # write fork so mid-decode forks stay infallible
                        # under the reservation discipline (see _admit_many)
                        worst += 1
                    # published blocks pin otherwise-free pool capacity;
                    # evict LRU cache-only blocks before refusing the head
                    while (
                        worst > self.blocks.free_count - self._reserved_total
                        and self._reclaim_cache_block()
                    ):
                        pass
                    if worst > self.blocks.free_count - self._reserved_total:
                        break
            b = self._head_charge(eff_len)
            if b > budget:
                stalled = True
                break
            if self.layout == "paged":
                if self.preempt:
                    pending_need += -(-head // self.block_size)
                else:
                    self._reserved_total += worst
            budget -= b
            heapq.heappop(self._ready)
            batch.append(req)
        return batch, budget, stalled

    def _eff_tier(self, req: Request) -> int:
        """The ladder rung ``req`` admits at RIGHT NOW: the requested rung,
        demoted to the current shed level when that is lower-quality (higher
        index).  A preemption victim replays at the rung it originally
        admitted under — re-deciding would break the bit-identical replay
        (the snapshotted tokens were generated by the original rung)."""
        resume = self._preempt_resume.get(req.req_id)
        if resume is not None:
            return resume[2]
        want = self.tiers.index(req.tier) if req.tier is not None else 0
        return max(want, self._shed_level)

    def _group_by_tier(
        self, batch: List[Request]
    ) -> List[Tuple[int, List[Request]]]:
        """Split an admission batch by effective rung (admission order kept
        inside each group, groups in ladder order) — each group prefills
        under its own rung config in one dispatch."""
        groups: Dict[int, List[Request]] = {}
        for r in batch:
            groups.setdefault(self._eff_tier(r), []).append(r)
        return sorted(groups.items())

    def _admit_phase(self) -> None:
        """Admit ready requests in policy order, subject to free slots,
        (paged) the block-pool reservation, and the interleaving budget —
        shared across every admission batch of this step.  Chunked prefill:
        resident mid-prefill rows spend the budget FIRST (oldest prefill
        first, no skip-ahead — a stalled resident chunk also closes
        admission for the step), so every started prefill finishes before
        new prompts open and the budget bounds each step's prefill work by
        one chunk bucket per row instead of one prompt bucket."""
        budget = self._prefill_budget()
        stalled = False
        if self._prefilling:
            budget, stalled = self._resume_chunks(budget)
        while not stalled and self._ready and self.pool.free_count:
            batch, budget, st = self._pop_admissible(budget)
            stalled = stalled or st
            if not batch:
                break                 # head doesn't fit the pool/budget yet
            if self.tiers is None:
                chunked = [
                    r for r in batch
                    if self._chunks_prefill(int(self._eff_prompt(r).size))
                ]
                oneshot = [r for r in batch if r not in chunked]
                if oneshot:
                    self._admit_many(oneshot)  # sync: may free slots again
                if chunked:
                    started = [self._start_chunked(r) for r in chunked]
                    # first chunk dispatches the same step the budget was
                    # charged for it (_head_charge); later chunks resume
                    # above on subsequent steps
                    self._dispatch_chunks(started)
            else:
                for t, group in self._group_by_tier(batch):
                    self._admit_many(group, tier_idx=t)
        if stalled:
            self.stats.prefill_stall_ticks += 1
        self.stats.peak_active = max(self.stats.peak_active, self.n_active)

    def _resume_chunks(self, budget: float) -> Tuple[float, bool]:
        """Dispatch the next chunk for every resident mid-prefill row the
        budget covers, in start order (FIFO, no skip-ahead: a stalled head
        blocks younger rows' chunks, which is what keeps each prefill's
        finish time bounded).  One chunk per row per step — the decode
        interleave between chunks is the whole point."""
        rows: List[_ActiveSlot] = []
        stalled = False
        for state in list(self._prefilling):
            clen = min(
                self.prefill_chunk, state.prefill_len - state.prefill_pos
            )
            b = self.buckets.bucket(clen)
            if b > budget:
                stalled = True
                break
            budget -= b
            rows.append(state)
        if rows:
            self._dispatch_chunks(rows)
        return budget, stalled

    def _start_chunked(self, req: Request) -> _ActiveSlot:
        """Make a chunked admission resident WITHOUT prefilling anything
        yet: acquire the slot, zero the table row (all-sentinel — blocks
        are acquired chunk by chunk in ``_dispatch_chunks``), and park the
        row on the resume queue with its cursor at 0.  Without preemption
        the worst-case reservation ``_pop_admissible`` took stays
        unconverted (``_future`` carries all of it) and ``_ensure_blocks``
        converts per acquired block."""
        eff = self._eff_prompt(req)
        slot = self.pool.acquire()
        self._tables[slot, :] = self.num_blocks
        self._held[slot] = []
        self._cur_len[slot] = 0
        self._cl_true[slot] = 0
        self._last_emit_work[slot] = self.stats.work_ticks
        if not self.preempt:
            self._future[slot] = self._worst_blocks(
                req.prompt.size, req.max_new
            )
        resume = self._preempt_resume.pop(req.req_id, None)
        if resume is None:
            self.stats.admitted += 1
            state = _ActiveSlot(req, slot, [], self.clock)
        else:
            # chunked replay of a preemption victim: accepted tokens are
            # part of the effective prompt (``eff``) AND the resume token
            # list — the final chunk's sampled token appends after them
            state = _ActiveSlot(req, slot, list(resume[0]), resume[1])
            state.ttft = resume[3]
        state.prefill_pos = 0
        state.prefill_len = int(eff.size)
        state.eff_prompt = eff
        self._slot_tier[slot] = 0
        self._bump_tier_gauge(0, +1)
        self._active[slot] = state
        self._prefilling.append(state)
        return state

    def _dispatch_chunks(self, rows: List[_ActiveSlot]) -> None:
        """ONE ``_prefill_chunk`` dispatch advancing every row in ``rows``
        by its next chunk.  Rows pad to the admit-width x max-chunk-bucket
        shape (program key: that pair — the warmed one-shot program
        family), each row reading its already-written prefix through its
        block table and scattering this chunk's K/V into freshly ensured
        blocks.  Rows that reach the end of their prompt sample their
        first token in-program (same key/position fold as the one-shot
        admit) and join the decode set; for the others the sampled token
        is garbage the host never reads."""
        A = self._admit_width(len(rows))
        clens = [
            min(self.prefill_chunk, s.prefill_len - s.prefill_pos)
            for s in rows
        ]
        cb = max(self.buckets.bucket(c) for c in clens)
        toks = np.full((A, cb), self.pad_id, np.int32)
        starts = np.zeros((A,), np.int32)
        chunk_lens = np.ones((A,), np.int32)
        req_ids = np.zeros((A,), np.int32)
        tables = np.full(
            (A, self._tables.shape[1]), self.num_blocks, np.int32
        )
        for i, (state, clen) in enumerate(zip(rows, clens)):
            if state.released or state.preempted:
                # evicted by an earlier row's _ensure_blocks this very
                # loop: its table row stays all-sentinel (chunk writes
                # drop) and its cursor is left for the replay
                continue
            slot, pos = state.slot, state.prefill_pos
            self._ensure_blocks(slot, pos + clen - 1)
            toks[i, :clen] = state.eff_prompt[pos:pos + clen]
            starts[i] = pos
            chunk_lens[i] = clen
            req_ids[i] = state.req.req_id
            tables[i] = self._tables[slot]
        self.stats.peak_blocks_in_use = max(
            self.stats.peak_blocks_in_use, self.blocks.busy_count
        )
        self.stats.peak_block_bytes_per_device = (
            self.stats.peak_blocks_in_use * self._block_bytes_dev
        )
        self.cache, tok0s, req_keys = _prefill_chunk_jit(
            cfg=self.cfg, params=self.params, cache=self.cache,
            tokens=toks, starts=starts, chunk_lens=chunk_lens,
            tables=tables, req_ids=req_ids, base_key=self._base_key,
            sampling=self.sampling, block_size=self.block_size,
        )
        # per-chunk work charge: each chunk bills its OWN bucket, so
        # prefill_tokens / work_ticks (and with them the starvation gauge)
        # meter what the device actually ran this step — not the whole
        # prompt at admission
        tok_sum = 0
        live = [
            (i, s, c) for i, (s, c) in enumerate(zip(rows, clens))
            if not (s.released or s.preempted)
        ]
        for _, _, clen in live:
            b = self.buckets.bucket(clen)
            self.stats.prefills[b] = self.stats.prefills.get(b, 0) + 1
            tok_sum += b
        self.stats.prefill_chunks += len(live)
        self.stats.prefill_tokens += tok_sum
        self._prefill_carry += tok_sum
        self.stats.work_ticks += self._prefill_carry // self.num_slots
        self._prefill_carry %= self.num_slots
        finals: List[Tuple[int, _ActiveSlot]] = []
        for i, state, clen in live:
            state.prefill_pos += clen
            self._cur_len[state.slot] = state.prefill_pos
            self._cl_true[state.slot] = state.prefill_pos
            if not state.prefilling:
                finals.append((i, state))
        for _, state in finals:
            self._prefilling.remove(state)
            self._last_emit_work[state.slot] = self.stats.work_ticks
            if state.ttft < 0:
                state.ttft = self.clock - state.req.arrival
                self.stats.ttft_ticks.append(state.ttft)
        if self.loop == "async":
            if finals:
                # merge ONLY the final rows' first tokens + keys into the
                # device carry; mid-prefill rows stay out of the decode
                # set, so their carry entries stay whatever they were.
                # slots/valid align with the dispatch's tok0 rows, and the
                # non-final rows borrow distinct unclaimed slot ids so the
                # scatter stays collision-free (invalid rows rewrite what
                # they gathered — see merge_admit_carry)
                row_slot = {i: s.slot for i, s in finals}
                rest = [
                    s for s in range(self.num_slots)
                    if s not in row_slot.values()
                ]
                slots = np.empty((A,), np.int32)
                valid = np.zeros((A,), bool)
                for i in range(A):
                    if i in row_slot:
                        slots[i] = row_slot[i]
                        valid[i] = True
                    else:
                        slots[i] = rest.pop()
                self._lt_dev, self._sk_dev = _admit_merge_jit(
                    self._lt_dev, self._sk_dev, slots, tok0s, req_keys,
                    valid,
                )
                for _, s in finals:
                    s.pending_first = True
                self._pending_tok0.append(
                    ([s for _, s in finals], tok0s, [i for i, _ in finals])
                )
            return
        if not finals:
            return
        with self._host_blocked():
            tok0s = np.asarray(tok0s)
            req_keys = np.asarray(req_keys, np.uint32)
        eos = self.sampling.eos_id
        for i, state in finals:
            slot, tok0 = state.slot, int(tok0s[i])
            self._last_token[slot] = tok0
            self._slot_keys[slot] = req_keys[i]
            state.tokens.append(tok0)
            self.stats.generated_tokens += 1
            if (len(state.tokens) >= state.req.max_new
                    or (eos >= 0 and tok0 == eos)):
                self._finish(
                    state, "eos" if (eos >= 0 and tok0 == eos) else "length"
                )

    def _decode_states(self) -> List[Optional[_ActiveSlot]]:
        """The rows a decode chunk serves: ``_active`` with mid-prefill
        rows masked to ``None`` — the chunk's tokens/advances for those
        rows are garbage (their table rows were scrubbed at dispatch), and
        the None mask makes every acceptance/advance loop skip them the
        same way it skips empty slots."""
        return [
            None if (s is not None and s.prefilling) else s
            for s in self._active
        ]

    def _chunk_inputs(self):
        """Dispatch inputs shared by both loops: the active-row mask and
        (paged) this chunk's block tables, grown to cover every position the
        chunk could write an ACCEPTED token to (overshoot past max_new
        targets sentinel entries and is dropped); the admission reservation
        guarantees these acquires can never fail."""
        steps = self.steps_per_tick
        tables = None
        block_size = 0
        # write span past cur_len: a decode chunk's last accepted write
        # lands at cur_len + steps - 1; a speculative tick's verify writes
        # through cur_len + draft_k (see _spec_tick)
        span = self._draft_k_eff if self.spec else steps - 1
        if self.layout == "paged":
            bs = self.block_size
            for slot, state in enumerate(self._active):
                if state is None or state.prefilling:
                    # mid-prefill rows join no decode chunk: their blocks
                    # grow in _dispatch_chunks, not here
                    continue
                hi = min(
                    int(self._cur_len[slot]) + span,
                    state.req.prompt.size + state.req.max_new - 2,
                )
                # CoW first: every block this chunk may write into must be
                # private and unpublished before its writes reach it.  A
                # non-speculative chunk writes from cur_len; a speculative
                # async chunk writes anywhere in [_cl_true, hi] (the host
                # only bounds cur_len between harvests), so guard the whole
                # candidate range — privately held indices are no-ops.
                # Both the guard's fork and _ensure_blocks may preempt
                # other rows (preemption on): a victim later in this loop
                # reads as None, an earlier one already has its table row
                # zeroed — either way the active mask below and the
                # sentinel discipline keep the dispatch exact.
                if self._prefix is not None:
                    lo = (
                        int(self._cl_true[slot])
                        if self.spec and self.loop == "async"
                        else int(self._cur_len[slot])
                    )
                    for idx in range(lo // bs, hi // bs + 1):
                        self._cow_guard(slot, state, idx)
                self._ensure_blocks(slot, hi)
            self.stats.peak_blocks_in_use = max(
                self.stats.peak_blocks_in_use, self.blocks.busy_count
            )
            self.stats.peak_block_bytes_per_device = (
                self.stats.peak_blocks_in_use * self._block_bytes_dev
            )
            tables = self._tables.copy()
            for slot, state in enumerate(self._active):
                if state is not None and state.prefilling:
                    # the decode tick writes K/V for EVERY row at its
                    # cur_len; a mid-prefill row's real table holds
                    # already-written prompt K/V a garbage decode write
                    # would corrupt, so its row in the dispatched copy is
                    # scrubbed to the sentinel (writes drop, like released
                    # rows)
                    tables[slot, :] = self.num_blocks
            block_size = self.block_size
        active = np.asarray(
            [s is not None and not s.prefilling for s in self._active], bool
        )
        return active, tables, block_size, steps

    def _accept_chunk(
        self,
        states: List[Optional[_ActiveSlot]],
        toks: np.ndarray,
        steps: int,
        work_end: int,
    ) -> None:
        """Accept a fetched chunk's tokens for the rows that were live at
        its dispatch: each row takes tokens until it finishes (eos /
        max_new) and discards the bounded overshoot; rows whose completion
        was discovered after the dispatch (``state.done``) contribute only
        idle steps.  Updates the busy/idle accounting and the starvation
        gauge (``work_end`` is the chunk's position on the work clock)."""
        eos = self.sampling.eos_id
        accepted = 0
        for slot, state in enumerate(states):
            if state is None or state.done or state.preempted:
                # preempted rows discard their in-flight tokens (counted
                # idle): the replay regenerates them bit-identically
                continue
            # predictively released rows may already have a successor in the
            # slot; leave the successor's emission mark alone
            early = state.released
            for s in range(steps):
                tok = int(toks[s, slot])
                state.tokens.append(tok)
                accepted += 1
                if eos >= 0 and tok == eos:
                    self._finish(state, "eos")
                    break
                if len(state.tokens) >= state.req.max_new:
                    self._finish(state, "length")
                    break
            if not early:
                gap = int(work_end - self._last_emit_work[slot])
                if gap > self.stats.max_decode_gap_ticks:
                    self.stats.max_decode_gap_ticks = gap
                self._last_emit_work[slot] = work_end
        self.stats.busy_slot_steps += accepted
        self.stats.idle_slot_steps += self.num_slots * steps - accepted
        self.stats.generated_tokens += accepted

    def _accept_spec_chunk(
        self,
        states: List[Optional[_ActiveSlot]],
        toks: np.ndarray,          # (draft_k + 1, N)
        n_acc: np.ndarray,         # (N,)
        work_end: int,
        draft_k: int,
    ) -> None:
        """Speculative counterpart of ``_accept_chunk``: each live row takes
        its own ``n_acc`` tokens (1..draft_k+1 — uneven per row), finishing
        on eos / max_new exactly as sequential acceptance would.  A tick's
        device capacity is ``num_slots * (draft_k + 1)`` token-slots; the
        accept-rate counters meter the draft multiplier's hit rate
        (``n_acc - 1`` drafted tokens survived the exact verifier, clipped
        to what the row could still emit so end-of-request truncation never
        inflates the readout).  ``draft_k`` is the window the CHUNK was
        dispatched with (dynamic_draft_k may have moved ``_draft_k_eff``
        since), and each live row also feeds the rolling accept window the
        adaptation rule reads."""
        eos = self.sampling.eos_id
        accepted = 0
        cap = draft_k + 1
        for slot, state in enumerate(states):
            if state is None or state.done or state.preempted:
                # preempted rows discard their in-flight tokens (counted
                # idle): the replay regenerates them bit-identically
                continue
            early = state.released
            na = int(n_acc[slot])
            self.stats.verify_calls += 1
            self.stats.draft_tokens += draft_k
            emitted = 0
            for s in range(na):
                tok = int(toks[s, slot])
                state.tokens.append(tok)
                accepted += 1
                emitted += 1
                if eos >= 0 and tok == eos:
                    self._finish(state, "eos")
                    break
                if len(state.tokens) >= state.req.max_new:
                    self._finish(state, "length")
                    break
            self.stats.accepted_tokens += max(0, min(na - 1, emitted))
            if self.dynamic_draft:
                self._accept_hist.append((draft_k, max(0, min(na - 1, emitted))))
            if not early:
                gap = int(work_end - self._last_emit_work[slot])
                if gap > self.stats.max_decode_gap_ticks:
                    self.stats.max_decode_gap_ticks = gap
                self._last_emit_work[slot] = work_end
        self.stats.busy_slot_steps += accepted
        self.stats.idle_slot_steps += self.num_slots * cap - accepted
        self.stats.generated_tokens += accepted
        if self.dynamic_draft:
            self._update_draft_k()

    def _update_draft_k(self) -> None:
        """dynamic_draft_k adaptation rule (applies to the NEXT dispatch).

        A drafted token costs ``1/draft_cost_ratio`` of a verify position,
        so drafting pays iff the accept rate is at least the break-even
        ``1/draft_cost_ratio``.  Over a full rolling window of per-row
        (drafted, accepted) pairs: strictly below break-even -> halve the
        window (next rung down the warmed ladder); at/above break-even ->
        re-grow one rung.  The window clears on every change, so each rung
        is measured on a full window of its own chunks before the next
        move — that hysteresis is the regression-pinned contract
        (tests/test_specdec.py)."""
        if len(self._accept_hist) < self.draft_window:
            return
        drafted = sum(d for d, _ in self._accept_hist)
        acc = sum(a for _, a in self._accept_hist)
        if not drafted:
            return
        rate = acc / drafted
        i = self._draft_ks.index(self._draft_k_eff)
        if rate < 1.0 / self.draft_cost_ratio and i + 1 < len(self._draft_ks):
            self._draft_k_eff = self._draft_ks[i + 1]
            self.stats.draft_k_shrinks += 1
            self._accept_hist.clear()
        elif rate >= 1.0 / self.draft_cost_ratio and i > 0:
            self._draft_k_eff = self._draft_ks[i - 1]
            self.stats.draft_k_grows += 1
            self._accept_hist.clear()
        self.stats.draft_k_current = self._draft_k_eff

    def step(self) -> List[CompletedRequest]:
        """Admit what fits (under the interleaving budget), run one decode
        chunk, release finished slots.  Returns the requests completed
        during this call — under ``loop="async"`` completions surface one
        step after their chunk was dispatched (the pipeline lag)."""
        if self._closed:
            raise RuntimeError(
                "ServeSession is closed — its pipeline was flushed by "
                "close(); create a new session"
            )
        t0 = time.perf_counter()
        try:
            with _span("serve.step", clock=self.clock), self._mesh_ctx():
                if self.loop == "async":
                    return self._step_async()
                return self._step_sync()
        finally:
            self.stats.wall_s += time.perf_counter() - t0

    def _mesh_ctx(self):
        """Every device dispatch runs under ``jax.set_mesh(mesh)`` when the
        session is tensor-parallel — ``constrain()`` and the Pallas
        ``shard_map`` read the mesh at trace time, and the mesh context is
        part of the jit cache key, so warmup and serving must install the
        SAME context for the zero-recompile contract to hold."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    # -- quality tiers: load shedding and per-rung dispatch -------------------

    def _current_decode_gap(self) -> int:
        """LIVE starvation signal: worst work-tick gap since a resident
        row's latest accepted token (``max_decode_gap_ticks`` is its
        monotone high-water mark — useless for a shedder that must observe
        recovery)."""
        g = 0
        for slot, state in enumerate(self._active):
            if (state is None or state.done or state.released
                    or state.prefilling):
                # mid-prefill rows haven't emitted yet — they are metered
                # by ttft, not the decode gap
                continue
            g = max(g, int(self.stats.work_ticks - self._last_emit_work[slot]))
        return g

    def _update_shed(self) -> None:
        """Load-adaptive shedding, once per step before admission.  A BREACH
        — ready-queue depth above ``shed_queue_depth`` or the live decode
        gap above ``shed_gap_ticks`` — raises the shed level one rung (new
        admissions then serve at ``max(requested, level)``); recovery only
        lowers it after ``shed_hold_steps`` CONSECUTIVE steps below
        ``shed_restore_fraction`` of the breach thresholds, and the
        consecutive-step window clears on every level change or unhealthy
        step — the same measure-a-full-window-per-rung hysteresis contract
        as ``_update_draft_k``, so the level cannot flap."""
        if not self._shed_on:
            return
        depth = len(self._ready)
        gap = self._current_decode_gap()
        breach = (
            (self.shed_queue_depth is not None
             and depth > self.shed_queue_depth)
            or (self.shed_gap_ticks is not None and gap > self.shed_gap_ticks)
        )
        if breach:
            self._shed_ok_steps = 0
            if self._shed_level + 1 < len(self.tiers):
                self._shed_level += 1
                self.stats.tier_demotions += 1
                self.stats.shed_level = self._shed_level
            return
        healthy = (
            (self.shed_queue_depth is None
             or depth <= self.shed_restore_fraction * self.shed_queue_depth)
            and (self.shed_gap_ticks is None
                 or gap <= self.shed_restore_fraction * self.shed_gap_ticks)
        )
        if not healthy:
            self._shed_ok_steps = 0
            return
        if self._shed_level == 0:
            return
        self._shed_ok_steps += 1
        if self._shed_ok_steps >= self.shed_hold_steps:
            self._shed_level -= 1
            self.stats.tier_restorations += 1
            self.stats.shed_level = self._shed_level
            self._shed_ok_steps = 0

    def _dispatch_tier_chunks(self, active, tables, block_size, steps):
        """One ``_decode_tick`` dispatch per ladder rung holding >= 1 active
        row, chaining the cache (and, async, the device token carry) through
        the rung dispatches in ladder order.  Each dispatch masks ``active``
        down to its rung's rows and makes the OTHER rungs' resident rows
        write-inert the same way released rows already are — paged: their
        table rows scrubbed to the sentinel in this rung's copy, so every KV
        scatter drops; slots: their ``cur_len`` pinned to ``max_len``, so
        every positional ``.at[].set`` lands out of bounds and drops (do not
        swap either path for a clamping primitive — see ``_decode_tick``).
        In-program ``where(active, toks, 0)`` zeroes non-rung rows' tokens,
        so the per-rung outputs merge by elementwise sum.  Returns the
        (still in-flight) per-rung token futures."""
        async_ = self.loop == "async"
        parts = []
        for t in range(len(self.tiers)):
            mask = active & (self._slot_tier == t)
            if not mask.any():
                continue
            if self.layout == "paged":
                tb, cl = tables.copy(), self._cur_len.copy()
                tb[~mask, :] = self.num_blocks
            else:
                tb = None
                cl = np.where(mask, self._cur_len, self.max_len)
                cl = cl.astype(np.int32)
            self.cache, toks_f, lt = _decode_tick_jit(
                cfg=self._tier_cfgs[t], params=self.params, cache=self.cache,
                last_token=self._lt_dev if async_ else self._last_token,
                cur_len=cl, active=mask,
                slot_keys=self._sk_dev if async_ else self._slot_keys,
                tables=tb, sampling=self.sampling, steps=steps,
                block_size=block_size, attn_impl=self.attn_impl,
            )
            if async_:
                self._lt_dev = lt
            parts.append(toks_f)
        return parts

    def _step_sync(self) -> List[CompletedRequest]:
        """PR-3 strictly-alternating loop: dispatch one chunk, block on its
        tokens, then do every piece of bookkeeping — the parity baseline the
        async loop is benchmarked against."""
        self._pull_arrivals()
        self._update_shed()
        with _span("serve.admit"):
            self._admit_phase()

        if self.n_active == 0:
            # idle: jump to the next arrival instead of burning empty ticks
            if self._pending:
                self.clock = max(self.clock + 1, self._pending[0][0])
            else:
                self.clock += 1
            return self._drain_finished()
        if self.n_decoding == 0:
            # only mid-prefill rows resident: nothing to decode this step
            # (their chunks were dispatched in _admit_phase); the clock
            # still advances so ttft/latency stay meaningful and the next
            # step keeps the chunks flowing
            self.clock += 1
            return self._drain_finished()

        with _span("serve.dispatch"):
            active, tables, block_size, steps = self._chunk_inputs()
            if self.spec:
                k = self._draft_k_eff
                self.cache, toks_f, n_acc_f, _, _ = _spec_tick_jit(
                    cfg=self.cfg, draft_cfg=self.draft_cfg, params=self.params,
                    cache=self.cache, last_token=self._last_token,
                    cur_len=self._cur_len, active=active,
                    slot_keys=self._slot_keys, tables=tables,
                    sampling=self.sampling, draft_k=k,
                    block_size=block_size, attn_impl=self.attn_impl,
                )
            elif self.tiers is not None:
                parts = self._dispatch_tier_chunks(
                    active, tables, block_size, steps
                )
            else:
                self.cache, toks_f, _ = _decode_tick_jit(
                    cfg=self.cfg, params=self.params, cache=self.cache,
                    last_token=self._last_token, cur_len=self._cur_len,
                    active=active, slot_keys=self._slot_keys, tables=tables,
                    sampling=self.sampling, steps=steps,
                    block_size=block_size, attn_impl=self.attn_impl,
                )
                parts = [toks_f]

        if self.spec:
            with self._host_blocked():
                toks = np.asarray(toks_f)        # (draft_k + 1, N)
                n_acc = np.asarray(n_acc_f)
            with _span("serve.accept"):
                # one spec tick on the scheduler clock; the device ran
                # draft_k + 1 token-steps' worth of work
                self.clock += 1
                self.stats.ticks += 1
                self.stats.work_ticks += k + 1

                states = self._decode_states()
                self._accept_spec_chunk(
                    states, toks, n_acc, self.stats.work_ticks, k
                )
                for slot, state in enumerate(states):
                    if state is None:
                        continue
                    # per-row uneven advance: mirror the device carry
                    # exactly (continuing rows accepted all n_acc tokens;
                    # finished rows' values are reset at the slot's next
                    # admission)
                    na = int(n_acc[slot])
                    self._cur_len[slot] = min(
                        self._cur_len[slot] + na, self.max_len - 1
                    )
                    if na:
                        self._last_token[slot] = int(toks[na - 1, slot])
            return self._drain_finished()

        with self._host_blocked():
            toks = np.asarray(parts[0])          # (steps, N)
            for p in parts[1:]:
                # per-rung chunks carry disjoint row masks (zeros
                # elsewhere), so the merged chunk is the elementwise sum
                toks = toks + np.asarray(p)
        with _span("serve.accept"):
            self.clock += steps
            self.stats.ticks += steps
            self.stats.work_ticks += steps

            states = self._decode_states()
            self._accept_chunk(states, toks, steps, self.stats.work_ticks)
            for slot, state in enumerate(states):
                if state is None:
                    continue
                # device advanced this row all `steps` steps whether or not
                # it finished mid-chunk; keep the host view in lockstep
                self._cur_len[slot] = min(
                    self._cur_len[slot] + steps, self.max_len - 1
                )
                self._last_token[slot] = int(toks[steps - 1, slot])
        return self._drain_finished()

    def _release_predicted_done(self) -> None:
        """Predictive early slot turnover (async loop): a row whose
        in-flight chunk provably completes it by length — pending first
        token + accepted tokens + the chunk's steps reach ``max_new``; an
        eos can only finish it *sooner* — releases its slot and blocks NOW,
        so this step's admissions refill the slot without waiting for the
        harvest.  The successor's admit and first chunk queue behind the
        in-flight chunk on the device stream, so the retiring row's stale
        writes land before the successor's prefill overwrites them and are
        never attended.  Its tokens still arrive at the next harvest
        (``_Inflight.states`` holds the reference); ``state.released``
        keeps the resource frees exactly-once."""
        fl = self._inflight
        if fl is None:
            return
        # a speculative chunk's guaranteed emission is 1 (accept-0 still
        # emits the verifier's correction token); lockstep chunks emit
        # exactly fl.steps
        min_emit = 1 if self.spec else fl.steps
        for state in fl.states:
            if state is None or state.done or state.released:
                continue
            tok0_pending = 1 if state.pending_first else 0
            if len(state.tokens) + tok0_pending + min_emit >= state.req.max_new:
                self._release_resources(state)

    def _step_async(self) -> List[CompletedRequest]:
        """Double-buffered pipeline step: admit (no sync — first tokens
        merge into the device carry), dispatch chunk N+1, and only then
        block on chunk N's tokens — so queue management, admission, and
        finish bookkeeping for chunk N overlap the device computing N+1."""
        self._release_predicted_done()
        self._pull_arrivals()
        self._update_shed()
        with _span("serve.admit"):
            self._admit_phase()

        prev = self._inflight
        with _span("serve.dispatch"):
            self._inflight = self._dispatch_async(prev)
        if prev is not None:
            self._harvest(prev)
        return self._drain_finished()

    def _dispatch_async(self, prev: Optional[_Inflight]) -> Optional[_Inflight]:
        """Dispatch the next decode chunk without waiting for the one in
        flight, and return it; with no row decoding, advance the clock and
        return ``None``."""
        new = None
        if self.n_decoding:
            active, tables, block_size, steps = self._chunk_inputs()
            if self.spec:
                # the length carry is device-resident (_cl_dev): rows
                # advance by their own accepted counts, which the host
                # only learns at harvest.  _cur_len meanwhile tracks the
                # conservative upper bound (full draft_k + 1 per live
                # row), which is all block allocation needs.
                k = self._draft_k_eff
                (self.cache, toks_f, n_acc_f, self._lt_dev,
                 self._cl_dev) = _spec_tick_jit(
                    cfg=self.cfg, draft_cfg=self.draft_cfg,
                    params=self.params, cache=self.cache,
                    last_token=self._lt_dev, cur_len=self._cl_dev,
                    active=active, slot_keys=self._sk_dev, tables=tables,
                    sampling=self.sampling, draft_k=k,
                    block_size=block_size, attn_impl=self.attn_impl,
                )
                self.clock += 1
                self.stats.ticks += 1
                self.stats.work_ticks += k + 1
                new = _Inflight(toks_f, 1, self._decode_states(),
                                self.stats.work_ticks, n_acc=n_acc_f,
                                draft_k=k)
                self._cur_len = np.minimum(
                    self._cur_len + (k + 1) * active,
                    self.max_len - 1,
                ).astype(np.int32)
            else:
                if self.tiers is not None:
                    # per-rung dispatches (each masks cur_len/tables itself
                    # with fresh arrays and chains _lt_dev through)
                    toks_f = tuple(self._dispatch_tier_chunks(
                        active, tables, block_size, steps
                    ))
                else:
                    # cur_len is copied because the host mutates it while the
                    # chunk is in flight (numpy operands may be aliased
                    # zero-copy by the device buffer); `active` and `tables`
                    # are fresh arrays already
                    self.cache, toks_f, self._lt_dev = _decode_tick_jit(
                        cfg=self.cfg, params=self.params, cache=self.cache,
                        last_token=self._lt_dev, cur_len=self._cur_len.copy(),
                        active=active, slot_keys=self._sk_dev, tables=tables,
                        sampling=self.sampling, steps=steps,
                        block_size=block_size, attn_impl=self.attn_impl,
                    )
                self.clock += steps
                self.stats.ticks += steps
                self.stats.work_ticks += steps
                new = _Inflight(toks_f, steps, self._decode_states(),
                                self.stats.work_ticks)
                # advance the host view past the chunk just dispatched (the
                # device carry advances identically; the clamp matches the
                # sync loop's post-harvest update)
                self._cur_len = np.minimum(
                    self._cur_len + steps * active, self.max_len - 1
                ).astype(np.int32)
        elif self.n_active:
            # only mid-prefill rows resident: no decode chunk to dispatch
            # (their chunks went out in _admit_phase); the clock still
            # advances so the next step keeps the chunks flowing
            self.clock += 1
        elif prev is None:
            # idle: jump to the next arrival instead of burning empty ticks
            if self._pending:
                self.clock = max(self.clock + 1, self._pending[0][0])
            else:
                self.clock += 1
        return new

    def _harvest(self, fl: _Inflight) -> None:
        """Block on an in-flight chunk's token transfer (the device is
        already executing the next chunk) and run the deferred bookkeeping:
        admit-time first tokens queued since the previous harvest, then the
        chunk's tokens for the rows that were live at its dispatch."""
        with self._host_blocked():
            if isinstance(fl.toks, tuple):
                # quality tiers: per-rung chunk parts with disjoint row
                # masks (zeros elsewhere) — the merged chunk is the sum
                toks = np.asarray(fl.toks[0])
                for p in fl.toks[1:]:
                    toks = toks + np.asarray(p)
            else:
                toks = np.asarray(fl.toks)       # (steps, N)
            n_acc = np.asarray(fl.n_acc) if fl.n_acc is not None else None
            pend, self._pending_tok0 = self._pending_tok0, []
            drained = [
                (states, np.asarray(t0s), idxs) for states, t0s, idxs in pend
            ]
        with _span("serve.accept"):
            self._accept_harvest(fl, toks, n_acc, drained)

    def _accept_harvest(self, fl: _Inflight, toks: np.ndarray,
                        n_acc: Optional[np.ndarray], drained) -> None:
        """The harvest's bookkeeping on the fetched tokens: first tokens of
        admits, then the chunk's tokens (reconciling a speculative chunk's
        lengths first)."""
        eos = self.sampling.eos_id
        for states, tok0s, idxs in drained:
            for state, i in zip(states, idxs):
                state.pending_first = False
                if state.preempted:
                    # preempted before its first token was harvested: the
                    # resume snapshot holds only accepted tokens, so this
                    # tok0 is discarded and replayed identically
                    continue
                tok0 = int(tok0s[i])
                state.tokens.append(tok0)
                self.stats.generated_tokens += 1
                if (len(state.tokens) >= state.req.max_new
                        or (eos >= 0 and tok0 == eos)):
                    # discovered one chunk late: the row decoded one garbage
                    # chunk meanwhile (skipped below via state.done);
                    # len(tokens) covers re-admitted rows that resume with
                    # their accepted tokens already in the list
                    self._finish(
                        state, "eos" if (eos >= 0 and tok0 == eos) else "length"
                    )
        if n_acc is None:
            self._accept_chunk(fl.states, toks, fl.steps, fl.work_end)
            return
        # speculative chunk: reconcile the host length views with the
        # now-known per-row accepted counts before accepting.  Only rows
        # still owned by their dispatched occupant matter — a finished or
        # preempted row's slot values are rewritten at its next admission
        # (and the identity guard is what makes a successor admitted
        # between dispatch and harvest safe)
        for slot, state in enumerate(fl.states):
            if (state is None or state.done or state.preempted
                    or self._active[slot] is not state):
                continue
            na = int(n_acc[slot])
            self._cl_true[slot] = min(
                int(self._cl_true[slot]) + na, self.max_len - 1
            )
            ub = int(self._cl_true[slot])
            if self._inflight is not None and self._inflight.states[slot] is state:
                ub += self._inflight.draft_k + 1  # the still-in-flight chunk
            self._cur_len[slot] = min(ub, self.max_len - 1)
        self._accept_spec_chunk(fl.states, toks, n_acc, fl.work_end, fl.draft_k)

    def close(self) -> Dict[int, CompletedRequest]:
        """Flush the pipeline (harvest the in-flight chunk and any pending
        admit tokens) and seal the session: subsequent ``submit``/``step``/
        ``run`` raise ``RuntimeError``.  Ready/pending requests that were
        never admitted stay unserved.  Idempotent; returns the completed
        results."""
        if not self._closed:
            fl, self._inflight = self._inflight, None
            if fl is not None:
                with self._mesh_ctx():
                    self._harvest(fl)
            self._closed = True
        return dict(self._completed)

    def run(self, max_steps: Optional[int] = None) -> Dict[int, CompletedRequest]:
        """Drive until every queued request completes, or ``max_steps``
        calls to ``step()`` (each executes up to ``steps_per_tick`` decode
        ticks — a watchdog on scheduler iterations, not device ticks)."""
        if self._closed:
            raise RuntimeError(
                "ServeSession is closed — its pipeline was flushed by "
                "close(); create a new session"
            )
        n = 0
        while not self.drained:
            self.step()
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        return dict(self._completed)

    @property
    def results(self) -> Dict[int, CompletedRequest]:
        return dict(self._completed)

    # -- warmup / compile accounting ------------------------------------------

    def warmup(self) -> Dict[str, int]:
        """Compile the decode tick, the admit-carry merge, and every
        prompt-bucket prefill program up-front.  All warmup rows are no-ops,
        so session state is semantically untouched; the output caches are
        *chained* back into ``self.cache`` (content-identical up to
        positions that are invisible until overwritten) because the
        cache-donating programs consume their input buffers on non-CPU
        backends.  After this, no request pattern recompiles; returns
        ``compile_stats``."""
        with self._mesh_ctx():
            return self._warmup_impl()

    def _warmup_impl(self) -> Dict[str, int]:
        if self.mesh is not None:
            # normalize placements (see _pin_carry_jit): every later warmup
            # and serving dispatch then sees identical operand shardings
            self.cache = _pin_pool_jit(self.cache, n_kv=self.cfg.num_kv_heads)
            self._lt_dev = _pin_carry_jit(self._lt_dev)
            self._sk_dev = _pin_carry_jit(self._sk_dev)
            self._cl_dev = _pin_carry_jit(self._cl_dev)
            self._base_key = _pin_carry_jit(self._base_key)
        widths = sorted({self._admit_width(n) for n in range(1, self.num_slots + 1)})
        # quality tiers: every program that keys on the model config compiles
        # once PER LADDER RUNG (serving never dispatches the base cfg then)
        warm_cfgs = self._tier_cfgs if self.tiers is not None else (self.cfg,)
        for A in widths:
            for b in self.buckets.sizes:
                prompts = np.zeros((A, b), np.int32)
                prompt_lens = np.ones((A,), np.int32)
                slots = np.arange(A, dtype=np.int32)
                valid = np.zeros((A,), bool)    # all rows no-op: state safe
                req_ids = np.zeros((A,), np.int32)
                for acfg in warm_cfgs:
                    if self.layout == "paged":
                        nb = -(-b // self.block_size)
                        out = _admit_fused_paged_jit(
                            cfg=acfg, params=self.params, cache=self.cache,
                            prompts=prompts, prompt_lens=prompt_lens,
                            # all-sentinel ids: every scatter dropped,
                            # state safe
                            block_ids=np.full((A, nb), self.num_blocks,
                                              np.int32),
                            req_ids=req_ids, base_key=self._base_key,
                            sampling=self.sampling, block_size=self.block_size,
                        )
                    elif self.prefill_mode == "fused":
                        out = _admit_fused_jit(
                            cfg=acfg, params=self.params, cache=self.cache,
                            prompts=prompts, prompt_lens=prompt_lens,
                            slots=slots, valid=valid, req_ids=req_ids,
                            base_key=self._base_key, sampling=self.sampling,
                        )
                    else:
                        out = _admit_decode_jit(
                            cfg=acfg, params=self.params, cache=self.cache,
                            prompts=prompts, prompt_lens=prompt_lens,
                            slots=slots, valid=valid, req_ids=req_ids,
                            base_key=self._base_key, sampling=self.sampling,
                            max_len=self.max_len, cache_dtype=self.cache_dtype,
                        )
                    jax.block_until_ready(out)
                    self.cache = out[0]
                    if self.chunked:
                        # chunk prefill dispatches at (admit width x chunk
                        # bucket) with the session's fixed table width —
                        # all-sentinel tables make every warmup write drop,
                        # so state stays semantically untouched
                        out = _prefill_chunk_jit(
                            cfg=acfg, params=self.params, cache=self.cache,
                            tokens=np.zeros((A, b), np.int32),
                            starts=np.zeros((A,), np.int32),
                            chunk_lens=np.ones((A,), np.int32),
                            tables=np.full(
                                (A, self._tables.shape[1]),
                                self.num_blocks, np.int32,
                            ),
                            req_ids=req_ids, base_key=self._base_key,
                            sampling=self.sampling,
                            block_size=self.block_size,
                        )
                        jax.block_until_ready(out)
                        self.cache = out[0]
            # the async admit-carry merge compiles once per admit width;
            # all-False valid keeps the device carry content intact.  tok0s
            # and keys are jnp arrays on purpose: the real calls pass admit-
            # program futures, and the jit cache keys numpy and jax.Array
            # operands separately even at identical avals
            t0w, kw = jnp.zeros((A,), jnp.int32), jnp.zeros((A, 2), jnp.uint32)
            if self.mesh is not None:
                # under the mesh, match the real futures' shardings exactly:
                # use the admit program's own (no-op) outputs
                t0w, kw = out[1], out[2]
            self._lt_dev, self._sk_dev = _admit_merge_jit(
                self._lt_dev, self._sk_dev, np.arange(A, dtype=np.int32),
                t0w, kw, np.zeros((A,), bool),
            )
            if self.spec and self.loop == "async":
                # the spec length-carry merge compiles once per admit
                # width; all-False valid keeps the carry content intact.
                # The real call passes host numpy prompt_lens — match it
                self._cl_dev = _spec_merge_len_jit(
                    self._cl_dev, np.arange(A, dtype=np.int32),
                    np.ones((A,), np.int32), np.zeros((A,), bool),
                )
        # warm the work-tick program with the SAME operand types the
        # session's loop dispatches (async: device-resident carry; sync:
        # host numpy) — mixing them would leave the first real chunk a
        # cache miss.  Speculative sessions dispatch _spec_tick instead of
        # the decode tick, never both
        dev_carry = self.loop == "async"
        if self.spec:
            # dynamic_draft_k: draft_k is a STATIC jit arg, so warm every
            # rung of the halving ladder — adaptation then switches between
            # already-compiled programs and never compiles mid-trace
            for dk in (self._draft_ks if self.dynamic_draft else (self.draft_k,)):
                out = _spec_tick_jit(
                    cfg=self.cfg, draft_cfg=self.draft_cfg, params=self.params,
                    cache=self.cache,
                    last_token=self._lt_dev if dev_carry else self._last_token,
                    cur_len=self._cl_dev if dev_carry else self._cur_len.copy(),
                    active=np.zeros((self.num_slots,), bool),
                    slot_keys=self._sk_dev if dev_carry else self._slot_keys,
                    tables=self._tables.copy(),
                    sampling=self.sampling, draft_k=dk,
                    block_size=self.block_size, attn_impl=self.attn_impl,
                )
                jax.block_until_ready(out)
                self.cache = out[0]
                if dev_carry:
                    self._lt_dev, self._cl_dev = out[3], out[4]
        else:
            for tcfg in warm_cfgs:
                out = _decode_tick_jit(
                    cfg=tcfg, params=self.params, cache=self.cache,
                    last_token=self._lt_dev if dev_carry else self._last_token,
                    cur_len=self._cur_len.copy(),
                    active=np.zeros((self.num_slots,), bool),
                    slot_keys=self._sk_dev if dev_carry else self._slot_keys,
                    tables=self._tables.copy() if self.layout == "paged" else None,
                    sampling=self.sampling, steps=self.steps_per_tick,
                    block_size=self.block_size if self.layout == "paged" else 0,
                    attn_impl=self.attn_impl,
                )
                jax.block_until_ready(out)
                self.cache = out[0]
        if self.layout == "paged" and self.prefix_sharing:
            # copy-on-write fork program: src == dst makes the warmup copy a
            # content no-op; src/dst are traced, so this one compile serves
            # every real fork
            self.cache = _copy_block_jit(
                self.cache, np.int32(0), np.int32(0), n_kv=self.cfg.num_kv_heads
            )
            jax.block_until_ready(self.cache)
        if self.zero_on_evict:
            self.cache = _evict_jit(self.cache, np.int32(0))
            jax.block_until_ready(self.cache)
        return self.compile_stats()

    def compile_stats(self) -> Dict[str, int]:
        return scheduler_compile_stats()
