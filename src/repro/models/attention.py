"""Grouped-query attention: chunked-causal for train/prefill (memory-bounded,
exact softmax), plus single-token decode against a static KV cache.

K/V are never head-repeated: scores are computed with grouped einsums
(q reshaped to (B, S, Hkv, group, hd)), so KV-cache HBM footprint stays at
``n_kv`` heads — this is what makes decode_32k x batch 128 fit.

All projections route through ``layers.dense`` (approximate-multiplier aware).
The attention entry points run under the ``attention`` name scope and their
KV cache writes under ``kv_write``; the projections inside carry ``dense``.
The score/AV einsums stay exact float — the paper approximates the MAC arrays
of conv/fc layers, and projection matmuls are the analogous LM hot spots;
see DESIGN.md §5.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.approx import ApproxConfig, concat_weights, w_dim
from repro.models import layers as L

__all__ = [
    "AttnParams",
    "ATTN_IMPLS",
    "init_attn",
    "attention_core",
    "self_attention",
    "decode_attention",
    "paged_decode_attention",
    "paged_verify_attention",
    "paged_chunk_prefill_attention",
    "seed_kv_cache",
]

_NEG = -1e30

# paged decode-attention implementations: the XLA clamp-gather-mask path
# (the exact parity oracle) and the Pallas in-place block-pool kernel
# (kernels/paged_attention; interpret mode off-TPU)
ATTN_IMPLS = ("gather", "pallas")


class AttnParams(NamedTuple):
    wq: jax.Array   # (d, Hq*hd)
    wk: jax.Array   # (d, Hkv*hd)
    wv: jax.Array   # (d, Hkv*hd)
    wo: jax.Array   # (Hq*hd, d)


def init_attn(key, d_model: int, n_heads: int, n_kv: int, head_dim: int) -> AttnParams:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return AttnParams(
        wq=L.init_dense(k1, d_model, n_heads * head_dim),
        wk=L.init_dense(k2, d_model, n_kv * head_dim),
        wv=L.init_dense(k3, d_model, n_kv * head_dim),
        wo=L.init_dense(k4, n_heads * head_dim, d_model),
    )


def attention_core(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, Hkv, hd)
    v: jax.Array,            # (B, Sk, Hkv, hd)
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    kv_len: Optional[jax.Array] = None,   # (B,) valid cache lengths for decode
    q_chunk: int = 512,
) -> jax.Array:
    """Exact softmax GQA, scanned over query chunks (O(Sq*chunk*Sk) transient).

    Sharding strategy (TP): when the flat head count divides the "model"
    axis, heads are repeated and head-sharded (scores (B,H,c,Sk)/tp per
    device); otherwise K/V are sequence-sharded over "model" (SP) and GSPMD
    inserts the softmax all-reduce.
    """
    from repro.parallel.sharding import constrain, mesh_axis_size

    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    tp = mesh_axis_size("model")
    head_sharded = H % tp == 0
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    H_orig = H
    if Sq > 1 and g > 1:
        # train/prefill: repeat KV to full heads (cheap vs activations) so one
        # einsum over the flat, shardable head axis does the work
        b_, s_, h_, d_ = k.shape
        k = jnp.broadcast_to(k[:, :, :, None, :], (b_, s_, h_, g, d_)).reshape(b_, s_, H, d_)
        v = jnp.broadcast_to(v[:, :, :, None, :], (b_, s_, h_, g, d_)).reshape(b_, s_, H, d_)
        Hkv_eff = H
    else:
        Hkv_eff = Hkv

    if Sq > 1 and not head_sharded and tp > 1 and Hkv_eff == H:
        # Indivisible head counts (e.g. 56 heads on a 16-way model axis) make
        # GSPMD flip between partial-head and sequence shardings with
        # "involuntary full rematerialization" copies. Pad the head axis to
        # the next multiple of tp (zero heads are pure overhead of H_pad/H-1,
        # far cheaper than replicated score tensors) and slice afterwards.
        H = -(-H // tp) * tp
        pad = [(0, 0), (0, 0), (0, H - H_orig), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        Hkv_eff = H
        head_sharded = True

    if Sq == 1 and Hkv_eff % tp != 0:
        # decode against a grouped cache whose KV heads don't divide the TP
        # axis: head-sharding q would make GSPMD all-gather the whole KV
        # cache per layer (~1 GB/layer at 32k ctx). Keep the cache
        # sequence-sharded and let the scores/AV contraction stay on S with
        # a tiny (B,H,1) softmax all-reduce instead.  [§Perf C4]
        head_sharded = False
        q = constrain(q, ("batch", None, None, None))
        k = constrain(k, ("batch", "model", None, None))
        v = constrain(v, ("batch", "model", None, None))
    elif head_sharded:
        q = constrain(q, ("batch", None, "model", None))
        if Hkv_eff % tp == 0:
            k = constrain(k, ("batch", None, "model", None))
            v = constrain(v, ("batch", None, "model", None))
    else:
        # SP fallback: shard the KV sequence axis
        k = constrain(k, ("batch", "model", None, None))
        v = constrain(v, ("batch", "model", None, None))

    ge = H // Hkv_eff
    kt = k.swapaxes(1, 2)                        # (B, Hkv_eff, Sk, hd) bf16
    vt = v.swapaxes(1, 2)
    kv_pos = jnp.arange(Sk)

    def one_chunk(q_blk: jax.Array, blk_start) -> jax.Array:
        c = q_blk.shape[1]
        qt = (q_blk * scale.astype(q.dtype)).reshape(B, c, Hkv_eff, ge, hd)
        # (B, Hkv_eff, g, c, Sk): bf16 operands, f32 accumulation
        scores = jnp.einsum(
            "bchgd,bhkd->bhgck", qt, kt, preferred_element_type=jnp.float32
        )
        # masks are ADDITIVE on small pre-broadcast shapes: jnp.where on the
        # full score tensor would pin a full-size pred residual for backward
        if causal:
            q_pos = blk_start + q_offset + jnp.arange(c)
            neg = jnp.where(q_pos[:, None] >= kv_pos, 0.0, _NEG)     # (c, Sk)
            scores = scores + neg[None, None, None, :, :]
        if kv_len is not None:
            neg = jnp.where(kv_pos[None, :] < kv_len[:, None], 0.0, _NEG)  # (B, Sk)
            scores = scores + neg[:, None, None, None, :]
        probs = jax.nn.softmax(scores, axis=-1).astype(vt.dtype)
        out = jnp.einsum(
            "bhgck,bhkd->bchgd", probs, vt, preferred_element_type=jnp.float32
        )
        return out.reshape(B, c, H, hd).astype(q.dtype)

    def unpad(o):
        return o[:, :, :H_orig] if H != H_orig else o

    if Sq <= q_chunk or Sq % q_chunk != 0:
        return unpad(one_chunk(q, 0))

    n_blk = Sq // q_chunk
    qb = q.reshape(B, n_blk, q_chunk, H, hd).swapaxes(0, 1)  # (n, B, c, H, hd)

    def body(start, q_blk):
        return start + q_chunk, one_chunk(q_blk, start)

    _, ob = jax.lax.scan(body, 0, qb)
    return unpad(ob.swapaxes(0, 1).reshape(B, Sq, H, hd))


@jax.named_scope("attention")
def self_attention(
    x: jax.Array,                 # (B, S, d)
    p: AttnParams,
    *,
    n_heads: int,
    n_kv: int,
    cfg: ApproxConfig,
    positions: Optional[jax.Array] = None,        # (B, S) rope positions
    m_rope: Optional[Tuple[jax.Array, Tuple[int, ...]]] = None,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    q_chunk: int = 512,
    fuse_qkv: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Training/prefill self-attention. Returns (out, (k, v)) so callers can
    seed a decode cache from prefill."""
    B, S, d = x.shape
    hd = w_dim(p.wq, 1) // n_heads
    if fuse_qkv:
        # §Perf lever: one activation-quantization + one feature-map pass
        # feeding a single wide dot (per-output-channel weight scales make
        # the fused quantization bit-identical to the separate one)
        wqkv = concat_weights([p.wq, p.wk, p.wv], axis=1)
        qkv = L.dense(x, wqkv, cfg)
        nq = n_heads * hd
        nk = n_kv * hd
        q, k, v = qkv[..., :nq], qkv[..., nq : nq + nk], qkv[..., nq + nk :]
        q = q.reshape(B, S, n_heads, hd)
        k = k.reshape(B, S, n_kv, hd)
        v = v.reshape(B, S, n_kv, hd)
    else:
        q = L.dense(x, p.wq, cfg).reshape(B, S, n_heads, hd)
        k = L.dense(x, p.wk, cfg).reshape(B, S, n_kv, hd)
        v = L.dense(x, p.wv, cfg).reshape(B, S, n_kv, hd)
    if use_rope:
        if m_rope is not None:
            pos_thw, sections = m_rope
            q, k = L.apply_m_rope(q, k, pos_thw, sections, theta=rope_theta)
        else:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            q, k = L.apply_rope(q, k, positions, theta=rope_theta)
    out = attention_core(q, k, v, causal=True, q_chunk=q_chunk)
    out = L.dense(out.reshape(B, S, n_heads * hd), p.wo, cfg)
    return out, (k, v)


@jax.named_scope("kv_write")
def seed_kv_cache(
    k_cache: jax.Array,           # (B, Smax, Hkv, hd)
    v_cache: jax.Array,
    k: jax.Array,                 # (B, S0, Hkv, hd) prefill keys (post-rope)
    v: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Write one layer's prefill K/V into positions [0, S0) of its decode
    cache. The K returned by ``self_attention`` is already rotary-embedded at
    positions 0..S0-1 — exactly what ``decode_attention`` would have written
    step by step, so fused prefill and teacher-forced prefill seed identical
    caches (tests/test_engine.py)."""
    return (
        jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, 0, 0, 0)),
        jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, 0, 0, 0)),
    )


def _decode_qkv(
    x: jax.Array,                 # (B, 1, d)
    p: AttnParams,
    cur_len: jax.Array,           # (B,) new-token positions
    *,
    n_heads: int,
    n_kv: int,
    cfg: ApproxConfig,
    rope_theta: float,
    use_rope: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared decode prologue: project the new token's q/k/v through
    ``layers.dense`` (approximate-multiplier aware) and rotate q/k at each
    row's ``cur_len``.  ``decode_attention`` and ``paged_decode_attention``
    differ only in how the K/V *cache* is laid out — this prologue is
    layout-independent and deliberately single-sourced so every execution
    mode change applies to both."""
    B = x.shape[0]
    hd = w_dim(p.wq, 1) // n_heads
    q = L.dense(x, p.wq, cfg).reshape(B, 1, n_heads, hd)
    k = L.dense(x, p.wk, cfg).reshape(B, 1, n_kv, hd)
    v = L.dense(x, p.wv, cfg).reshape(B, 1, n_kv, hd)
    if use_rope:
        q, k = L.apply_rope(q, k, cur_len[:, None], theta=rope_theta)
    return q, k, v


@jax.named_scope("attention")
def decode_attention(
    x: jax.Array,                 # (B, 1, d)
    p: AttnParams,
    k_cache: jax.Array,           # (B, Smax, Hkv, hd)
    v_cache: jax.Array,
    cur_len: jax.Array,           # (B,) current lengths (new token index)
    *,
    n_heads: int,
    n_kv: int,
    cfg: ApproxConfig,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decode step: append K/V at ``cur_len``, attend over the cache."""
    B = x.shape[0]
    q, k, v = _decode_qkv(
        x, p, cur_len, n_heads=n_heads, n_kv=n_kv, cfg=cfg,
        rope_theta=rope_theta, use_rope=use_rope,
    )
    hd = q.shape[3]
    # scatter new kv at cur_len (per-batch dynamic index)
    with jax.named_scope("kv_write"):
        b_idx = jnp.arange(B)
        k_cache = k_cache.at[b_idx, cur_len].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[b_idx, cur_len].set(v[:, 0].astype(v_cache.dtype))
    out = attention_core(q, k_cache, v_cache, causal=False, kv_len=cur_len + 1, q_chunk=1)
    out = L.dense(out.reshape(B, 1, n_heads * hd), p.wo, cfg)
    return out, (k_cache, v_cache)


@jax.named_scope("attention")
def paged_decode_attention(
    x: jax.Array,                 # (B, 1, d)
    p: AttnParams,
    k_blocks: jax.Array,          # (num_blocks, block_size, Hkv, hd) one layer
    v_blocks: jax.Array,
    block_table: jax.Array,       # (B, W) int32 physical block ids
    cur_len: jax.Array,           # (B,) current lengths (new token index)
    *,
    block_size: int,
    n_heads: int,
    n_kv: int,
    cfg: ApproxConfig,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    attn_impl: str = "gather",
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """``decode_attention`` against a paged KV cache: append K/V into the
    request's current block, attend over its blocks via the block table.

    Row ``b``'s logical position ``pos`` lives at offset ``pos % block_size``
    of physical block ``block_table[b, pos // block_size]``.  The table is
    fixed-width (``W = max_len // block_size``) with unallocated entries set
    to the sentinel ``num_blocks``, so ONE compiled program serves any
    context layout; table *contents* are traced data.  That content-
    agnosticism is what makes the scheduler's copy-on-write prefix sharing
    free at this layer: several rows' tables may point at the SAME physical
    block (a shared prompt prefix) and both impls below just walk them —
    neither reads which request owns a block, and the scheduler guarantees a
    shared block is never written while shared (writes fork first), so no
    read-path change is needed (pinned by tests/test_prefix_sharing.py
    under both impls).

    * the append scatter targets the sentinel for rows past their allocated
      blocks (or past the table) — out-of-bounds scatter updates are DROPPED
      under jit (dynamic_update_slice would CLAMP; do not swap the write
      path), so overshoot and inactive rows write nothing;
    * ``attn_impl="gather"`` (the parity oracle): ``k_blocks[block_table]``
      materializes a transient (B, W*block_size, Hkv, hd) view — sentinel
      entries clamp to the last real block, bounded garbage the ``kv_len``
      mask zeroes *exactly* (scores at ~-1e30, softmax probability 0.0, AV
      bit-identical to the slot layout's in-place cache);
    * ``attn_impl="pallas"`` streams blocks from the pool straight into
      VMEM tiles (``kernels.paged_attention``): the transient never exists
      in HBM, sentinel blocks are skipped by predicate, and the new token
      is fused into the current block's tile — the kernel reads the
      *pre-scatter* pool, so attention and the persistence scatter run in
      parallel.  Attention floats agree with the gather path to f32
      roundoff (online vs fused softmax reduction order); greedy tokens are
      bit-identical across serve traces (tests/test_paged.py).  That token
      contract assumes an f32 pool: under reduced cache dtypes the gather
      path additionally rounds its softmax *probs* to the cache dtype
      (``attention_core``) while the kernel keeps them f32, so bf16-cache
      parity is statistical — same discipline as the quantized modes.

    Projections route through ``layers.dense`` exactly as in
    ``decode_attention`` — every execution mode (incl. the Pallas
    approx-matmul kernel) is layout- and impl-agnostic."""
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"attn_impl {attn_impl!r} not in {ATTN_IMPLS}")
    B = x.shape[0]
    q, k, v = _decode_qkv(
        x, p, cur_len, n_heads=n_heads, n_kv=n_kv, cfg=cfg,
        rope_theta=rope_theta, use_rope=use_rope,
    )
    hd = q.shape[3]
    num_blocks = k_blocks.shape[0]
    W = block_table.shape[1]
    with jax.named_scope("kv_write"):
        blk = cur_len // block_size
        off = cur_len % block_size
        phys = jnp.take_along_axis(
            block_table, jnp.minimum(blk, W - 1)[:, None], axis=1
        )[:, 0]
        phys = jnp.where(blk < W, phys, num_blocks)  # past-table -> dropped
        new_k = k_blocks.at[phys, off].set(k[:, 0].astype(k_blocks.dtype))
        new_v = v_blocks.at[phys, off].set(v[:, 0].astype(v_blocks.dtype))
    if attn_impl == "pallas":
        from repro.kernels.paged_attention import (
            paged_attention_pallas,
            validate_tp_heads,
        )
        from repro.parallel.sharding import mesh_axis_size

        # pre-scatter pool operands on purpose: the kernel fuses the new
        # token in VMEM, so the scatter above only persists it for the
        # NEXT step and never serializes with this step's attention.  The
        # fused token is cast to the POOL dtype first — the kernel must
        # attend the same rounded value every later step will read back
        def call(qh, kh, vh, kp, vp, bt, cl):
            return paged_attention_pallas(
                qh, kh, vh, kp, vp, bt, cl, block_size=block_size
            )

        tp = mesh_axis_size("model")
        if tp > 1:
            # pallas_call is not partitioned by GSPMD — map it per shard of
            # the installed mesh.  Each shard runs the unmodified kernel
            # over its Hkv/tp pool heads and H/tp query heads (group
            # structure preserved, see validate_tp_heads); the block table
            # and lengths replicate, so every shard walks the same
            # host-global table.
            from jax.sharding import PartitionSpec as P

            validate_tp_heads(n_heads, n_kv, tp)
            hspec = P(None, "model", None)
            pspec = P(None, None, "model", None)
            call = jax.shard_map(
                call,
                in_specs=(hspec, hspec, hspec, pspec, pspec,
                          P(None, None), P(None)),
                out_specs=hspec,
                check_vma=False,
            )
        out = call(
            q[:, 0],
            k[:, 0].astype(k_blocks.dtype), v[:, 0].astype(v_blocks.dtype),
            k_blocks, v_blocks,
            block_table, cur_len,
        )[:, None]
    else:
        kg = new_k[block_table].reshape(B, W * block_size, n_kv, hd)
        vg = new_v[block_table].reshape(B, W * block_size, n_kv, hd)
        out = attention_core(q, kg, vg, causal=False, kv_len=cur_len + 1, q_chunk=1)
    out = L.dense(out.reshape(B, 1, n_heads * hd), p.wo, cfg)
    return out, (new_k, new_v)


@jax.named_scope("attention")
def paged_verify_attention(
    x: jax.Array,                 # (B, S, d) — S = draft_k + 1 verify positions
    p: AttnParams,
    k_blocks: jax.Array,          # (num_blocks, block_size, Hkv, hd) one layer
    v_blocks: jax.Array,
    block_table: jax.Array,       # (B, W) int32 physical block ids
    cur_len: jax.Array,           # (B,) position of the FIRST verify token
    *,
    block_size: int,
    n_heads: int,
    n_kv: int,
    cfg: ApproxConfig,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Multi-position decode attention for speculative verification: score
    ``S`` consecutive tokens of row ``b`` at cache positions ``cur_len[b] +
    j`` in ONE pass against the paged pool.

    Projections and rope run batched over the S positions (per-position
    math is independent, so float results match the single-token path
    bit-for-bit); K/V for all S positions are scattered through the block
    table first (sentinel/out-of-table targets dropped, exactly as in
    ``paged_decode_attention``), and then each position attends with its
    own ragged causal horizon ``kv_len = cur_len + j + 1``.  The attention
    itself deliberately reuses ``attention_core`` once per verify position
    (Sq == 1), NOT one batched Sq == S call: that makes every position's
    score/softmax/AV reduction the exact instruction sequence of the
    sequential decode oracle, so greedy verification is bit-identical *by
    construction* rather than by numerical accident.  S is the (small)
    draft depth, so the unrolled loop costs S tiny einsums against the one
    shared block gather — the gather transient, the dominant term, is
    materialized once.

    Always the gather read path: the Pallas paged-attention kernel's tile
    schedule is single-query (see ROADMAP TPU hardening); since gather and
    kernel greedy tokens are bit-identical, a kernel session can draft
    through the kernel and verify through this path without breaking the
    exactness contract."""
    B, S, _ = x.shape
    hd = w_dim(p.wq, 1) // n_heads
    q = L.dense(x, p.wq, cfg).reshape(B, S, n_heads, hd)
    k = L.dense(x, p.wk, cfg).reshape(B, S, n_kv, hd)
    v = L.dense(x, p.wv, cfg).reshape(B, S, n_kv, hd)
    pos = cur_len[:, None] + jnp.arange(S, dtype=cur_len.dtype)[None, :]
    if use_rope:
        q, k = L.apply_rope(q, k, pos, theta=rope_theta)
    num_blocks = k_blocks.shape[0]
    W = block_table.shape[1]
    with jax.named_scope("kv_write"):
        blk = pos // block_size                  # (B, S)
        off = pos % block_size
        phys = jnp.take_along_axis(block_table, jnp.minimum(blk, W - 1), axis=1)
        phys = jnp.where(blk < W, phys, num_blocks)  # past-table -> dropped
        new_k = k_blocks.at[phys, off].set(k.astype(k_blocks.dtype))
        new_v = v_blocks.at[phys, off].set(v.astype(v_blocks.dtype))
    kg = new_k[block_table].reshape(B, W * block_size, n_kv, hd)
    vg = new_v[block_table].reshape(B, W * block_size, n_kv, hd)
    outs = [
        attention_core(
            q[:, j : j + 1], kg, vg, causal=False,
            kv_len=cur_len + j + 1, q_chunk=1,
        )
        for j in range(S)
    ]
    out = jnp.concatenate(outs, axis=1)          # (B, S, H, hd)
    out = L.dense(out.reshape(B, S, n_heads * hd), p.wo, cfg)
    return out, (new_k, new_v)


def paged_chunk_prefill_attention(*args, **kwargs):
    """Chunk-prefill attention: score one chunk of a prompt at cache
    positions ``cur_len[b] + j`` while reading the already-prefilled prefix
    *through the block table* — the attention seam of chunked prefill.

    This IS ``paged_verify_attention``: the verify pass already does exactly
    what a prefill chunk needs (scatter the chunk's K/V through the table
    first — sentinel-tail entries of a partially-filled table drop the
    writes — then attend each position with its own causal horizon
    ``kv_len = cur_len + j + 1``), and because every position reuses the
    decode oracle's instruction sequence, a chunked prefill is bit-identical
    to the fused one-shot prefill by construction, not by numerical
    accident.  Padding positions past the chunk's real length write garbage
    K/V *inside* the row's own allocated blocks only; those positions are
    overwritten by the next chunk's scatter-before-gather (or by decode's
    write-before-attend at position ``prompt_len``) before any horizon can
    read them — the same PR-6 write-skip discipline that makes partial
    tables safe.

    Both session attention impls route chunk reads through this gather path:
    the Pallas paged-attention kernel's tile schedule is single-query, and
    gather/pallas greedy parity is already pinned, so a pallas session
    chunk-prefills through gather and decodes through the kernel without
    breaking the exactness contract."""
    return paged_verify_attention(*args, **kwargs)
