"""Unified decoder stack for all assigned architecture families.

* dense / vlm / audio : pre-RMSNorm GQA + SwiGLU FFN
* moe                 : GQA + (shared + routed top-k) MoE FFN
* ssm                 : Mamba-1 blocks
* hybrid              : Mamba-2 blocks + a weight-shared attention block
                        applied every ``attn_every`` layers (Zamba2-style)

Layer parameters are stacked on a leading axis and executed with
``lax.scan`` (optionally remat'd) so the compiled HLO is layer-count
independent — essential for 512-device dry-run compiles of 60+-layer models.

Caches are pytrees stacked the same way; ``decode_step`` scans over
(params, cache) jointly.

Name scopes label each layer's operations in the compiled HLO (``op_name``)
and so in device profiles: ``embed``, ``norm``, ``attention``, ``kv_write``,
``dense`` and ``mlp`` (the FFN's gate and activation); an operation belongs to
the innermost scope on its path, and what XLA adds (the scan's copies) to none.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.approx import ApproxConfig, concat_weights, w_dim
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.attention import (
    AttnParams,
    decode_attention,
    init_attn,
    paged_decode_attention,
    paged_verify_attention,
    seed_kv_cache,
    self_attention,
)
from repro.models.moe import MoEParams, init_moe, moe_ffn

__all__ = [
    "init_params",
    "forward",
    "init_cache",
    "init_paged_cache",
    "seed_cache",
    "decode_step",
    "paged_decode_step",
    "paged_verify_step",
    "paged_chunk_prefill_step",
    "FFNParams",
]


class FFNParams(NamedTuple):
    w_gate: jax.Array
    w_up: jax.Array
    w_down: jax.Array


def _init_ffn(key, d: int, ff: int) -> FFNParams:
    k1, k2, k3 = jax.random.split(key, 3)
    return FFNParams(
        w_gate=L.init_dense(k1, d, ff),
        w_up=L.init_dense(k2, d, ff),
        w_down=L.init_dense(k3, ff, d),
    )


@jax.named_scope("mlp")
def _ffn(x, p: FFNParams, cfg: ApproxConfig, fuse_gate_up: bool = False):
    # Megatron split: gate/up are column-parallel, down is row-parallel —
    # pinning the hidden activation head-sharded over "model" keeps the whole
    # MLP local per shard with a single psum after w_down (no-op off-mesh).
    from repro.parallel.sharding import constrain

    if fuse_gate_up:
        # §Perf lever: gate & up share one quant + feature pass / wide dot
        w = concat_weights([p.w_gate, p.w_up], axis=1)
        gu = L.dense(x, w, cfg)
        ff = w_dim(p.w_gate, 1)
        h = jax.nn.silu(gu[..., :ff]) * gu[..., ff:]
    else:
        h = jax.nn.silu(L.dense(x, p.w_gate, cfg)) * L.dense(x, p.w_up, cfg)
    h = constrain(h, ("batch",) + (None,) * (h.ndim - 2) + ("model",))
    return L.dense(h, p.w_down, cfg)


@jax.named_scope("embed")
def _embed(cfg: ModelConfig, params, batch) -> jax.Array:
    """The token embedding lookup (or the given embeddings) in the model
    dtype."""
    if cfg.embed_input:
        return params["embed"][batch["tokens"]].astype(jnp.dtype(cfg.dtype))
    return batch["embeddings"].astype(jnp.dtype(cfg.dtype))


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _init_layer(cfg: ModelConfig, key) -> Dict[str, Any]:
    d = cfg.d_model
    if cfg.family == "ssm":
        k1 = key
        return {
            "ln": jnp.ones((d,)),
            "mamba": S.init_mamba1(k1, d, cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.conv_width),
        }
    if cfg.family == "hybrid":
        return {
            "ln": jnp.ones((d,)),
            "mamba": S.init_mamba2(key, d, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.conv_width),
        }
    k1, k2, k3, k4 = jax.random.split(key, 4)
    layer = {
        "ln1": jnp.ones((d,)),
        "ln2": jnp.ones((d,)),
        "attn": init_attn(k1, d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim),
    }
    if cfg.family == "moe":
        layer["moe"] = init_moe(
            k2, d, cfg.d_ff, cfg.moe_experts, shared_d_ff=cfg.moe_shared_ff
        )
    else:
        layer["ffn"] = _init_ffn(k2, d, cfg.d_ff)
    return layer


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, 4)
    layer_keys = jax.random.split(keys[0], cfg.num_layers)
    stacked = jax.vmap(lambda k: _init_layer(cfg, k))(layer_keys)
    params: Dict[str, Any] = {"layers": stacked}
    if cfg.embed_input:
        params["embed"] = L.truncated_normal_init(keys[1], (cfg.vocab_size, cfg.d_model))
    params["final_norm"] = jnp.ones((cfg.d_model,))
    params["lm_head"] = L.init_dense(keys[2], cfg.d_model, cfg.padded_vocab)
    if cfg.family == "hybrid":
        k1, k2 = jax.random.split(keys[3])
        params["shared_attn"] = {
            "ln1": jnp.ones((cfg.d_model,)),
            "ln2": jnp.ones((cfg.d_model,)),
            "attn": init_attn(k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim),
            "ffn": _init_ffn(k2, cfg.d_model, cfg.d_ff),
        }
    if cfg.param_dtype != "float32":
        pd = jnp.dtype(cfg.param_dtype)
        params = jax.tree.map(
            lambda a: a.astype(pd) if a.dtype == jnp.float32 else a, params
        )
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _attn_block(cfg: ModelConfig, x, layer, m_rope_pos=None):
    a = cfg.approx
    h, kv = self_attention(
        L.rms_norm(x, layer["ln1"]),
        layer["attn"],
        n_heads=cfg.num_heads,
        n_kv=cfg.num_kv_heads,
        cfg=a,
        m_rope=(m_rope_pos, cfg.m_rope_sections) if (cfg.pos_embedding == "m_rope" and m_rope_pos is not None) else None,
        rope_theta=cfg.rope_theta,
        use_rope=cfg.pos_embedding in ("rope", "m_rope"),
        q_chunk=cfg.q_chunk,
        fuse_qkv=cfg.fuse_qkv,
    )
    x = x + h
    aux = jnp.float32(0)
    if cfg.family == "moe":
        B, Sq, d = x.shape
        h2, aux = moe_ffn(
            L.rms_norm(x, layer["ln2"]).reshape(B * Sq, d),
            layer["moe"],
            top_k=cfg.moe_top_k,
            cfg=a,
            capacity_factor=cfg.capacity_factor,
            unroll_experts=cfg.unroll_experts,
        )
        x = x + h2.reshape(B, Sq, d)
    else:
        x = x + _ffn(L.rms_norm(x, layer["ln2"]), layer["ffn"], a, cfg.fuse_gate_up)
    return x, kv, aux


def _layer_slice(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def _run_dense_like(cfg: ModelConfig, params, x, m_rope_pos=None, collect_kv: bool = False):
    """Scan over stacked layers (or unroll when cfg.scan_layers=False — used
    by the dry-run's cost-extraction lowering); returns (x, aux_sum) or, with
    ``collect_kv``, (x, aux_sum, (k, v)) with k/v stacked (L, B, S, Hkv, hd)
    — the fused-prefill cache seed."""

    def body(carry, layer):
        x, aux = carry
        x, kv, a = _attn_block(cfg, x, layer, m_rope_pos)
        return (x, aux + a), (kv if collect_kv else None)

    fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        (x, aux), kvs = jax.lax.scan(fn, (x, jnp.float32(0)), params["layers"])
        return (x, aux, kvs) if collect_kv else (x, aux)
    carry = (x, jnp.float32(0))
    kv_list = []
    for i in range(cfg.num_layers):
        carry, kv = fn(carry, _layer_slice(params["layers"], i))
        kv_list.append(kv)
    x, aux = carry
    if collect_kv:
        kvs = jax.tree.map(lambda *xs: jnp.stack(xs), *kv_list)
        return x, aux, kvs
    return x, aux


def _run_ssm(cfg: ModelConfig, params, x):
    def body(carry, layer):
        x = carry
        h, _ = S.mamba1_forward(
            L.rms_norm(x, layer["ln"]), layer["mamba"], cfg=cfg.approx, chunk=cfg.ssm_chunk
        )
        return x + h, None

    fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        x, _ = jax.lax.scan(fn, x, params["layers"])
        return x, jnp.float32(0)
    for i in range(cfg.num_layers):
        x, _ = fn(x, _layer_slice(params["layers"], i))
    return x, jnp.float32(0)


def _shared_attn_apply(cfg: ModelConfig, shared, x):
    h, kv = self_attention(
        L.rms_norm(x, shared["ln1"]),
        shared["attn"],
        n_heads=cfg.num_heads,
        n_kv=cfg.num_kv_heads,
        cfg=cfg.approx,
        rope_theta=cfg.rope_theta,
        q_chunk=cfg.q_chunk,
    )
    x = x + h
    x = x + _ffn(L.rms_norm(x, shared["ln2"]), shared["ffn"], cfg.approx, cfg.fuse_gate_up)
    return x, kv


def _group_layers(cfg: ModelConfig):
    k = cfg.attn_every
    assert cfg.num_layers % k == 0, (cfg.num_layers, k)
    return cfg.num_layers // k, k


def _run_hybrid(cfg: ModelConfig, params, x):
    """Groups of ``attn_every`` Mamba-2 layers, then the weight-shared
    attention block (Zamba2-style)."""
    n_groups, k = _group_layers(cfg)
    stacked = jax.tree.map(
        lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["layers"]
    )
    shared = params["shared_attn"]

    def group_body(x, group_params):
        def inner(x, layer):
            h, _ = S.mamba2_forward(
                L.rms_norm(x, layer["ln"]), layer["mamba"], cfg=cfg.approx, chunk=cfg.ssm_chunk
            )
            return x + h, None

        x, _ = jax.lax.scan(inner, x, group_params)
        x, _ = _shared_attn_apply(cfg, shared, x)
        return x, None

    fn = jax.checkpoint(group_body) if cfg.remat else group_body
    if cfg.scan_layers:
        x, _ = jax.lax.scan(fn, x, stacked)
        return x, jnp.float32(0)
    for i in range(n_groups):
        x, _ = fn(x, _layer_slice(stacked, i))
    return x, jnp.float32(0)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    *,
    return_kv: bool = False,
):
    """batch: {"tokens": (B,S) int32} or {"embeddings": (B,S,d)} (+ optional
    "positions_thw" (B,3,S) for m_rope). Returns (logits (B,S,V), aux_loss),
    or with ``return_kv`` (attention families only) (logits, aux, (k, v))
    where k/v are stacked (L, B, S, Hkv, hd) — feed to ``seed_cache`` so
    prefill seeds the decode cache in one fused pass."""
    from repro.parallel.sharding import constrain

    dtype = jnp.dtype(cfg.dtype)
    x = _embed(cfg, params, batch)
    if cfg.pos_embedding == "sinusoidal":
        x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(dtype)
    x = constrain(x, ("batch", None, None))

    m_rope_pos = batch.get("positions_thw") if cfg.pos_embedding == "m_rope" else None
    if cfg.pos_embedding == "m_rope" and m_rope_pos is None:
        S_ = x.shape[1]
        m_rope_pos = jnp.broadcast_to(jnp.arange(S_)[None, None, :], (x.shape[0], 3, S_))

    kvs = None
    if cfg.family == "ssm":
        if return_kv:
            raise NotImplementedError("ssm has no attention KV; use decode-mode prefill")
        x, aux = _run_ssm(cfg, params, x)
    elif cfg.family == "hybrid":
        if return_kv:
            raise NotImplementedError("hybrid prefill needs conv/ssm state; use decode-mode prefill")
        x, aux = _run_hybrid(cfg, params, x)
    elif return_kv:
        x, aux, kvs = _run_dense_like(cfg, params, x, m_rope_pos, collect_kv=True)
    else:
        x, aux = _run_dense_like(cfg, params, x, m_rope_pos)

    x = L.rms_norm(x, params["final_norm"])
    logits = _mask_pad(cfg, L.dense(x, params["lm_head"], cfg.approx))
    # keep the vocab axis model-sharded: the (B,S,V) f32 logits are the
    # single largest activation at 50k-150k vocabs
    logits = constrain(logits, ("batch", None, "model"))
    logits = logits.astype(jnp.float32)
    return (logits, aux, kvs) if return_kv else (logits, aux)


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-layer cache pytree."""
    if cfg.family == "ssm":
        di, N, cw = cfg.d_inner, cfg.ssm_state, cfg.conv_width
        return {
            "conv": jnp.zeros((cfg.num_layers, batch, cw - 1, di), dtype),
            "ssm": jnp.zeros((cfg.num_layers, batch, di, N), jnp.float32),
        }
    if cfg.family == "hybrid":
        n_groups, k = cfg.num_layers // cfg.attn_every, cfg.attn_every
        di, N, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        conv_dim = di + 2 * N
        return {
            "conv": jnp.zeros((cfg.num_layers, batch, cfg.conv_width - 1, conv_dim), dtype),
            "ssm": jnp.zeros((cfg.num_layers, batch, nh, di // nh, N), jnp.float32),
            "k": jnp.zeros((n_groups, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((n_groups, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        }
    return {
        "k": jnp.zeros((cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
    }


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    """Paged KV cache: a global pool of ``num_blocks`` fixed-size blocks per
    layer instead of a per-request ``max_len`` stripe.  Total HBM is
    ``num_blocks * block_size`` KV rows per layer regardless of how many
    requests are resident — the block table (see ``serve.scheduler``) maps
    each request's logical positions onto its owned blocks.

    Attention families only: SSM/hybrid decode state is O(1) per request
    (conv tap + ssm state, no sequence axis), so there is nothing to page —
    those families keep the slot layout."""
    if cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError(
            f"{cfg.family} caches carry per-request conv/ssm state with no "
            "sequence axis; the paged layout applies to attention-family "
            "KV caches only"
        )
    shape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def seed_cache(cfg: ModelConfig, cache, kvs) -> Dict[str, jax.Array]:
    """Write fused-prefill K/V (from ``forward(..., return_kv=True)``) into a
    fresh ``init_cache`` pytree at positions [0, S0) for every layer."""
    k, v = kvs                                   # (L, B, S0, Hkv, hd)
    kc, vc = jax.vmap(seed_kv_cache)(cache["k"], cache["v"], k, v)
    return dict(cache, k=kc, v=vc)


def decode_step(
    cfg: ModelConfig,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    cur_len: jax.Array,                 # (B,)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. batch: {"tokens": (B,1)} or {"embeddings": (B,1,d)}.
    Returns (logits (B,1,V), new_cache)."""
    dtype = jnp.dtype(cfg.dtype)
    x = _embed(cfg, params, batch)
    if cfg.pos_embedding == "sinusoidal":
        x = x + L.sinusoidal_at(cur_len, cfg.d_model)[:, None, :].astype(dtype)

    a = cfg.approx

    if cfg.family == "ssm":
        def body(x, scanned):
            layer, conv, h = scanned
            y, (conv, h) = S.mamba1_decode_step(
                L.rms_norm(x, layer["ln"]), layer["mamba"], (conv, h), cfg=a
            )
            return x + y, (conv, h)

        x, (conv_new, ssm_new) = _scan_decode(
            body, x, (params["layers"], cache["conv"], cache["ssm"]), cfg.scan_layers
        )
        return _head(cfg, params, x), {"conv": conv_new, "ssm": ssm_new}

    if cfg.family == "hybrid":
        n_groups, k = _group_layers(cfg)
        grouped = jax.tree.map(
            lambda t: t.reshape(n_groups, k, *t.shape[1:]),
            (params["layers"], cache["conv"], cache["ssm"]),
        )
        shared = params["shared_attn"]

        def group_body(carry, scanned):
            x = carry
            (layers_g, conv_g, ssm_g), kc, vc = scanned

            def inner(x, sc):
                layer, conv, h = sc
                y, (conv, h) = S.mamba2_decode_step(
                    L.rms_norm(x, layer["ln"]), layer["mamba"], (conv, h), cfg=a
                )
                return x + y, (conv, h)

            x, (conv_g, ssm_g) = _scan_decode(inner, x, (layers_g, conv_g, ssm_g))
            h2, kv = decode_attention(
                L.rms_norm(x, shared["ln1"]), shared["attn"], kc, vc, cur_len,
                n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, cfg=a,
                rope_theta=cfg.rope_theta,
            )
            x = x + h2
            x = x + _ffn(L.rms_norm(x, shared["ln2"]), shared["ffn"], a, cfg.fuse_gate_up)
            return x, ((conv_g, ssm_g), kv[0], kv[1])

        x, ((conv_new, ssm_new), k_new, v_new) = _scan_decode(
            group_body, x, (grouped, cache["k"], cache["v"]), cfg.scan_layers
        )
        unstack = lambda t: t.reshape(cfg.num_layers, *t.shape[2:])
        return _head(cfg, params, x), {
            "conv": unstack(conv_new),
            "ssm": unstack(ssm_new),
            "k": k_new,
            "v": v_new,
        }

    # dense / moe / vlm / audio
    def body(x, scanned):
        layer, kc, vc = scanned
        h, (kc, vc) = decode_attention(
            L.rms_norm(x, layer["ln1"]), layer["attn"], kc, vc, cur_len,
            n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, cfg=a,
            rope_theta=cfg.rope_theta,
            use_rope=cfg.pos_embedding in ("rope", "m_rope"),
        )
        return _decode_mlp(cfg, x + h, layer, a), (kc, vc)

    x, (k_new, v_new) = _scan_decode(
        body, x, (params["layers"], cache["k"], cache["v"]), cfg.scan_layers
    )
    return _head(cfg, params, x), {"k": k_new, "v": v_new}


def _decode_mlp(cfg: ModelConfig, x, layer, a: ApproxConfig):
    """The post-attention half of a decode-path attention-family block."""
    if cfg.family == "moe":
        B = x.shape[0]
        h2, _ = moe_ffn(
            L.rms_norm(x, layer["ln2"]).reshape(B, cfg.d_model),
            layer["moe"], top_k=cfg.moe_top_k, cfg=a,
            capacity_factor=cfg.capacity_factor,
            unroll_experts=cfg.unroll_experts,
        )
        return x + h2.reshape(B, 1, cfg.d_model)
    return x + _ffn(L.rms_norm(x, layer["ln2"]), layer["ffn"], a, cfg.fuse_gate_up)


def paged_decode_step(
    cfg: ModelConfig,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    cur_len: jax.Array,                 # (B,)
    block_tables: jax.Array,            # (B, W) int32
    *,
    block_size: int,
    attn_impl: str = "gather",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``decode_step`` against an ``init_paged_cache`` pytree: identical
    math, but each row's K/V reads and the new token's write are routed
    through its block table (``attention.paged_decode_attention``).  The
    table is shared across layers — block ``b`` of layer ``l`` lives at
    ``cache["k"][l, table[row, pos // block_size]]``.  ``attn_impl``
    selects the per-layer attention path: the XLA block gather
    (``"gather"``, the oracle) or the in-place Pallas block-pool kernel
    (``"pallas"``)."""
    if cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError("paged decode applies to attention-family caches only")
    from repro.parallel.sharding import constrain

    dtype = jnp.dtype(cfg.dtype)
    x = _embed(cfg, params, batch)
    if cfg.pos_embedding == "sinusoidal":
        x = x + L.sinusoidal_at(cur_len, cfg.d_model)[:, None, :].astype(dtype)
    # TP: the residual stream stays replicated over "model" — each layer's
    # row-parallel wo/w_down psum re-materializes it (no-op off-mesh)
    x = constrain(x, ("batch", None, None))

    a = cfg.approx

    def body(x, scanned):
        layer, kc, vc = scanned
        h, (kc, vc) = paged_decode_attention(
            L.rms_norm(x, layer["ln1"]), layer["attn"], kc, vc,
            block_tables, cur_len,
            block_size=block_size,
            n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, cfg=a,
            rope_theta=cfg.rope_theta,
            use_rope=cfg.pos_embedding in ("rope", "m_rope"),
            attn_impl=attn_impl,
        )
        return _decode_mlp(cfg, x + h, layer, a), (kc, vc)

    x, (k_new, v_new) = _scan_decode(
        body, x, (params["layers"], cache["k"], cache["v"]), cfg.scan_layers
    )
    return _head(cfg, params, x), {"k": k_new, "v": v_new}


def paged_verify_step(
    cfg: ModelConfig,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    cur_len: jax.Array,                 # (B,) position of the first token
    block_tables: jax.Array,            # (B, W) int32
    *,
    block_size: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Speculative-decoding verify pass: score ``S = draft_k + 1``
    consecutive tokens per row against the paged cache in ONE dispatch.

    ``batch["tokens"]`` is (B, S): row ``b``'s token ``j`` sits at cache
    position ``cur_len[b] + j``.  Returns (logits (B, S, V), new_cache):
    ``logits[:, j]`` is the next-token distribution *after* token ``j`` —
    what a sequential ``paged_decode_step`` at ``cur_len + j`` would have
    produced — and the cache holds this pass's K/V (computed under
    ``cfg.approx``, i.e. the verifier's exact path) at positions
    ``[cur_len, cur_len + S)``, overwriting whatever the draft pass wrote
    there.  Position/rope/masking per verify slot are exactly the
    single-token decode path's (see ``paged_verify_attention``), so greedy
    acceptance against this pass is bit-identical to sequential decoding.

    Dense-like attention families only: MoE routing is capacity-coupled
    across the token batch, so a (B*S)-token verify would route
    differently than B sequential single-token steps and the acceptance
    rule would lose its exactness contract."""
    if cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError("paged verify applies to attention-family caches only")
    if cfg.family == "moe":
        raise NotImplementedError(
            "moe routing is capacity-coupled across the token batch — a "
            "batched verify pass routes differently than sequential decode, "
            "breaking the speculative acceptance contract"
        )
    from repro.parallel.sharding import constrain

    dtype = jnp.dtype(cfg.dtype)
    x = _embed(cfg, params, batch)
    S = x.shape[1]
    if cfg.pos_embedding == "sinusoidal":
        pos = cur_len[:, None] + jnp.arange(S, dtype=cur_len.dtype)[None, :]
        x = x + L.sinusoidal_at(pos.reshape(-1), cfg.d_model).reshape(
            x.shape[0], S, cfg.d_model
        ).astype(dtype)
    x = constrain(x, ("batch", None, None))

    a = cfg.approx

    def body(x, scanned):
        layer, kc, vc = scanned
        h, (kc, vc) = paged_verify_attention(
            L.rms_norm(x, layer["ln1"]), layer["attn"], kc, vc,
            block_tables, cur_len,
            block_size=block_size,
            n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, cfg=a,
            rope_theta=cfg.rope_theta,
            use_rope=cfg.pos_embedding in ("rope", "m_rope"),
        )
        x = x + h
        return x + _ffn(L.rms_norm(x, layer["ln2"]), layer["ffn"], a,
                        cfg.fuse_gate_up), (kc, vc)

    x, (k_new, v_new) = _scan_decode(
        body, x, (params["layers"], cache["k"], cache["v"]), cfg.scan_layers
    )
    return _head(cfg, params, x), {"k": k_new, "v": v_new}


def paged_chunk_prefill_step(
    cfg: ModelConfig,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    prefill_pos: jax.Array,             # (B,) cursor: tokens already prefilled
    block_tables: jax.Array,            # (B, W) int32, sentinel-tailed
    *,
    block_size: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked-prefill step: teacher-force one (B, C) chunk of each row's
    prompt into the paged cache at positions ``[prefill_pos, prefill_pos +
    C)``, reading the already-written prefix *through the block table*.

    This IS ``paged_verify_step`` — the verify pass already has the exact
    semantics a prefill chunk needs (scatter this chunk's K/V through the
    table before any gather; attend each position at its own causal
    horizon), and reusing it makes the chunked prefill bit-identical to the
    fused one-shot prefill by construction: ``logits[:, j]`` of the final
    chunk's last real position is bitwise the fused prefill's last-position
    logits, and the pool K/V after the final chunk is bitwise the
    one-shot-scattered pool (pinned by ``tests/test_chunked_prefill.py``).

    Contract for partial tables (the PR-6 invariant the chunks lean on):

    * table entries covering ``[0, prefill_pos + C)`` must name real blocks;
      *tail* entries may still be the sentinel ``num_blocks`` — the scatter
      drops writes through them, and positions ``>= kv_len`` never enter any
      horizon, so an unallocated tail is indistinguishable from an absent
      one;
    * rows padded past their real chunk length write garbage K/V only at
      positions ``>= prefill_pos + chunk_len`` inside their own blocks —
      overwritten by the next chunk's scatter-before-gather or by decode's
      write-before-attend, and masked by ``kv_len`` until then.

    Same family gates as the verify pass: attention families only, and moe
    is excluded because its routing is capacity-coupled across the token
    batch (a chunked prefill would route differently than the fused
    oracle)."""
    return paged_verify_step(
        cfg, params, cache, batch, prefill_pos, block_tables,
        block_size=block_size,
    )


def cache_max_len(cfg: ModelConfig, cache) -> int:
    if "k" in cache:
        return cache["k"].shape[2] if cfg.family != "hybrid" else cache["k"].shape[2]
    return 1 << 20


def _scan_decode(body, x, scanned, scan_layers: bool = True):
    if scan_layers:
        return jax.lax.scan(body, x, scanned)
    n = jax.tree.leaves(scanned)[0].shape[0]
    outs = []
    for i in range(n):
        x, o = body(x, _layer_slice(scanned, i))
        outs.append(o)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, stacked


def _mask_pad(cfg: ModelConfig, logits):
    """-inf on padded vocab columns (additive, broadcast from (Vp,))."""
    V, Vp = cfg.vocab_size, cfg.padded_vocab
    if Vp == V:
        return logits
    neg = jnp.where(jnp.arange(Vp) < V, 0.0, -1e30).astype(logits.dtype)
    return logits + neg


def _head(cfg: ModelConfig, params, x):
    from repro.parallel.sharding import constrain

    x = L.rms_norm(x, params["final_norm"])
    logits = _mask_pad(cfg, L.dense(x, params["lm_head"], cfg.approx)).astype(jnp.float32)
    # TP: lm_head is column-parallel, so logits stay vocab-sharded; sampling
    # reduces them to token ids and only THOSE replicate back to the host
    return constrain(logits, ("batch",) + (None,) * (logits.ndim - 2) + ("model",))
