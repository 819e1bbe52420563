"""Public wrapper around the Pallas paged decode-attention kernel.

Validates shapes, normalizes index dtypes, and auto-selects interpret mode
off-TPU (``REPRO_FORCE_INTERPRET=1`` forces it anywhere — the CPU CI path,
which runs the real kernel body through the Pallas interpreter).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the policy is looked up per call, so a test can patch it after import
from repro.kernels import interpret as _interpret
from repro.kernels.paged_attention.kernel import paged_attention_kernel_call

__all__ = ["paged_attention_pallas", "validate_tp_heads"]


def validate_tp_heads(num_heads: int, num_kv_heads: int, tp: int) -> None:
    """Reject head counts that cannot shard over a ``tp``-way model axis.

    The kernel is mapped per-shard under tensor parallelism (``shard_map``
    over the head dims of q/k/v and the pool), so each shard must hold an
    integral number of query AND KV heads — otherwise the per-shard
    ``H % n_kv`` group structure (each KV head serving ``H // n_kv`` query
    heads) would differ across shards and the grid would be ragged."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if num_heads % tp or num_kv_heads % tp:
        raise ValueError(
            f"pallas paged attention under tp={tp} needs per-shard integral "
            f"head counts: num_heads={num_heads}, num_kv_heads={num_kv_heads} "
            f"must both divide by tp"
        )
    if (num_heads // tp) % (num_kv_heads // tp):
        raise ValueError(
            f"per-shard group structure broken: {num_heads // tp} query heads "
            f"not a multiple of {num_kv_heads // tp} KV heads per shard"
        )


def paged_attention_pallas(
    q: jax.Array,            # (B, H, hd) post-rope queries, one decode step
    k_new: jax.Array,        # (B, Hkv, hd) new token K (post-rope)
    v_new: jax.Array,        # (B, Hkv, hd) new token V
    k_pool: jax.Array,       # (L, num_blocks, block_size, Hkv * hd) whole pool
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, W) physical block ids, sentinel == num_blocks
    cur_len: jax.Array,      # (B,) new-token positions
    layer: jax.Array | int,  # the pool layer attended
    *,
    block_size: int,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, H, hd) attention outputs in the caller's query dtype.

    The pool operands are READ-ONLY: the new token is fused into the
    current block's VMEM tile inside the kernel, and persisting it to the
    pool for the next step is the caller's scatter (see
    ``models.attention.paged_decode_attention``).
    """
    if interpret is None:
        interpret = _interpret.default_interpret()
    B, H, hd = q.shape
    n_kv = k_new.shape[1]
    if k_pool.ndim != 4:
        raise ValueError(
            f"pool must be (L, num_blocks, block_size, Hkv*hd), got {k_pool.shape}"
        )
    _, num_blocks, bs, lanes = k_pool.shape
    if bs != block_size:
        raise ValueError(f"pool block_size {bs} != block_size arg {block_size}")
    if v_pool.shape != k_pool.shape:
        raise ValueError(f"k/v pool shapes differ: {k_pool.shape} vs {v_pool.shape}")
    if lanes != n_kv * hd or H % n_kv:
        raise ValueError(
            f"q heads/dim {(H, hd)} incompatible with new-token K "
            f"{k_new.shape} and pool rows of {lanes}"
        )
    if k_new.shape != (B, n_kv, hd) or v_new.shape != (B, n_kv, hd):
        raise ValueError(
            f"new-token K/V must be {(B, n_kv, hd)}, got "
            f"{k_new.shape} / {v_new.shape}"
        )
    if block_table.ndim != 2 or block_table.shape[0] != B or cur_len.shape != (B,):
        raise ValueError(
            f"block_table {block_table.shape} / cur_len {cur_len.shape} "
            f"inconsistent with batch {B}"
        )
    out = paged_attention_kernel_call(
        q,
        k_new,
        v_new,
        k_pool,
        v_pool,
        block_table.astype(jnp.int32),
        cur_len.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32),
        block_size=block_size,
        interpret=interpret,
    )
    return out.astype(q.dtype)
