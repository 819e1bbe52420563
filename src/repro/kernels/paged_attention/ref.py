"""Pure-JAX oracle for the paged decode-attention kernel.

Same *semantics* as the kernel — walk the block table, fuse the new token
at ``cur_len``, skip sentinel blocks, mask positions past ``cur_len`` — but
computed the straightforward way: gather every table entry (clamped), mask,
one exact fused softmax.  This is the reference the property tests
difference the kernel against (``tests/test_kernels_property.py``); it is
deliberately independent of ``models.attention`` so a bug in the serving
path cannot hide a matching bug here.

Exactness contract: the kernel's online softmax reorders the f32
reductions, so kernel-vs-ref agreement is to f32 roundoff (~1e-6), not
bitwise; masked positions carry softmax weight exactly 0.0 in both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["paged_attention_ref"]

_NEG = -1e30


def paged_attention_ref(
    q: jax.Array,            # (B, H, hd)
    k_new: jax.Array,        # (B, Hkv, hd)
    v_new: jax.Array,        # (B, Hkv, hd)
    k_pool: jax.Array,       # (L, num_blocks, block_size, Hkv * hd)
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, W) int32, sentinel == num_blocks
    cur_len: jax.Array,      # (B,) int32
    layer: jax.Array | int,  # the pool layer attended
    *,
    block_size: int,
) -> jax.Array:
    """Exact-softmax paged GQA over pool layer ``layer``; (B, H, hd) f32.
    Rows with no valid position (every block sentinel) return zeros,
    matching the kernel's empty-row flush."""
    B, H, hd = q.shape
    n_kv = k_new.shape[1]
    num_blocks = k_pool.shape[1]
    W = block_table.shape[1]
    g = H // n_kv
    S = W * block_size

    clamped = jnp.minimum(block_table, num_blocks - 1)
    kg = k_pool[layer, clamped].reshape(B, S, n_kv, hd).astype(jnp.float32)
    vg = v_pool[layer, clamped].reshape(B, S, n_kv, hd).astype(jnp.float32)

    pos = jnp.arange(S, dtype=jnp.int32)
    at_cur = pos[None, :] == cur_len[:, None]                    # (B, S)
    kg = jnp.where(at_cur[..., None, None], k_new.astype(jnp.float32)[:, None], kg)
    vg = jnp.where(at_cur[..., None, None], v_new.astype(jnp.float32)[:, None], vg)

    # a position is attended iff it is <= cur AND its block is allocated
    blk_alloc = block_table < num_blocks                         # (B, W)
    pos_alloc = jnp.repeat(blk_alloc, block_size, axis=1)        # (B, S)
    valid = (pos[None, :] <= cur_len[:, None]) & pos_alloc

    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qg = (q.astype(jnp.float32) * scale).reshape(B, n_kv, g, hd)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kg, preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, vg, preferred_element_type=jnp.float32)
    any_valid = jnp.any(valid, axis=1)                           # (B,)
    return jnp.where(any_valid[:, None, None], out.reshape(B, H, hd), 0.0)
