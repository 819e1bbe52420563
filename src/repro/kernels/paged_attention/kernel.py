"""Pallas TPU kernel: paged decode attention over the global block pool.

One decode step of GQA against the paged KV cache (``cache_layout="paged"``)
WITHOUT ever materializing the per-request block gather: the XLA path builds
a transient ``(B, W*block_size, Hkv, hd)`` view of every request's blocks
per layer per step, which is the dominant per-tick HBM traffic once the
host side is hidden (PR 4).  This kernel instead walks each request's block
table and streams K/V blocks from the pool straight into VMEM tiles:

* grid ``(B, W)`` with the table walk innermost; the block index maps read
  a scalar-prefetched fetch table derived from ``block_table``, so grid
  step ``(b, w)`` DMAs physical block ``block_table[b, w]`` — the pool is
  indexed where it lives, and only blocks a request actually holds ever
  cross HBM->VMEM;
* the new token's K/V (``k_new``/``v_new``, already rotary-embedded at
  ``cur_len``) is fused into the current block's VMEM tile at offset
  ``cur_len % block_size`` before the QK^T — attention never waits on the
  pool scatter, which the caller runs in parallel to persist the token for
  the NEXT step;
* per-block scores feed a running online softmax (``m``/``l``/``acc``
  scratch carried across the ``w`` walk, flushed at ``w == W - 1``);
* sentinel table entries (``id >= num_blocks``: unallocated / padding
  rows) and blocks past ``cur_len`` are SKIPPED — ``@pl.when`` drops the
  tile's compute, and the fetch table re-maps every such step to the row's
  last valid block so Pallas's consecutive-same-block dedup elides their
  DMAs too, where the gather path had to clamp, gather garbage, and rely
  on the kv_len mask.  Rows with no valid block (inactive slots) flush
  exactly zero.

Numerics: scores/softmax/AV all accumulate in f32 exactly like
``attention_core``; masked in-block tail positions sit at -1e30, so their
softmax weight underflows to exactly 0.0 — but the ONLINE softmax sums in
block order, not the fused-softmax reduction order, so attention outputs
agree with the gather oracle to f32 roundoff (~1e-7 relative), not
bitwise.  Greedy ARGMAX outputs stay bit-identical across serve traces
(asserted in tests/test_paged.py); ``ref.py`` is the exact-math oracle the
property tests difference against.

TPU lowering note: Mosaic index maps may only read scalars from SMEM, so
the "last valid block at or before ``w``" search runs in the jitted
wrapper (a cumulative max over the table) and the index map reads one
entry of its result.  Tiles are ``(block_size, Hkv, hd)``; granite's
``hd`` 64 / ``block_size`` 16 compiles for v5e in bf16 and f32
(``tests/test_tpu_compile.py``).  Interpret mode (CPU CI,
``REPRO_FORCE_INTERPRET=1``) runs this exact kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_kernel_call"]

_NEG = -1e30


def _kernel(
    tbl_ref,      # (B, W) int32 scalar-prefetch: physical block ids
    len_ref,      # (B,)  int32 scalar-prefetch: new-token positions
    fetch_ref,    # (B, W) int32 scalar-prefetch: block DMA'd at (b, w)
    q_ref,        # (1, H, hd) this row's query
    kn_ref,       # (1, Hkv, hd) new token K (post-rope)
    vn_ref,       # (1, Hkv, hd) new token V
    k_ref,        # (1, block_size, Hkv, hd) pool block block_table[b, w]
    v_ref,
    out_ref,      # (1, H, hd)
    m_ref,        # (H, 1) f32 scratch: running max
    l_ref,        # (H, 1) f32 scratch: running normalizer
    acc_ref,      # (H, hd) f32 scratch: running weighted V sum
    *,
    block_size: int,
    num_blocks: int,
    n_kv: int,
    W: int,
):
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = len_ref[b]
    entry = tbl_ref[b, w]
    # process only blocks that are allocated AND hold >= 1 valid position
    # (position w*block_size <= cur); everything else contributes nothing —
    # this predicate is the in-place analogue of the gather path's
    # clamp-then-mask, and it is also what keeps HBM reads proportional to
    # the ACTUAL context instead of the table width
    valid = (entry < num_blocks) & (w * block_size <= cur)

    @pl.when(valid)
    def _block():
        H, hd = q_ref.shape[1], q_ref.shape[2]
        g = H // n_kv
        q = q_ref[0].astype(jnp.float32)                 # (H, hd)
        k = k_ref[0].astype(jnp.float32)                 # (bs, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        # fused token append: overwrite row `off` of the CURRENT block's
        # VMEM tile with the new K/V — the HBM pool still holds last step's
        # contents, and never needs to be read-after-written within a step
        off = cur % block_size
        row = jax.lax.broadcasted_iota(jnp.int32, (block_size, 1, 1), 0)
        sel = (row == off) & (w == cur // block_size)
        k = jnp.where(sel, kn_ref[0].astype(jnp.float32)[None], k)
        v = jnp.where(sel, vn_ref[0].astype(jnp.float32)[None], v)

        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
        qg = (q * scale).reshape(n_kv, g, hd)
        s = jnp.einsum(
            "hgd,thd->hgt", qg, k, preferred_element_type=jnp.float32
        )
        pos = w * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_size), 2
        )
        s = jnp.where(pos <= cur, s, _NEG).reshape(H, block_size)

        # online softmax: rescale the running sums by exp(m_prev - m_new);
        # masked positions underflow to weight exactly 0.0
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # (H, bs)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.einsum(
            "hgt,thd->hgd", p.reshape(n_kv, g, block_size), v,
            preferred_element_type=jnp.float32,
        ).reshape(H, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(w == W - 1)
    def _flush():
        l = l_ref[...]
        # l == 0 <=> no valid block at all (inactive / all-sentinel row):
        # emit zeros rather than 0/0 NaNs
        out_ref[0] = jnp.where(l > 0.0, acc_ref[...] / jnp.where(l > 0.0, l, 1.0), 0.0)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def paged_attention_kernel_call(
    q: jax.Array,            # (B, H, hd)
    k_new: jax.Array,        # (B, Hkv, hd)
    v_new: jax.Array,        # (B, Hkv, hd)
    k_pool: jax.Array,       # (num_blocks, block_size, Hkv, hd)
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, W) int32, sentinel == num_blocks
    cur_len: jax.Array,      # (B,) int32
    *,
    block_size: int,
    interpret: bool = False,
) -> jax.Array:
    """One decode step of paged GQA: (B, H, hd) f32 attention outputs.

    Table/length *contents* are traced data (scalar-prefetch operands), so
    one compiled program serves every context layout — same discipline as
    the gather path.  The pool operands are read-only: persisting the new
    token is the caller's (cheap, O(B*Hkv*hd)) scatter, free to run in
    parallel with this kernel.
    """
    B, H, hd = q.shape
    num_blocks, bs, n_kv, hd_k = k_pool.shape
    assert bs == block_size, (bs, block_size)
    assert hd == hd_k and H % n_kv == 0, (q.shape, k_pool.shape)
    W = block_table.shape[1]

    # The paged indirection.  A BlockSpec index map always implies a fetch,
    # so a skipped step cannot simply be "not fetched" — the predicate in
    # the kernel body skips the COMPUTE, and this table makes the skip real
    # for the DMA too by re-mapping every invalid step to the row's last
    # valid block at or before it (block 0 before a row's first valid
    # block): Pallas elides the copy when consecutive grid steps map to the
    # same block, so skipped runs issue no extra HBM traffic.
    js = jnp.arange(W, dtype=jnp.int32)[None, :]
    ok = (block_table < num_blocks) & (js * block_size <= cur_len[:, None])
    last = jax.lax.cummax(jnp.where(ok, js, -1), axis=1)
    fetch = jnp.where(
        last >= 0,
        jnp.take_along_axis(block_table, jnp.maximum(last, 0), axis=1),
        0,
    )

    def pool_index(b, w, tbl, lens, fetch):
        return (fetch[b, w], 0, 0, 0)

    def row_index(b, w, tbl, lens, fetch):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((1, H, hd), row_index),
            pl.BlockSpec((1, n_kv, hd), row_index),
            pl.BlockSpec((1, n_kv, hd), row_index),
            pl.BlockSpec((1, block_size, n_kv, hd), pool_index),
            pl.BlockSpec((1, block_size, n_kv, hd), pool_index),
        ],
        out_specs=pl.BlockSpec((1, H, hd), row_index),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, block_size=block_size, num_blocks=num_blocks, n_kv=n_kv, W=W
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        )
    # the launcher's own name, fixed: profiles and the HLO name the custom
    # call after it, whatever jitted code calls this launcher
    return pl.pallas_call(
        kernel,
        name="paged_attention_kernel_call",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(block_table, cur_len, fetch, q, k_new, v_new, k_pool, v_pool)
