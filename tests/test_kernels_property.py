"""Property-based differential tests for the Pallas kernel families.

* ``approx_matmul_pallas`` must be bit-exact to the ``mul8x8_table`` LUT
  oracle on EVERY shape, not just the hand-picked ones in test_kernels.py;
* ``paged_attention_pallas`` (the paged decode-attention kernel) must match
  its pure-JAX exact-softmax oracle ``paged_attention_ref`` to f32 roundoff
  on random block-table layouts — sentinel-padded rows, sentinel holes,
  off-boundary and past-table ``cur_len``, GQA ``Hkv < n_heads``.

Runs through ``_hypothesis_compat``: real ``hypothesis`` when installed,
otherwise a deterministic seeded fallback with the same assertions.

approx-matmul coverage axes:
* random M/N/K including odd / prime / non-multiple-of-block sizes;
* leading batch dimensions on the lhs (1 and 2 extra dims);
* EVERY registered multiplier family — aggregated (exact + mul8x8_1/2/3,
  low-rank indicator corrections), truncation (pkm/etm, generic "lut"-kind
  corrections), and the MSR fixed-shift family (mul8x8_msr2/4/6) — all
  route through the same fused kernel decomposition;
* pruned operand ranges (the paper's co-optimized (0,31) bands).

Marked ``slow``: each example runs interpret-mode kernel work; CI runs
these in the second-tier job under ``REPRO_FORCE_INTERPRET=1``.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import multipliers as M
from repro.kernels.approx_matmul.ops import approx_matmul_pallas, select_blocks
from repro.kernels.approx_matmul.ref import approx_matmul_ref
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_attention_ref,
)

pytestmark = pytest.mark.slow

# Every registered multiplier runs through the Pallas kernel: aggregated
# designs via the low-rank indicator corrections, pkm/etm/MSR via the
# generic per-bit "lut"-kind corrections (both exact by construction).
KERNEL_MULTIPLIERS = M.MULTIPLIERS


def _codes(rng: np.random.Generator, shape, high: int):
    return jnp.asarray(rng.integers(0, high + 1, shape), jnp.uint8)


def _seed(*parts) -> int:
    """Deterministic example seed from ints/registry names — NOT Python
    hash(), whose per-process str randomization would make a failing
    counterexample irreproducible."""
    acc = 0
    for p in parts:
        acc = (acc * 1_000_003 + (M.MULTIPLIERS.index(p) if isinstance(p, str) else int(p))) % 2**32
    return acc


def _check(a, b, name: str):
    lut = jnp.asarray(M.mul8x8_table(name))
    ref = np.asarray(approx_matmul_ref(a, b, lut))
    out = np.asarray(approx_matmul_pallas(a, b, multiplier=name))
    assert out.shape == ref.shape
    assert np.array_equal(ref, out), (name, a.shape, b.shape)


def test_kernel_multiplier_registry_is_exhaustive():
    """EVERY registered multiplier builds a correction whose reconstructed
    error table equals exact - LUT entrywise — the kernel decomposition's
    exactness precondition, with no ref-only escape hatch left."""
    from repro.core import lowrank as lr

    assert set(KERNEL_MULTIPLIERS) == set(M.MULTIPLIERS)
    exact = M.exact_table(8, 8).astype(np.int64)
    for name in M.MULTIPLIERS:
        for side in ("lhs", "rhs"):
            corr = lr.build_correction(name, side=side)
            err = exact - M.mul8x8_table(name).astype(np.int64)
            assert np.array_equal(corr.error_table(), err), (name, side)


@settings(max_examples=20, deadline=None)
@given(
    st.integers(1, 40),                      # M
    st.integers(1, 40),                      # N
    st.integers(1, 70),                      # K
    st.sampled_from(KERNEL_MULTIPLIERS),
    st.integers(0, 2**31 - 1),               # data seed
)
def test_pallas_matches_lut_oracle_random_shapes(m, n, k, name, seed):
    rng = np.random.default_rng(seed)
    _check(_codes(rng, (m, k), 255), _codes(rng, (k, n), 255), name)


@settings(max_examples=10, deadline=None)
@given(
    st.integers(1, 3),                       # leading batch dim
    st.integers(1, 3),                       # second batch dim (1 == absent)
    st.integers(1, 12),                      # M
    st.integers(1, 24),                      # N
    st.integers(1, 48),                      # K
    st.sampled_from(KERNEL_MULTIPLIERS),
)
def test_pallas_matches_lut_oracle_leading_batch_dims(b1, b2, m, n, k, name):
    rng = np.random.default_rng(_seed(b1, b2, m, n, k, name))
    shape = (b1, m, k) if b2 == 1 else (b1, b2, m, k)
    _check(_codes(rng, shape, 255), _codes(rng, (k, n), 255), name)


@settings(max_examples=10, deadline=None)
@given(
    st.integers(1, 16),
    st.integers(1, 16),
    st.integers(1, 64),
    st.sampled_from(KERNEL_MULTIPLIERS),
    st.sampled_from([31, 63, 255]),          # pruned operand bands
    st.sampled_from([31, 255]),
)
def test_pallas_matches_lut_oracle_pruned_ranges(m, n, k, name, amax, wmax):
    """Range-pruned calls (lhs_max/rhs_max drop correction features) must
    stay exact on the restricted domain — the co-optimized band profile."""
    rng = np.random.default_rng(_seed(m, n, k, name, amax, wmax))
    a = _codes(rng, (m, k), amax)
    b = _codes(rng, (k, n), wmax)
    lut = jnp.asarray(M.mul8x8_table(name))
    ref = np.asarray(approx_matmul_ref(a, b, lut))
    out = np.asarray(
        approx_matmul_pallas(a, b, multiplier=name, lhs_max=amax, rhs_max=wmax)
    )
    assert np.array_equal(ref, out), (name, m, n, k, amax, wmax)


@settings(max_examples=15, deadline=None)
@given(
    st.integers(1, 300),
    st.integers(1, 300),
    st.integers(1, 600),
    st.integers(0, 2**31 - 1),
)
def test_select_blocks_invariants(m, n, k, seed):
    """Structural invariants of the block-shrink logic for ANY problem:
    blocks divide the padded dims, padding never loses data, sublane/lane
    minima hold, and blocks never exceed the requested maxima."""
    (bm_, bn_, bk_), (mp, np_, kp) = select_blocks(m, n, k)
    assert mp % bm_ == 0 and np_ % bn_ == 0 and kp % bk_ == 0
    assert mp >= m and np_ >= n and kp >= k
    assert bm_ % 8 == 0 and bn_ % 128 == 0 and bk_ % 128 == 0
    assert bm_ <= 128 and bn_ <= 128 and bk_ <= 256
    # padding is tight: strictly less than one block of waste
    assert mp - m < bm_ and np_ - n < bn_ and kp - k < bk_


# ---------------------------------------------------------------------------
# Paged decode-attention kernel vs the pure-JAX oracle
# ---------------------------------------------------------------------------


def _paged_case(rng, B, W, bs, n_kv, g, hd, *, holes=False, n_layers=1, layer=0):
    """Random paged decode-attention inputs: each row holds a random number
    of distinct blocks (possibly zero — an inactive all-sentinel row), its
    ``cur_len`` lands anywhere in the last allocated block (including offset
    0, the fresh-boundary case), and with ``holes`` an allocated middle
    block is knocked back to the sentinel — the predicate-skip case the
    clamp-gather path never sees.  The pools are whole, ``(n_layers,
    num_blocks, bs, Hkv * hd)``, and ``layer`` is the one attended."""
    H = n_kv * g
    num_blocks = B * W + 1                       # at least one spare block
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, n_kv, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, n_kv, hd)), jnp.float32)
    pool = (n_layers, num_blocks, bs, n_kv * hd)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    tbl = np.full((B, W), num_blocks, np.int32)
    cur = np.zeros((B,), np.int32)
    free = list(rng.permutation(num_blocks))
    for b in range(B):
        n_alloc = int(rng.integers(0, W + 1))
        tbl[b, :n_alloc] = [free.pop() for _ in range(n_alloc)]
        if n_alloc:
            cur[b] = int(rng.integers((n_alloc - 1) * bs, n_alloc * bs))
            if holes and n_alloc > 1:
                tbl[b, int(rng.integers(0, n_alloc - 1))] = num_blocks
        else:
            cur[b] = int(rng.integers(0, W * bs))   # inactive row
    return q, kn, vn, kp, vp, jnp.asarray(tbl), jnp.asarray(cur), jnp.int32(layer)


def _check_paged(args, bs):
    out = np.asarray(paged_attention_pallas(*args, block_size=bs))
    ref = np.asarray(paged_attention_ref(*args, block_size=bs))
    assert out.shape == ref.shape
    # online vs fused softmax reorders the f32 sums: roundoff, not bitwise
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    st.integers(1, 4),                       # B
    st.integers(1, 4),                       # W (table width)
    st.sampled_from([1, 2, 4, 8]),           # block_size
    st.integers(1, 2),                       # Hkv
    st.integers(1, 3),                       # GQA group (H = Hkv * g)
    st.sampled_from([4, 16]),                # head_dim
    st.integers(1, 3),                       # pool layers
    st.integers(0, 2**31 - 1),               # data seed
)
def test_paged_attention_matches_ref_random_tables(B, W, bs, n_kv, g, hd, n_layers, seed):
    rng = np.random.default_rng(seed)
    layer = seed % n_layers
    _check_paged(
        _paged_case(rng, B, W, bs, n_kv, g, hd, n_layers=n_layers, layer=layer), bs
    )


@pytest.mark.parametrize("layer", [1, 2])
def test_paged_attention_reads_only_its_layer(layer):
    """The kernel attends pool layer ``layer`` and no other: every other
    layer holds NaN, which any read of it would carry into the output, and
    the result equals the oracle over a one-layer pool holding just that
    layer."""
    rng = np.random.default_rng(10 + layer)
    q, kn, vn, kp, vp, tbl, cur, lyr = _paged_case(
        rng, 3, 3, 4, 2, 2, 8, n_layers=3, layer=layer
    )
    others = np.arange(3) != layer
    kp_nan = jnp.where(others[:, None, None, None], jnp.nan, kp)
    vp_nan = jnp.where(others[:, None, None, None], jnp.nan, vp)
    out = np.asarray(paged_attention_pallas(
        q, kn, vn, kp_nan, vp_nan, tbl, cur, lyr, block_size=4))
    assert np.isfinite(out).all()
    ref = np.asarray(paged_attention_ref(
        q, kn, vn, kp[layer:layer + 1], vp[layer:layer + 1], tbl, cur, 0,
        block_size=4))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(
    st.integers(2, 4),                       # B
    st.integers(2, 4),                       # W
    st.sampled_from([2, 4]),                 # block_size
    st.integers(1, 3),                       # GQA group
    st.integers(0, 2**31 - 1),
)
def test_paged_attention_skips_sentinel_holes(B, W, bs, g, seed):
    """Sentinel entries BELOW cur_len (never produced by the scheduler, but
    exactly what the kernel's predicate-skip must handle): both kernel and
    oracle must exclude those positions entirely."""
    rng = np.random.default_rng(seed)
    _check_paged(_paged_case(rng, B, W, bs, 2, g, 8, holes=True), bs)


def test_paged_attention_inactive_rows_are_exact_zero():
    """All-sentinel rows (empty decode slots) flush exactly 0.0 — no NaNs
    from the 0/0 normalizer, no garbage from the clamped DMA."""
    rng = np.random.default_rng(0)
    q, kn, vn, kp, vp, tbl, cur, lyr = _paged_case(rng, 3, 2, 4, 2, 2, 8)
    tbl = jnp.full_like(tbl, kp.shape[1])    # every row inactive
    out = np.asarray(
        paged_attention_pallas(q, kn, vn, kp, vp, tbl, cur, lyr, block_size=4))
    assert np.array_equal(out, np.zeros_like(out))


def test_paged_attention_past_table_cur_len():
    """Overshoot rows (cur_len beyond the table, the scheduler's discarded
    garbage regime) still produce finite outputs that agree with the
    oracle: the fused append simply never lands."""
    rng = np.random.default_rng(1)
    q, kn, vn, kp, vp, tbl, cur, lyr = _paged_case(rng, 2, 2, 4, 1, 2, 8)
    cur = jnp.asarray([2 * 4 + 3, 2 * 4], jnp.int32)    # both past the table
    args = (q, kn, vn, kp, vp, tbl, cur, lyr)
    _check_paged(args, 4)
    assert np.isfinite(np.asarray(paged_attention_pallas(*args, block_size=4))).all()


def test_paged_attention_ops_validation():
    """Shape mistakes fail loudly in the wrapper, not deep in pallas."""
    rng = np.random.default_rng(0)
    q, kn, vn, kp, vp, tbl, cur, lyr = _paged_case(rng, 2, 2, 4, 2, 2, 8)
    with pytest.raises(ValueError, match="block_size"):
        paged_attention_pallas(q, kn, vn, kp, vp, tbl, cur, lyr, block_size=8)
    with pytest.raises(ValueError, match="new-token"):
        paged_attention_pallas(q, kn[:1], vn, kp, vp, tbl, cur, lyr, block_size=4)
    with pytest.raises(ValueError, match="batch"):
        paged_attention_pallas(q, kn, vn, kp, vp, tbl[:1], cur, lyr, block_size=4)
    with pytest.raises(ValueError, match="incompatible"):
        paged_attention_pallas(q[:, :3], kn, vn, kp, vp, tbl, cur, lyr, block_size=4)
    # a per-layer pool (the layout before the whole pool) is refused
    with pytest.raises(ValueError, match="pool must be"):
        paged_attention_pallas(q, kn, vn, kp[0], vp[0], tbl, cur, lyr, block_size=4)
