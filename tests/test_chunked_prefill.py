"""Chunked prefill (serve stack PR 10): split a prompt's prefill into
block-table chunks dispatched across successive steps, interleaved with
decode under the existing prefill budgets.

* **exactness**: chunked greedy outputs are bit-identical to the unchunked
  paged oracle (sync + async loops, gather + pallas attention impls, and
  through forced-preemption replay), and to standalone ``generate`` for
  prompts longer than the largest bucket — which only the chunked path can
  admit at all;
* **partial-table invariants**: a block table whose tail entries are still
  sentinels serves reads identically to a truncated context — entries past
  the cursor are invisible whatever they hold — across both attention
  impls, random cursors (block-boundary and mid-block), and chunk ==
  block_size;
* **compiled shapes**: chunk dispatches reuse the one-shot
  (admit width x bucket) program family — zero recompiles after
  ``warmup()``;
* **accounting**: ``prefills`` / ``prefill_tokens`` / ``prefill_chunks``
  charge per-chunk buckets, and ``CompletedRequest.ttft`` samples each
  request's first-token latency exactly once (final chunk, surviving
  preemption).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.serve import ServeSession, generate, scheduler_compile_stats

KEY = jax.random.PRNGKey(0)


def _cfg(**over):
    return dataclasses.replace(
        reduced_config(get_config("granite-3-2b")), remat=False, q_chunk=16,
        **over
    )


_PARAMS = {}


def _params(cfg):
    if cfg.name not in _PARAMS:
        from repro.models.transformer import init_params

        _PARAMS[cfg.name] = init_params(cfg, KEY)
    return _PARAMS[cfg.name]


def _session(cfg, *, chunked=True, **over):
    kw = dict(num_slots=3, max_len=48, prompt_buckets=(4, 8, 16),
              cache_layout="paged", block_size=4)
    if chunked:
        kw.update(chunked_prefill=True, prefill_chunk=4)
    kw.update(over)
    return ServeSession(cfg, _params(cfg), **kw)


def _trace(rng, n, vocab, *, plen=(2, 15), new=(1, 7), rate=1.0):
    out, t = [], 0
    for _ in range(n):
        t += int(rng.poisson(rate))
        out.append((rng.integers(0, vocab, int(rng.integers(*plen))),
                    int(rng.integers(*new)), t))
    return out


# ---------------------------------------------------------------------------
# Fast tier: validation + accounting + model-layer parity pins
# ---------------------------------------------------------------------------


def test_chunked_prefill_validation():
    """Composition gates fail at construction with the reason, in the
    session's established validation style."""
    cfg = _cfg()
    with pytest.raises(ValueError, match="requires chunked_prefill"):
        _session(cfg, chunked=False, prefill_chunk=4)
    with pytest.raises(ValueError, match='cache_layout="paged"'):
        ServeSession(cfg, _params(cfg), chunked_prefill=True)
    with pytest.raises(ValueError, match="prompt buckets"):
        _session(cfg, prefill_chunk=5)       # not in the bucket set
    with pytest.raises(ValueError, match="spec_decode"):
        _session(cfg, spec_decode=True)
    with pytest.raises(ValueError, match="tiers"):
        _session(cfg, tiers=("exact", "approx_lowrank"))
    with pytest.raises(ValueError, match="prefix sharing"):
        _session(cfg, prefix_sharing=True)
    # default chunk = largest bucket; chunking off leaves the old submit cap
    assert _session(cfg, prefill_chunk=None).prefill_chunk == 16
    with pytest.raises(ValueError, match="largest"):
        _session(cfg, chunked=False).submit(np.arange(1, 20), max_new=2)
    # chunked: beyond-bucket prompts admit, only raw context binds
    sess = _session(cfg)
    sess.submit(np.arange(1, 20), max_new=2, req_id=0)
    with pytest.raises(ValueError, match="max_len"):
        sess.submit(np.arange(1, 20), max_new=40, req_id=1)


def test_sentinel_tail_table_reads_as_truncated_context():
    """Property: entries past the cursor's block are invisible — a
    sentinel-tailed table and the same table with its tail aimed at
    garbage-filled real blocks attend bit-identically, for random cursors
    (mid-block and block-boundary / chunk == block_size), under BOTH
    attention impls."""
    from repro.models.attention import init_attn, paged_decode_attention

    cfg = _cfg()
    rng = np.random.default_rng(3)
    d, hq, hkv, hd, bs, w, nb, b = 32, 2, 1, 16, 4, 6, 16, 2
    p = init_attn(jax.random.PRNGKey(1), d, hq, hkv, hd)
    # a one-layer pool, (L, blocks, block_size, Hkv * hd)
    k_blocks = jnp.asarray(rng.standard_normal((1, nb + 1, bs, hkv * hd)),
                           jnp.float32)
    v_blocks = jnp.asarray(rng.standard_normal((1, nb + 1, bs, hkv * hd)),
                           jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, 1, d)), jnp.float32)
    # cursors: mid-block, block boundary (== chunk == block_size), deeper
    for cur in (2, bs, bs + 1, 2 * bs, 3 * bs - 1):
        need = (cur // bs) + 1              # decode writes at position cur
        tail = np.full((b, w), nb, np.int32)
        real = np.full((b, w), nb, np.int32)
        for row in range(b):
            blocks = rng.choice(nb, size=w, replace=False)
            tail[row, :need] = blocks[:need]
            real[row, :] = blocks           # tail aims at garbage blocks
        cur_len = np.full((b,), cur, np.int32)
        outs = {}
        for impl in ("gather", "pallas"):
            for name, table in (("tail", tail), ("real", real)):
                o, (kb, vb) = paged_decode_attention(
                    x, p, k_blocks, v_blocks, jnp.int32(0), jnp.asarray(table),
                    jnp.asarray(cur_len), block_size=bs, n_heads=hq,
                    n_kv=hkv, cfg=cfg.approx, attn_impl=impl,
                )
                outs[impl, name] = np.asarray(o)
                outs[impl, name, "k"] = np.asarray(kb)
            # the property itself is BITWISE per impl: tail contents are
            # invisible, not merely negligible
            assert np.array_equal(outs[impl, "tail"], outs[impl, "real"]), (
                impl, cur)
            assert np.array_equal(outs[impl, "tail", "k"],
                                  outs[impl, "real", "k"]), (impl, cur)
        # across impls the contract is numerical (greedy-token parity is
        # pinned end-to-end by test_chunked_matches_unchunked_oracle)
        assert np.allclose(outs["gather", "tail"], outs["pallas", "tail"],
                           atol=1e-5), cur


def test_chunk_prefill_step_matches_oneshot_and_fused():
    """Model-layer pin: N-chunk ``paged_chunk_prefill_step`` == one-shot
    ``paged_verify_step`` == fused ``forward`` prefill, bitwise — logits
    AND pool contents — for block-boundary and mid-block chunk splits."""
    from repro.models.transformer import (
        forward, init_paged_cache, paged_chunk_prefill_step,
        paged_verify_step,
    )

    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(5)
    b, plen, bs, w, nb = 2, 13, 4, 8, 32
    toks = rng.integers(0, cfg.vocab_size, (b, plen)).astype(np.int32)
    logits_f, _ = forward(cfg, params, {"tokens": jnp.asarray(toks)})
    logits_f = np.asarray(logits_f)

    tables = np.full((b, w), nb, np.int32)
    need = -(-plen // bs)
    for row in range(b):
        tables[row, :need] = np.arange(need) + row * need
    cache = init_paged_cache(cfg, nb, bs, jnp.float32)
    lv, cache_one = paged_verify_step(
        cfg, params, cache, {"tokens": jnp.asarray(toks)},
        jnp.zeros((b,), jnp.int32), jnp.asarray(tables), block_size=bs,
    )
    assert np.array_equal(logits_f, np.asarray(lv))

    for cuts in ((4,), (7,), (4, 8), (5, 6, 11)):   # block-edge + mid-block
        cache = init_paged_cache(cfg, nb, bs, jnp.float32)
        parts, pos = [], 0
        for hi in (*cuts, plen):
            l, cache = paged_chunk_prefill_step(
                cfg, params, cache, {"tokens": jnp.asarray(toks[:, pos:hi])},
                jnp.full((b,), pos, jnp.int32), jnp.asarray(tables),
                block_size=bs,
            )
            parts.append(np.asarray(l))
            pos = hi
        lc = np.concatenate(parts, axis=1)
        assert np.array_equal(logits_f, lc), cuts
        assert np.array_equal(np.asarray(cache_one["k"]),
                              np.asarray(cache["k"])), cuts


def test_per_chunk_accounting_and_ttft():
    """prefills / prefill_tokens / prefill_chunks charge each chunk's own
    bucket; CompletedRequest.ttft matches the stats samples exactly once
    per request."""
    cfg = _cfg()
    sess = _session(cfg, loop="sync", num_slots=2)
    rng = np.random.default_rng(0)
    sess.submit(rng.integers(1, cfg.vocab_size, 10), max_new=3, req_id=0)
    sess.submit(rng.integers(1, cfg.vocab_size, 3), max_new=3, req_id=1)
    res = sess.run(max_steps=500)
    st = sess.stats
    # req 0: chunks 4+4+2 (buckets 4,4,4); req 1: one-shot bucket 4
    assert st.prefill_chunks == 3
    assert st.prefills == {4: 4}
    assert st.prefill_tokens == 16
    assert sorted(st.ttft_ticks) == sorted(r.ttft for r in res.values())
    assert all(r.ttft >= 0 for r in res.values())
    assert len(st.ttft_ticks) == 2


# ---------------------------------------------------------------------------
# Slow tier: end-to-end parity + compiled-shape + bench contracts
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("loop", ["sync", "async"])
@pytest.mark.parametrize("attn_impl", ["gather", "pallas"])
def test_chunked_matches_unchunked_oracle(loop, attn_impl):
    """The tentpole oracle: chunking is a pure scheduling change — same
    trace, bit-identical greedy tokens vs the unchunked paged session,
    under both loops and both attention impls."""
    cfg = _cfg()
    rng = np.random.default_rng(9)
    trace = _trace(rng, 8, cfg.vocab_size)
    outs = {}
    for chunked in (False, True):
        sess = _session(cfg, chunked=chunked, loop=loop,
                        attn_impl=attn_impl, prefill_decode_ratio=2.0)
        ids = [sess.submit(p, max_new=n, arrival=t, req_id=i)
               for i, (p, n, t) in enumerate(trace)]
        res = sess.run(max_steps=5_000)
        assert sess.drained
        outs[chunked] = {i: res[i].tokens.tolist() for i in ids}
        if chunked:
            assert sess.stats.prefill_chunks > 0
    assert outs[False] == outs[True]


@pytest.mark.slow
def test_beyond_bucket_prompt_matches_generate():
    """Prompts longer than the largest bucket — admissible ONLY with
    chunking — decode bit-identically to standalone ``generate``."""
    cfg = _cfg()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (18, 23, 33)]       # all > max bucket 16
    for loop in ("sync", "async"):
        sess = _session(cfg, loop=loop, max_len=48, num_blocks=40)
        for i, p in enumerate(prompts):
            sess.submit(p, max_new=6, req_id=i, arrival=i)
        res = sess.run(max_steps=5_000)
        assert sess.drained
        for i, p in enumerate(prompts):
            alone = np.asarray(
                generate(cfg, _params(cfg), p[None, :], max_new=6)
            )[0, len(p):]
            assert res[i].tokens.tolist() == alone.tolist(), (loop, i)


@pytest.mark.slow
@pytest.mark.parametrize("loop", ["sync", "async"])
def test_chunked_replay_after_forced_preemption(loop):
    """A starved pool forces eviction mid-flight; victims replay their
    (long) prompt + accepted recompute through the CHUNKED path and the
    outputs stay bit-identical to a roomy-pool run."""
    cfg = _cfg()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (14, 13, 11, 6)]
    outs = {}
    for blocks in (40, 9):
        sess = _session(cfg, loop=loop, num_slots=2, num_blocks=blocks,
                        preemption=True)
        for i, p in enumerate(prompts):
            sess.submit(p, max_new=8, req_id=i, arrival=i)
        res = sess.run(max_steps=5_000)
        assert sess.drained
        outs[blocks] = {i: res[i].tokens.tolist() for i in res}
        # ttft sampled exactly once per request even through preemption
        assert len(sess.stats.ttft_ticks) == len(prompts)
    assert outs[40] == outs[9]


@pytest.mark.slow
def test_zero_recompiles_after_warmup():
    """Chunk dispatches stay inside the warmed (admit width x bucket)
    program set — a mixed trace with beyond-bucket prompts and chunked
    replication compiles nothing after ``warmup()``."""
    cfg = _cfg()
    rng = np.random.default_rng(6)
    sess = _session(cfg, loop="async", prefill_decode_ratio=2.0)
    before = sess.warmup()
    assert before["prefill_chunk"] > 0
    for i, (p, n, t) in enumerate(_trace(rng, 8, cfg.vocab_size,
                                         plen=(2, 20))):
        sess.submit(p, max_new=n, arrival=t, req_id=i)
    sess.run(max_steps=5_000)
    assert sess.drained
    assert sess.compile_stats() == before


@pytest.mark.slow
def test_serve_chunked_bench_smoke():
    """The bench harness: a miniature bursty trace must run both arms at
    equal budgets with zero recompiles, zero cross-arm token mismatches, a
    clean generate oracle, and self-describing metric docs (the gap/TTFT
    win criteria are asserted on the real bench config, solo-run — this
    pins the machinery)."""
    import benchmarks.serve_chunked as B

    r = B.bench(short=4, long=3, oracle=2)
    assert r["recompiles_after_warmup"] == 0
    assert r["token_mismatches"] == 0
    assert r["oracle_mismatches"] == 0
    assert r["total_tokens"]["chunked"] == r["total_tokens"]["unchunked"]
    for arm in ("unchunked", "chunked"):
        a = r["arms"][arm]
        assert a["max_decode_gap_ticks"] >= 0
        assert a["short_ttft_p95_ticks"] >= 0
    assert r["arms"]["chunked"]["prefill_chunks"] > 0
    assert set(r["field_docs"])  # embedded metric docs travel with the JSON
