"""What the serving program marks for a profiler.

* Device: the model's layers run under ``jax.named_scope`` — ``embed``,
  ``norm``, ``attention``, ``kv_write``, ``dense``, ``mlp`` and ``sample`` —
  so each compiled HLO instruction's ``op_name`` names its layer.  Checked on
  the compiled decode tick (paged, exact and approx, both attention impls)
  and on the paged admit program.
* Host: ``ServeSession.step`` opens one ``serve.step`` span per call, with
  ``serve.admit``, ``serve.dispatch``, ``serve.harvest_wait`` and
  ``serve.accept`` nested inside it and never overlapping one another.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.transformer import init_paged_cache, init_params
from repro.serve import (
    SamplingConfig,
    ServeSession,
    freeze_params,
    resolve_execution_mode,
)
from repro.serve import scheduler as S

SCOPES = ("embed", "norm", "attention", "kv_write", "dense", "mlp", "sample")
CHILD_SPANS = ("serve.admit", "serve.dispatch", "serve.harvest_wait",
               "serve.accept")
N, BS, NUM_BLOCKS, W = 3, 4, 24, 8


def _cfg(mode):
    return dataclasses.replace(
        reduced_config(get_config("granite-3-2b")), remat=False, q_chunk=16,
        approx=resolve_execution_mode(mode),
    )


def _params(cfg):
    return freeze_params(cfg, init_params(cfg, jax.random.PRNGKey(0)))


def _scopes(hlo_text: str) -> set:
    """The innermost program scope of every instruction's ``op_name``; a
    scope under a transformation reads ``vmap(sample)`` there."""
    out = set()
    for op in re.findall(r'op_name="([^"]*)"', hlo_text):
        for part in reversed(op.split("/")):
            while part.endswith(")") and "(" in part:
                part = part[part.index("(") + 1:-1]
            if part in SCOPES:
                out.add(part)
                break
    return out


@pytest.mark.parametrize("attn_impl", ["gather", "pallas"])
@pytest.mark.parametrize("mode", ["exact", "approx"])
def test_decode_tick_carries_layer_scopes(mode, attn_impl):
    cfg = _cfg(mode)
    tick = jax.jit(S._decode_tick, static_argnames=(
        "cfg", "sampling", "steps", "block_size", "attn_impl"))
    text = tick.lower(
        cfg, _params(cfg), init_paged_cache(cfg, NUM_BLOCKS, BS, jnp.float32),
        jnp.zeros((N,), jnp.int32), jnp.full((N,), 5, jnp.int32),
        jnp.ones((N,), bool), jnp.zeros((N, 2), jnp.uint32),
        jnp.zeros((N, W), jnp.int32), sampling=SamplingConfig(), steps=2,
        block_size=BS, attn_impl=attn_impl,
    ).compile().as_text()
    assert _scopes(text) == set(SCOPES)


def test_admit_program_carries_layer_scopes():
    cfg = _cfg("exact")
    A, Sb = 2, 8
    admit = jax.jit(S._admit_fused_paged,
                    static_argnames=("cfg", "sampling", "block_size"))
    text = admit.lower(
        cfg, _params(cfg), init_paged_cache(cfg, NUM_BLOCKS, BS, jnp.float32),
        jnp.zeros((A, Sb), jnp.int32), jnp.full((A,), 5, jnp.int32),
        jnp.zeros((A, Sb // BS), jnp.int32), jnp.arange(A, dtype=jnp.int32),
        jnp.zeros((2,), jnp.uint32), sampling=SamplingConfig(), block_size=BS,
    ).compile().as_text()
    assert _scopes(text) == set(SCOPES)


def _program_spans(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append((int(e.start_ns), int(e.end_ns), e.name,
                                  dict(e.stats)))
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def _parent(span, spans):
    """The innermost span strictly enclosing ``span``, or None."""
    best = None
    for other in spans:
        if other is span:
            continue
        if other[0] <= span[0] and span[1] <= other[1]:
            if best is None or other[0] >= best[0]:
                best = other
    return best


@pytest.mark.parametrize("loop", ["async", "sync"])
def test_step_spans_nest_inside_one_serve_step(loop, tmp_path):
    cfg = _cfg("exact")
    sess = ServeSession(cfg, _params(cfg), num_slots=N, max_len=32,
                        prompt_buckets=(4, 8), cache_layout="paged",
                        block_size=BS, loop=loop)
    sess.warmup()
    rng = np.random.default_rng(3)
    for i in range(5):
        sess.submit(rng.integers(0, cfg.vocab_size, int(rng.integers(2, 8))),
                    max_new=int(rng.integers(2, 6)), req_id=i)
    calls, clocks = 0, []
    block0 = sess.stats.host_block_s
    with jax.profiler.trace(str(tmp_path)):
        while not sess.drained:
            clocks.append(sess.clock)
            sess.step()
            calls += 1
    blocked = sess.stats.host_block_s - block0
    spans = _program_spans(str(tmp_path))

    steps = [s for s in spans if s[2] == "serve.step"]
    assert len(steps) == calls
    assert [s[3]["clock"] for s in steps] == clocks
    children = [s for s in spans if s[2] != "serve.step"]
    assert {s[2] for s in children} == set(CHILD_SPANS)
    parents = {}
    for s in children:
        parent = _parent(s, spans)
        assert parent is not None and parent[2] in ("serve.step", "serve.admit")
        # every child lies inside one serve.step
        assert any(r[0] <= s[0] and s[1] <= r[1] for r in steps)
        parents.setdefault(parent[:3], []).append(s)
    for group in [steps] + list(parents.values()):
        group = sorted(group, key=lambda s: s[:2])
        for a, b in zip(group, group[1:]):
            assert a[1] <= b[0], (a[2], b[2])      # siblings never overlap
    # the wait span lies inside the host_block_s timer
    wait = sum(s[1] - s[0] for s in spans if s[2] == "serve.harvest_wait")
    assert 0 < wait * 1e-9 <= blocked
