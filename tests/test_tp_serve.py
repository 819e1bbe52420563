"""Tensor-parallel serving: parity with the single-device paged oracle.

Every end-to-end test runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the forced device
count never leaks into this process.  The contract (ISSUE PR-8): on a
``(tp,)``-device ``"model"`` mesh the greedy tokens are bit-identical to the
no-mesh session, the jit caches see zero recompiles after warmup, and the
per-device KV-pool footprint scales as ``1/tp``.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.kernels.paged_attention import validate_tp_heads

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HARNESS = textwrap.dedent(
    """
    import os
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8").strip()
    import dataclasses, json
    import numpy as np
    import jax
    from repro.configs import get_config, reduced_config
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params
    from repro.serve.scheduler import ServeSession, scheduler_compile_stats

    cfg = dataclasses.replace(
        reduced_config(get_config("granite-3-2b")),
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=512, remat=False, q_chunk=64, dtype="float32",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))

    def trace():
        # an odd count: the last admission is narrower than the first, so a
        # placement that differs between warmup and serving recompiles
        rng = np.random.default_rng(0)
        return [(rng.integers(0, 512, int(rng.integers(4, 14))).astype(np.int32), 8)
                for _ in range(5)]

    def serve(mesh, **kw):
        sess = ServeSession(cfg, params, num_slots=2, max_len=64,
                            prompt_buckets=(16,), cache_layout="paged",
                            block_size=8, num_blocks=32, mesh=mesh, **kw)
        sess.warmup()
        for i, (p, n) in enumerate(trace()):
            sess.submit(p, max_new=n, req_id=i)
        before = sum(scheduler_compile_stats().values())
        res = sess.run()
        rec = sum(scheduler_compile_stats().values()) - before
        return res, rec, sess
    """
)


def _run(script, timeout=420):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=_REPO, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _compare_script(arms_kw: str, tps=(2,)) -> str:
    return _HARNESS + textwrap.dedent(
        f"""
        rows = []
        for kw in {arms_kw}:
            r0, rec0, s0 = serve(None, **kw)
            for tp in {tuple(tps)}:
                mesh = make_mesh((tp,), ("model",))
                r, rec, s = serve(mesh, **kw)
                mm = sum(int(not np.array_equal(r0[i].tokens, r[i].tokens))
                         for i in r0)
                rows.append(dict(
                    tp=tp, kw=repr(kw), recompiles=rec, mismatches=mm,
                    oracle_recompiles=rec0,
                    bytes_dev=s.stats.peak_block_bytes_per_device,
                    bytes_oracle=s0.stats.peak_block_bytes_per_device,
                    ticks=s.stats.ticks, oracle_ticks=s0.stats.ticks,
                    stats_tp=s.stats.tp, stats_devices=s.stats.devices,
                ))
        print(json.dumps(rows))
        """
    )


def _check(rows):
    for r in rows:
        ctx = r["kw"] + f" tp={r['tp']}"
        assert r["mismatches"] == 0, f"token mismatch under mesh: {ctx}"
        assert r["recompiles"] == 0, f"recompiles after warmup: {ctx}"
        assert r["oracle_recompiles"] == 0, ctx
        # tick-for-tick schedule parity: same trace, same tick count
        assert r["ticks"] == r["oracle_ticks"], ctx
        # the paged pool footprint shards exactly 1/tp per device
        assert r["bytes_dev"] * r["tp"] == r["bytes_oracle"], ctx
        assert r["stats_tp"] == r["tp"] and r["stats_devices"] == r["tp"], ctx


def test_tp2_parity_dense_subprocess():
    """Fast tier-1 gate: tp=2, dense attention, greedy decode."""
    _check(_run(_compare_script("[{}]", tps=(2,))))


@pytest.mark.slow
def test_tp_parity_matrix_subprocess():
    """tp in {2, 4} x {dense, pallas shard_map, exact spec decode}."""
    arms = ("[{}, {'attn_impl': 'pallas'}, "
            "{'spec_decode': True, 'draft_k': 2, 'draft_mode': 'exact'}]")
    _check(_run(_compare_script(arms, tps=(2, 4)), timeout=600))


def test_validate_tp_heads():
    validate_tp_heads(8, 4, 2)          # 4 q / 2 kv heads per shard
    validate_tp_heads(4, 4, 4)          # MHA, one head per shard
    with pytest.raises(ValueError):
        validate_tp_heads(8, 4, 0)      # degenerate tp
    with pytest.raises(ValueError):
        validate_tp_heads(8, 4, 3)      # heads not divisible
    with pytest.raises(ValueError):
        validate_tp_heads(8, 2, 4)      # kv heads not divisible
    with pytest.raises(ValueError):
        validate_tp_heads(12, 8, 4)     # per-shard GQA ratio fractional


def test_mesh_ctor_validation():
    """Mesh plumbing rejects unsupported layouts without needing >1 device."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config, reduced_config
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params
    from repro.serve.scheduler import ServeSession

    cfg = dataclasses.replace(
        reduced_config(get_config("granite-3-2b")),
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
        d_ff=64, vocab_size=128, remat=False, q_chunk=32, dtype="float32",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="paged"):
        ServeSession(cfg, params, num_slots=2, max_len=32, prompt_buckets=(8,),
                     cache_layout="slots", mesh=mesh)
    with pytest.raises(ValueError, match="model"):
        ServeSession(cfg, params, num_slots=2, max_len=32, prompt_buckets=(8,),
                     cache_layout="paged", block_size=8, num_blocks=16,
                     mesh=mesh, tp_axis="tp")
    # tp=1 mesh is a degenerate but valid configuration
    sess = ServeSession(cfg, params, num_slots=2, max_len=32,
                        prompt_buckets=(8,), cache_layout="paged",
                        block_size=8, num_blocks=16, mesh=mesh)
    sess.warmup()
    sess.submit(np.arange(4, dtype=np.int32), max_new=4)
    res = sess.run()
    assert len(res[0].tokens) == 4
    assert sess.stats.tp == 1 and sess.stats.devices == 1
