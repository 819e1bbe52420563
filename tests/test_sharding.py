"""Sharding rules + a small-mesh dry-run executed in a subprocess (so the
forced device count never leaks into this test process)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import param_pspec, prune_pspec


class _FakeMesh:
    """Minimal stand-in so rule logic is testable without real devices."""

    def __init__(self, shape):
        self.shape = shape

    @property
    def axis_names(self):
        return tuple(self.shape)


def test_param_rules_dense():
    cfg = get_config("granite-3-2b")
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert param_pspec("['layers']['attn'].wq", (40, 2048, 2048), cfg, mesh) == P(None, "data", "model")
    assert param_pspec("['layers']['attn'].wo", (40, 2048, 2048), cfg, mesh) == P(None, "model", "data")
    assert param_pspec("['layers']['ffn'].w_down", (40, 8192, 2048), cfg, mesh) == P(None, "model", "data")
    assert param_pspec("['lm_head']", (2048, 49664), cfg, mesh) == P("data", "model")
    assert param_pspec("['embed']", (49155, 2048), cfg, mesh) == P(None, "data")  # 49155 % 16 != 0
    assert param_pspec("['layers']['ln1']", (40, 2048), cfg, mesh) == P()


def test_param_rules_moe_ep_vs_tp():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # qwen: 60 experts (not divisible by 16) -> expert-TP fallback
    cfg = get_config("qwen2-moe-a2.7b")
    spec = param_pspec("['layers']['moe'].w_gate", (24, 60, 2048, 1408), cfg, mesh)
    assert spec == P(None, None, "data", "model")
    # synthetic 64-expert variant -> EP engages
    import dataclasses

    cfg64 = dataclasses.replace(cfg, moe_experts=64)
    spec = param_pspec("['layers']['moe'].w_gate", (24, 64, 2048, 1408), cfg64, mesh)
    assert spec == P(None, "model", "data", None)


def test_prune_pspec_divisibility():
    mesh = make_mesh((1,), ("data",))
    assert prune_pspec(mesh, P("data"), (7,)) == P(None) or prune_pspec(
        mesh, P("data"), (7,)
    ) == P("data")  # axis size 1 always divides


def test_small_mesh_dryrun_subprocess(tmp_path):
    """End-to-end: lower + compile a reduced arch on a forced 8-device mesh
    (2 data x 4 model), proving the sharding rules produce a compilable
    SPMD program — the same code path the production dry-run uses."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json, sys
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced_config, SHAPES
        from repro.core.approx import ApproxConfig
        from repro.launch.dryrun import build_lowerable
        from repro.launch.mesh import make_mesh
        from repro.train import optim as O

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(
            reduced_config(get_config("granite-3-2b")),
            approx=ApproxConfig(mode="lowrank"), q_chunk=32,
        )
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=4)
        with jax.set_mesh(mesh):
            jfn, args = build_lowerable(cfg, shape, mesh, O.OptConfig(), microbatch=1)
            compiled = jfn.lower(*args).compile()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        has_coll = any(op in hlo for op in ("all-reduce", "all-gather", "reduce-scatter"))
        print(json.dumps({"ok": True, "collectives": has_coll,
                          "temp": int(mem.temp_size_in_bytes)}))
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["collectives"]


def test_constrain_and_cache_pspecs_subprocess():
    """``constrain`` / ``cache_pspecs`` semantics on a real forced 8-device
    mesh: divisibility fallback, missing-axis drop, ``"batch"`` resolution to
    the (pod, data) pair, and the paged-pool head-dim sharding."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import init_paged_cache
        from repro.parallel.sharding import cache_pspecs, constrain

        def spec_of(x):
            return tuple(x.sharding.spec) if isinstance(
                x.sharding, NamedSharding) else None

        out = {}
        f = jax.jit(lambda x: constrain(x, ("batch", None, "model")))

        # no mesh context: constrain is a no-op, jit still compiles
        out["no_mesh"] = spec_of(f(jnp.zeros((8, 4, 8)))) is None

        # pure-TP mesh: no pod/data axes -> "batch" drops; "model" applies
        with jax.set_mesh(make_mesh((4,), ("model",))):
            y = f(jnp.zeros((8, 4, 8)))
            out["tp_only"] = spec_of(y) == (None, None, "model")
            # divisibility fallback: 6 % 4 != 0 -> trailing axis dropped
            # (fully replicated normalizes to the empty spec)
            z = f(jnp.zeros((8, 4, 6)))
            out["indivisible"] = spec_of(z) in ((), (None, None, None))

        # pod x data x model mesh: "batch" -> ("pod", "data")
        with jax.set_mesh(make_mesh((2, 2, 2), ("pod", "data", "model"))):
            y = f(jnp.zeros((8, 4, 8)))
            out["batch_pair"] = spec_of(y) == (("pod", "data"), None, "model")

        # paged cache_pspecs: k/v (L, blocks, block_size, Hkv*hd) shard
        # their head-major last dim over "model"; block tables / metadata
        # and the block dim stay replicated
        cfg = dataclasses.replace(
            reduced_config(get_config("granite-3-2b")),
            num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
        )
        cache = init_paged_cache(cfg, num_blocks=16, block_size=8)
        mesh = make_mesh((4,), ("model",))
        sh = cache_pspecs(cfg, mesh, cache, layout="paged")
        out["paged_kv"] = tuple(sh["k"].spec) == (None, None, None, "model")
        out["paged_v"] = tuple(sh["v"].spec) == (None, None, None, "model")

        # Hkv not divisible by tp -> replicate rather than mis-shard
        cfg3 = dataclasses.replace(cfg, num_heads=3, num_kv_heads=3)
        cache3 = init_paged_cache(cfg3, num_blocks=16, block_size=8)
        sh3 = cache_pspecs(cfg3, mesh, cache3, layout="paged")
        out["paged_fallback"] = all(
            ax is None for ax in sh3["k"].spec) and all(
            ax is None for ax in sh3["v"].spec)

        # Hkv = 2 does not divide tp = 4 while the merged width 2 x 256 =
        # 512 does: sharding 512 lanes four ways would split heads, so the
        # pool replicates
        cfg2 = dataclasses.replace(cfg, num_heads=2, num_kv_heads=2, head_dim=256)
        cache2 = init_paged_cache(cfg2, num_blocks=16, block_size=8)
        out["merged_512"] = cache2["k"].shape[-1] == 512
        sh2 = cache_pspecs(cfg2, mesh, cache2, layout="paged")
        out["paged_split_heads_refused"] = all(
            ax is None for ax in sh2["k"].spec) and all(
            ax is None for ax in sh2["v"].spec)

        # the scheduler's output pin agrees with cache_pspecs in both cases
        from repro.serve.scheduler import _pin_pool
        with jax.set_mesh(mesh):
            for name, c, n_kv, want in (
                ("pin_kv", cache, cfg.num_kv_heads, "model"),
                ("pin_split_heads_refused", cache2, cfg2.num_kv_heads, None),
            ):
                pinned = jax.jit(lambda t: _pin_pool(t, n_kv))(c)
                specs = [spec_of(pinned[kv]) or () for kv in ("k", "v")]
                out[name] = all(
                    sp == (None, None, None, "model") if want
                    else all(ax is None for ax in sp) for sp in specs)
        print(json.dumps(out))
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(res.values()), {k: v for k, v in res.items() if not v}
