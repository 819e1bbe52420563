"""Compile the serving path's Pallas kernels for a described TPU v5e at
granite-3-2b's published widths (d 2048, d_ff 8192, 32/8 heads of dim 64).

Nothing runs: the TPU compiler lowers each kernel for a chip that is
described, not attached, so Mosaic refusals (unaligned tiles, non-scalar
SMEM reads in an index map, VMEM overruns) fail here instead of on the chip.
The topology is described only inside the module fixture — only one process
may hold the TPU library, so no import, ``skipif`` or ``parametrize`` may
touch it.  Every compile runs in this process with the persistent
compilation cache switched off: a program compiled for a described chip
cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.approx_matmul.ops import approx_matmul_pallas
from repro.kernels.paged_attention.kernel import paged_attention_kernel_call


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(2048, 8192), (8192, 2048)])
def test_approx_matmul_compiles_for_v5e(one_chip, no_compile_cache, m, k, n):
    fn = jax.jit(lambda a, b: approx_matmul_pallas(
        a, b, multiplier="mul8x8_2", interpret=False))
    compiled = fn.lower(
        _spec((m, k), jnp.uint8, one_chip), _spec((k, n), jnp.uint8, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_paged_attention_compiles_for_v5e(one_chip, no_compile_cache, dtype):
    """The kernel over a whole (L, blocks, block_size, Hkv*hd) pool, with
    the layer attended as a scalar-prefetch operand."""
    B, H, n_kv, hd, bs, num_blocks, W, L = 8, 32, 8, 64, 16, 512, 34, 3
    fn = jax.jit(lambda *a: paged_attention_kernel_call(
        *a, block_size=bs, interpret=False))
    compiled = fn.lower(
        _spec((B, H, hd), dtype, one_chip),
        _spec((B, n_kv, hd), dtype, one_chip),
        _spec((B, n_kv, hd), dtype, one_chip),
        _spec((L, num_blocks, bs, n_kv * hd), dtype, one_chip),
        _spec((L, num_blocks, bs, n_kv * hd), dtype, one_chip),
        _spec((B, W), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _custom_calls(compiled_text):
    """(instruction name, op_name) of every TPU custom call."""
    return [(m.group(1), m.group(2)) for m in re.finditer(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', compiled_text)]


def test_kernel_names_survive_the_callers_scopes(one_chip, no_compile_cache):
    """Profiles name each kernel's operation after its launcher, inside a
    model scope and under another jitted caller alike: the instruction is
    ``%approx_matmul_kernel_call.N`` or ``%paged_attention_kernel_call.N``,
    and its ``op_name`` carries the caller's scope."""
    B, H, n_kv, hd, bs, num_blocks, W, L = 8, 32, 8, 64, 16, 64, 4, 2

    def caller(a, b, q, kn, vn, kp, vp, tbl, cl, layer):
        with jax.named_scope("dense"):
            y = approx_matmul_pallas(a, b, multiplier="mul8x8_2",
                                     interpret=False)
        with jax.named_scope("attention"):
            o = paged_attention_kernel_call(q, kn, vn, kp, vp, tbl, cl, layer,
                                            block_size=bs, interpret=False)
        return y, o

    text = jax.jit(caller).lower(
        _spec((8, 2048), jnp.uint8, one_chip),
        _spec((2048, 2048), jnp.uint8, one_chip),
        _spec((B, H, hd), jnp.bfloat16, one_chip),
        _spec((B, n_kv, hd), jnp.bfloat16, one_chip),
        _spec((B, n_kv, hd), jnp.bfloat16, one_chip),
        _spec((L, num_blocks, bs, n_kv * hd), jnp.bfloat16, one_chip),
        _spec((L, num_blocks, bs, n_kv * hd), jnp.bfloat16, one_chip),
        _spec((B, W), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    ).compile().as_text()
    calls = _custom_calls(text)
    names = sorted(n.rsplit(".", 1)[0] for n, _ in calls)
    assert names == ["approx_matmul_kernel_call", "paged_attention_kernel_call"]
    for name, op_name in calls:
        scope = "dense" if name.startswith("approx") else "attention"
        assert f"/{scope}/" in op_name, (name, op_name)


# an optimized-HLO instruction: name, result dtype and dims, opcode
_INSTR = re.compile(r"%([\w.-]+) = \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(")
_MOVES = re.compile(r"copy|dynamic-slice|dynamic-update-slice")


def test_decode_tick_updates_the_pool_in_place(one_chip, no_compile_cache, monkeypatch):
    """The serving decode tick at granite's widths (cut to 2 layers; 8
    slots, ``max_len`` 2560, blocks of 16, the real Pallas kernel, the
    cache donated) moves no pool: no copy, slice or update-slice whose
    result is one layer of the pool or the whole pool, fused or not, and
    the donated pool aliases the output whole.  A layer loop that slices
    the pool per layer, or a pool layout XLA relays, fails here."""
    import dataclasses

    import repro.kernels.interpret as kernels_interpret
    from repro.configs import get_config
    from repro.models.transformer import init_paged_cache, init_params
    from repro.serve import scheduler
    from repro.serve.engine import SamplingConfig

    monkeypatch.setattr(kernels_interpret, "default_interpret", lambda: False)
    cfg = dataclasses.replace(
        get_config("granite-3-2b"), num_layers=2, param_dtype="bfloat16")
    N, max_len, bs = 8, 2560, 16
    W = max_len // bs
    NB = N * W

    def shapes(tree):
        return jax.tree.map(
            lambda s: _spec(s.shape, s.dtype, one_chip), jax.eval_shape(tree))

    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: init_paged_cache(cfg, NB, bs, jnp.bfloat16))
    tick = jax.jit(
        scheduler._decode_tick,
        static_argnames=("cfg", "sampling", "steps", "block_size", "attn_impl"),
        donate_argnames=("cache",),
    )
    compiled = tick.lower(
        cfg=cfg, params=params, cache=cache,
        last_token=_spec((N,), jnp.int32, one_chip),
        cur_len=_spec((N,), jnp.int32, one_chip),
        active=_spec((N,), jnp.bool_, one_chip),
        slot_keys=_spec((N, 2), jnp.uint32, one_chip),
        tables=_spec((N, W), jnp.int32, one_chip),
        sampling=SamplingConfig(eos_id=-1), steps=1, block_size=bs,
        attn_impl="pallas",
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text                 # the kernel, not the interpreter
    moves = []
    for name, dims, op in _INSTR.findall(text):
        d = [int(x) for x in dims.split(",") if x]
        pool_sized = len(d) >= 2 and d[0] in (1, cfg.num_layers) and d[1] == NB
        if pool_sized and (_MOVES.search(op) or (op == "fusion" and _MOVES.search(name))):
            moves.append((name, dims, op))
    assert not moves, moves
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
