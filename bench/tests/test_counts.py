"""Operation and byte counts of the kernels and of the model step at
granite-3-2b's shapes, worked by hand."""
import types

import pytest

import harness
import readers

PEAKS = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
GRANITE = types.SimpleNamespace(num_layers=40, d_model=2048, num_heads=32,
                                num_kv_heads=8, head_dim=64, d_ff=8192,
                                vocab_size=49155)


def test_approx_matmul_decode_call_is_memory_bound():
    k = harness.kernel_costs()["approx_matmul"]
    ops, nbytes = k.ops_bytes(8, 2048, 8192)
    assert ops == 2 * 8 * 2048 * 8192 == 268_435_456
    assert nbytes == 8 * 2048 + 2048 * 8192 + 4 * 8 * 8192 == 17_055_744
    assert k.bound(8, 2048, 8192, PEAKS) == "memory"


def test_approx_matmul_prefill_call_is_compute_bound():
    k = harness.kernel_costs()["approx_matmul"]
    ops, nbytes = k.ops_bytes(8 * 2048, 2048, 8192)
    assert ops == 549_755_813_888
    assert nbytes == 16384 * 2048 + 2048 * 8192 + 4 * 16384 * 8192
    assert k.bound(8 * 2048, 2048, 8192, PEAKS) == "compute"


def test_paged_attention_counts():
    k = harness.kernel_costs()["paged_attention"]
    ops, nbytes = k.ops_bytes(GRANITE, rows=8, ctx=8000)
    assert ops == 4 * 32 * 64 * 8000
    # K and V of 8000 positions, 8 heads of 64, bf16; q bf16 and out f32
    assert nbytes == 2 * 8000 * 8 * 64 * 2 + 8 * 32 * 64 * (2 + 4)


def test_model_flops_per_token():
    n = readers.matmul_params(GRANITE)
    assert n == 40 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192) + 2048 * 49155
    assert n == 2_533_365_760
    # one decode position at context 1000 attends 1000 + 1 positions
    f = readers.model_flops(GRANITE, 1000, 1001)
    assert f == pytest.approx(2 * n + 4 * 40 * 32 * 64 * 1001)
    # a prompt of 3 positions attends 1 + 2 + 3
    assert readers.model_flops(GRANITE, 0, 3) == pytest.approx(
        3 * 2 * n + 4 * 40 * 32 * 64 * 6)
