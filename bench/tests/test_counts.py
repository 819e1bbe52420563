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


@pytest.mark.parametrize("name", ["granite-3-2b.exact",
                                  "granite-3-2b.approx-mul8x8_2"])
def test_model_flops_per_token(name):
    conf = harness.load_config(name)
    ref = harness.reference_module(conf)
    n = ref.matmul_params(conf)
    assert n == 40 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192) + 2048 * 49155
    assert n == 2_533_365_760
    # one decode position at context 1000 attends 1000 + 1 positions
    assert ref.model_flops(conf, 1000, 1001) == 2 * n + 4 * 40 * 32 * 64 * 1001 \
        == 5_394_739_200
    # a prompt of 3 positions attends 1 + 2 + 3
    assert ref.model_flops(conf, 0, 3) == 3 * 2 * n + 4 * 40 * 32 * 64 * 6 \
        == 15_202_160_640
    # mfu counts the prompt admitted in the window and the output tokens
    # delivered there after the first: positions 0-2, then 3 and 4
    rec = types.SimpleNamespace(
        conf=conf, reference=ref, peaks={"bf16_flops_per_s": 100.0},
        t_open=0.0, t_close=1.0,
        reqs={0: {"admit": 0.5, "plen": 3, "times": [0.6, 0.7, 0.8]},
              1: {"admit": -1.0, "plen": 9, "times": [-0.5, 1.5]}})
    assert readers.mfu(rec) == 15_202_160_640 + 5_068_042_240 + 5_068_369_920 \
        == 25_338_572_800
