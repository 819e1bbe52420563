"""The plain reference against the program it checks, at tiny sizes on the
CPU: the same weights from the same seed, the same multiplier, the same
quantized dense layer, and the same logits."""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference as ref
from run import rehearsal

prog = harness.import_program()
sys.path.insert(0, str(harness.REPO / "src"))


def tiny(conf_name):
    conf, _ = rehearsal(harness.load_config(conf_name),
                        harness.load_traffic("chat"))
    return conf, harness.model_config(prog, conf)


def test_mul8x8_2_matches_the_program_lut():
    from repro.core.multipliers import mul8x8_table
    a, b = np.meshgrid(np.arange(256), np.arange(256), indexing="ij")
    np.testing.assert_array_equal(ref.mul8x8_2(a, b), mul8x8_table("mul8x8_2"))


def test_approx_sum_is_the_lut_sum():
    rng = np.random.default_rng(0)
    qa = rng.integers(0, 256, (16, 300)).astype(np.float32)
    qb = rng.integers(0, 256, (300, 24)).astype(np.float32)
    want = ref.mul8x8_2(qa[:, :, None].astype(int), qb[None].astype(int)).sum(1)
    got = np.asarray(ref._approx_sum(jnp.asarray(qa), jnp.asarray(qb)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["granite-3-2b.exact", "granite-3-2b.approx-mul8x8_2"])
def test_weights_are_the_programs(name):
    conf, cfg = tiny(name)
    a = ref.arch_of(conf)
    key = harness.prng_key(2**40 + 17)
    params = jax.jit(lambda k: prog.init_params(cfg, k))(key)
    for i in range(a.layers):
        w = ref._layer_weights(a, key, i)
        lay = jax.tree.map(lambda x: x[i], params["layers"])
        for ours, theirs in (("wq", lay["attn"].wq), ("wk", lay["attn"].wk),
                             ("wv", lay["attn"].wv), ("wo", lay["attn"].wo),
                             ("wg", lay["ffn"].w_gate), ("wu", lay["ffn"].w_up),
                             ("wd", lay["ffn"].w_down)):
            np.testing.assert_array_equal(np.asarray(w[ours], np.float32),
                                          np.asarray(theirs, np.float32))
    np.testing.assert_array_equal(np.asarray(ref._embed(a, key), np.float32),
                                  np.asarray(params["embed"], np.float32))
    np.testing.assert_array_equal(np.asarray(ref._lm_head(a, key), np.float32),
                                  np.asarray(params["lm_head"], np.float32))


def test_approx_dense_is_the_programs_on_one_tensor():
    from repro.core.approx import _approx_dense_frozen, prequantize_tree
    conf, cfg = tiny("granite-3-2b.approx-mul8x8_2")
    a = ref.arch_of(conf)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(128, 256)) / 11, jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(6, 128)), jnp.float32)
    qw = prequantize_tree({"lm_head": w}, cfg.approx)["lm_head"]
    lowrank = dataclasses.replace(cfg.approx, mode="lowrank")
    want = _approx_dense_frozen(x, qw, lowrank)
    got = ref._dense(x, w, jnp.zeros(6, jnp.int32), 6, a, "reference")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
