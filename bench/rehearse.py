"""Compile a cell's serving programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --config granite-3-2b.exact \
        --num-slots 32 --bucket 2048

Lowers the widest admit program (``num_slots`` rows x the bucket) and the
decode tick of ``ServeSession`` at the configuration's real shapes, on one
chip of a described ``v5e:2x2``, and prints ``memory_analysis()`` of each
with the resident bytes of the weights and the KV pool. Nothing runs, so
nothing here is a measurement of time; it says whether the programs fit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def _bytes(tree) -> int:
    import jax
    import numpy as np
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--num-slots", type=int, required=True)
    ap.add_argument("--bucket", type=int, default=0, help="default: largest")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    conf = harness.load_config(args.config)
    conf["session"]["num_slots"] = args.num_slots
    prog = harness.import_program()
    cfg = harness.model_config(prog, conf)
    sess_kw = conf["session"]
    bucket = args.bucket or max(sess_kw["prompt_buckets"])
    bs = sess_kw["block_size"]
    N = sess_kw["num_slots"]
    W = sess_kw["max_len"] // bs
    num_blocks = N * W

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def spec(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev), tree)

    params = jax.eval_shape(lambda k: harness.make_weights(prog, cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: prog.init_paged_cache(cfg, num_blocks, bs, jnp.bfloat16))
    sched = prog.scheduler
    sampling = prog.SamplingConfig(eos_id=-1)
    out = {"config": args.config, "num_slots": N, "bucket": bucket,
           "weights_bytes": _bytes(params), "pool_bytes": _bytes(cache)}

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

    admit = jax.jit(sched._admit_fused_paged,
                    static_argnames=("cfg", "sampling", "block_size"),
                    donate_argnames=("cache",))
    c = admit.lower(
        cfg=cfg, params=spec(params), cache=spec(cache),
        prompts=i32(N, bucket), prompt_lens=i32(N),
        block_ids=i32(N, -(-bucket // bs)), req_ids=i32(N),
        base_key=jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev),
        sampling=sampling, block_size=bs).compile()
    out["admit"] = _mem(c)
    tick = jax.jit(sched._decode_tick,
                   static_argnames=("cfg", "sampling", "steps", "block_size",
                                    "attn_impl"),
                   donate_argnames=("cache",))
    c = tick.lower(
        cfg=cfg, params=spec(params), cache=spec(cache),
        last_token=i32(N), cur_len=i32(N),
        active=jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=dev),
        slot_keys=jax.ShapeDtypeStruct((N, 2), jnp.uint32, sharding=dev),
        tables=i32(N, W), sampling=sampling, steps=1, block_size=bs,
        attn_impl=sess_kw["attn_impl"]).compile()
    out["decode_tick"] = _mem(c)
    print(json.dumps(out), flush=True)
    return 0


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}


if __name__ == "__main__":
    sys.exit(main())
