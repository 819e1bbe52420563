"""Serving launcher: batched decoding through the scan engine on the local
mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --batch 4 --new 8 --exec approx_lowrank
    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --engine continuous --requests 16 --num-slots 4
    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --engine continuous --cache-layout paged --block-size 8 \
        --num-slots 8 --num-blocks 64 --policy sjf

``--exec`` selects the execution mode (exact / exact_quant / approx /
approx_lowrank — see ``repro.serve.engine.resolve_execution_mode``);
``--engine legacy`` runs the per-token Python loop baseline for comparison;
``--engine continuous`` serves a mixed-length synthetic trace through the
continuous-batching scheduler (``repro.serve.scheduler``) — slot-striped KV
by default, or the paged block-table cache with ``--cache-layout paged``
(``--num-blocks`` caps KV memory independently of ``--num-slots``;
``--policy`` picks the admission order; ``--attn-impl pallas`` swaps the
per-layer block gather for the in-place Pallas paged-attention kernel).  ``--loop`` selects the host loop
(async double-buffered pipeline by default; ``sync`` is the PR-3 baseline),
and ``--prefill-decode-ratio`` / ``--prefill-token-budget`` rate-limit
admitted prefill tokens against resident decode work so long-prompt bursts
cannot starve active decodes (see docs/serving.md); ``--chunked-prefill``
additionally splits each prompt into ``--prefill-chunk``-wide chunks
interleaved with decode across steps, tightening the decode stall bound
from one prompt bucket to one chunk.  ``--prefix-sharing``
turns on refcounted copy-on-write prefix sharing over the block pool and
``--preemption`` replaces the worst-case block reservation with
oversubscription + evict-and-replay; ``--pad-id`` sets the model's real pad
token for bucketed prefill rows.  ``--tiers`` serves a quality ladder
(comma-separated execution modes, e.g. ``exact,approx_lowrank,approx_msr``):
each synthetic request is routed to a random rung, and the
``--shed-queue-depth`` / ``--shed-gap-ticks`` thresholds arm the
load-adaptive shedder that demotes new admissions down the ladder under
pressure (see docs/serving.md "Quality tiers").
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.serve.scheduler import (
    ADMISSION_POLICIES,
    ATTN_IMPLS,
    CACHE_LAYOUTS,
    SERVE_LOOPS,
)
from repro.serve.engine import (
    EXECUTION_MODES,
    SamplingConfig,
    freeze_params,
    generate,
    greedy_generate_legacy,
    resolve_execution_mode,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--multiplier", default="mul8x8_2")
    ap.add_argument("--exec", dest="exec_mode", default="approx_lowrank",
                    choices=EXECUTION_MODES)
    ap.add_argument("--engine", default="scan", choices=("scan", "legacy", "continuous"))
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--freeze-weights", action="store_true",
                    help="pre-quantize matmul weights to uint8 QWeights")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="continuous engine: decode slot pool size")
    ap.add_argument("--requests", type=int, default=16,
                    help="continuous engine: synthetic trace length")
    ap.add_argument("--max-len", type=int, default=128,
                    help="continuous engine: per-request cache capacity")
    ap.add_argument("--cache-layout", default="slots", choices=CACHE_LAYOUTS,
                    help="continuous engine: per-slot max_len stripes, or a "
                         "paged block-table KV cache")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged layout: KV rows per block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged layout: global block-pool size (default "
                         "matches the slot layout's HBM)")
    ap.add_argument("--attn-impl", default="gather", choices=ATTN_IMPLS,
                    help="paged layout: decode-attention path — the XLA "
                         "block gather (oracle) or the in-place Pallas "
                         "block-pool kernel (interpret mode off-TPU)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="paged layout: refcounted copy-on-write prefix "
                         "sharing — requests whose prompts share leading "
                         "blocks map them to the same physical blocks")
    ap.add_argument("--preemption", action="store_true",
                    help="paged layout: drop the worst-case block "
                         "reservation and oversubscribe the pool; on "
                         "exhaustion the least-important resident request "
                         "is evicted and replayed (bit-identical)")
    ap.add_argument("--pad-id", type=int, default=0,
                    help="continuous engine: pad token id for bucketed "
                         "prefill rows (the model's real pad token)")
    ap.add_argument("--policy", default="priority", choices=ADMISSION_POLICIES,
                    help="continuous engine: admission order")
    ap.add_argument("--loop", default="async", choices=SERVE_LOOPS,
                    help="continuous engine: async double-buffered pipeline "
                         "(default) or the strictly-alternating sync loop")
    ap.add_argument("--prefill-decode-ratio", type=float, default=None,
                    help="continuous engine: admit at most RATIO * resident "
                         "decode tokens of bucketed prefill per step")
    ap.add_argument("--prefill-token-budget", type=int, default=None,
                    help="continuous engine: flat per-step prefill token "
                         "budget (alternative to --prefill-decode-ratio)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="paged layout: split each prompt's prefill into "
                         "--prefill-chunk-wide chunks dispatched across "
                         "successive steps and interleaved with decode "
                         "under the prefill budget — a long prompt then "
                         "stalls decode by at most one chunk, not one "
                         "prompt bucket (outputs stay bit-identical)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: chunk width (must be one of the "
                         "prompt buckets; default: the largest bucket)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="paged layout: self-speculative decoding — each "
                         "tick runs --draft-k steps through the "
                         "approximate draft path, then one exact verify "
                         "pass accepts the longest matching prefix "
                         "(outputs bit-identical to non-speculative)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="spec decode: drafted positions per verify")
    ap.add_argument("--draft-mode", default="approx",
                    choices=EXECUTION_MODES,
                    help="spec decode: the draft path's execution mode "
                         "(the draft multiplier reuses --multiplier; "
                         "'exact' is the every-token-accepts self-test)")
    ap.add_argument("--dynamic-draft-k", action="store_true",
                    help="spec decode: self-tune the draft window down/up "
                         "a warmed --draft-k -> 1 halving ladder around "
                         "the break-even accept rate 1/--draft-cost-ratio")
    ap.add_argument("--draft-cost-ratio", type=float, default=4.0,
                    help="dynamic draft: verify-position cost over "
                         "draft-step cost; its inverse is the break-even "
                         "accept rate")
    ap.add_argument("--draft-window", type=int, default=32,
                    help="dynamic draft: rolling (drafted, accepted) "
                         "chunks judged before each ladder move")
    ap.add_argument("--tiers", default=None,
                    help="continuous engine: comma-separated execution-mode "
                         "quality ladder (best first), e.g. "
                         "'exact,approx_lowrank,approx_msr'; requests are "
                         "routed per-rung with bit-identical per-request "
                         "outputs and zero recompiles after warmup")
    ap.add_argument("--tier-multiplier", default="mul8x8_2",
                    help="tiers: multiplier for approx rungs (MSR rungs "
                         "fall back to mul8x8_msr4 unless an MSR name is "
                         "given)")
    ap.add_argument("--shed-queue-depth", type=int, default=None,
                    help="tiers: demote new admissions one rung when the "
                         "ready queue exceeds this depth")
    ap.add_argument("--shed-gap-ticks", type=int, default=None,
                    help="tiers: demote new admissions one rung when the "
                         "live decode gap exceeds this many work ticks")
    ap.add_argument("--shed-hold-steps", type=int, default=8,
                    help="shedder: consecutive healthy steps before "
                         "restoring one rung")
    ap.add_argument("--shed-restore-fraction", type=float, default=0.5,
                    help="shedder: healthy = load below this fraction of "
                         "the shed thresholds (hysteresis)")
    ap.add_argument("--tp", type=int, default=0,
                    help="continuous engine: tensor-parallel degree — "
                         "serve under a (tp,)-device 'model' mesh with "
                         "params Megatron-split and the paged KV pool "
                         "sharded along the KV-head dim (requires "
                         "--cache-layout paged; on CPU force devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduced_config(cfg), remat=False, q_chunk=64)
    cfg = dataclasses.replace(
        cfg, approx=resolve_execution_mode(args.exec_mode, args.multiplier)
    )
    if not cfg.embed_input:
        raise SystemExit(f"{args.arch} takes embedding inputs (frontend stub); "
                         "use an embed-input arch for token serving")
    from repro.models.transformer import init_params

    params = init_params(cfg, jax.random.PRNGKey(0))
    if args.freeze_weights:
        params = freeze_params(cfg, params)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    sampling = SamplingConfig(
        temperature=args.temperature, top_k=args.top_k, eos_id=args.eos_id
    )
    if args.engine == "legacy" and sampling != SamplingConfig():
        print("warning: --engine legacy is greedy-only; "
              "--temperature/--top-k/--eos-id are ignored")

    if args.engine == "continuous":
        from repro.serve.scheduler import ServeSession

        rng = np.random.default_rng(0)
        # bucket set covers --prompt-len; cache covers the longest request.
        # Preemption replays prompt + accepted tokens through prefill, so
        # the buckets must also cover the longest possible replay prompt —
        # unless chunked prefill is on, which chunks any replay length
        # through the existing buckets and needs no wider top.
        top = args.prompt_len
        if args.preemption and not args.chunked_prefill:
            top = args.prompt_len + args.new - 1
        buckets = [8]
        while buckets[-1] < top:
            buckets.append(buckets[-1] * 2)
        max_len = max(args.max_len, buckets[-1] + args.new)
        if args.cache_layout == "paged" and max_len % args.block_size:
            max_len += args.block_size - max_len % args.block_size
        mesh = None
        if args.tp:
            if args.cache_layout != "paged":
                raise SystemExit("--tp requires --cache-layout paged")
            if args.tp > jax.device_count():
                raise SystemExit(
                    f"--tp {args.tp} > {jax.device_count()} visible devices "
                    "(on CPU: XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={args.tp})"
                )
            mesh = make_mesh((args.tp,), ("model",))
        tiers = None
        if args.tiers:
            tiers = tuple(t.strip() for t in args.tiers.split(",") if t.strip())
        sess = ServeSession(
            cfg, params, num_slots=args.num_slots, max_len=max_len,
            prompt_buckets=tuple(buckets), sampling=sampling,
            cache_layout=args.cache_layout, block_size=args.block_size,
            num_blocks=args.num_blocks, policy=args.policy, loop=args.loop,
            prefill_decode_ratio=args.prefill_decode_ratio,
            prefill_token_budget=args.prefill_token_budget,
            chunked_prefill=args.chunked_prefill,
            prefill_chunk=args.prefill_chunk,
            attn_impl=args.attn_impl, pad_id=args.pad_id,
            prefix_sharing=args.prefix_sharing, preemption=args.preemption,
            spec_decode=args.spec_decode, draft_k=args.draft_k,
            draft_mode=args.draft_mode, draft_multiplier=args.multiplier,
            dynamic_draft_k=args.dynamic_draft_k,
            draft_cost_ratio=args.draft_cost_ratio,
            draft_window=args.draft_window,
            tiers=tiers, tier_multiplier=args.tier_multiplier,
            shed_queue_depth=args.shed_queue_depth,
            shed_gap_ticks=args.shed_gap_ticks,
            shed_hold_steps=args.shed_hold_steps,
            shed_restore_fraction=args.shed_restore_fraction,
            mesh=mesh,
        )
        sess.warmup()
        for _ in range(args.requests):
            plen = int(rng.integers(min(2, args.prompt_len), args.prompt_len + 1))
            prompt = rng.integers(0, cfg.vocab_size, plen)
            lo = min(max(2, args.new // 4), args.new)
            max_new = int(rng.integers(lo, args.new + 1))
            tier = str(rng.choice(tiers)) if tiers is not None else None
            sess.submit(prompt, max_new=max_new, tier=tier)
        t0 = time.perf_counter()
        results = sess.run()
        dt = time.perf_counter() - t0
        generated = sum(len(r.tokens) for r in results.values())
        st = sess.stats
        print(f"[continuous/{args.exec_mode}/{args.cache_layout}/{args.loop}] "
              f"{len(results)} requests, "
              f"{generated} tokens in {dt:.3f}s ({generated/dt:.1f} tok/s, "
              f"post-compile), slot utilization {st.slot_utilization*100:.1f}% "
              f"over {st.ticks} ticks x {args.num_slots} slots")
        print(f"  ttft p50/p95 = {st.ttft_p50:.0f}/{st.ttft_p95:.0f} ticks, "
              f"latency p50/p95 = {st.latency_p50:.0f}/{st.latency_p95:.0f} "
              f"ticks, peak concurrency {st.peak_active}")
        print(f"  host/device overlap {st.overlap_fraction*100:.0f}% of wall, "
              f"decode-gap gauge {st.max_decode_gap_ticks} work ticks, "
              f"prefill stalls {st.prefill_stall_ticks}")
        if args.cache_layout == "paged":
            print(f"  KV pool: {sess.num_blocks} x {args.block_size}-row "
                  f"blocks, peak in use {st.peak_blocks_in_use}, "
                  f"attention impl {st.attn_impl}")
            if args.prefix_sharing or args.preemption:
                print(f"  sharing: {st.prefix_hit_blocks} prefix-hit blocks, "
                      f"{st.cow_forks} CoW forks, "
                      f"{st.preemptions} preemptions")
            if args.tp:
                print(f"  tensor parallel: tp={st.tp} over {st.devices} "
                      f"devices, peak KV "
                      f"{st.peak_block_bytes_per_device/2**20:.2f} MiB/device")
        if tiers is not None:
            served = {t: 0 for t in tiers}
            for r in results.values():
                served[r.tier] = served.get(r.tier, 0) + 1
            print(f"  tiers {','.join(tiers)}: served " +
                  " ".join(f"{t}={n}" for t, n in served.items()) +
                  f", demotions {st.tier_demotions}, "
                  f"restorations {st.tier_restorations}, "
                  f"shed level now {st.shed_level}")
        if args.spec_decode:
            print(f"  spec decode: draft {args.draft_mode}/{args.multiplier} "
                  f"k={args.draft_k}, accept rate {st.accept_rate*100:.1f}% "
                  f"({st.accepted_tokens}/{st.draft_tokens} drafted tokens "
                  f"over {st.verify_calls} verifies)")
            if args.dynamic_draft_k:
                print(f"  dynamic draft: k now {st.draft_k_current} "
                      f"({st.draft_k_shrinks} shrinks, "
                      f"{st.draft_k_grows} grows)")
        first = results[min(results)]
        print("sample:", first.full_sequence.tolist())
        return

    def run():
        if args.engine == "legacy":
            return greedy_generate_legacy(cfg, params, prompt, max_new=args.new)
        return generate(cfg, params, prompt, max_new=args.new, sampling=sampling)

    jax.block_until_ready(run())                 # compile once
    t0 = time.perf_counter()
    out = run()
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"[{args.engine}/{args.exec_mode}] generated {args.batch}x{args.new} tokens "
          f"in {dt:.3f}s ({args.batch*args.new/dt:.1f} tok/s, post-compile)")
    print("sample:", out[0].tolist())


if __name__ == "__main__":
    main()
