"""paged_attention: one decode step of GQA for every row of the batch, over
each row's blocks of the paged bf16 KV pool, one layer per call.

Work: 4 H hd x the positions each row attends (QK^T and PV), against the
bf16 peak. Bytes: the K and V of the valid positions (the new token's
included), the bf16 queries and the f32 output. The positions come from the
client's record of each step's decode rows, not from the trace: a call's
operands are the whole pool, of which it reads only the rows' blocks.

A call is an operation event named after the kernel's jitted launcher,
``%paged_attention_kernel_call.N = f32[B,H,hd]{...} custom-call(...)``.
"""
from __future__ import annotations

NAME = "paged_attention_kernel_call"


def matches(event) -> bool:
    return event.name.startswith("%" + NAME)


def ops_bytes(cfg, rows: int, ctx: int) -> tuple:
    """One layer's call: ``rows`` decode rows attending ``ctx`` positions in
    all (the new tokens included)."""
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ops = 4 * H * hd * ctx
    nbytes = 2 * ctx * Hkv * hd * 2 + rows * H * hd * (2 + 4)
    return ops, nbytes


def least_seconds(rec, events, peaks):
    from readers import traced_steps
    steps = traced_steps(rec)
    if not steps:
        return None
    total = 0.0
    for s in steps:
        if not s["rows"]:
            continue
        ops, nbytes = ops_bytes(rec.cfg, s["rows"], s["ctx"])
        total += rec.cfg.num_layers * max(ops / peaks["bf16_flops_per_s"],
                                          nbytes / peaks["hbm_bytes_per_s"])
    return total
