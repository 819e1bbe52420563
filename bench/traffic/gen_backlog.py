"""Backlog: a queue that is never empty. Requests carry no due time; the
client keeps ``backlog_per_slot`` x slots of them waiting.

Where the mix sets ``order_seed``, the request sizes are drawn in one fixed
order from it, the same for every run; the run's seed then draws only the
token ids. Over a window of a few dozen requests the order otherwise moves
the throughput more than the system does.
"""
from __future__ import annotations

import numpy as np

import draws


def stream(mix: dict, rng, vocab: int, rate=None):
    """Endless ``(None, prompt, max_new)``."""
    order = (np.random.default_rng(mix["order_seed"]) if "order_seed" in mix
             else rng)
    plens = draws.lengths(mix["prompt_tokens"], order, mix["stratum"])
    outs = draws.lengths(mix["output_tokens"], order, mix["stratum"])
    while True:
        yield None, rng.integers(0, vocab, next(plens), dtype="int32"), next(outs)
