"""Open loop: requests due at Poisson arrival times at the cell's fixed rate,
sent whether or not earlier ones have been answered.

Where the mix sets ``order_seed``, the arrival gaps and the request sizes
are drawn in one fixed order from it, the same for every run; the run's
seed then draws only the token ids. A tail over a window of a few dozen
requests otherwise follows the order more than the system.
"""
from __future__ import annotations

import numpy as np

import draws


def stream(mix: dict, rng, vocab: int, rate: float):
    """Endless ``(due_s, prompt, max_new)``, ``due_s`` from the stream's start."""
    order = (np.random.default_rng(mix["order_seed"]) if "order_seed" in mix
             else rng)
    gaps = draws.poisson_gaps(rate, order, mix["stratum"])
    plens = draws.lengths(mix["prompt_tokens"], order, mix["stratum"])
    outs = draws.lengths(mix["output_tokens"], order, mix["stratum"])
    t = 0.0
    while True:
        t += next(gaps)
        yield t, rng.integers(0, vocab, next(plens), dtype="int32"), next(outs)
