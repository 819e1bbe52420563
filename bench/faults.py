"""Faults planted in the timed path, to show that ``correct`` catches them:
each patches the program under test and returns the function that undoes it
(``run.run(..., patch=...)``).

- ``altered_token``: every third decode tick hands the host each row's
  token plus one, as if the token were wrong where it is produced.
- ``state_unchanged``: every decode tick hands back the KV cache it was
  given, so the new tokens' keys and values are never written.
"""
from __future__ import annotations


def altered_token(prog):
    sched = prog.scheduler
    orig = sched._decode_tick_jit
    calls = {"n": 0}

    def broken(**kw):
        cache, toks, last = orig(**kw)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            toks = (toks + 1) % kw["cfg"].vocab_size
        return cache, toks, last

    sched._decode_tick_jit = broken
    return lambda: setattr(sched, "_decode_tick_jit", orig)


def state_unchanged(prog):
    import jax
    import jax.numpy as jnp
    sched = prog.scheduler
    orig = sched._decode_tick_jit

    def broken(**kw):
        # the tick donates its cache operand on the chip: keep a copy
        kept = jax.tree.map(jnp.copy, kw["cache"])
        _, toks, last = orig(**kw)
        return kept, toks, last

    sched._decode_tick_jit = broken
    return lambda: setattr(sched, "_decode_tick_jit", orig)


FAULTS = {"altered_token": altered_token, "state_unchanged": state_unchanged}
