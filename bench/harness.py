"""What every cell shares: finding its files by name, and the program under test.

A cell (``cells/<cell>.json``) names a configuration (``configs/<config>.json``)
and a traffic mix (``traffic/<mix>.json``); a mix names its generator by its
``kind`` (``traffic/gen_<kind>.py``); a per-layer metric is a reader
``metrics/<metric>.py``; a kernel's cost is ``kernels/<kernel>.py``. Adding any
of them is adding a file: nothing here lists them.

A configuration may name its plain reference, ``"reference": "<name>"``:
``references/<name>.py`` (default: ``reference.py``, the dense pre-norm GQA
decoder). That module describes the architecture to the harness, so that an
architecture other than the dense decoder joins by adding files:

- ``served_logits(conf, key, requests, T, S, precision)``: the reference's
  logits ``(n, vocab)`` float32 at every served position of ``requests =
  [(prompt, served_tokens), ...]`` and the ``n`` tokens served there, within
  a budget of ``T`` positions and ``S`` served tokens; ``precision`` is
  ``"reference"`` or ``"control"`` (the next lower precision).
- ``model_flops(conf, start, stop)``: the forward FLOPs of positions
  ``start..stop-1`` of one request, which ``mfu`` counts.
- ``REHEARSAL``: the self-tests' tiny sizes (``run.rehearsal``), architecture
  keys and an ``as_run`` to merge.
- ``ARCH_KEYS``: its own architecture keys, each to the program's
  ``ModelConfig`` attribute, checked beside this module's ``ARCH_KEYS``.
- ``SCOPES``: the names of the program's ``jax.named_scope``s that its own
  layers run under, read in a traced run beside ``program_trace.SCOPES``, so
  that a reader ``metrics/<metric>.py`` can take a new layer's device time by
  its scope, in any program.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import types

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
OUT = BENCH / ".out"          # traces and scratch output, never committed


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = BENCH) -> dict:
    return load_json(root / "cells" / f"{name}.json")


def load_config(name: str, root: pathlib.Path = BENCH) -> dict:
    return load_json(root / "configs" / f"{name}.json")


def load_traffic(name: str, root: pathlib.Path = BENCH) -> dict:
    return load_json(root / "traffic" / f"{name}.json")


def load_module(path: pathlib.Path) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(mix: dict, root: pathlib.Path = BENCH) -> types.ModuleType:
    return load_module(root / "traffic" / f"gen_{mix['kind']}.py")


def metric_readers(names, root: pathlib.Path = BENCH) -> dict:
    return {n: load_module(root / "metrics" / f"{n}.py") for n in names}


def kernel_costs(root: pathlib.Path = BENCH) -> dict:
    return {p.stem: load_module(p) for p in sorted((root / "kernels").glob("*.py"))}


_REFERENCES = {}


def reference_module(conf: dict, root: pathlib.Path = BENCH) -> types.ModuleType:
    """The configuration's plain reference, loaded once per process so that
    its compiled programs are kept between runs."""
    path = (root / "references" / f"{conf['reference']}.py"
            if "reference" in conf else root / "reference.py")
    if path not in _REFERENCES:
        _REFERENCES[path] = load_module(path)
    return _REFERENCES[path]


def benchmark(root: pathlib.Path = BENCH) -> dict:
    return load_json(root.parent / "BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics a cell reports: its end-to-end ones, or with ``trace`` its
    per-layer ones (those whose ``workloads`` name it, or that move one of
    its end-to-end metrics when they list none)."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def seed_key_parts(seed: int) -> tuple:
    """A seed of any size as two 32-bit words (low, high)."""
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def prng_key(seed: int):
    import jax
    lo, hi = seed_key_parts(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def import_program() -> types.SimpleNamespace:
    """The system under test: the serving session and what builds its model."""
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.transformer import init_paged_cache, init_params
    from repro.serve import scheduler
    from repro.serve.engine import (SamplingConfig, freeze_params,
                                    resolve_execution_mode)
    return types.SimpleNamespace(
        get_config=get_config, enable_compile_cache=enable_compile_cache,
        init_params=init_params, init_paged_cache=init_paged_cache,
        scheduler=scheduler, ServeSession=scheduler.ServeSession,
        compile_stats=scheduler.scheduler_compile_stats,
        SamplingConfig=SamplingConfig, freeze_params=freeze_params,
        resolve_execution_mode=resolve_execution_mode)


# the configuration file's architecture keys, and the program's names for them
ARCH_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
}


def arch_keys(conf: dict, root: pathlib.Path = BENCH) -> dict:
    """``ARCH_KEYS`` and the configuration's reference module's own."""
    return {**ARCH_KEYS, **reference_module(conf, root).ARCH_KEYS}


def model_config(prog, conf: dict, root: pathlib.Path = BENCH):
    """The program's model config for a configuration file, checked against
    the file's architecture so that the two cannot drift apart."""
    cfg = dataclasses.replace(
        prog.get_config(conf["model"]), param_dtype=conf["param_dtype"],
        approx=prog.resolve_execution_mode(conf["mode"], conf["multiplier"]))
    if conf.get("program_overrides"):
        cfg = dataclasses.replace(cfg, **conf["program_overrides"])
    for ours, theirs in arch_keys(conf, root).items():
        if getattr(cfg, theirs) != conf[ours]:
            raise ValueError(f"{conf['model']}: the program has {theirs}="
                             f"{getattr(cfg, theirs)}, the configuration file "
                             f"{ours}={conf[ours]}")
    return cfg


def make_weights(prog, cfg, key):
    """Weights from the key in the type they are served in: bf16, or frozen
    uint8 codes when the mode quantizes (call under one ``jax.jit``)."""
    return prog.freeze_params(cfg, prog.init_params(cfg, key))
