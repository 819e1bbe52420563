"""Sharding rules: parameter/batch/cache PartitionSpecs for the production
mesh (DP over pod x data, TP/EP over model, FSDP parameter sharding over
data, SP fallback for long sequences / few KV heads).

Rules are path-based over the param pytree and divisibility-checked against
the actual mesh: a dim is only sharded if its size divides the axis product
(GSPMD would pad otherwise; for *parameters* we keep shards exact so that
checkpoints reshard cleanly across cluster sizes — elastic restore).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import batch_axes

__all__ = [
    "param_pspec",
    "param_shardings",
    "batch_pspecs",
    "cache_pspecs",
    "constrain",
    "mesh_axis_size",
    "current_mesh",
]


def current_mesh() -> Optional[AbstractMesh]:
    """The mesh installed by ``with jax.set_mesh(mesh):`` (None outside)."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axis_size(name: str) -> int:
    m = current_mesh()
    return int(m.shape[name]) if m is not None and name in m.axis_names else 1


def constrain(x: jax.Array, axes: Tuple[Any, ...]) -> jax.Array:
    """with_sharding_constraint that degrades gracefully: no mesh -> no-op;
    per-dim axis entries are dropped when missing from the mesh or when the
    dim size does not divide the axis size. ``"batch"`` resolves to the DP
    axes ``("pod", "data")`` present in the mesh."""
    m = current_mesh()
    if m is None:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes):
        if ax is None:
            spec.append(None)
            continue
        names = tuple(a for a in ("pod", "data") if a in m.axis_names) if ax == "batch" \
            else tuple(a for a in (ax if isinstance(ax, tuple) else (ax,)) if a in m.axis_names)
        size = int(np.prod([m.shape[a] for a in names])) if names else 1
        if names and dim % size == 0:
            spec.append(names if len(names) > 1 else names[0])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))

# (regex on path, (dim -> axis name) from the END of the shape)
# axis names: "fsdp" -> data, "tp" -> model; resolved per-mesh.
_RULES: Tuple[Tuple[str, Dict[int, str]], ...] = (
    # attention / dense projections: (…, d_in, d_out)
    (r"\.wq$|\.wk$|\.wv$|w_gate$|w_up$|shared_gate$|shared_up$", {-2: "fsdp", -1: "tp"}),
    (r"\.wo$|w_down$|shared_down$", {-2: "tp", -1: "fsdp"}),
    (r"router$|shared_router$", {-2: "fsdp"}),
    # embeddings / head
    (r"^\['embed'\]$", {-2: "tp", -1: "fsdp"}),
    (r"^\['lm_head'\]$", {-2: "fsdp", -1: "tp"}),
    # mamba
    (r"\.in_proj$|\.x_proj$", {-2: "fsdp", -1: "tp"}),
    (r"\.out_proj$", {-2: "tp", -1: "fsdp"}),
    (r"\.dt_proj$", {-1: "tp"}),
    (r"\.conv_w$|\.conv_b$|\.a_log$|\.d_skip$|\.dt_bias$|\.norm_g$", {-1: "tp"}),
    # everything else (norm scales, biases): replicated
)

_MOE_EP_RULES: Tuple[Tuple[str, Dict[int, str]], ...] = (
    # expert-parallel: experts dim over model axis
    (r"\['moe'\]\.w_gate$|\['moe'\]\.w_up$", {-3: "tp", -2: "fsdp"}),
    (r"\['moe'\]\.w_down$", {-3: "tp", -1: "fsdp"}),
)


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    return int(mesh.shape[name]) if name in mesh.axis_names else 1


def param_pspec(
    path: str,
    shape: Tuple[int, ...],
    cfg: ModelConfig,
    mesh: Mesh,
) -> P:
    # frozen QWeight leaves: codes shard like the original weight; the small
    # per-channel scale/zero-point/col-sum tensors replicate
    if path.endswith((".scale", ".zero_point", ".col_sum")):
        return P()
    if path.endswith(".codes"):
        path = path[: -len(".codes")]

    fsdp_ax = "data" if "data" in mesh.axis_names else None
    tp_ax = "model" if "model" in mesh.axis_names else None
    alias = {"fsdp": fsdp_ax, "tp": tp_ax}

    rules = _RULES
    if cfg.family == "moe" and cfg.moe_experts % _axis_size(mesh, tp_ax) == 0:
        rules = _MOE_EP_RULES + _RULES   # EP when experts divide the TP axis

    for pat, dims in rules:
        if re.search(pat, path):
            spec = [None] * len(shape)
            for rel, ax_alias in dims.items():
                ax = alias[ax_alias]
                idx = len(shape) + rel
                if ax is None or idx < 0:
                    continue
                if shape[idx] % mesh.shape[ax] == 0:
                    spec[idx] = ax
            # never shard the stacked-layer leading axis
            return P(*spec)
    return P()


def param_shardings(cfg: ModelConfig, params_shape: Any, mesh: Mesh) -> Any:
    """Map a params pytree (arrays or ShapeDtypeStructs) -> NamedShardings."""

    def one(path, leaf):
        spec = param_pspec(jax.tree_util.keystr(path), leaf.shape, cfg, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, kind: str) -> Dict[str, P]:
    """PartitionSpecs for input batches by shape kind."""
    b = P(batch_axes(mesh))
    specs: Dict[str, P] = {}
    if cfg.embed_input:
        specs["tokens"] = b
    else:
        specs["embeddings"] = b
    if kind == "train":
        specs["labels"] = b
    if cfg.pos_embedding == "m_rope":
        specs["positions_thw"] = b
    if kind == "decode":
        specs["cur_len"] = b
    return specs


def prune_pspec(mesh: Mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """Drop per-dim axes whose size does not divide the dim (e.g. batch=1
    for long_500k): jit in_shardings require exact divisibility.  Trailing
    replicated dims are dropped too — the form jit gives its outputs — so
    an array placed here and a program output of the same layout share one
    jit cache key (``P(..., "model", None) != P(..., "model")``)."""
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        names = tuple(a for a in names if a in mesh.axis_names)
        size = int(np.prod([mesh.shape[a] for a in names])) if names else 1
        if names and dim % size == 0:
            out.append(names if len(names) > 1 else names[0])
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def safe_sharding(mesh: Mesh, spec: P, leaf) -> NamedSharding:
    return NamedSharding(mesh, prune_pspec(mesh, spec, leaf.shape))


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, cache_shape: Any, *, layout: str = "slots") -> Any:
    """Decode-cache shardings.

    ``layout="slots"`` (per-slot stripes, leaves ``(L, B, S, Hkv, hd)``):
    batch over DP axes; KV heads over model when divisible, otherwise
    sequence-parallel (SP) over model.

    ``layout="paged"`` (block pool, leaves ``(L, num_blocks, block_size,
    Hkv * hd)``, head-major in the last dim): block *contents* shard the
    last dim over model when ``Hkv`` divides it — each shard holds
    ``Hkv/tp`` whole heads of every block, so the host-global block tables
    index all shards identically. Divisibility is checked on ``Hkv``, not
    on the merged width: a width that divides while ``Hkv`` does not would
    split heads, so such a pool simply replicates. The block dim is never
    sharded (tables are host state) and there is no SP fallback: splitting
    ``block_size`` would partition the softmax *within* single blocks.
    """
    if layout not in ("slots", "paged"):
        raise ValueError(f"cache_pspecs: unknown layout {layout!r}")
    dp = batch_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None
    tp_size = _axis_size(mesh, tp)

    def paged_one(path, leaf):
        ks = jax.tree_util.keystr(path)
        shape = leaf.shape
        spec = [None] * len(shape)
        if (ks.endswith("['k']") or ks.endswith("['v']")) and tp and cfg.num_kv_heads % tp_size == 0:
            spec[3] = tp
        return NamedSharding(mesh, prune_pspec(mesh, P(*spec), shape))

    if layout == "paged":
        return jax.tree_util.tree_map_with_path(paged_one, cache_shape)

    def one(path, leaf):
        ks = jax.tree_util.keystr(path)
        shape = leaf.shape
        spec = [None] * len(shape)
        spec[1] = dp  # (L_or_groups, B, ...)
        if ks.endswith("['k']") or ks.endswith("['v']"):
            # (L, B, S, Hkv, hd)
            if tp and shape[3] % tp_size == 0:
                spec[3] = tp
            elif tp and shape[2] % tp_size == 0:
                spec[2] = tp          # SP over cache length
        elif ks.endswith("['ssm']"):
            # mamba1 (L,B,di,N) / mamba2 (L,B,nh,hd,N)
            if tp and shape[2] % tp_size == 0:
                spec[2] = tp
        elif ks.endswith("['conv']"):
            if tp and shape[3] % tp_size == 0:
                spec[3] = tp
        return NamedSharding(mesh, prune_pspec(mesh, P(*spec), shape))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


# Optimizer-state shardings mirror parameter shardings structurally
# ({"m": params-like, "v": params-like, "step": scalar}); constructed in
# train/optim.py::opt_state_shardings.
