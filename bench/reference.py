"""Plain reference of the served model, for the check that decides ``correct``.

It imports nothing of the program and takes nothing the program made: it
draws the same weights from the seed itself (the program's initializer,
written out here: truncated normal on [-2, 2] over sqrt(fan_in), rounded to
the configuration's bf16), and computes the decoder in float32 at
``Precision.HIGHEST`` - pre-RMSNorm GQA attention with RoPE, a SwiGLU FFN, a
final RMSNorm and a separate lm_head - one layer at a time.

``approx`` configurations compute every projection and the lm_head as the
mode defines it: weights frozen to uint8 codes per output channel (their
scale worked out in the weights' bf16), activations quantized to uint8 codes
with one scale per tensor, the code products summed under the mul8x8_2
approximate multiplier, and the zero points taken out. A tensor is what one
request alone would send: its whole prompt, or one decode position.

The mul8x8_2 multiplier splits each 8-bit operand into pieces lo = x[2:0],
mid = x[5:3], hi = x[7:6] and adds the nine piece products at their shifts;
every 3x3 piece product goes through MUL3x3_2 (Lu et al., ISCAS 2022, Table
III), which differs from the exact product only where both pieces are 5, 6
or 7. So ``sum_k approx(a_k, b_k)`` is ``A @ B`` plus, for each lhs piece i
and value u in {5, 6, 7}, the indicator ``[A_i == u]`` times the weight-side
table ``2^(s_i + s_j) * E[u, B_j]`` summed over rhs pieces j. Every operand
of those dots is exact in bf16 and every product exact in f32.

Several requests are packed into one sequence of fixed length with segment
ids (one compiled shape per cell); attention never crosses a segment.

This is the reference of every configuration that names no other
(``harness.reference_module``): ``served_logits``, ``model_flops``,
``REHEARSAL``, ``ARCH_KEYS`` and ``SCOPES`` are the interface the harness
reads.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

# the self-tests' tiny sizes: a four-layer decoder of width 128
REHEARSAL = {
    "num_hidden_layers": 4, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
    "vocab_size": 512, "as_run": {"padded_vocab_size": 512}}
# no architecture keys beyond ``harness.ARCH_KEYS``
ARCH_KEYS = {}
# no program scopes beyond ``program_trace.SCOPES``
SCOPES = ()

# MUL3x3_2 minus the exact product, at the only entries where they differ
# (Table III, with (7,6) read from its output bits as 46)
_MUL3X3_2_ERR = {(5, 7): -8, (7, 5): -8, (6, 6): 4, (6, 7): 4, (7, 6): 4,
                 (7, 7): -4}
_PIECES = ((0, 3), (3, 3))          # (shift, bits) of lo and mid; hi is exact


def mul8x8_2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The approximate product, elementwise, from its definition (int64)."""
    a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
    out = a * b
    for si, _ in _PIECES:
        for sj, _ in _PIECES:
            pa, pb = (a >> si) & 7, (b >> sj) & 7
            for (u, v), e in _MUL3X3_2_ERR.items():
                out = out + ((pa == u) & (pb == v)) * (e << (si + sj))
    return out


class Arch(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    padded_vocab: int
    rope_theta: float
    eps: float
    approx: bool
    qmax: int


def arch_of(conf: dict) -> Arch:
    return Arch(conf["num_hidden_layers"], conf["hidden_size"],
                conf["num_attention_heads"], conf["num_key_value_heads"],
                conf["head_dim"], conf["intermediate_size"],
                conf["vocab_size"], conf["as_run"]["padded_vocab_size"],
                float(conf["rope_theta"]), float(conf["as_run"]["rms_norm_eps"]),
                conf["mode"] == "approx", 255)


# -- weights -------------------------------------------------------------------


def _draw(key, shape, fan_in):
    std = 1.0 / np.sqrt(fan_in)
    w = std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return w.astype(jnp.bfloat16)


def _layer_weights(a: Arch, key, i):
    lk = jax.random.split(jax.random.split(key, 4)[0], a.layers)[i]
    k_attn, k_ffn, _, _ = jax.random.split(lk, 4)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    kg, ku, kd = jax.random.split(k_ffn, 3)
    hq, hk = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return {"wq": _draw(kq, (a.d, hq), a.d), "wk": _draw(kk, (a.d, hk), a.d),
            "wv": _draw(kv, (a.d, hk), a.d), "wo": _draw(ko, (hq, a.d), hq),
            "wg": _draw(kg, (a.d, a.ff), a.d), "wu": _draw(ku, (a.d, a.ff), a.d),
            "wd": _draw(kd, (a.ff, a.d), a.ff)}


def _embed(a: Arch, key):
    return _draw(jax.random.split(key, 4)[1], (a.vocab, a.d), a.vocab)


def _lm_head(a: Arch, key):
    return _draw(jax.random.split(key, 4)[2], (a.d, a.padded_vocab), a.d)


# -- quantized dense -----------------------------------------------------------


def _freeze(w, qmax):
    """Per-output-channel uint8 affine codes of a bf16 weight, the scale
    worked out in bf16 as the weight's own type."""
    lo = jnp.minimum(jnp.min(w, axis=0, keepdims=True), 0.0)
    hi = jnp.maximum(jnp.max(w, axis=0, keepdims=True), 0.0)
    scale = jnp.maximum((hi - lo) / float(qmax), 1e-8).astype(jnp.float32)
    zp = jnp.clip(jnp.round(-lo / scale), 0, qmax)
    codes = jnp.clip(jnp.round(w / scale) + zp, 0, qmax)
    return codes, scale, zp


def _group_affine(x, groups, n_groups, qmax):
    """One min/max scale per group of rows (a group is one tensor)."""
    lo = jax.ops.segment_min(jnp.min(x, axis=1), groups, n_groups)
    hi = jax.ops.segment_max(jnp.max(x, axis=1), groups, n_groups)
    lo, hi = jnp.minimum(lo, 0.0)[groups], jnp.maximum(hi, 0.0)[groups]
    scale = jnp.maximum((hi - lo) / float(qmax), 1e-8)[:, None]
    zp = jnp.clip(jnp.round(-lo[:, None] / scale), 0, qmax)
    return scale, zp


def _bdot(a, b):
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _approx_sum(qa, qb):
    """sum_k mul8x8_2(qa[m, k], qb[k, n]) for integer-valued f32 codes."""
    ia, ib = qa.astype(jnp.int32), qb.astype(jnp.int32)
    out = _bdot(qa, qb)
    for si, _ in _PIECES:
        pa = (ia >> si) & 7
        for u in (5, 6, 7):
            ind = (pa == u).astype(jnp.float32)
            for sj, _ in _PIECES:
                pb = (ib >> sj) & 7
                tab = sum(jnp.where(pb == v, float(e << (si + sj)), 0.0)
                          for (uu, v), e in _MUL3X3_2_ERR.items() if uu == u)
                out = out + _bdot(ind, tab)
    return out


def _dense(x, w, groups, n_groups, a: Arch, precision: str):
    """x (T, K) f32 @ w (K, N) bf16 in the reference's arithmetic, or for
    ``approx`` in the control's (uint4 codes, exact products)."""
    w = w.astype(jnp.float32)
    if not a.approx:
        return jnp.dot(x, w, precision=HI)
    qmax = a.qmax if precision == "reference" else 15     # control: uint4
    qw, sw, zw = _freeze(w.astype(jnp.bfloat16), qmax)
    qw, sw, zw = qw.astype(jnp.float32), sw, zw.astype(jnp.float32)
    sx, zx = _group_affine(x, groups, n_groups, qmax)
    qx = jnp.clip(jnp.round(x / sx) + zx, 0, qmax)
    raw = _approx_sum(qx, qw) if precision == "reference" else _bdot(qx, qw)
    K = x.shape[1]
    acc = (raw - zx * jnp.sum(qw, axis=0, keepdims=True)
           - jnp.sum(qx, axis=1, keepdims=True) * zw + K * zx * zw)
    return acc * (sx * sw)


# -- the decoder ---------------------------------------------------------------


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv            # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class Packed(NamedTuple):
    """Requests packed into one sequence of T positions."""
    tokens: jax.Array       # (T,) int32
    pos: jax.Array          # (T,) position within its request
    seg: jax.Array          # (T,) request index; padding gets its own
    groups: jax.Array       # (T,) activation tensor id of each row
    select: jax.Array       # (S,) rows whose logits predict a served token


@functools.partial(jax.jit, static_argnames=("a", "precision", "q_chunk"))
def _layer(a: Arch, key, i, x, p: Packed, precision: str, q_chunk: int = 512):
    w = _layer_weights(a, key, i)
    T = x.shape[0]
    dense = functools.partial(_dense, groups=p.groups, n_groups=T, a=a,
                              precision=precision)
    h = _rms(x, a.eps)
    q = dense(h, w["wq"]).reshape(T, a.heads, a.head_dim)
    k = dense(h, w["wk"]).reshape(T, a.kv_heads, a.head_dim)
    v = dense(h, w["wv"]).reshape(T, a.kv_heads, a.head_dim)
    q, k = _rope(q, p.pos, a.rope_theta), _rope(k, p.pos, a.rope_theta)
    g = a.heads // a.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    idx = jnp.arange(T)

    def chunk(c0):
        qc = jax.lax.dynamic_slice_in_dim(q, c0, q_chunk) / np.sqrt(a.head_dim)
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI)
        qi = c0 + jnp.arange(q_chunk)
        ok = ((p.seg[qi][:, None] == p.seg[None, :])
              & (qi[:, None] >= idx[None, :]))
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    att = jax.lax.map(chunk, jnp.arange(0, T, q_chunk)).reshape(T, -1)
    x = x + dense(att, w["wo"])
    h = _rms(x, a.eps)
    x = x + dense(jax.nn.silu(dense(h, w["wg"])) * dense(h, w["wu"]), w["wd"])
    return x


@functools.partial(jax.jit, static_argnames=("a",))
def _embed_rows(a: Arch, key, tokens):
    return _embed(a, key)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("a", "precision"))
def _head(a: Arch, key, x, p: Packed, precision: str):
    rows = _rms(x, a.eps)[p.select]
    # the selected rows are decode-like: each its own activation tensor
    groups = jnp.arange(rows.shape[0])
    logits = _dense(rows, _lm_head(a, key), groups, rows.shape[0], a, precision)
    return logits[:, :a.vocab]


def logits(a: Arch, key, p: Packed, precision: str = "reference"):
    """(S, vocab) logits at the selected rows of the packed sequence."""
    x = _embed_rows(a, key, p.tokens)
    for i in range(a.layers):
        x = _layer(a, key, jnp.int32(i), x, p, precision)
    return _head(a, key, x, p, precision)


def pack(requests, T: int, S: int) -> tuple:
    """Pack ``[(prompt, served_tokens), ...]`` into ``T`` positions; returns
    ``(Packed, served)`` where ``served`` (S,) holds the token each selected
    row should predict, -1 on padding."""
    toks, pos, seg, grp, sel, served = [], [], [], [], [], []
    nxt = 0
    for r, (prompt, out) in enumerate(requests):
        base = len(toks)
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        n, plen = len(seq), len(prompt)
        toks += list(seq)
        pos += list(range(n))
        seg += [r] * n
        # the prompt is one tensor; each decode position is its own
        grp += [nxt] * plen + list(range(nxt + 1, nxt + 1 + n - plen))
        nxt += 1 + n - plen
        sel += list(range(base + plen - 1, base + n))
        served += list(out)
    if len(toks) > T or len(sel) > S:
        raise ValueError(f"{len(toks)} positions / {len(sel)} served tokens do "
                         f"not pack into {T} / {S}")
    pad = T - len(toks)
    toks += [0] * pad
    pos += [0] * pad
    seg += [len(requests)] * pad
    grp += list(range(nxt, nxt + pad))
    sel += [0] * (S - len(sel))
    served += [-1] * (S - len(served))
    arr = lambda v: jnp.asarray(np.asarray(v, np.int32))  # noqa: E731
    return (Packed(arr(toks), arr(pos), arr(seg), arr(grp), arr(sel)),
            np.asarray(served, np.int64))


def served_logits(conf: dict, key, requests, T: int, S: int,
                  precision: str = "reference") -> tuple:
    """The reference's logits (n, vocab) at every served position of
    ``requests = [(prompt, served_tokens), ...]``, packed into ``T`` positions
    and ``S`` served tokens, and the n tokens served there."""
    packed, served = pack(requests, T, S)
    keep = served >= 0
    return np.asarray(logits(arch_of(conf), key, packed, precision))[keep], served[keep]


# -- the FLOP count --------------------------------------------------------------


def matmul_params(conf: dict) -> int:
    d, hd = conf["hidden_size"], conf["head_dim"]
    attn = (d * conf["num_attention_heads"] * hd * 2
            + d * conf["num_key_value_heads"] * hd * 2)
    return (conf["num_hidden_layers"] * (attn + 3 * d * conf["intermediate_size"])
            + d * conf["vocab_size"])


def model_flops(conf: dict, start: int, stop: int) -> float:
    """Forward FLOPs of positions ``start..stop-1`` of a request: 2 x matmul
    parameters, plus 4 L H hd x the context each position attends."""
    n = stop - start
    ctx = (start + 1 + stop) * n / 2          # sum of (p + 1) over the span
    return (2.0 * matmul_params(conf) * n
            + 4.0 * conf["num_hidden_layers"] * conf["num_attention_heads"]
            * conf["head_dim"] * ctx)
