"""approx_matmul: one call multiplies (M, K) uint8 activation codes by
(K, N) uint8 weight codes under an approximate 8x8 multiplier, into int32.

Work: an M x K x N 8-bit multiply-accumulate, 2 M K N operations, against
the chip's int8 peak. Bytes: the uint8 operands and the int32 output. The
kernel's low-rank feature maps, its f32 dots and any padding of M are cost,
never work: a change that drops them moves the share, not the count.

A call is an operation event named after the kernel's jitted launcher,
``%approx_matmul_kernel_call.N = s32[M,N]{...} custom-call(u8[M,K]...,
u8[K,N]...)``; its shapes come from that text.
"""
from __future__ import annotations

import re

NAME = "approx_matmul_kernel_call"
_U8 = re.compile(r"u8\[(\d+),(\d+)\]")


def matches(event) -> bool:
    return event.name.startswith("%" + NAME)


def shapes(event):
    """(M, K, N) of a call, from its operands' shapes."""
    (m, k), (k2, n) = (tuple(map(int, s)) for s in _U8.findall(event.name)[:2])
    assert k == k2, event.name
    return m, k, n


def ops_bytes(m: int, k: int, n: int) -> tuple:
    return 2 * m * k * n, m * k + k * n + 4 * m * n


def least_seconds(rec, events, peaks):
    total = 0.0
    for e in events:
        ops, nbytes = ops_bytes(*shapes(e))
        total += max(ops / peaks["int8_ops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total


def bound(m: int, k: int, n: int, peaks) -> str:
    ops, nbytes = ops_bytes(m, k, n)
    return ("compute" if ops / peaks["int8_ops_per_s"]
            >= nbytes / peaks["hbm_bytes_per_s"] else "memory")
