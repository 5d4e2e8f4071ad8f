"""jit'd public wrapper for the fused int4 matmul kernel.

Pads M/N to block multiples, picks the block sizes and CPU interpret
mode, and exposes the analytic per-call HBM traffic for the floor model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.int4_matmul.int4_matmul import int4_matmul_pallas


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# M and N tile edges, and the largest K tile short of all of K
BLOCK = 128
K_BLOCK = 2048


def blocks(M: int, K: int, N: int, group: int):
    """(bm, bn, bk) for an (M, K) x (K, N) call.  The scale block
    (bk // group, bn) sits on the TPU's (8, 128) tile, so it must span a
    multiple of 8 groups or all of K: bk is the largest multiple of
    ``8 * group`` up to ``K_BLOCK`` that divides K, else all of K.  K is
    never padded, since padding the weights would copy them on every
    call."""
    step = 8 * group
    bk = next((b for b in range(K_BLOCK - K_BLOCK % step, 0, -step)
               if K % b == 0), K)
    return min(BLOCK, _round_up(M, 8)), min(BLOCK, _round_up(N, 128)), bk


def int4_matmul(x: jnp.ndarray, packed: jnp.ndarray, scales: jnp.ndarray,
                *, group: int = 128) -> jnp.ndarray:
    """x (M, K) @ int4-packed (K//2, N) with per-group scales -> (M, N).

    ``group`` is the scale group along K; K must be a multiple of it."""
    M, K = x.shape
    K2, N = packed.shape
    assert K == 2 * K2, f"K mismatch: x K={K}, packed implies {2 * K2}"
    assert K % group == 0 and group % 2 == 0, (K, group)
    interpret = jax.default_backend() != "tpu"
    bm, bn, bk = blocks(M, K, N, group)

    Mp, Np = _round_up(M, bm), _round_up(N, bn)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    if Np != N:
        packed = jnp.pad(packed, ((0, 0), (0, Np - N)))
        scales = jnp.pad(scales, ((0, 0), (0, Np - N)))
    out = int4_matmul_pallas(x, packed, scales, group=group,
                             bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:M, :N]


def traffic_bytes(M: int, K: int, N: int, group: int = 128) -> dict:
    """Analytic HBM bytes per call (fused path)."""
    return {
        "x": M * K * 2,
        "weights": K * N // 2,
        "scales": (K // group) * N * 4,
        "out": M * N * 2,
    }
