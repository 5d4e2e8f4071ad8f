"""Pallas TPU kernel: fused int4 dequant + matmul (the ExLlamaV2 lesson,
paper §7, adapted to TPU).

HBM traffic per call ~= packed nibbles (K*N/2 bytes) + scales — the 4x
weight-traffic reduction actually lands because bf16 weights never exist
in HBM.  Nibble unpack + per-group scaling happen in VMEM/registers; the
MXU sees an f32-accumulated GEMM.

Packed row r holds k = 2r in its low nibble and k = 2r + 1 in its high
nibble.  Rather than interleave the two nibble planes back into (K, N)
rows inside the kernel (a sublane shuffle the TPU has no cheap form
for), the wrapper splits x into its even and odd columns and the kernel
runs two half-depth dots: ``x[:, 0::2] @ lo + x[:, 1::2] @ hi``.

Grid (M/BM, N/BN, K/BK), K innermost (sequential accumulation into a VMEM
scratch tile).  The scale block is (BK//group, BN), so BK//group is a
multiple of 8 or BK is all of K (ops.py picks it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_ref, *, group: int):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = w_ref[...].astype(jnp.int32)            # (BK//2, BN), int8 bits
    # sign-extend each nibble: shift it to the top of the word, then
    # arithmetic-shift it back down
    lo = ((packed << 28) >> 28).astype(jnp.float32)
    hi = ((packed << 24) >> 28).astype(jnp.float32)
    rows, bn = lo.shape
    scales = s_ref[...].astype(jnp.float32)          # (BK//group, BN)
    # packed row r belongs to group r // (group // 2)
    s_rows = jnp.broadcast_to(scales[:, None, :],
                              (scales.shape[0], group // 2, bn)
                              ).reshape(rows, bn)
    dims = (((1,), (0,)), ((), ()))
    acc_ref[...] += (
        jax.lax.dot_general(xe_ref[...], lo * s_rows, dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(xo_ref[...], hi * s_rows, dims,
                              preferred_element_type=jnp.float32))

    @pl.when(k == nk - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "bm", "bn", "bk", "interpret"))
def int4_matmul_pallas(x: jnp.ndarray, packed: jnp.ndarray, scales: jnp.ndarray,
                       *, group: int = 128, bm: int = 128, bn: int = 128,
                       bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """x (M, K); packed (K//2, N) uint8; scales (K//group, N) -> (M, N).

    M/N/K must be multiples of the block sizes and BK a multiple of the
    scale group (ops.py pads and picks blocks)."""
    M, K = x.shape
    N = packed.shape[1]
    assert packed.shape[0] == K // 2, (packed.shape, K)
    assert group % 2 == 0 and bk % group == 0
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    xf = x.astype(jnp.float32).reshape(M, K // 2, 2)
    # uint8 -> int8 is a free bit reinterpretation; the kernel masks the
    # nibbles out of the sign-extended word
    codes = jax.lax.bitcast_convert_type(packed, jnp.int8)

    grid = (M // bm, N // bn, K // bk)
    x_spec = pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k))
    return pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid=grid,
        in_specs=[
            x_spec,
            x_spec,
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xf[..., 0], xf[..., 1], codes, scales)
