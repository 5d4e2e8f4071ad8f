"""Pallas TPU kernel: fused paged GQA single-token decode attention.

The paged decode path used to *materialise* the slot-major virtual KV
view (``paged_view``: gather every block-table page into a fresh
(B, max_blocks*page, Hkv, hd) buffer) before the SDPA even ran — per
layer per step that is a full extra read+write of the virtual KV on top
of the SDPA's own read, i.e. the exact avoidable data movement the
paper's realised-vs-floor gap is made of.  This kernel fuses the gather
into the flash-decoding sweep: the block table rides in as a
scalar-prefetch operand, the BlockSpec index map dereferences it, and
each slot's pages are read **in place** from the pool, once, with no
intermediate view.

Pool layout is head-major within a page, (n_pages, Hkv, page, hd): one
block is a whole page for every KV head, so the last two block dims are
the pool's own (page, hd), which the TPU's tiling accepts at any page
size (a page shorter than the dtype's tile — 16 rows at bf16, 32 at
int8 — is padded in VMEM).

Grid (B, max_blocks); the page axis is the innermost sequential
dimension so the per-head (m, l, acc) online-softmax carry lives in VMEM
scratch across a slot's pages (same scheme as kernels/decode_attention).
Blocks past a slot's live length — block-table entries parked on the
garbage sentinel — are skipped via ``pl.when`` (their DMA re-targets the
same sentinel page, so consecutive skipped steps cost no new fetch),
which is what makes the kernel's KV traffic track *allocated* pages
instead of the constant virtual length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest, page: int,
            scale: float, n_kv: int, quantized: bool):
    """One (slot, page) grid step over every KV head of the page.

    With ``quantized`` the k/v blocks hold int8 codes and two extra
    inputs carry the page's per-(head, token) float32 scales, riding the
    SAME block-table indirection.  The scales are applied on the
    (G, page) score and probability tiles — ``q·(c·s) == (q·c)·s`` and
    ``p·(c·s) == (p·s)·c`` — so no dequantised copy of the page is
    built, in VMEM or in HBM: the stored-width traffic cut is *realised*
    (the paper's GPTQ+ExLlamaV2-style path, vs the gather route's
    bnb-style dequantised view)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    ni = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * page < length)
    def _block():
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)      # (G, hd)
            k = k_ref[0, h].astype(jnp.float32)      # (page, hd)
            v = v_ref[0, h].astype(jnp.float32)      # (page, hd)
            G = q.shape[0]
            # partial last page: tokens at absolute position >= length
            # mask out
            tok = i * page + jax.lax.broadcasted_iota(jnp.int32, (G, page), 1)
            valid = tok < length

            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (G, page)
            if quantized:
                scores = scores * ks_ref[0, h:h + 1, :]      # (1, page)
            scores = jnp.where(valid, scores * scale, NEG_INF)

            m_prev = m_ref[h]                        # (G, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)              # (G, page)
            p = jnp.where(valid, p, 0.0)

            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = p * vs_ref[0, h:h + 1, :] if quantized else p
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(i == ni - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q: jnp.ndarray, k_pool: jnp.ndarray,
                                  v_pool: jnp.ndarray,
                                  block_table: jnp.ndarray,
                                  lengths: jnp.ndarray,
                                  k_scale_pool: jnp.ndarray = None,
                                  v_scale_pool: jnp.ndarray = None, *,
                                  interpret: bool = False) -> jnp.ndarray:
    """q (B, Hq, hd); k_pool/v_pool (n_pages, Hkv, page, hd);
    block_table (B, max_blocks) page ids; lengths (B,) live tokens per
    slot -> (B, Hq, hd).

    A slot's output attends over virtual positions ``0..lengths[b]-1``,
    read through its block-table row; a slot with ``lengths[b] == 0``
    returns zeros (free lane, output discarded by the scheduler).

    With ``k_scale_pool``/``v_scale_pool`` (n_pages, Hkv, page) the
    pools hold int8 codes and the kernel applies the scales inside the
    page sweep: the scale tiles follow the same ``bt[b, i]`` index maps,
    and the output attends over exactly ``codes * scale`` — the function
    the dequantised-view gather reference computes at float32."""
    B, Hq, hd = q.shape
    _, Hkv, page, _ = k_pool.shape
    max_blocks = block_table.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    block_table = block_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    quantized = k_scale_pool is not None

    # the fused gather: the index map dereferences the prefetched block
    # table, so page i of slot b streams straight from the pool — no
    # materialised view
    pool_spec = pl.BlockSpec((1, Hkv, page, hd),
                             lambda b, i, bt, ln: (bt[b, i], 0, 0, 0))
    slot_spec = pl.BlockSpec((1, Hkv, G, hd),
                             lambda b, i, bt, ln: (b, 0, 0, 0))
    in_specs = [slot_spec, pool_spec, pool_spec]
    operands = [block_table, lengths, qg, k_pool, v_pool]
    if quantized:
        scale_spec = pl.BlockSpec((1, Hkv, page),
                                  lambda b, i, bt, ln: (bt[b, i], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale_pool, v_scale_pool]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block table + lengths
        grid=(B, max_blocks),
        in_specs=in_specs,
        out_specs=slot_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, scale=hd ** -0.5, n_kv=Hkv,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, Hq, hd)
