"""Pallas TPU kernel: fused paged GQA single-token decode attention.

The paged decode path used to *materialise* the slot-major virtual KV
view (``paged_view``: gather every block-table page into a fresh
(B, max_blocks*page, Hkv, hd) buffer) before the SDPA even ran — per
layer per step that is a full extra read+write of the virtual KV on top
of the SDPA's own read, i.e. the exact avoidable data movement the
paper's realised-vs-floor gap is made of.  This kernel fuses the gather
into the flash-decoding sweep: the block table rides in as a
scalar-prefetch operand and each lane's pages are read **in place**
from the pool, once, with no intermediate view.

Pool layout is head-major within a page, (n_pages, Hkv, page, hd), so
one DMA moves a whole page for every KV head.

The walk: grid (B,), one program per lane, which walks only that lane's
live pages.  The pools stay in HBM (``pl.ANY``); the program copies
``ppb`` pages at a time into a VMEM block of ``bk = ppb * page``
tokens, double-buffered: block ``j + 1``'s page DMAs are started before
block ``j`` is computed.  The walk runs ``ceil(len / bk)`` blocks and
copies only pages ``< ceil(len / page)``, so a lane reads nothing past
its last live page and a free lane (length 0) reads nothing.  Rows of
a block at or past the length contribute exact zeros whatever the VMEM
behind them holds (stale pages of the previous block or lane).

The page grid: grid (B, max_blocks), one step per (lane, page), each
page a BlockSpec block whose index map dereferences the block table.
Steps past a lane's length skip their compute (``pl.when``), and a
table entry parked on the garbage sentinel re-targets the same page,
so consecutive skipped steps fetch nothing new.  It serves the pools
the walk cannot copy on the chip:

* int8 pools: the compiler refuses a copy of any slice of the
  (n_pages, Hkv, page) scale pool, whose minor dimension (``page``
  tokens) is narrower than the 128 lanes of a tile; a BlockSpec block
  is the one way it moves a page's scales;
* pages that are not a multiple of ``PAGE_ROWS`` = 8 tokens: the walk
  lands a page at a sublane offset of its block, which must span whole
  8-row tiles (pages 8, 16 and 32 compile; 4 does not).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tokens of KV per compute block (int4_matmul/ops.BLOCK's counterpart)
TOKENS = 512
# the walk lands a page at a sublane offset of its block: on the chip a
# page must span whole 8-row tiles
PAGE_ROWS = 8


def pages_per_block(page: int, max_blocks: int) -> int:
    """Pages copied into one compute block: ``TOKENS`` worth, at most a
    whole block-table row."""
    return min(max_blocks, max(1, TOKENS // page))


def _walk_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                 sems, *, page: int, ppb: int, max_blocks: int,
                 scale: float, n_kv: int):
    """One lane: walk its live pages block by block over every KV head."""
    b = pl.program_id(0)
    # a length past the table would walk the next lane's row
    length = jnp.minimum(len_ref[b], max_blocks * page)
    bk = ppb * page
    live_pages = pl.cdiv(length, page)
    n_blocks = pl.cdiv(length, bk)

    def walk_pages(j, slot, act):
        """``act`` (start or wait) on the K and V DMA of each live page
        of block j; a block past the lane's last page has none."""
        n = jnp.clip(live_pages - j * ppb, 0, ppb)
        for p in range(ppb):
            @pl.when(p < n)
            def _page():
                # clipped, so no table entry can address outside the pool
                page_id = jnp.clip(bt_ref[b * max_blocks + j * ppb + p], 0,
                                   k_hbm.shape[0] - 1)
                for i, (pool, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                    act(pltpu.make_async_copy(
                        pool.at[page_id],
                        buf.at[slot, :, pl.ds(p * page, page)],
                        sems.at[i, slot]))

    walk_pages(0, 0, lambda c: c.start())
    G, hd = q_ref.shape[2], q_ref.shape[3]

    def block(j, carry):
        slot = j % 2
        walk_pages(j + 1, 1 - slot, lambda c: c.start())
        walk_pages(j, slot, lambda c: c.wait())
        tok = j * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        valid = tok < length
        row = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        out = []
        for h in range(n_kv):
            m_prev, l_prev, acc_prev = carry[3 * h:3 * h + 3]
            # products of two bf16 values are exact in f32: scores in
            # f32 from one MXU pass
            q = q_ref[0, h]                                     # (G, hd)
            k = k_buf[slot, h].astype(q.dtype)                  # (bk, hd)
            # 0 x NaN is NaN in the PV dot: dead rows of V are zeroed,
            # not only their probabilities
            v = jnp.where(row < length, v_buf[slot, h].astype(jnp.float32),
                          0.0)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # (G, bk)
            scores = jnp.where(valid, scores * scale, NEG_INF)
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)  # (G, bk)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc_prev * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # (G, hd)
            out += [m_new, l_new, acc_new]
        return tuple(out)

    init = (jnp.full((G, 1), NEG_INF, jnp.float32),
            jnp.zeros((G, 1), jnp.float32),
            jnp.zeros((G, hd), jnp.float32)) * n_kv
    carry = jax.lax.fori_loop(0, n_blocks, block, init)
    for h in range(n_kv):
        _, l, acc = carry[3 * h:3 * h + 3]
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _page_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest, page: int,
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
    slot -> (B, Hq, hd).  A length past ``max_blocks * page`` attends
    over the whole table row.

    A slot's output attends over virtual positions ``0..lengths[b]-1``,
    read through its block-table row; a slot with ``lengths[b] == 0``
    returns zeros (free lane, output discarded by the scheduler).

    With ``k_scale_pool``/``v_scale_pool`` (n_pages, Hkv, page) the
    pools hold int8 codes and the kernel applies the scales inside the
    page sweep: the scale tiles follow the same ``bt[b, i]`` index maps,
    and the output attends over exactly ``codes * scale`` — the function
    the dequantised-view gather reference computes at float32.

    A model-dtype pool whose page is a multiple of ``PAGE_ROWS`` takes
    the walk; an int8 pool or another page size takes the page grid."""
    B, Hq, hd = q.shape
    _, Hkv, page, _ = k_pool.shape
    max_blocks = block_table.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    block_table = block_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    quantized = k_scale_pool is not None

    if not quantized and page % PAGE_ROWS == 0:
        ppb = pages_per_block(page, max_blocks)
        bk = ppb * page
        lane_spec = pl.BlockSpec((1, Hkv, G, hd),
                                 lambda b, bt, ln: (b, 0, 0, 0))
        hbm_spec = pl.BlockSpec(memory_space=pl.ANY)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # block table + lengths
            grid=(B,),
            in_specs=[lane_spec, hbm_spec, hbm_spec],
            out_specs=lane_spec,
            scratch_shapes=[pltpu.VMEM((2, Hkv, bk, hd), k_pool.dtype),
                            pltpu.VMEM((2, Hkv, bk, hd), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
        )
        kernel = functools.partial(_walk_kernel, page=page, ppb=ppb,
                                   max_blocks=max_blocks, scale=hd ** -0.5,
                                   n_kv=Hkv)
        # flat, so a page id is one scalar load
        operands = [block_table.reshape(-1), lengths, qg, k_pool, v_pool]
        # the kernel clips page ids to the pool; the per-DMA checks
        # would double the scalar work of each page copy
        params = pltpu.CompilerParams(disable_bounds_checks=True)
    else:
        # the fused gather: the index map dereferences the prefetched
        # block table, so page i of slot b streams straight from the
        # pool — no materialised view
        pool_spec = pl.BlockSpec((1, Hkv, page, hd),
                                 lambda b, i, bt, ln: (bt[b, i], 0, 0, 0))
        lane_spec = pl.BlockSpec((1, Hkv, G, hd),
                                 lambda b, i, bt, ln: (b, 0, 0, 0))
        in_specs = [lane_spec, pool_spec, pool_spec]
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
            out_specs=lane_spec,
            scratch_shapes=[
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, hd), jnp.float32),
            ],
        )
        kernel = functools.partial(_page_kernel, page=page,
                                   scale=hd ** -0.5, n_kv=Hkv,
                                   quantized=quantized)
        params = None
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        name="paged_decode_attention",
        compiler_params=params,
        interpret=interpret,
    )(*operands)
    return out.reshape(B, Hq, hd)
