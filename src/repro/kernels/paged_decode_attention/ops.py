"""jit'd public wrapper + analytic traffic accounting for the fused
paged decode-attention kernel.

On a model-dtype pool the kernel runs one program per lane; each walks
its live pages in compute blocks of ``pages_per_block(page,
max_blocks)`` pages (512 tokens at page 16), double-buffered page DMAs
from the pool.  ``walk_blocks`` bounds how many blocks a run's decode
steps walked.  An int8 pool keeps one grid step per (lane, page)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_decode_attention.paged_decode_attention import (
    paged_decode_attention_pallas, pages_per_block)


# staticcheck: hotpath
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           lengths: jnp.ndarray,
                           k_scale_pool: Optional[jnp.ndarray] = None,
                           v_scale_pool: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """q (B, Hq, hd); k_pool/v_pool (n_pages, Hkv, page, hd);
    block_table (B, max_blocks); lengths (B,) -> (B, Hq, hd).

    Reads each slot's pages in place through the block table
    (scalar-prefetch indirection) — no materialised virtual view.  On a
    model-dtype pool one program per slot copies its live pages block by
    block, and nothing past a slot's last live page.  With scale pools
    (n_pages, Hkv, page) the pools hold int8 codes, read in one
    (slot, page) grid step a page, and the kernel applies the scales
    in-register inside its page sweep.  Interpret mode off-TPU."""
    interpret = jax.default_backend() != "tpu"
    return paged_decode_attention_pallas(q, k_pool, v_pool, block_table,
                                         lengths, k_scale_pool,
                                         v_scale_pool, interpret=interpret)


def kv_token_bytes(Hkv: int, hd: int, kv_bytes: int,
                   kv_quant: str = "none") -> int:
    """Stored bytes per cached token (K + V together).

    ``kv_quant="int8"``: one int8 code per element plus one float32
    scale per (token, head) for each of K and V."""
    if kv_quant == "int8":
        return 2 * Hkv * (hd + 4)
    return 2 * Hkv * hd * kv_bytes


def traffic_bytes(live_blocks: int, page_size: int, Hkv: int, hd: int,
                  *, n_slots: int, max_blocks: int, n_layers: int = 1,
                  kv_bytes: int = 2, kv_quant: str = "none") -> dict:
    """Analytic per-decode-step HBM KV traffic for the two paged routes.

    ``live_blocks`` is the summed ``ceil(live_len/page)`` over slots at
    that step: the pages the fused kernel reads, since each slot's walk
    stops at its last live page (on an int8 pool the page grid skips
    the compute of later pages, and a sentinel entry re-fetches
    nothing).  The gather route is charged per layer for the full
    virtual view three times: the gather's pool read, the
    materialised-view write, and the SDPA's read of that view — the two
    middle terms are the traffic the fused kernel deletes.

    With ``kv_quant="int8"`` the routes diverge the way the paper's
    realised-savings gap does: the fused kernel reads live pages once at
    *stored* width (codes + scales — it achieves the analytic floor by
    construction), while the gather route reads the pool at stored
    width but then writes AND re-reads a dequantised model-dtype view
    of the whole virtual span (bnb-style), so most of the stored-width
    cut never reaches the step's actual traffic.  ``floor`` is the
    irreducible per-step KV term: live tokens once at stored width."""
    stored = kv_token_bytes(Hkv, hd, kv_bytes, kv_quant)
    model_tok = 2 * Hkv * hd * kv_bytes
    virtual = n_slots * max_blocks * page_size
    live = live_blocks * page_size
    if kv_quant == "none":
        gather = n_layers * 3 * virtual * model_tok
    else:
        # pool read (stored width) + dequantised-view write + SDPA read
        # (both model width) over the constant virtual span
        gather = n_layers * virtual * (stored + 2 * model_tok)
    return {
        "fused": n_layers * live * stored,
        "gather_sdpa": gather,
        "floor": n_layers * live * stored,
    }


def walk_blocks(step_kv_blocks: Sequence[int], page: int, max_blocks: int,
                n_slots: int) -> float:
    """At most how many compute blocks a layer of a decode step walks on
    a model-dtype pool, as a mean over a run's steps.

    ``step_kv_blocks`` is the scheduler's per-decode-step trace of live
    pages summed over lanes (``ContinuousResult.step_kv_blocks``).  A
    lane with ``pages_b`` live pages walks ``ceil(pages_b / ppb)``
    blocks; the trace keeps only the sum over lanes, so the walk is
    bounded by one partial block a lane, and by the whole table."""
    ppb = pages_per_block(page, max_blocks)
    pages = np.asarray(step_kv_blocks, np.int64)
    most = np.minimum(n_slots * -(-max_blocks // ppb),
                      (pages + n_slots * (ppb - 1)) // ppb)
    return float(np.mean(most))


def serving_traffic_bytes(step_kv_blocks: Sequence[int], cfg, *,
                          page_size: int, n_slots: int, max_blocks: int,
                          kv_bytes: Optional[int] = None,
                          kv_quant: str = "none") -> dict:
    """Mean per-decode-step KV traffic for both routes from a run's
    live-block trace (``ContinuousResult.step_kv_blocks``).

    ``kv_bytes`` defaults to the KV element size implied by the model
    dtype (an unquantised paged cache stores KV at the model dtype;
    under ``kv_quant="int8"`` it also sets the width the gather route's
    dequantised view materialises at)."""
    if kv_bytes is None:
        kv_bytes = 4 if cfg.dtype == "float32" else 2
    mean_blocks = int(round(float(np.mean(np.asarray(step_kv_blocks)))))
    return traffic_bytes(mean_blocks, page_size, cfg.n_kv_heads,
                         cfg.head_dim, n_slots=n_slots,
                         max_blocks=max_blocks, n_layers=cfg.n_layers,
                         kv_bytes=kv_bytes, kv_quant=kv_quant)
