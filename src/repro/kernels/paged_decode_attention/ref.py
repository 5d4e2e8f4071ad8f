"""Pure-jnp oracle for the fused paged decode-attention kernel: the
gather+SDPA route the kernel replaces (materialise the virtual view via
the block table, then masked softmax-attention over it)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_decode_attention_ref(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray,
                               block_table: jnp.ndarray,
                               lengths: jnp.ndarray) -> jnp.ndarray:
    """q (B, Hq, hd); k_pool/v_pool (n_pages, Hkv, page, hd);
    block_table (B, max_blocks); lengths (B,) -> (B, Hq, hd) f32.

    A slot with ``lengths[b] == 0`` returns zeros (matching the kernel's
    free-lane contract)."""
    B, Hq, hd = q.shape
    Hkv = k_pool.shape[1]
    # (B, max_blocks, Hkv, page, hd) -> (B, Hkv, S, hd)
    k_view = jnp.take(k_pool, block_table, axis=0).transpose(0, 2, 1, 3, 4)
    v_view = jnp.take(v_pool, block_table, axis=0).transpose(0, 2, 1, 3, 4)
    k_view = k_view.reshape(B, Hkv, -1, hd)
    v_view = v_view.reshape(B, Hkv, -1, hd)
    S = k_view.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,bksh->bkgs", qg,
                        k_view.astype(jnp.float32)) * (hd ** -0.5)
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(mask[:, None, None, :], probs, 0.0)   # len-0 lanes
    out = jnp.einsum("bkgs,bksh->bkgh", probs, v_view.astype(jnp.float32))
    return out.reshape(B, Hq, hd)


def paged_decode_attention_quant_ref(q: jnp.ndarray, k_pool: jnp.ndarray,
                                     v_pool: jnp.ndarray,
                                     k_scale_pool: jnp.ndarray,
                                     v_scale_pool: jnp.ndarray,
                                     block_table: jnp.ndarray,
                                     lengths: jnp.ndarray) -> jnp.ndarray:
    """Int8-KV oracle: dequantise the pools to float32 (codes * scale,
    the gather route's materialised-view semantics) and run the plain
    reference.  The fused quant kernel computes the same function with
    the dequantisation moved inside its block loads."""
    k = k_pool.astype(jnp.float32) * k_scale_pool[..., None]
    v = v_pool.astype(jnp.float32) * v_scale_pool[..., None]
    return paged_decode_attention_ref(q, k, v, block_table, lengths)
