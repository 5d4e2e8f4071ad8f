"""GQA attention: full-sequence (train/prefill) and cached decode with
selectable backends (the paper's §6 attention-backend matrix).

Backends for the decode step:
  sdpa     — fused jnp softmax-attention (the dispatcher default)
  math     — explicitly decomposed softmax (the paper's MATH fallback)
  split_kv — flash-decoding style partitioned KV with partial-softmax
             combine (what GSPMD emits for a sequence-sharded cache)
  pallas   — the Pallas TPU kernel (kernels/decode_attention), interpret
             mode on CPU; on the paged path this selects the FUSED
             block-table kernel (kernels/paged_decode_attention) that
             reads pages in place — no paged_view gather
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.spans import scope
from repro.launch import hints
from repro.models.common import dense_init

Params = Dict[str, jnp.ndarray]

DECODE_BACKENDS = ("sdpa", "math", "split_kv", "pallas")

# above this sequence length, full attention runs q-block-chunked (exact
# math, flash-style memory): scores never materialise beyond (bq, S).
# configure() lets launchers/perf-experiments retune without rebuild.
CHUNKED_ATTN_THRESHOLD = 8192
CHUNK_Q = 1024


def configure(threshold: Optional[int] = None, chunk_q: Optional[int] = None):
    global CHUNKED_ATTN_THRESHOLD, CHUNK_Q
    if threshold is not None:
        CHUNKED_ATTN_THRESHOLD = threshold
    if chunk_q is not None:
        CHUNK_Q = chunk_q


def init_attention(key, cfg: ArchConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": dense_init(ks[0], cfg.d_model, hq * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, hkv * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, hkv * hd, dtype),
        "wo": dense_init(ks[3], hq * hd, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    return p


def _project_qkv(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    from repro.quant.paths import matmul
    B, S, _ = x.shape
    q = matmul(x, p["wq"])
    k = matmul(x, p["wk"])
    v = matmul(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = hints.constrain(q.reshape(B, S, cfg.n_heads, cfg.head_dim),
                        ("dp", None, "tp"))
    k = hints.constrain(k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
                        ("dp", None, "tp"))
    v = hints.constrain(v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
                        ("dp", None, "tp"))
    return q, k, v


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """q (B,Sq,Hq,hd), k (B,Sk,Hkv,hd) -> scores (B,Hkv,G,Sq,Sk) f32.

    bf16 operands with an f32 accumulator (MXU-native; matches the
    paper's bf16-tensor-core SDPA semantics)."""
    B, Sq, Hq, hd = q.shape
    G = Hq // cfg.n_kv_heads
    qg = q.reshape(B, Sq, cfg.n_kv_heads, G, hd)
    return jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                      preferred_element_type=jnp.float32) * (hd ** -0.5)


def _gqa_out(probs: jnp.ndarray, v: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """probs (B,Hkv,G,Sq,Sk) f32, v (B,Sk,Hkv,hd) -> (B,Sq,Hq*hd) f32."""
    B = probs.shape[0]
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    Sq = o.shape[1]
    return o.reshape(B, Sq, cfg.n_heads * cfg.head_dim)


def _causal_probs(scores: jnp.ndarray, q0: jnp.ndarray, S: int,
                  window: Optional[int]) -> jnp.ndarray:
    """scores (B,K,G,bq,S) for q rows starting at q0 -> masked softmax."""
    bq = scores.shape[3]
    qpos = q0 + jnp.arange(bq)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1)


def attention_full(p: Params, x: jnp.ndarray, angles: jnp.ndarray,
                   cfg: ArchConfig, apply_rope_fn,
                   positions: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full causal attention (train / prefill). Returns (out, (k, v)).

    Long sequences (> CHUNKED_ATTN_THRESHOLD) run q-block-chunked via
    lax.scan — exact math, (bq, S) score footprint instead of (S, S)."""
    from repro.quant.paths import matmul
    B, S, _ = x.shape
    with scope("qkv"):
        q, k, v = _project_qkv(p, x, cfg)
        q = apply_rope_fn(q, angles)
        k = apply_rope_fn(k, angles)

    if S <= CHUNKED_ATTN_THRESHOLD:
        with scope("attend"):
            # prefer kv-head TP; context-parallel (query-seq) fallback
            # for head counts that don't divide the model axis
            scores = hints.constrain_first_fit(
                _gqa_scores(q, k, cfg),
                [("dp", "tp"), ("dp", None, None, "tp")])
            probs = _causal_probs(scores, jnp.int32(0), S,
                                  cfg.sliding_window)
            out = _gqa_out(probs, v, cfg).astype(x.dtype)
        with scope("attn_out"):
            return matmul(out, p["wo"]), (k, v)

    bq = CHUNK_Q
    assert S % bq == 0, (S, bq)
    qb = q.reshape(B, S // bq, bq, cfg.n_heads, cfg.head_dim)

    def body(_, inp):
        i, qi = inp                                   # qi (B,bq,Hq,hd)
        scores = hints.constrain_first_fit(
            _gqa_scores(qi, k, cfg),
            [("dp", "tp"), ("dp", None, None, "tp")])
        probs = _causal_probs(scores, i * bq, S, cfg.sliding_window)
        return None, _gqa_out(probs, v, cfg).astype(x.dtype)

    # chunk body is always rematted: the (bq, S) score tile is recomputed
    # in backward instead of saved — flash-attention residual behaviour
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    with scope("attend"):
        _, blocks = jax.lax.scan(
            body, None, (jnp.arange(S // bq), jnp.moveaxis(qb, 1, 0)))
        out = jnp.moveaxis(blocks, 0, 1).reshape(
            B, S, cfg.n_heads * cfg.head_dim)
    with scope("attn_out"):
        return matmul(out, p["wo"]), (k, v)


# --------------------------------------------------------------------------
# decode (single new token against a static cache)
# --------------------------------------------------------------------------

def decode_mask(pos: jnp.ndarray, s_max: int, *, ring: bool = False):
    """Valid-slot mask for a decode step.

    Full cache (s_max >= ctx): slots 0..pos valid.
    Ring cache (sliding window == s_max): slots <= pos valid until the
    ring wraps (pos >= s_max), after which every slot holds an in-window
    token.  Softmax is permutation-invariant over slots, so slot order
    never matters; RoPE was applied at absolute positions on write.

    ``pos`` may be a scalar (one shared position, the static-batch path)
    or a (B,) vector of per-slot positions (continuous batching) — the
    latter yields a (B, s_max) per-slot length mask.
    """
    idx = jnp.arange(s_max)
    if jnp.ndim(pos):
        m = idx[None, :] <= pos[:, None]
        if ring:
            m = m | (pos[:, None] >= s_max)
        return m
    m = idx <= pos
    if ring:
        m = m | (pos >= s_max)
    return m


def _kv_write(dst: jnp.ndarray, new: jnp.ndarray, write_pos: jnp.ndarray,
              active: Optional[jnp.ndarray] = None):
    """Write the new (B, 1, ...) row into the cache's sequence axis.

    Scalar ``write_pos`` writes every sequence at the same slot (static
    batch); a (B,) vector writes each sequence at its own slot (slotted
    continuous batching) via a vmapped single-row update.

    ``active`` (B,) bool turns the write into a per-lane no-op: an
    inactive lane re-writes the row already under its position, so a
    horizon-K fused tick can keep finished lanes riding along in the
    batch without corrupting their cache (the multi-step analogue of the
    ring path's write clamp).
    """
    new = new.astype(dst.dtype)
    if jnp.ndim(write_pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(dst, new, write_pos, axis=1)
    if active is None:
        return jax.vmap(
            lambda d, n, p: jax.lax.dynamic_update_slice_in_dim(d, n, p, axis=0)
        )(dst, new, write_pos)

    def upd(d, n, p, a):
        old = jax.lax.dynamic_slice_in_dim(d, p, n.shape[0], axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            d, jnp.where(a, n, old), p, axis=0)
    return jax.vmap(upd)(dst, new, write_pos, active)


def _bmask(mask: jnp.ndarray, B: int) -> jnp.ndarray:
    """Normalise a valid-slot mask to (B, S): a shared (S,) mask (static
    batch, one position for all sequences) broadcasts; a (B, S) per-slot
    mask (continuous batching) passes through."""
    if mask.ndim == 2:
        return mask
    return jnp.broadcast_to(mask[None, :], (B, mask.shape[0]))


def _sdpa_decode(q, k_cache, v_cache, mask, cfg, k_scale=None, v_scale=None):
    """k_scale/v_scale (B,S,Hkv): int8-KV path.  The per-token scales are
    constant over head_dim, so they FOLD into the score/prob tensors
    exactly — the int8 codes only convert-fuse into the dots and no bf16
    KV copy is ever materialised (EXPERIMENTS.md §Perf C).

    ``mask`` is (S,) shared or (B, S) per-slot."""
    mask = _bmask(mask, q.shape[0])
    scores = _gqa_scores(q, k_cache.astype(q.dtype), cfg)    # (B,K,G,1,S)
    if k_scale is not None:
        scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    scores = jnp.where(mask[:, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    return _gqa_out(probs, v_cache.astype(q.dtype), cfg)


def _math_decode(q, k_cache, v_cache, mask, cfg):
    """Explicitly decomposed softmax (separate max/exp/sum/div ops)."""
    mask = _bmask(mask, q.shape[0])
    scores = _gqa_scores(q, k_cache, cfg)
    neg = jnp.float32(-1e30)
    scores = jnp.where(mask[:, None, None, None, :], scores, neg)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    z = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / z
    return _gqa_out(probs, v_cache, cfg)


def _split_kv_decode(q, k_cache, v_cache, mask, cfg, n_partitions: int = 8):
    """Flash-decoding: partition the KV axis, partial softmax per
    partition, numerically-exact combine (log-sum-exp merge)."""
    mask = _bmask(mask, q.shape[0])
    B, S, Hkv, hd = k_cache.shape
    P = n_partitions
    while S % P:
        P //= 2
    sp = S // P
    kp = k_cache.reshape(B, P, sp, Hkv, hd)
    vp = v_cache.reshape(B, P, sp, Hkv, hd)
    maskp = mask.reshape(B, P, sp)

    def part(kpi, vpi, mi):
        scores = _gqa_scores(q, kpi, cfg)                    # (B,K,G,1,sp)
        scores = jnp.where(mi[:, None, None, None, :], scores, -jnp.inf)
        m = jnp.max(scores, axis=-1)                         # (B,K,G,1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        e = jnp.exp(scores - m_safe[..., None])
        e = jnp.where(mi[:, None, None, None, :], e, 0.0)
        l = jnp.sum(e, axis=-1)
        acc = jnp.einsum("bkgqs,bskh->bkgqh", e, vpi.astype(jnp.float32))
        return m, l, acc

    ms, ls, accs = jax.vmap(part, in_axes=(1, 1, 1), out_axes=0)(kp, vp, maskp)
    m_glob = jnp.max(ms, axis=0)
    m_glob_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
    scale = jnp.exp(jnp.where(jnp.isfinite(ms), ms - m_glob_safe, -jnp.inf))
    l_glob = jnp.sum(ls * scale, axis=0)
    acc = jnp.sum(accs * scale[..., None], axis=0)
    out = acc / jnp.maximum(l_glob, 1e-30)[..., None]        # (B,K,G,1,hd)
    B_, K, G, _, hd_ = out.shape
    return out.transpose(0, 3, 1, 2, 4).reshape(B_, 1, K * G * hd_)


def _decode_attend(q, k_read, v_read, mask, cfg: ArchConfig, backend: str,
                   out_dtype, k_scale=None, v_scale=None,
                   paged=None) -> jnp.ndarray:
    """Run the selected decode backend over an (already updated) K/V view.

    Shared by the contiguous and paged decode paths — the backend matrix
    (§6) is identical in both layouts, and this is the ONE place backend
    routing happens.  ``paged`` is the
    ``(k_pool, v_pool, block_table, lengths)`` tuple of the paged cache
    (``k_read``/``v_read`` are None then), or the 6-tuple
    ``(..., k_scale_pool, v_scale_pool)`` of an int8-quantised pool:
    ``backend="pallas"`` routes to the fused paged kernel, which reads
    pages in place through the block table — no virtual view is ever
    materialised, and on the quantised pool the codes dequantise
    in-register inside the kernel's block loads (the traffic cut is
    *realised*) — while every other backend runs over the gathered
    ``paged_view`` reference; the quantised gather route materialises a
    dequantised model-dtype view first (bnb-style: stored bytes shrink
    but the per-step read traffic does not)."""
    if paged is not None:
        k_pool, v_pool, block_table, lengths = paged[:4]
        ks_pool, vs_pool = paged[4:] if len(paged) == 6 else (None, None)
        if backend == "pallas":
            from repro.kernels.paged_decode_attention import ops as pda_ops
            B = q.shape[0]
            o = pda_ops.paged_decode_attention(q[:, 0], k_pool, v_pool,
                                               block_table, lengths,
                                               k_scale_pool=ks_pool,
                                               v_scale_pool=vs_pool)
            return o.reshape(B, 1,
                             cfg.n_heads * cfg.head_dim).astype(out_dtype)
        if ks_pool is not None:
            from repro.quant import kv as kvq
            k_read = kvq.dequantize_kv(paged_view(k_pool, block_table),
                                       paged_view(ks_pool, block_table),
                                       out_dtype)
            v_read = kvq.dequantize_kv(paged_view(v_pool, block_table),
                                       paged_view(vs_pool, block_table),
                                       out_dtype)
        else:
            k_read = paged_view(k_pool, block_table)
            v_read = paged_view(v_pool, block_table)
    if backend == "sdpa":
        return _sdpa_decode(q, k_read, v_read, mask, cfg,
                            k_scale=k_scale, v_scale=v_scale).astype(out_dtype)
    if backend == "math":
        return _math_decode(q, k_read, v_read, mask, cfg).astype(out_dtype)
    if backend == "split_kv":
        return _split_kv_decode(q, k_read, v_read, mask, cfg).astype(out_dtype)
    if backend == "pallas":
        from repro.kernels.decode_attention import ops as da_ops
        B = q.shape[0]
        o = da_ops.decode_attention(q[:, 0], k_read, v_read, mask=mask)
        return o.reshape(B, 1, cfg.n_heads * cfg.head_dim).astype(out_dtype)
    raise ValueError(f"unknown decode backend {backend!r}")


def attention_decode(p: Params, x: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, write_pos: jnp.ndarray,
                     mask: jnp.ndarray, angles: jnp.ndarray, cfg: ArchConfig,
                     apply_rope_fn, backend: str = "sdpa",
                     k_scale=None, v_scale=None, active=None):
    """One-token decode.  x (B,1,D); cache (B,S_max,Hkv,hd).

    ``write_pos`` is the cache slot for the new K/V (== absolute pos for a
    full cache, pos % window for a ring cache) — scalar for a static
    batch, (B,) for per-slot positions (continuous batching); ``mask``
    (S_max,) or (B,S_max) marks valid slots (see ``decode_mask``).
    k_scale/v_scale (B,S_max,Hkv) enable the int8-quantised cache
    (repro.quant.kv).  ``active`` (B,) bool makes inactive lanes' cache
    writes per-lane no-ops (horizon-K fused ticks: lanes that hit EOS or
    their token budget mid-horizon stop mutating state on device).

    Returns (out, new_k, new_v[, new_k_scale, new_v_scale])."""
    from repro.quant import kv as kvq
    with scope("qkv"):
        q, k_new, v_new = _project_qkv(p, x, cfg)
        q = apply_rope_fn(q, angles)
        k_new = apply_rope_fn(k_new, angles)
    quantized = k_scale is not None
    with scope("kv_write"):
        if quantized:
            kq, ks = kvq.quantize_kv_write(k_new)
            vq, vs = kvq.quantize_kv_write(v_new)
            k_cache = _kv_write(k_cache, kq, write_pos, active)
            v_cache = _kv_write(v_cache, vq, write_pos, active)
            k_scale = _kv_write(k_scale, ks, write_pos, active)
            v_scale = _kv_write(v_scale, vs, write_pos, active)
        else:
            k_cache = _kv_write(k_cache, k_new, write_pos, active)
            v_cache = _kv_write(v_cache, v_new, write_pos, active)
    with scope("attend"):
        k_read, v_read = k_cache, v_cache    # sdpa folds scales; others
        if quantized and backend != "sdpa":  # take a dequantised view
            k_read = kvq.dequantize_kv(k_cache, k_scale, x.dtype)
            v_read = kvq.dequantize_kv(v_cache, v_scale, x.dtype)
        out = _decode_attend(q, k_read, v_read, mask, cfg, backend, x.dtype,
                             k_scale=k_scale if quantized else None,
                             v_scale=v_scale if quantized else None)
    from repro.quant.paths import matmul
    with scope("attn_out"):
        out = matmul(out, p["wo"])
    if quantized:
        return out, k_cache, v_cache, k_scale, v_scale
    return out, k_cache, v_cache


# --------------------------------------------------------------------------
# paged decode (slot -> block-table -> page-pool indirection)
# --------------------------------------------------------------------------

def paged_view(pool: jnp.ndarray, block_table: jnp.ndarray) -> jnp.ndarray:
    """Gather a slot-major contiguous K/V view out of a page pool.

    pool (n_pages, Hkv, page_size, hd) — or a scale pool (n_pages, Hkv,
    page_size); block_table (B, max_blocks) of page indices ->
    (B, max_blocks * page_size, Hkv, hd) (resp. (B, S, Hkv)).  Every
    slot's view has the same (constant) virtual length, so the decode
    step stays ONE compiled program; which physical pages back it is
    pure data."""
    B, max_blocks = block_table.shape
    Hkv, page = pool.shape[1:3]
    pages = jnp.swapaxes(jnp.take(pool, block_table, axis=0), 2, 3)
    return pages.reshape(B, max_blocks * page, Hkv, *pool.shape[3:])


def attention_decode_paged(p: Params, x: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           pos: jnp.ndarray, mask: jnp.ndarray,
                           angles: jnp.ndarray, cfg: ArchConfig,
                           apply_rope_fn, backend: str = "sdpa",
                           active=None, k_scale_pool=None,
                           v_scale_pool=None):
    """One-token decode through a paged KV cache.

    x (B,1,D); k_pool/v_pool (n_pages, Hkv, page_size, hd);
    block_table (B, max_blocks); pos (B,) absolute per-slot positions.
    The new K/V row is scattered into the slot's current page
    (``block_table[b, pos[b] // page_size]`` at offset
    ``pos[b] % page_size``), then the slot-major view is gathered and the
    regular masked decode backend runs over it.  ``mask`` is the
    (B, max_blocks*page_size) valid-slot mask (``decode_mask(pos, ...)``).

    Lanes whose block-table row points at the reserved garbage page
    (free / mid-prefill slots) write there and read finite junk — their
    outputs are discarded by the scheduler.  ``active`` (B,) bool
    redirects inactive lanes' writes to the garbage page and freezes
    their position (horizon-K fused ticks: lanes that finish mid-horizon
    stop touching their allocated pages).  Returns
    (out, new_k_pool, new_v_pool[, new_k_scale_pool, new_v_scale_pool]).

    k_scale_pool/v_scale_pool (n_pages, Hkv, page_size) switch the pool
    to the int8-quantised layout: the new row is quantised on write
    (codes into k_pool, per-head scale into k_scale_pool, same page/off
    — the scale pools share the block table), and reads dequantise per
    route (in-register in the fused kernel; a materialised model-dtype
    view on the gather reference).

    ``backend="pallas"`` runs the fused paged kernel
    (kernels/paged_decode_attention): the gather is fused into the SDPA
    sweep and pages are read in place, so per-step KV traffic follows
    *allocated* pages instead of 3x the constant virtual view.  Every
    other backend takes the gather+SDPA reference route through the
    materialised ``paged_view``."""
    with scope("qkv"):
        q, k_new, v_new = _project_qkv(p, x, cfg)
        q = apply_rope_fn(q, angles)
        k_new = apply_rope_fn(k_new, angles)
    page_size = k_pool.shape[2]
    quantized = k_scale_pool is not None
    with scope("kv_write"):
        page = jnp.take_along_axis(block_table, (pos // page_size)[:, None],
                                   axis=1)[:, 0]
        if active is not None:
            page = jnp.where(active, page, 0)   # 0 = reserved garbage page
        off = pos % page_size
        if quantized:
            from repro.quant import kv as kvq
            k_new, ks = kvq.quantize_kv_write(k_new)
            v_new, vs = kvq.quantize_kv_write(v_new)
            k_scale_pool = k_scale_pool.at[page, :, off].set(ks[:, 0])
            v_scale_pool = v_scale_pool.at[page, :, off].set(vs[:, 0])
        k_pool = k_pool.at[page, :, off].set(k_new[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[page, :, off].set(v_new[:, 0].astype(v_pool.dtype))
    with scope("attend"):
        # routing (fused in-place kernel vs gathered-view reference)
        # lives in _decode_attend; a slot's live length is pos+1 (the
        # row just written), matching decode_mask(pos, ...) exactly
        paged = (k_pool, v_pool, block_table, pos + 1)
        if quantized:
            paged = paged + (k_scale_pool, v_scale_pool)
        out = _decode_attend(q, None, None, mask, cfg, backend, x.dtype,
                             paged=paged)
    from repro.quant.paths import matmul
    with scope("attn_out"):
        out = matmul(out, p["wo"])
    if quantized:
        return out, k_pool, v_pool, k_scale_pool, v_scale_pool
    return out, k_pool, v_pool


def attention_prefill_paged(p: Params, x: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, slot_pages: jnp.ndarray,
                            start_pos: jnp.ndarray, angles: jnp.ndarray,
                            cfg: ArchConfig, apply_rope_fn,
                            k_scale_pool=None, v_scale_pool=None):
    """Prefill one chunk of ONE session through the paged cache.

    x (1, C, D) is the chunk's hidden states; ``slot_pages``
    (max_blocks,) is the session's block-table row; ``start_pos`` is the
    (page-aligned, traced) absolute position of chunk token 0.  The
    chunk's K/V are written into the slot's pages, then the chunk
    attends causally over the cached prefix + itself through the
    gathered view — exact math (masked positions contribute exact
    zeros), so chunked prefill is token-identical to whole-prompt
    prefill.  k_scale_pool/v_scale_pool select the int8-quantised pool
    layout: the chunk quantises per token on write and the attention
    reads a dequantised view, so quantisation commutes with chunking
    (chunked == whole-prompt stays exact).  Returns
    (out (1, C, D), new_k_pool, new_v_pool[, new scale pools])."""
    _, C, _ = x.shape
    page_size = k_pool.shape[2]
    with scope("qkv"):
        q, k_new, v_new = _project_qkv(p, x, cfg)
        q = apply_rope_fn(q, angles)
        k_new = apply_rope_fn(k_new, angles)
    n_chunk_pages = -(-C // page_size)
    pad = n_chunk_pages * page_size - C

    def to_pages(t, dtype):   # (1, C, Hkv, ...) -> (n_pages_c, Hkv, page, ...)
        t = jnp.pad(t[0], ((0, pad),) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((n_chunk_pages, page_size) + t.shape[1:])
        return jnp.swapaxes(t, 1, 2).astype(dtype)

    quantized = k_scale_pool is not None
    if quantized:
        from repro.quant import kv as kvq
    with scope("kv_write"):
        first = start_pos // page_size
        idx = jax.lax.dynamic_slice_in_dim(slot_pages, first, n_chunk_pages)
        if quantized:
            k_new, ks = kvq.quantize_kv_write(k_new)
            v_new, vs = kvq.quantize_kv_write(v_new)
            k_scale_pool = k_scale_pool.at[idx].set(to_pages(ks, jnp.float32))
            v_scale_pool = v_scale_pool.at[idx].set(to_pages(vs, jnp.float32))
        k_pool = k_pool.at[idx].set(to_pages(k_new, k_pool.dtype))
        v_pool = v_pool.at[idx].set(to_pages(v_new, v_pool.dtype))
    with scope("attend"):
        rows = slot_pages[None, :]
        if quantized:
            k_view = kvq.dequantize_kv(paged_view(k_pool, rows),
                                       paged_view(k_scale_pool, rows),
                                       x.dtype)
            v_view = kvq.dequantize_kv(paged_view(v_pool, rows),
                                       paged_view(v_scale_pool, rows),
                                       x.dtype)
        else:
            k_view = paged_view(k_pool, rows)
            v_view = paged_view(v_pool, rows)
        virtual = k_view.shape[1]
        qpos = start_pos + jnp.arange(C)
        mask = jnp.arange(virtual)[None, :] <= qpos[:, None]  # (C, virtual)
        scores = _gqa_scores(q, k_view.astype(q.dtype), cfg)  # (1,K,G,C,virt)
        scores = jnp.where(mask[None, None, None, :, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v_view.astype(q.dtype), cfg).astype(x.dtype)
    from repro.quant.paths import matmul
    with scope("attn_out"):
        out = matmul(out, p["wo"])
    if quantized:
        return out, k_pool, v_pool, k_scale_pool, v_scale_pool
    return out, k_pool, v_pool
