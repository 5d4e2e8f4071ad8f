"""Model assembly for all six families (dense / moe / ssm / hybrid /
vlm / audio).

Pure-functional API (params are pytrees; ``jax.eval_shape`` over ``init``
gives the allocation-free abstract trees the dry-run lowers with):

    m = Model(cfg)
    params = m.init(key)
    logits, aux = m.forward(params, batch)          # train / full-seq
    loss, metrics = m.loss(params, batch)
    cache = m.init_cache(batch_size, max_len)
    logits, cache = m.prefill(params, batch, cache)
    logits, cache = m.decode_step(params, cache, tokens)
    program = m.step_program(params, cache_len, batch)  # dispatch A/B

Layer stacks are scanned (stacked params, MaxText-style) so compile time
is depth-independent; ``unroll=True`` switches to a Python loop for
dry-run cost-analysis fidelity (XLA counts while bodies once).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.dispatch import StepProgram
from repro.core.spans import scope
from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.models.common import (apply_norm, apply_rope, cross_entropy,
                                 embed_init, init_mlp, init_norm,
                                 make_angle_fn, mlp_forward)

Params = Dict[str, Any]
Cache = Dict[str, Any]


def _stack_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


class Model:
    def __init__(self, cfg: ArchConfig, *, decode_backend: str = "sdpa",
                 ssd_chunk: int = mamba2.DEFAULT_CHUNK, remat: str = "none"):
        self.cfg = cfg
        self.decode_backend = decode_backend
        self.ssd_chunk = ssd_chunk
        self.remat = remat   # none | blocks (checkpoint each scan body)
        self.angle_fn = make_angle_fn(cfg) if cfg.n_heads else None
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def _maybe_remat(self, body):
        """Block-level rematerialisation: the scan body saves only its
        carry; internals (scores, MLP intermediates) recompute in bwd.
        Wrapping the whole loss in jax.checkpoint does NOT reduce scan
        residuals — the recompute rebuilds them — so remat must live at
        the body (measured in EXPERIMENTS.md §Dry-run)."""
        if self.remat == "none":
            return body
        return jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _init_block(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 4)
        if cfg.family in ("ssm", "hybrid"):
            return {"norm1": init_norm(cfg, dt),
                    "mamba": mamba2.init_mamba(ks[0], cfg, dt)}
        p: Params = {
            "norm1": init_norm(cfg, dt),
            "attn": attn.init_attention(ks[0], cfg, dt),
            "norm2": init_norm(cfg, dt),
        }
        if cfg.family == "moe":
            p["moe"] = moe.init_moe(ks[1], cfg, dt)
        else:
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated, dt)
        return p

    def _init_shared_attn(self, key) -> Params:
        """Zamba2-style shared attention+MLP block (one set of weights)."""
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 2)
        return {
            "norm1": init_norm(cfg, dt),
            "attn": attn.init_attention(ks[0], cfg, dt),
            "norm2": init_norm(cfg, dt),
            "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated, dt),
        }

    def init(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 5)
        n_tables = max(1, cfg.n_codebooks)
        if n_tables == 1:
            embed = embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt)
        else:
            embed = jax.vmap(
                lambda k: embed_init(k, cfg.vocab_size, cfg.d_model, dt)
            )(jax.random.split(ks[0], n_tables))
        params: Params = {
            "embed": embed,
            "blocks": _stack_init(self._init_block, ks[1], cfg.n_layers),
            "final_norm": init_norm(cfg, dt),
        }
        if cfg.family == "hybrid":
            params["shared_attn"] = self._init_shared_attn(ks[2])
        if not cfg.tie_embeddings:
            if n_tables == 1:
                params["lm_head"] = embed_init(ks[3], cfg.vocab_size, cfg.d_model, dt)
            else:
                params["lm_head"] = jax.vmap(
                    lambda k: embed_init(k, cfg.vocab_size, cfg.d_model, dt)
                )(jax.random.split(ks[3], n_tables))
        return params

    def abstract_params(self, seed: int = 0):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(seed)))

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------
    def embed_tokens(self, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.n_codebooks:
            # tokens (B, S, K): sum of per-codebook embeddings (MusicGen)
            parts = [jnp.take(params["embed"][k], tokens[..., k], axis=0)
                     for k in range(cfg.n_codebooks)]
            return functools.reduce(jnp.add, parts)
        return jnp.take(params["embed"], tokens, axis=0)

    def lm_logits(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        if cfg.n_codebooks:
            return jnp.einsum("bsd,kvd->bskv", x, head)
        return x @ head.T

    # ------------------------------------------------------------------
    # blocks: full-sequence
    # ------------------------------------------------------------------
    def _mlp_block(self, bp: Params, x):
        """norm2, the MLP (or MoE) and the residual add: ``(x, aux)``."""
        cfg = self.cfg
        with scope("mlp"):
            h = apply_norm(x, bp["norm2"])
            if cfg.family == "moe":
                m_out, aux = moe.moe_forward(bp["moe"], h, cfg)
            else:
                m_out, aux = mlp_forward(bp["mlp"], h, cfg.mlp_gated), 0.0
            return x + m_out, aux

    def _head(self, params: Params, x):
        """Final norm and logits."""
        with scope("head"):
            return self.lm_logits(params, apply_norm(x, params["final_norm"]))

    def _attn_block_full(self, bp: Params, x, angles):
        with scope("qkv"):
            h = apply_norm(x, bp["norm1"])
        a_out, (k, v) = attn.attention_full(bp["attn"], h, angles, self.cfg,
                                            apply_rope)
        with scope("attn_out"):
            x = x + a_out
        x, aux = self._mlp_block(bp, x)
        return x, aux, (k, v)

    def _mamba_block_full(self, bp: Params, x):
        y, h_fin, conv = mamba2.mamba_forward(
            bp["mamba"], apply_norm(x, bp.get("norm1")), self.cfg,
            chunk=self.ssd_chunk)
        return x + y, h_fin, conv

    # ------------------------------------------------------------------
    # forward (train / prefill backbone)
    # ------------------------------------------------------------------
    def _positions(self, batch: Dict, B: int, S: int):
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        return pos

    def backbone(self, params: Params, batch: Dict, *, collect_cache: bool = False,
                 unroll: bool = False):
        """Full-sequence backbone.  Returns (hidden, aux, layer_caches)."""
        cfg = self.cfg
        unroll = unroll or getattr(self, "unroll_layers", False)
        x = batch.get("embeds")
        tokens = batch.get("tokens")
        if x is None:
            with scope("embed"):
                x = self.embed_tokens(params, tokens)
        elif tokens is not None and cfg.family == "vlm":
            # merged stream: embeds already contain patch + text embeddings
            pass
        B, S = x.shape[0], x.shape[1]
        angles = self.angle_fn(self._positions(batch, B, S)) if self.angle_fn else None

        if cfg.family in ("dense", "vlm", "audio", "moe"):
            def body(carry, bp):
                h, aux = carry
                h, aux_l, (k, v) = self._attn_block_full(bp, h, angles)
                ys = (k, v) if collect_cache else None
                return (h, aux + aux_l), ys

            aux0 = jnp.float32(0.0)
            body = self._maybe_remat(body)
            if unroll:
                aux, kvs = aux0, []
                for i in range(cfg.n_layers):
                    bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                    (x, aux), ys = body((x, aux), bp)
                    if collect_cache:
                        kvs.append(ys)
                layer_caches = (jnp.stack([kv[0] for kv in kvs]),
                                jnp.stack([kv[1] for kv in kvs])) if collect_cache else None
            else:
                (x, aux), kv = jax.lax.scan(body, (x, aux0), params["blocks"])
                layer_caches = kv if collect_cache else None
            return x, aux, layer_caches

        if cfg.family == "ssm":
            def body(carry, bp):
                h = carry
                h, h_fin, conv = self._mamba_block_full(bp, h)
                ys = (h_fin, conv) if collect_cache else None
                return h, ys

            body = self._maybe_remat(body)
            if unroll:
                states = []
                for i in range(cfg.n_layers):
                    bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                    x, ys = body(x, bp)
                    if collect_cache:
                        states.append(ys)
                stacked = ((jnp.stack([s[0] for s in states]),
                            jnp.stack([s[1] for s in states]))
                           if collect_cache else None)
                return x, jnp.float32(0.0), stacked
            x, states = jax.lax.scan(body, x, params["blocks"])
            return x, jnp.float32(0.0), (states if collect_cache else None)

        if cfg.family == "hybrid":
            return self._hybrid_backbone(params, x, angles, collect_cache)
        raise ValueError(cfg.family)

    def _hybrid_groups(self):
        cfg = self.cfg
        ae = cfg.attn_every
        starts = list(range(0, cfg.n_layers, ae))
        return [(s, min(s + ae, cfg.n_layers)) for s in starts]

    def _hybrid_backbone(self, params, x, angles, collect_cache):
        cfg = self.cfg
        groups = self._hybrid_groups()
        ssm_states, attn_caches = [], []
        for (g0, g1) in groups:
            # shared attention block at the start of each group
            sp = params["shared_attn"]
            a_out, (k, v) = attn.attention_full(
                sp["attn"], apply_norm(x, sp["norm1"]), angles, cfg, apply_rope)
            x = x + a_out
            x = x + mlp_forward(sp["mlp"], apply_norm(x, sp["norm2"]), cfg.mlp_gated)
            if collect_cache:
                attn_caches.append((k, v))
            gp = jax.tree_util.tree_map(lambda a: a[g0:g1], params["blocks"])

            def body(h, bp):
                h, h_fin, conv = self._mamba_block_full(bp, h)
                return h, (h_fin, conv)
            x, states = jax.lax.scan(self._maybe_remat(body), x, gp)
            if collect_cache:
                ssm_states.append(states)
        if collect_cache:
            h_fin = jnp.concatenate([s[0] for s in ssm_states], axis=0)
            conv = jnp.concatenate([s[1] for s in ssm_states], axis=0)
            ks = jnp.stack([c[0] for c in attn_caches], axis=0)
            vs = jnp.stack([c[1] for c in attn_caches], axis=0)
            return x, 0.0, ((h_fin, conv), (ks, vs))
        return x, 0.0, None

    def forward(self, params: Params, batch: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
        x, aux, _ = self.backbone(params, batch)
        return self._head(params, x), aux

    def loss(self, params: Params, batch: Dict, *, aux_weight: float = 0.01,
             z_loss: float = 0.0) -> Tuple[jnp.ndarray, Dict]:
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        ce = cross_entropy(logits, labels, z_loss)
        total = ce + aux_weight * aux
        return total, {"loss": total, "ce": ce, "aux": aux}

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int,
                   kv_dtype=None, slotted: bool = False,
                   paged: bool = False, page_size: int = 16,
                   n_pages: Optional[int] = None,
                   kv_quant: Optional[str] = None) -> Cache:
        """KV/state cache.  ``slotted=True`` makes ``pos`` a (batch,)
        vector of per-slot positions — the continuous-batching layout
        where each batch row is an independent session slot and the
        decode step stays ONE compiled program at constant shapes while
        sessions churn (see repro.serving.scheduler).

        ``paged=True`` (implies slotted) replaces the per-slot
        ``max_len`` K/V rows with a **page pool** plus a per-slot block
        table: ``k``/``v`` become (L, n_pages, Hkv, page_size, hd) and
        ``block_table`` (batch, max_blocks) maps each slot's virtual
        positions onto pool pages.  Page 0 is the reserved garbage
        sentinel (never allocated; free lanes point at it).  With
        ``n_pages < 1 + batch_size * max_blocks`` the pool is
        *oversubscribed*: slots no longer each reserve a full
        ``max_len`` row, capacity follows live tokens instead
        (repro.serving.scheduler manages allocation/reclaim).  Distinct
        slots' block tables may alias the SAME physical page (prefix
        sharing): aliased pages are read-only by convention — the
        scheduler CoW-copies (``copy_kv_page``) before any write could
        land in one.

        ``kv_quant="int8"`` (equivalently ``kv_dtype=jnp.int8``) stores
        K/V as int8 codes with per-(token, head) float32 scales.  On
        paged caches the scales ride parallel ``k_scale``/``v_scale``
        pools of shape (L, n_pages, Hkv, page_size) sharing the block
        table, so a page id addresses codes and scales together —
        allocation, CoW, tiering, and prefix sharing all work unchanged
        page-at-a-time."""
        cfg = self.cfg
        if kv_quant is not None:
            if kv_quant not in ("none", "int8"):
                raise ValueError(f"kv_quant must be none|int8, got {kv_quant!r}")
            if kv_quant == "int8":
                kv_dtype = jnp.int8
        kv_dtype = kv_dtype or self.dtype
        if paged:
            slotted = True
        if slotted and cfg.family not in ("dense", "vlm", "audio", "moe"):
            raise NotImplementedError(
                "slotted (continuous-batching) caches target the "
                f"attention families, got {cfg.family!r}")
        if paged:
            if cfg.sliding_window:
                raise NotImplementedError(
                    "paged KV + sliding-window (ring) caches not supported")
            assert page_size >= 1
            max_blocks = -(-max_len // page_size)
            if n_pages is None:
                n_pages = 1 + batch_size * max_blocks   # full backing
            assert n_pages >= 2, "need the garbage page plus >=1 real page"
            # head-major pages: the fused kernel's block is one page of
            # every head, tiled on its (page_size, hd) minor dims
            shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size,
                     cfg.head_dim)
            cache = {"k": jnp.zeros(shape, kv_dtype),
                     "v": jnp.zeros(shape, kv_dtype),
                     "pos": jnp.zeros((batch_size,), jnp.int32),
                     "block_table": jnp.zeros((batch_size, max_blocks),
                                              jnp.int32)}
            if kv_dtype == jnp.int8:
                # scale pools share the block table: page p's codes in
                # k[:, p] pair with its scales in k_scale[:, p]
                cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
                cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            return cache
        pos = (jnp.zeros((batch_size,), jnp.int32) if slotted
               else jnp.zeros((), jnp.int32))
        if cfg.family in ("dense", "vlm", "audio", "moe"):
            kv_len = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
            shape = (cfg.n_layers, batch_size, kv_len, cfg.n_kv_heads, cfg.head_dim)
            cache = {"k": jnp.zeros(shape, kv_dtype), "v": jnp.zeros(shape, kv_dtype),
                     "pos": pos}
            if kv_dtype == jnp.int8:
                # per-(token, head) scales: the int8 KV-quant path
                cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
                cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            return cache
        if cfg.family == "ssm":
            return {
                "h": jnp.zeros((cfg.n_layers, batch_size, cfg.n_ssm_heads,
                                cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((cfg.n_layers, batch_size, cfg.ssm_conv - 1,
                                   cfg.conv_channels), self.dtype),
                "pos": pos,
            }
        if cfg.family == "hybrid":
            n_apps = len(self._hybrid_groups())
            kv_len = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
            return {
                "h": jnp.zeros((cfg.n_layers, batch_size, cfg.n_ssm_heads,
                                cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((cfg.n_layers, batch_size, cfg.ssm_conv - 1,
                                   cfg.conv_channels), self.dtype),
                "k": jnp.zeros((n_apps, batch_size, kv_len, cfg.n_kv_heads,
                                cfg.head_dim), kv_dtype),
                "v": jnp.zeros((n_apps, batch_size, kv_len, cfg.n_kv_heads,
                                cfg.head_dim), kv_dtype),
                "pos": pos,
            }
        raise ValueError(cfg.family)

    def copy_kv_page(self, cache: Cache, src: jnp.ndarray,
                     dst: jnp.ndarray) -> Cache:
        """Copy one pool page — every layer's K and V rows — onto
        another: the copy-on-write fault of prefix sharing.  A session
        admitted onto shared pages whose next KV write would land in a
        page other sessions still read gets a private copy first
        (serving/scheduler.py); ``src``/``dst`` are traced scalars, so
        ONE compiled copy program serves every fault."""
        assert "block_table" in cache, "copy_kv_page targets paged caches"
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        return dict(cache, **{
            key: cache[key].at[:, dst].set(cache[key][:, src])
            for key in self._page_slab_keys(cache)})

    @staticmethod
    def _page_slab_keys(cache: Cache) -> Tuple[str, ...]:
        """Cache keys indexed (L, n_pages, ...) — everything a page id
        addresses.  Quantised pools carry scale slabs alongside codes."""
        if "k_scale" in cache:
            return ("k", "v", "k_scale", "v_scale")
        return ("k", "v")

    def save_kv_pages(self, cache: Cache, pages: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, ...]:
        """Gather ``pages`` (a (P,) id vector) out of the paged pool —
        every layer's K and V rows — as (L, P, page, ...) slabs: the
        device→host half of KV-page tiering (serving/memory/tiers.py).
        Returns one slab per page-indexed pool: (k, v) for bf16 caches,
        (k, v, k_scale, v_scale) for int8-quantised ones — codes and
        scales move together, bit-exact.  ``pages`` is traced, so one
        compiled program serves every save of the same P; callers pad
        P to a power of two with the garbage page to bound the program
        count."""
        assert "block_table" in cache, "save_kv_pages targets paged caches"
        pages = jnp.asarray(pages, jnp.int32)
        return tuple(cache[key][:, pages]
                     for key in self._page_slab_keys(cache))

    def restore_kv_pages(self, cache: Cache, pages: jnp.ndarray,
                         *slabs: jnp.ndarray) -> Cache:
        """Scatter saved KV slabs back into pool ``pages`` — the
        host→device half of tiering.  ``slabs`` must match
        ``save_kv_pages`` order ((k, v) or (k, v, k_scale, v_scale)).
        Padding lanes target the garbage page (a write sink by
        contract; duplicate scatter indices onto it are harmless)."""
        assert "block_table" in cache, "restore_kv_pages targets paged caches"
        keys = self._page_slab_keys(cache)
        assert len(slabs) == len(keys), (len(slabs), keys)
        pages = jnp.asarray(pages, jnp.int32)
        return dict(cache, **{
            key: cache[key].at[:, pages].set(slab.astype(cache[key].dtype))
            for key, slab in zip(keys, slabs)})

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def prefill(self, params: Params, batch: Dict, cache: Cache
                ) -> Tuple[jnp.ndarray, Cache]:
        """Populate the cache from a full prompt; returns last-pos logits."""
        cfg = self.cfg
        x, _, caches = self.backbone(params, batch, collect_cache=True)
        S = x.shape[1]

        def place(slab, dst, pre=None):
            """Write last min(S, kv_len) keys into the (possibly ring)
            cache so that token at absolute pos p lands at slot p % kv_len
            (no-op roll for full caches).  ``pre`` transforms the kept
            slab first (int8 KV quantisation)."""
            kv_len = dst.shape[2]
            s_eff = min(S, kv_len)
            kept = slab[:, :, S - s_eff:]
            kept = pre(kept) if pre is not None else kept.astype(dst.dtype)
            if s_eff == kv_len and S % kv_len:
                kept = jnp.roll(kept, S % kv_len, axis=2)
            return jax.lax.dynamic_update_slice_in_dim(dst, kept, 0, axis=2)

        quantized_kv = "k_scale" in cache

        with scope("kv_write"):
            if cfg.family in ("dense", "vlm", "audio", "moe"):
                k, v = caches    # (L, B, S, Hkv, hd) stacked by scan
                if quantized_kv:
                    from repro.quant import kv as kvq
                    kq, ks = kvq.quantize_kv_write(k)
                    vq, vs = kvq.quantize_kv_write(v)
                    cache = dict(cache,
                                 k=place(kq, cache["k"], pre=lambda t: t),
                                 v=place(vq, cache["v"], pre=lambda t: t),
                                 k_scale=place(ks[..., None], cache["k_scale"][..., None],
                                               pre=lambda t: t.astype(jnp.float32))[..., 0],
                                 v_scale=place(vs[..., None], cache["v_scale"][..., None],
                                               pre=lambda t: t.astype(jnp.float32))[..., 0])
                else:
                    cache = dict(cache, k=place(k, cache["k"]), v=place(v, cache["v"]))
            elif cfg.family == "ssm":
                h, conv = caches
                cache = dict(cache, h=h, conv=conv.astype(cache["conv"].dtype))
            else:  # hybrid
                (h, conv), (ks, vs) = caches
                cache = dict(cache, h=h, conv=conv.astype(cache["conv"].dtype),
                             k=place(ks, cache["k"]), v=place(vs, cache["v"]))
        cache["pos"] = jnp.asarray(S, jnp.int32)
        return self._head(params, x[:, -1:]), cache

    def prefill_into_slot(self, params: Params, batch: Dict, cache: Cache,
                          slot: jnp.ndarray) -> Tuple[jnp.ndarray, Cache]:
        """Prefill ONE session (batch-1 prompt) into one slot of a
        slotted cache (per-slot ``pos`` vector; see ``init_cache``).

        ``slot`` is a traced scalar, so admission into any slot reuses
        one compiled program per distinct prompt length; K/V land at
        positions ``0..S-1`` of the slot's row and ``pos[slot] = S``.
        Stale K/V beyond ``S`` from a previous occupant stay masked out
        by the per-slot length mask until overwritten.  Returns the
        last-position logits (1, 1, V) and the updated cache."""
        cfg = self.cfg
        if cfg.family not in ("dense", "vlm", "audio", "moe"):
            raise NotImplementedError(
                f"prefill_into_slot targets attention families, got "
                f"{cfg.family!r}")
        if "block_table" in cache:
            # paged cache: the whole prompt is one chunk (the scheduler
            # must have pointed block_table[slot] at allocated pages)
            return self.prefill_chunk_into_slot(params, batch, cache, slot,
                                                jnp.int32(0))
        x, _, caches = self.backbone(params, batch, collect_cache=True)
        S = x.shape[1]
        k, v = caches                            # (L, 1, S, Hkv, hd)
        kv_len = cache["k"].shape[2]
        assert x.shape[0] == 1, "prefill_into_slot takes a batch-1 prompt"
        assert S <= kv_len, (S, kv_len)
        zero = jnp.int32(0)
        start = (zero, jnp.asarray(slot, jnp.int32), zero, zero, zero)
        updates: Cache = {"pos": cache["pos"].at[slot].set(S)}
        with scope("kv_write"):
            if "k_scale" in cache:
                from repro.quant import kv as kvq
                k, ks = kvq.quantize_kv_write(k)
                v, vs = kvq.quantize_kv_write(v)
                updates.update(
                    k_scale=jax.lax.dynamic_update_slice(
                        cache["k_scale"], ks, start[:-1]),
                    v_scale=jax.lax.dynamic_update_slice(
                        cache["v_scale"], vs, start[:-1]))
            updates.update(
                k=jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), start),
                v=jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), start))
        cache = dict(cache, **updates)
        return self._head(params, x[:, -1:]), cache

    def prefill_chunk_into_slot(self, params: Params, batch: Dict,
                                cache: Cache, slot: jnp.ndarray,
                                start_pos: jnp.ndarray
                                ) -> Tuple[jnp.ndarray, Cache]:
        """Prefill one CHUNK of a session's prompt into a paged cache.

        ``batch["tokens"]`` is (1, C) — chunk tokens at absolute
        positions ``start_pos .. start_pos + C - 1``; ``start_pos`` must
        be page-aligned (chunk boundaries land on page boundaries, so a
        chunk's K/V writes cover whole pages).  The chunk attends over
        the session's cached prefix plus itself (exact math — see
        ``attention_prefill_paged``), so feeding a prompt chunk-by-chunk
        is token-identical to one whole-prompt prefill.  ``slot`` and
        ``start_pos`` are traced: one compiled program per distinct
        chunk length, amortised over all admissions.  Returns the
        chunk's last-position logits (1, 1, V) and the updated cache
        (``pos[slot] = start_pos + C``)."""
        cfg = self.cfg
        assert "block_table" in cache, "prefill_chunk_into_slot needs paged"
        tokens = batch["tokens"]
        assert tokens.shape[0] == 1, "chunk prefill takes one session"
        with scope("embed"):
            x = self.embed_tokens(params, tokens)
        C = x.shape[1]
        start_pos = jnp.asarray(start_pos, jnp.int32)
        positions = (start_pos + jnp.arange(C))[None, :]
        angles = self.angle_fn(positions)
        slot_pages = cache["block_table"][slot]

        quantized_kv = "k_scale" in cache
        slab_keys = self._page_slab_keys(cache)

        def body(h, inp):
            bp, pools = inp[0], inp[1:]
            with scope("qkv"):
                hn = apply_norm(h, bp["norm1"])
            res = attn.attention_prefill_paged(
                bp["attn"], hn, pools[0], pools[1],
                slot_pages, start_pos, angles, cfg, apply_rope,
                k_scale_pool=pools[2] if quantized_kv else None,
                v_scale_pool=pools[3] if quantized_kv else None)
            a_out, pools = res[0], res[1:]
            with scope("attn_out"):
                h = h + a_out
            return self._mlp_block(bp, h)[0], pools

        x, pools = jax.lax.scan(
            body, x,
            (params["blocks"],) + tuple(cache[key] for key in slab_keys))
        cache = dict(cache, pos=cache["pos"].at[slot].set(start_pos + C),
                     **dict(zip(slab_keys, pools)))
        return self._head(params, x[:, -1:]), cache

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _attn_block_decode(self, bp, x, k_cache, v_cache, write_pos, mask,
                           angles, backend=None, k_scale=None, v_scale=None,
                           active=None):
        with scope("qkv"):
            h = apply_norm(x, bp["norm1"])
        res = attn.attention_decode(
            bp["attn"], h, k_cache, v_cache,
            write_pos, mask, angles, self.cfg, apply_rope,
            backend=backend or self.decode_backend,
            k_scale=k_scale, v_scale=v_scale, active=active)
        with scope("attn_out"):
            x = x + res[0]
        return (self._mlp_block(bp, x)[0],) + res[1:]

    def _attn_block_decode_paged(self, bp, x, k_pool, v_pool, block_table,
                                 pos, mask, angles, backend=None,
                                 active=None, k_scale_pool=None,
                                 v_scale_pool=None):
        with scope("qkv"):
            h = apply_norm(x, bp["norm1"])
        res = attn.attention_decode_paged(
            bp["attn"], h, k_pool, v_pool,
            block_table, pos, mask, angles, self.cfg, apply_rope,
            backend=backend or self.decode_backend, active=active,
            k_scale_pool=k_scale_pool, v_scale_pool=v_scale_pool)
        with scope("attn_out"):
            x = x + res[0]
        return (self._mlp_block(bp, x)[0],) + res[1:]

    def _mamba_block_decode(self, bp, x, h, conv):
        y, h, conv = mamba2.mamba_decode_step(
            bp["mamba"], apply_norm(x, bp.get("norm1")), h, conv, self.cfg)
        return x + y, h, conv

    # staticcheck: hotpath
    def decode_step(self, params: Params, cache: Cache, tokens: jnp.ndarray,
                    active: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, Cache]:
        """One new token per sequence.  tokens (B,1) or (B,1,K).

        With a slotted cache (``pos`` is a (B,) vector) every sequence
        advances at its own position: per-slot write offsets and (B, S)
        length masks, same compiled program every step regardless of
        which sessions occupy which slots.

        With a paged cache the step goes through
        ``attention_decode_paged``; the model's ``decode_backend``
        selects the route — ``"pallas"`` runs the fused block-table
        kernel (pages read in place, no gathered view), anything else
        the gather+SDPA reference.

        ``active`` (B,) bool (slotted caches, attention families only)
        turns inactive lanes into device-side no-ops: their K/V write is
        clamped (contiguous: row rewrite; paged: redirected to the
        garbage page) and their position does not advance.  This is what
        lets a horizon-K fused tick (``decode_steps``) carry lanes that
        hit EOS or their token budget mid-horizon without corrupting
        their cache — their (garbage) logits still come out and are
        discarded by the sampler clamp."""
        cfg = self.cfg
        with scope("embed"):
            x = self.embed_tokens(params, tokens)
        B = x.shape[0]
        pos = cache["pos"]
        slotted = pos.ndim == 1
        paged = "block_table" in cache
        if active is not None:
            if not slotted or cfg.family not in ("dense", "vlm", "audio",
                                                 "moe"):
                raise NotImplementedError(
                    "active-lane masking targets slotted caches of the "
                    "attention families")
            active = jnp.asarray(active, bool)
        if self.angle_fn:
            if paged:
                # virtual per-slot length = block-table span; the write
                # position is resolved through the block table inside
                # attention_decode_paged
                kv_len = cache["block_table"].shape[1] * cache["k"].shape[3]
                ring, write_pos = False, pos
            else:
                kv_len = cache["k"].shape[2]
                ring = bool(cfg.sliding_window) and kv_len <= cfg.sliding_window
                write_pos = pos % kv_len if ring else pos
            mask = attn.decode_mask(pos, kv_len, ring=ring)
            positions = (pos[:, None] if slotted
                         else jnp.broadcast_to(pos[None, None], (B, 1)))
            angles = self.angle_fn(positions)
        else:
            angles, mask, write_pos = None, None, pos

        new_cache = dict(cache)
        quantized_kv = "k_scale" in cache
        if cfg.family in ("dense", "vlm", "audio", "moe"):
            if paged:
                block_table = cache["block_table"]
                slab_keys = self._page_slab_keys(cache)

                def body(h, inp):
                    bp, pools = inp[0], inp[1:]
                    res = self._attn_block_decode_paged(
                        bp, h, pools[0], pools[1], block_table, pos, mask,
                        angles, active=active,
                        k_scale_pool=pools[2] if quantized_kv else None,
                        v_scale_pool=pools[3] if quantized_kv else None)
                    return res[0], res[1:]
                x, pools = jax.lax.scan(
                    body, x,
                    (params["blocks"],)
                    + tuple(cache[key] for key in slab_keys))
                new_cache.update(zip(slab_keys, pools))
            elif quantized_kv:
                def body(h, inp):
                    bp, kc, vc, ks, vs = inp
                    h, kc, vc, ks, vs = self._attn_block_decode(
                        bp, h, kc, vc, write_pos, mask, angles,
                        k_scale=ks, v_scale=vs, active=active)
                    return h, (kc, vc, ks, vs)
                x, (k, v, ks, vs) = jax.lax.scan(
                    body, x, (params["blocks"], cache["k"], cache["v"],
                              cache["k_scale"], cache["v_scale"]))
                new_cache.update(k=k, v=v, k_scale=ks, v_scale=vs)
            else:
                def body(h, inp):
                    bp, kc, vc = inp
                    h, kc, vc = self._attn_block_decode(bp, h, kc, vc, write_pos,
                                                        mask, angles,
                                                        active=active)
                    return h, (kc, vc)
                x, (k, v) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
                new_cache.update(k=k, v=v)
        elif cfg.family == "ssm":
            def body(h, inp):
                bp, hs, conv = inp
                h, hs, conv = self._mamba_block_decode(bp, h, hs, conv)
                return h, (hs, conv)
            x, (hs, conv) = jax.lax.scan(body, x, (params["blocks"], cache["h"], cache["conv"]))
            new_cache.update(h=hs, conv=conv)
        else:  # hybrid
            groups = self._hybrid_groups()
            hs_out, conv_out, k_out, v_out = [], [], [], []
            sp = params["shared_attn"]
            for a, (g0, g1) in enumerate(groups):
                x2, kc, vc = self._attn_block_decode(
                    sp, x, cache["k"][a], cache["v"][a], write_pos, mask, angles)
                x = x2
                k_out.append(kc)
                v_out.append(vc)
                gp = jax.tree_util.tree_map(lambda arr: arr[g0:g1], params["blocks"])

                def body(h, inp):
                    bp, hs, conv = inp
                    h, hs, conv = self._mamba_block_decode(bp, h, hs, conv)
                    return h, (hs, conv)
                x, (hs, conv) = jax.lax.scan(
                    body, x, (gp, cache["h"][g0:g1], cache["conv"][g0:g1]))
                hs_out.append(hs)
                conv_out.append(conv)
            new_cache.update(h=jnp.concatenate(hs_out, axis=0),
                             conv=jnp.concatenate(conv_out, axis=0),
                             k=jnp.stack(k_out, axis=0), v=jnp.stack(v_out, axis=0))
        new_cache["pos"] = (pos + 1 if active is None
                            else pos + active.astype(jnp.int32))
        return self._head(params, x), new_cache

    # staticcheck: hotpath
    def decode_steps(self, params: Params, cache: Cache, tokens: jnp.ndarray,
                     key: jnp.ndarray, steps_left: Optional[jnp.ndarray] = None,
                     *, horizon: int, temperature: float = 0.0,
                     top_k: int = 0, eos_id: Optional[int] = None
                     ) -> Tuple[jnp.ndarray, Cache]:
        """Advance every sequence up to ``horizon`` tokens inside ONE
        compiled program: ``lax.scan`` over ``decode_step`` with
        on-device sampling (greedy argmax, or categorical with
        ``fold_in(key, step)`` per-step keys), returning the token
        matrix (B, horizon) in a single transfer.

        This is the paper's CUDA-Graphs lesson applied across steps: the
        per-token host round-trip (Python + dispatch + sync) is paid
        once per *macro-tick* instead of once per token.

        ``steps_left`` (B,) int32 caps each lane's real steps (slotted
        caches, attention families): a lane stops being ``active`` once
        its budget is spent or — with ``eos_id`` set — once it samples
        EOS, after which its cache writes are no-ops, its position
        freezes, and its emitted tokens repeat the last real one (the
        host trims by its own ``steps_left``/EOS accounting, so the
        padding is never observed).  ``steps_left=None`` runs every lane
        for the full horizon (the single-stream fused-generation path —
        any family, any cache layout).

        Greedy streams are token-identical to ``horizon=1`` stepping;
        stochastic sampling draws from the same family but under
        per-step folded keys (one key per device step, as the
        single-step scheduler does per tick)."""
        from repro.serving.sampling import sample
        masked = steps_left is not None
        if masked:
            if self.cfg.n_codebooks:
                raise NotImplementedError(
                    "steps_left masking serves single-codebook archs")
            steps_left = jnp.asarray(steps_left, jnp.int32)
        if eos_id is not None and not masked:
            raise NotImplementedError("eos_id requires steps_left masking")

        def body(carry, step):
            cache, tok, alive = carry
            active = (alive & (step < steps_left)) if masked else None
            logits, cache = self.decode_step(params, cache, tok,
                                             active=active)
            with scope("sample"):
                k = jax.random.fold_in(key, step)
                nxt = sample(logits[:, -1], k, temperature=temperature,
                             top_k=top_k)
                if masked:
                    nxt = jnp.where(active, nxt, tok[:, 0])
                    if eos_id is not None:
                        alive = alive & ~(active & (nxt == eos_id))
            return (cache, nxt[:, None], alive), nxt

        alive0 = jnp.ones((tokens.shape[0],), bool)
        (cache, _, _), toks = jax.lax.scan(body, (cache, tokens, alive0),
                                           jnp.arange(horizon))
        return jnp.moveaxis(toks, 0, 1), cache

    # ------------------------------------------------------------------
    # dispatch A/B decomposition (paper §5)
    # ------------------------------------------------------------------
    def step_program(self, params: Params, cache: Cache) -> StepProgram:
        """Decompose decode_step into [embed] + [block_i]* + [head] stages
        over a state dict, for the eager / stage_jit / full_jit A/B.
        Attention-family archs only (the A/B targets the paper's models).

        Block stages mirror ``decode_step``'s cache semantics exactly —
        ring (sliding-window) write offsets/masks and int8-KV scale
        threading included — so the A/B touches the launch term and ONLY
        the launch term on every cache layout it accepts."""
        cfg = self.cfg
        assert cfg.family in ("dense", "vlm", "audio", "moe")
        if cache is not None and "block_table" in cache:
            raise NotImplementedError(
                "step_program does not decompose the paged decode step; "
                "paged serving runs the full_jit arm only")

        def embed_stage(state):
            tokens = state["tokens"]
            with scope("embed"):
                x = self.embed_tokens(params, tokens)
            B = x.shape[0]
            pos = state["cache"]["pos"]
            positions = (pos[:, None] if pos.ndim == 1
                         else jnp.broadcast_to(pos[None, None], (B, 1)))
            return dict(state, x=x, angles=self.angle_fn(positions))

        def make_block_stage(i):
            bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])

            def stage(state):
                c = state["cache"]
                kv_len = c["k"].shape[2]
                # mirror decode_step's ring handling: once pos >= kv_len
                # the write must wrap (pos % kv_len) and the mask must
                # treat every slot as in-window, else the update clamps
                # to the last slot and attention silently goes wrong
                ring = bool(cfg.sliding_window) and kv_len <= cfg.sliding_window
                write_pos = c["pos"] % kv_len if ring else c["pos"]
                mask = attn.decode_mask(c["pos"], kv_len, ring=ring)
                if "k_scale" in c:
                    x, kc, vc, ks, vs = self._attn_block_decode(
                        bp, state["x"], c["k"][i], c["v"][i], write_pos,
                        mask, state["angles"],
                        k_scale=c["k_scale"][i], v_scale=c["v_scale"][i])
                    c = dict(c, k=c["k"].at[i].set(kc),
                             v=c["v"].at[i].set(vc),
                             k_scale=c["k_scale"].at[i].set(ks),
                             v_scale=c["v_scale"].at[i].set(vs))
                else:
                    x, kc, vc = self._attn_block_decode(
                        bp, state["x"], c["k"][i], c["v"][i], write_pos,
                        mask, state["angles"])
                    c = dict(c, k=c["k"].at[i].set(kc),
                             v=c["v"].at[i].set(vc))
                return dict(state, x=x, cache=c)
            return stage

        def head_stage(state):
            c = dict(state["cache"])
            c["pos"] = c["pos"] + 1
            return dict(state, logits=self._head(params, state["x"]), cache=c)

        stages = [embed_stage] + [make_block_stage(i) for i in range(cfg.n_layers)] \
            + [head_stage]
        return StepProgram(stages)


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation) — dry-run inputs
# --------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, *, seq_len: int, batch: int, kind: str
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract inputs for train/prefill/decode steps.

    vlm: precomputed patch embeddings replace token embedding lookups for
    the full-seq shapes (frontend stub per the assignment); decode feeds
    tokens.  audio: per-codebook token ids.
    """
    i32, bf16 = jnp.int32, jnp.bfloat16
    if kind in ("train", "prefill"):
        if cfg.family == "vlm":
            specs = {
                "embeds": jax.ShapeDtypeStruct((batch, seq_len, cfg.d_model), bf16),
                "positions": jax.ShapeDtypeStruct((batch, seq_len, 3), i32),
            }
        elif cfg.family == "audio":
            specs = {"tokens": jax.ShapeDtypeStruct(
                (batch, seq_len, cfg.n_codebooks), i32)}
        else:
            specs = {"tokens": jax.ShapeDtypeStruct((batch, seq_len), i32)}
        if kind == "train":
            lab_shape = ((batch, seq_len, cfg.n_codebooks) if cfg.family == "audio"
                         else (batch, seq_len))
            specs["labels"] = jax.ShapeDtypeStruct(lab_shape, i32)
        return specs
    # decode: one new token, KV cache of seq_len handled separately
    if cfg.family == "audio":
        return {"tokens": jax.ShapeDtypeStruct((batch, 1, cfg.n_codebooks), i32)}
    return {"tokens": jax.ShapeDtypeStruct((batch, 1), i32)}
