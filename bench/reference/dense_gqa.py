"""Plain reference of a dense decoder with grouped-query attention, as
the Qwen2 and InternLM2 model cards define it: RMSNorm before attention
and before the SwiGLU MLP, rotary position embedding (rotate-half), q/k/v
biases where the configuration has them, causal softmax attention with
each KV head shared by heads/kv_heads query heads, a final RMSNorm and
an output head (the embedding, where tied).

Everything runs in float32 with ``Precision.HIGHEST`` products, layer by
layer and in blocks of query rows, over the bf16 weights the benchmark
made from the seed (upcast).  It imports nothing of the program.

``quant="fp8"`` is the lower-precision control: the operands of every
linear layer (the output head included) are rounded to float8 e4m3,
weights per output column and activations per row, each scaled by its
largest magnitude over 448; attention and norms stay float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant", "block"))
def _layer(x, blocks, layer, cos, sin, *, dims, quant, block):
    hq, hkv, hd, eps, bias = dims
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, layer, keepdims=False).astype(jnp.float32), blocks)
    a = p["attn"]
    T = x.shape[0]
    h = _rms(x, p["norm1"], eps)
    q, k, v = (_mm(h, a["wq"], quant), _mm(h, a["wk"], quant),
               _mm(h, a["wv"], quant))
    if bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(T, hq, hd), cos, sin)
    k = jnp.repeat(_rope(k.reshape(T, hkv, hd), cos, sin), hq // hkv, 1)
    v = jnp.repeat(v.reshape(T, hkv, hd), hq // hkv, 1)
    kpos = jnp.arange(T)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        qpos = i * block + jnp.arange(block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(attend, jnp.arange(T // block)).reshape(T, hq * hd)
    x = x + _mm(o, a["wo"], quant)
    m = p["mlp"]
    h = _rms(x, p["norm2"], eps)
    g = jax.nn.silu(_mm(h, m["gate"], quant)) * _mm(h, m["up"], quant)
    return x + _mm(g, m["down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, rows, norm, head, *, eps, quant):
    xs = _rms(x[rows], norm.astype(jnp.float32), eps)
    return _mm(xs, head.astype(jnp.float32).T, quant)


def _rope_tables(T: int, hd: int, theta: float):
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def logits_at(cfg: Dict, params: Dict, tokens: Sequence[int],
              rows: Sequence[int], quant: Optional[str] = None) -> jax.Array:
    """Logits (len(rows), vocab) f32 of ``tokens`` at positions ``rows``."""
    n = len(tokens)
    # lengths padded to a power of two (multiples of 1024 past it), so a
    # few compiled programs serve every request
    block = min(1024, 1 << max(4, (n - 1).bit_length()))
    T = -(-n // block) * block
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // hq
    bias = bool(cfg.get("attention_bias", cfg.get("bias", False)))
    eps = float(cfg["rms_norm_eps"])
    ids = np.zeros(T, np.int32)
    ids[:n] = np.asarray(tokens, np.int32)
    x = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    cos, sin = _rope_tables(T, hd, float(cfg["rope_theta"]))
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer(x, params["blocks"], layer, cos, sin,
                   dims=(hq, hkv, hd, eps, bias), quant=quant, block=block)
    head = params["embed"] if cfg["tie_word_embeddings"] else params["lm_head"]
    return _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                 params["final_norm"], head, eps=eps, quant=quant)
