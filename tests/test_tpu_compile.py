"""Real-width compiles of the serving path's Pallas kernels for a TPU v5e
that is described, not attached.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes that do not tile, ops Mosaic cannot
lower, more VMEM than a kernel may use.  These tests hand the TPU
compiler the kernels at Qwen2.5-3B widths (16/2 heads, head_dim 128,
d_model 2048, d_ff 11008) and the page size serving uses, so a refusal
shows up here, not in chip time.  Nothing runs: a pass says the kernel
compiles, not that it is fast or right (the interpret-mode tests and
``chip_smoke.py`` cover results).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU compiler's library, and it
keeps it until it exits.
"""
import contextlib
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import attention as attention_mod
from repro.models import model as model_mod
from repro.kernels.int4_matmul.int4_matmul import int4_matmul_pallas
from repro.kernels.int4_matmul.ops import blocks
from repro.kernels.paged_decode_attention.paged_decode_attention import (
    paged_decode_attention_pallas)
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas

CFG = get_config("qwen2.5-3b")
PAGE = 16            # launch/serve.py --page-size default
SLOTS, MAX_BLOCKS = 4, 128       # 2048 tokens of context per slot


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; return its HLO text."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
# 4 x 128 pages: 2048 tokens a slot; 8 x 608: serve_long's 8 lanes of
# 9,728 tokens (bench/traffic/serve_long.json), a table of 19 blocks;
# page 8, the smallest page the walk places; page 4, which takes the page
# grid
@pytest.mark.parametrize("slots,max_blocks,page",
                         [(SLOTS, MAX_BLOCKS, PAGE), (8, 608, PAGE),
                          (SLOTS, 256, 8), (SLOTS, 512, 4)],
                         ids=["4x128", "serve_long", "page8", "page4"])
def test_paged_decode_attention_compiles(one_chip, kv_dtype, slots,
                                         max_blocks, page):
    Hq, Hkv, hd = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    n_pages = 1 + slots * max_blocks
    pool = _spec((n_pages, Hkv, page, hd), kv_dtype, one_chip)
    args = [_spec((slots, Hq, hd), jnp.bfloat16, one_chip), pool, pool,
            _spec((slots, max_blocks), jnp.int32, one_chip),
            _spec((slots,), jnp.int32, one_chip)]
    if kv_dtype == jnp.int8:
        scales = _spec((n_pages, Hkv, page), jnp.float32, one_chip)
        args += [scales, scales]
    hlo = _compile(paged_decode_attention_pallas, *args)
    assert "tpu_custom_call" in hlo


# M=1 is a decode step; M=2048 a 2048-token prefill (quant/paths.py
# sends prefill's B*S rows through the same kernel), the largest x tile:
# bm=128 by all of K=11008 on the down projection
@pytest.mark.parametrize("M,K,N", [(1, CFG.d_model, CFG.d_ff),
                                   (1, CFG.d_ff, CFG.d_model),
                                   (2048, CFG.d_model, CFG.d_ff),
                                   (2048, CFG.d_ff, CFG.d_model)],
                         ids=["up", "down", "up-prefill", "down-prefill"])
def test_int4_matmul_compiles(one_chip, M, K, N):
    group = 128
    bm, bn, bk = blocks(M, K, N, group)
    hlo = _compile(
        lambda x, w, s: int4_matmul_pallas(x, w, s, group=group, bm=bm,
                                           bn=bn, bk=bk),
        _spec((-(-M // bm) * bm, K), jnp.bfloat16, one_chip),
        _spec((K // 2, N), jnp.uint8, one_chip),
        _spec((K // group, N), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles(one_chip):
    hlo = _compile(lambda x, w: rmsnorm_pallas(x, w, br=8),
                   _spec((8, CFG.d_model), jnp.bfloat16, one_chip),
                   _spec((CFG.d_model,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def _program(text: str) -> str:
    """Compiled HLO without what only names it: op metadata and the
    stack-frame tables that metadata points into."""
    lines = text.splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    return re.sub(r",? metadata=\{[^}]*\}", "",
                  "\n".join([lines[0]] + lines[first:]))


def _decode_step_hlo(one_chip, backend, paged, n_layers):
    """The decode step of the serving cells (batch 1 contiguous, or 4
    slots paged) at full width and ``n_layers``, compiled for the chip."""
    model = model_mod.Model(dataclasses.replace(CFG, n_layers=n_layers),
                            decode_backend=backend)
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    B = SLOTS if paged else 1
    cache = place(jax.eval_shape(lambda: model.init_cache(
        B, MAX_BLOCKS * PAGE, paged=paged, page_size=PAGE)))
    args = (place(model.abstract_params()), cache,
            _spec((B, 1), jnp.int32, one_chip))
    return jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        *args).compile().as_text()


STEPS = pytest.mark.parametrize("backend,paged", [("sdpa", False),
                                                  ("pallas", True)],
                                ids=["contiguous", "paged-fused"])


@STEPS
def test_scopes_change_only_metadata(one_chip, monkeypatch, backend, paged):
    """The device scopes (core/spans.py) name ops and build nothing: the
    decode step compiled for the chip with and without them is one
    program.  Two layers at full width; the paged step's HLO numbers a
    few instructions differently, so there names are compared without
    their numbers."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def compiled():
        return _decode_step_hlo(one_chip, backend, paged, 2)

    scoped = compiled()
    assert "/mlp/" in scoped and "/kv_write/" in scoped
    for mod in (model_mod, attention_mod):
        monkeypatch.setattr(mod, "scope",
                            lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "/mlp/" not in bare
    a, b = _program(scoped), _program(bare)
    if paged:
        a, b = (re.sub(r"\.\d+\b", ".N", t) for t in (a, b))
    assert a == b


_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.-]+) = (.*?) ([a-z][a-z0-9-]*)"
                    r"\((.*)$")
_PASS_THROUGH = ("while", "tuple", "get-tuple-element", "bitcast",
                 "parameter")


def _ops_reading(text: str, shape: re.Pattern):
    """(op, op_name) of each op the chip runs as one (an instruction
    outside the fused computations) that has an operand of ``shape``;
    the loop and tuple plumbing that only passes arrays on is left
    out."""
    shapes, ops, fused, comp = {}, [], set(), None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            continue
        m = _INSTR.match(line)
        if m:
            name, out_shape, opcode, rest = m.groups()
            shapes[name] = out_shape
            if opcode == "fusion":
                fused |= set(re.findall(r"calls=(%[\w.-]+)", rest))
            ops.append((comp, name, opcode, rest))
    return [(name, re.findall(r'op_name="([^"]*)"', rest))
            for comp, name, opcode, rest in ops
            if comp not in fused and opcode not in _PASS_THROUGH
            and any(shape.search(shapes.get(o, "")) for o in
                    re.findall(r"%([\w.-]+)", rest.split("), ")[0]))]


@STEPS
def test_every_op_reading_an_mlp_weight_is_scoped_mlp(one_chip, monkeypatch,
                                                      backend, paged):
    """Every op of the decode step that reads an MLP weight
    ([2048,11008] or [11008,2048] at Qwen2.5-3B widths) carries the
    ``mlp`` scope, so a trace charges all MLP weight streaming to it.
    Four layers: at two, the chip's compiler prefetches the batch-1
    step's stacked down-projection in an unnamed copy, which it does not
    at the cells' depth."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mlp_weight = re.compile(r"\b(2048,11008|11008,2048)\]")
    readers = _ops_reading(_decode_step_hlo(one_chip, backend, paged, 4),
                           mlp_weight)
    assert readers
    assert all(n and "/mlp/" in n[0] for _, n in readers), readers
