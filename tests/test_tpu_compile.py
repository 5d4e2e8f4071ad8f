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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
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
def test_paged_decode_attention_compiles(one_chip, kv_dtype):
    Hq, Hkv, hd = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    n_pages = 1 + SLOTS * MAX_BLOCKS
    pool = _spec((n_pages, Hkv, PAGE, hd), kv_dtype, one_chip)
    args = [_spec((SLOTS, Hq, hd), jnp.bfloat16, one_chip), pool, pool,
            _spec((SLOTS, MAX_BLOCKS), jnp.int32, one_chip),
            _spec((SLOTS,), jnp.int32, one_chip)]
    if kv_dtype == jnp.int8:
        scales = _spec((n_pages, Hkv, PAGE), jnp.float32, one_chip)
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
