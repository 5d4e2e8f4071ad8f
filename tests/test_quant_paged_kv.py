"""Quantised KV pages on the paged routes: edge-case contracts.

The int8 paged cache stores codes + per-(token, head) scales as
parallel pool slabs sharing one block table, so every page-granular
mechanism (CoW prefix forks, host-tier park/restore, the garbage
sentinel) must move codes and scales together.  These tests pin the
corners the happy-path identity checks (table15) can miss:

  * a zero K/V vector round-trips exactly through the scale epsilon,
  * the garbage sentinel page is never dequantised into a live lane on
    either route — even when poisoned with the worst representable
    content (codes 127, scale 1e30; finite on purpose, since masked
    probabilities are exact zeros and ``0 * finite == 0`` while
    ``0 * nan`` would hide a real leak as much as reveal one),
  * host-tier blobs carry all four slabs and restore bit-exactly,
  * CoW forks on a shared quantised page copy the scales with the
    codes,
  * chunked prefill equals whole-prompt prefill under int8 (per-token
    quantisation commutes with chunking),
  * the fused kernel over int8 pages matches the dequantised reference
    with NaN scales behind every dead page, on the model-dtype walk's
    page-16 case.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.kernels.paged_decode_attention.ops import paged_decode_attention
from repro.kernels.paged_decode_attention.paged_decode_attention import (
    paged_decode_attention_pallas)
from repro.kernels.paged_decode_attention.ref import (
    paged_decode_attention_quant_ref)
from repro.models import Model
from repro.quant.kv import dequantize_kv, quantize_kv_write
from repro.serving import DecodeEngine, SessionRequest, SlotScheduler
from repro.serving.memory import (GARBAGE_PAGE, restore_kv_blobs,
                                  save_kv_blobs)

KEY = jax.random.PRNGKey(11)
CFG = get_config("qwen2.5-3b").reduced().replace(
    vocab_size=256, d_model=96, d_ff=192, n_layers=2, n_heads=4,
    n_kv_heads=2, head_dim=16, dtype="float32")


def _engine(cfg=CFG, **kw):
    m = Model(cfg, **kw.pop("model_kw", {}))
    return DecodeEngine(m, m.init(KEY), **kw)


def _fleet(n, *, page=4, shared_pages=2, base_new=4, dups=1):
    preamble = np.asarray(jax.random.randint(
        KEY, (shared_pages * page,), 0, CFG.vocab_size))
    reqs = []
    for i in range(n):
        k = jax.random.fold_in(KEY, 100 + i)
        tail = np.asarray(jax.random.randint(k, (3 + i,), 0,
                                             CFG.vocab_size))
        reqs.append(SessionRequest(
            f"s{i}", np.concatenate([preamble, tail]), base_new + i % 3))
    for i in range(dups):
        reqs.append(SessionRequest(f"dup{i}", preamble, base_new))
    return reqs


def _assert_identical(reqs, ref, res, what):
    for r in reqs:
        np.testing.assert_array_equal(
            ref.tokens_for(r.session_id), res.tokens_for(r.session_id),
            err_msg=f"{r.session_id} diverged: {what}")


class TestScaleEpsilon:
    def test_zero_vector_roundtrips_exactly(self):
        """An all-zero K/V vector has max|x| == 0; the scale epsilon
        must keep the codes zero and the dequantised value EXACTLY
        zero, not epsilon-sized noise."""
        x = jnp.zeros((2, 3, 2, 16), jnp.bfloat16)
        codes, scales = quantize_kv_write(x)
        np.testing.assert_array_equal(np.asarray(codes), 0)
        assert np.all(np.asarray(scales) > 0)          # finite, no 1/0
        back = dequantize_kv(codes, scales, jnp.float32)
        np.testing.assert_array_equal(np.asarray(back), 0.0)

    def test_mixed_zero_rows_stay_zero(self):
        """Zero rows next to large rows: each (token, head) scales
        independently, so the zero rows still come back exact."""
        x = jnp.zeros((1, 4, 1, 8), jnp.float32).at[0, 1].set(300.0)
        codes, scales = quantize_kv_write(x)
        back = np.asarray(dequantize_kv(codes, scales, jnp.float32))
        np.testing.assert_array_equal(back[0, 0], 0.0)
        np.testing.assert_array_equal(back[0, 2:], 0.0)
        np.testing.assert_allclose(back[0, 1], 300.0, rtol=0.01)


class TestGarbageSentinel:
    def _pools(self, poison):
        k = jax.random.PRNGKey(3)
        n_pages, page, Hkv, hd, B = 5, 4, 2, 16, 2
        ks = [jax.random.fold_in(k, i) for i in range(5)]
        k_pool = jax.random.randint(ks[0], (n_pages, Hkv, page, hd),
                                    -127, 128, jnp.int32).astype(jnp.int8)
        v_pool = jax.random.randint(ks[1], (n_pages, Hkv, page, hd),
                                    -127, 128, jnp.int32).astype(jnp.int8)
        k_sc = jax.random.uniform(ks[2], (n_pages, Hkv, page),
                                  jnp.float32, 0.01, 0.1)
        v_sc = jax.random.uniform(ks[3], (n_pages, Hkv, page),
                                  jnp.float32, 0.01, 0.1)
        if poison:     # worst representable content, finite on purpose
            k_pool = k_pool.at[GARBAGE_PAGE].set(127)
            v_pool = v_pool.at[GARBAGE_PAGE].set(127)
            k_sc = k_sc.at[GARBAGE_PAGE].set(1e30)
            v_sc = v_sc.at[GARBAGE_PAGE].set(1e30)
        else:
            k_pool = k_pool.at[GARBAGE_PAGE].set(0)
            v_pool = v_pool.at[GARBAGE_PAGE].set(0)
            k_sc = k_sc.at[GARBAGE_PAGE].set(0.0)
            v_sc = v_sc.at[GARBAGE_PAGE].set(0.0)
        q = jax.random.normal(ks[4], (B, 4, hd), jnp.float32)
        # slot 0: two live pages then sentinel padding; slot 1: one
        # partially-live page; both routes must never read page 0
        bt = jnp.array([[1, 2, GARBAGE_PAGE],
                        [3, GARBAGE_PAGE, GARBAGE_PAGE]], jnp.int32)
        lengths = jnp.array([7, 3], jnp.int32)
        return q, k_pool, v_pool, k_sc, v_sc, bt, lengths

    def test_poisoned_sentinel_never_dequantised(self):
        clean = self._pools(poison=False)
        dirty = self._pools(poison=True)
        for route in (paged_decode_attention,
                      paged_decode_attention_quant_ref):
            if route is paged_decode_attention:
                a = np.asarray(route(clean[0], clean[1], clean[2],
                                     clean[5], clean[6], clean[3],
                                     clean[4]))
                b = np.asarray(route(dirty[0], dirty[1], dirty[2],
                                     dirty[5], dirty[6], dirty[3],
                                     dirty[4]))
            else:
                a = np.asarray(route(*clean))
                b = np.asarray(route(*dirty))
            assert np.all(np.isfinite(b)), f"{route.__name__}: non-finite"
            np.testing.assert_array_equal(
                a, b, err_msg=f"{route.__name__} read the poisoned "
                              f"sentinel page")


def test_int8_kernel_matches_ref_with_nan_behind_every_dead_page(capsys):
    """test_paged_kernel's page-16 walk case (a table of 70 pages,
    lengths 0 to the whole table) on int8 codes: NaN scales and codes
    127 on every page no lane reads leave the output equal to the
    dequantised reference over the clean pool."""
    from test_paged_kernel import WALKS, walk_case
    page, max_blocks, lengths = WALKS[0]
    bt, ln, n_pages, dead = walk_case(page, max_blocks, lengths)
    ks = [jax.random.fold_in(KEY, i) for i in range(5)]
    Hkv, hd = 2, 32
    codes = [jax.random.randint(k, (n_pages, Hkv, page, hd), -127, 128,
                                jnp.int32).astype(jnp.int8) for k in ks[:2]]
    # dequantised values of unit scale, as the f32 walk's normal pools,
    # so the same tolerance holds
    scales = [jax.random.uniform(k, (n_pages, Hkv, page), jnp.float32,
                                 0.004, 0.012) for k in ks[2:4]]
    q = jax.random.normal(ks[4], (len(lengths), 8, hd), jnp.float32)
    ref = paged_decode_attention_quant_ref(q, *codes, *scales, bt, ln)
    out = paged_decode_attention_pallas(
        q, *(c.at[dead].set(127) for c in codes), bt, ln,
        *(s.at[dead].set(jnp.nan) for s in scales),
        interpret=pltpu.InterpretParams(detect_races=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert bool(jnp.all(out[0] == 0))
    assert "RACE" not in capsys.readouterr().out


class TestHostTierBlobs:
    def test_park_restore_bit_exact(self):
        """Int8 blobs carry four slabs (codes + scales for K and V) and
        a park/restore round trip is bit-exact on every one."""
        m = Model(CFG)
        cache = m.init_cache(2, 32, paged=True, page_size=4,
                             kv_dtype=jnp.int8)
        keys = ("k", "v", "k_scale", "v_scale")
        rng = np.random.RandomState(5)
        for key in ("k", "v"):
            cache[key] = jnp.asarray(rng.randint(
                -127, 128, cache[key].shape).astype(np.int8))
        for key in ("k_scale", "v_scale"):
            cache[key] = jnp.asarray(rng.uniform(
                1e-3, 1.0, cache[key].shape).astype(np.float32))
        pages = [2, 5, 3]
        save_jit = jax.jit(m.save_kv_pages)
        restore_jit = jax.jit(m.restore_kv_pages)
        blobs = save_kv_blobs(save_jit, cache, pages)
        assert len(blobs) == len(pages)
        assert all(len(b) == 4 for b in blobs)
        assert blobs[0][0].dtype == np.int8
        assert blobs[0][2].dtype == np.float32
        fresh = m.init_cache(2, 32, paged=True, page_size=4,
                             kv_dtype=jnp.int8)
        fresh = restore_kv_blobs(restore_jit, fresh, pages, blobs)
        for key in keys:
            np.testing.assert_array_equal(
                np.asarray(fresh[key][:, pages]),
                np.asarray(cache[key][:, pages]),
                err_msg=f"{key} not bit-exact through park/restore")


class TestQuantisedCoW:
    def test_copy_kv_page_moves_scales(self):
        m = Model(CFG)
        cache = m.init_cache(2, 32, paged=True, page_size=4,
                             kv_dtype=jnp.int8)
        cache["k"] = cache["k"].at[:, 1].set(7)
        cache["k_scale"] = cache["k_scale"].at[:, 1].set(0.5)
        cache["v_scale"] = cache["v_scale"].at[:, 1].set(0.25)
        out = m.copy_kv_page(cache, jnp.int32(1), jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(out["k"][:, 2]), 7)
        np.testing.assert_array_equal(
            np.asarray(out["k_scale"][:, 2]), 0.5)
        np.testing.assert_array_equal(
            np.asarray(out["v_scale"][:, 2]), 0.25)

    def test_cow_fork_on_shared_quantised_page(self):
        """Prefix sharing over int8 pages: the CoW replay (an exact
        page-aligned duplicate prompt) forks codes AND scales, so the
        shared-page run stays token-identical to the private-page
        run."""
        eng = _engine(kv_dtype=jnp.int8)
        reqs = _fleet(3, dups=1)
        ref = eng.generate_continuous(reqs, n_slots=2, max_len=40,
                                      paged=True, page_size=4)
        res = eng.generate_continuous(reqs, n_slots=2, max_len=40,
                                      paged=True, page_size=4,
                                      prefix_cache=True)
        assert res.prefix_hits >= 3
        assert res.cow_copies >= 1
        _assert_identical(reqs, ref, res, "int8 CoW fork")

    def test_shared_quantised_pages_never_written(self):
        """Poisoned-page guard, int8 edition: after a second wave that
        hits every cached prefix, the shared pages' codes and scales
        read back bit-unchanged."""
        eng = _engine(kv_dtype=jnp.int8)
        reqs = _fleet(3, dups=1)
        sched = SlotScheduler(eng.model, eng.params, n_slots=2,
                              max_len=40, paged=True, page_size=4,
                              kv_dtype=jnp.int8, prefix_cache=True)
        for r in reqs:
            sched.submit(r)
        sched.run()
        cached = sched.prefix.pages()
        assert cached, "first wave registered nothing"
        snap = {key: np.asarray(sched.cache[key][:, cached])
                for key in ("k", "v", "k_scale", "v_scale")}
        for r in reqs:
            sched.submit(dataclasses.replace(
                r, session_id="w2" + r.session_id))
        res = sched.run()
        assert res.prefix_hits == len(reqs)
        assert res.cow_copies >= 1
        for key, before in snap.items():
            np.testing.assert_array_equal(
                before, np.asarray(sched.cache[key][:, cached]),
                err_msg=f"a shared {key} page was written")


class TestQuantisedPrefillRoutes:
    def test_chunked_prefill_matches_whole_prompt(self):
        """Per-token quantisation commutes with chunking: chunked int8
        prefill must emit exactly the whole-prompt int8 streams."""
        eng = _engine(kv_dtype=jnp.int8)
        reqs = _fleet(4, dups=0)
        ref = eng.generate_continuous(reqs, n_slots=2, max_len=40,
                                      paged=True, page_size=4)
        res = eng.generate_continuous(reqs, n_slots=2, max_len=40,
                                      paged=True, page_size=4,
                                      prefill_chunk=4)
        _assert_identical(reqs, ref, res, "chunked int8 prefill")

    def test_routes_identical_under_int8(self):
        """f32 model dtype: the fused kernel's in-register codes*scale
        equals the gather route's dequantised f32 view exactly, so the
        two routes' greedy streams must coincide token-for-token."""
        reqs = _fleet(3, dups=0)
        gather = _engine(kv_dtype=jnp.int8)
        pallas = _engine(kv_dtype=jnp.int8,
                         model_kw={"decode_backend": "pallas"})
        a = gather.generate_continuous(reqs, n_slots=2, max_len=40,
                                       paged=True, page_size=4)
        b = pallas.generate_continuous(reqs, n_slots=2, max_len=40,
                                       paged=True, page_size=4)
        _assert_identical(reqs, a, b, "gather vs pallas under int8")
