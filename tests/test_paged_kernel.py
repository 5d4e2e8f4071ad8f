"""Fused Pallas paged-decode attention kernel
(kernels/paged_decode_attention): the block-table-aware flash-decoding
sweep that replaces the paged path's ``paged_view`` gather.

Two contracts:
  * kernel-level — matches the gather+SDPA oracle (ref.py) for every
    table10 page size, partial last pages, garbage-sentinel block-table
    entries, and free (length-0) lanes;
  * serving-level — with ``decode_backend="pallas"`` the paged scheduler
    emits greedy streams token-identical to the gather+SDPA reference
    across full backing, chunked prefill, and an oversubscribed pool
    with preemption, still compiled exactly once through churn.

Identity runs in f32: the bf16 SDPA rounds probabilities to bf16 before
the PV dot (backend-specific rounding), while the kernel accumulates in
f32 — at f32 both routes compute the same real-valued function at the
same precision (see benchmarks/table10_paged_kv.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.paged_decode_attention.ops import (paged_decode_attention,
                                                      serving_traffic_bytes,
                                                      traffic_bytes)
from repro.kernels.paged_decode_attention.ref import paged_decode_attention_ref
from repro.models import Model
from repro.serving import DecodeEngine, SessionRequest, SlotScheduler

KEY = jax.random.PRNGKey(23)
CFG = get_config("qwen2.5-3b").reduced().replace(dtype="float32")

# table10's PAGE_SIZES (benchmarks/table10_paged_kv.py) — kept literal
# so the tier-1 suite doesn't import the benchmarks package
TABLE10_PAGE_SIZES = (4, 8, 16)


def _rand_pool(key, n_pages, page, Hkv, hd, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    k_pool = jax.random.normal(ks[0], (n_pages, Hkv, page, hd), dtype)
    v_pool = jax.random.normal(ks[1], (n_pages, Hkv, page, hd), dtype)
    return k_pool, v_pool


class TestKernelVsRef:
    @pytest.mark.parametrize("page", TABLE10_PAGE_SIZES)
    def test_matches_gather_ref_all_table10_page_sizes(self, page):
        B, Hq, Hkv, hd, max_blocks = 3, 8, 2, 64, 4
        n_pages = 1 + B * max_blocks
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
        k_pool, v_pool = _rand_pool(ks[1], n_pages, page, Hkv, hd)
        bt = jnp.asarray(
            np.random.RandomState(page).permutation(
                np.arange(1, n_pages))[:B * max_blocks]
            .reshape(B, max_blocks), jnp.int32)
        lengths = jnp.asarray([max_blocks * page,        # full allocation
                               2 * page + page // 2,     # partial last page
                               1], jnp.int32)
        out = paged_decode_attention(q, k_pool, v_pool, bt, lengths)
        ref = paged_decode_attention_ref(q, k_pool, v_pool, bt, lengths)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=1e-5, rtol=1e-5)

    def test_partial_last_page_every_offset(self):
        """Sweep the live length across a page boundary: every partial
        fill of the last page masks exactly the right tail."""
        B, Hq, Hkv, hd, page, max_blocks = 1, 4, 2, 32, 8, 2
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
        k_pool, v_pool = _rand_pool(ks[1], 3, page, Hkv, hd)
        bt = jnp.asarray([[2, 1]], jnp.int32)
        for length in range(1, max_blocks * page + 1):
            lengths = jnp.asarray([length], jnp.int32)
            out = paged_decode_attention(q, k_pool, v_pool, bt, lengths)
            ref = paged_decode_attention_ref(q, k_pool, v_pool, bt, lengths)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref), atol=1e-5,
                                       err_msg=f"length={length}")

    def test_garbage_sentinel_blocks_never_read(self):
        """Blocks past a slot's allocation park on sentinel page 0.  The
        kernel must skip them entirely: poisoning page 0 with huge junk
        cannot change any lane whose live length stays within its real
        pages."""
        B, Hq, Hkv, hd, page, max_blocks = 2, 4, 2, 32, 4, 4
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
        k_pool, v_pool = _rand_pool(ks[1], 6, page, Hkv, hd)
        bt = jnp.asarray([[3, 5, 0, 0],        # 2 real pages, 2 sentinel
                          [1, 2, 4, 0]], jnp.int32)
        lengths = jnp.asarray([2 * page, 3 * page - 1], jnp.int32)
        clean = paged_decode_attention(q, k_pool, v_pool, bt, lengths)
        poison = 1e9
        k_pool = k_pool.at[0].set(poison)
        v_pool = v_pool.at[0].set(poison)
        out = paged_decode_attention(q, k_pool, v_pool, bt, lengths)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))

    def test_free_lane_returns_zeros(self):
        B, Hq, Hkv, hd, page = 2, 4, 2, 32, 8
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
        k_pool, v_pool = _rand_pool(ks[1], 3, page, Hkv, hd)
        bt = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
        out = paged_decode_attention(q, k_pool, v_pool, bt,
                                     jnp.asarray([page, 0], jnp.int32))
        assert bool(jnp.all(out[1] == 0))
        assert bool(jnp.all(jnp.isfinite(out[0])))

    def test_bf16_pool_close_to_ref(self):
        """The serving dtype: bf16 pool, f32 accumulation — close to the
        f32 oracle at bf16-grade tolerance."""
        B, Hq, Hkv, hd, page = 2, 8, 2, 64, 8
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, Hq, hd), jnp.bfloat16)
        k_pool, v_pool = _rand_pool(ks[1], 5, page, Hkv, hd, jnp.bfloat16)
        bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        lengths = jnp.asarray([2 * page, page + 3], jnp.int32)
        out = paged_decode_attention(q, k_pool, v_pool, bt, lengths)
        ref = paged_decode_attention_ref(q, k_pool, v_pool, bt, lengths)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=0.03, rtol=0.03)

    def test_traffic_accounting_shows_gather_elimination(self):
        tb = traffic_bytes(6, 8, 2, 64, n_slots=4, max_blocks=4,
                           n_layers=3, kv_bytes=2)
        kv = 2 * 2 * 64 * 2
        assert tb["fused"] == 3 * 6 * 8 * kv
        assert tb["gather_sdpa"] == 3 * 3 * (4 * 4 * 8) * kv
        assert tb["fused"] < tb["gather_sdpa"]

    def test_serving_traffic_derives_kv_bytes_from_dtype(self):
        """The paged cache stores KV at the model dtype: an f32 model
        moves 2x the bytes of a bf16 model for the same block trace."""
        kw = dict(page_size=8, n_slots=4, max_blocks=4)
        f32 = serving_traffic_bytes([6, 6], CFG, **kw)
        bf16 = serving_traffic_bytes([6, 6],
                                     CFG.replace(dtype="bfloat16"), **kw)
        assert f32["fused"] == 2 * bf16["fused"]
        assert f32["gather_sdpa"] == 2 * bf16["gather_sdpa"]


def _requests(n, cfg=CFG, base_len=4, base_new=3):
    reqs = []
    for i in range(n):
        k = jax.random.fold_in(KEY, 100 + i)
        prompt = np.asarray(
            jax.random.randint(k, (base_len + 2 * i,), 0, cfg.vocab_size))
        reqs.append(SessionRequest(f"s{i}", prompt, base_new + i % 4))
    return reqs


class TestServingTokenIdentity:
    """Fused kernel vs gather+SDPA through the full paged scheduler."""

    @classmethod
    def setup_class(cls):
        cls.params = Model(CFG).init(KEY)
        cls.gather = DecodeEngine(Model(CFG), cls.params)
        cls.fused = DecodeEngine(Model(CFG, decode_backend="pallas"),
                                 cls.params)

    def _assert_identical(self, reqs, **kw):
        ref = self.gather.generate_continuous(reqs, **kw)
        res = self.fused.generate_continuous(reqs, **kw)
        assert res.step_cache_size == 1
        for r in reqs:
            np.testing.assert_array_equal(
                ref.tokens_for(r.session_id), res.tokens_for(r.session_id),
                err_msg=f"{r.session_id} diverged fused-vs-gather")
        return ref, res

    @pytest.mark.parametrize("page", TABLE10_PAGE_SIZES)
    def test_full_backing_identity_all_table10_page_sizes(self, page):
        self._assert_identical(_requests(4), n_slots=3, max_len=32,
                               paged=True, page_size=page)

    def test_oversubscribed_pool_after_preemption(self):
        """Decode outgrows the pool -> youngest preempted, re-prefilled;
        the fused route must track the gather route through the whole
        preempt/requeue/re-admit cycle."""
        reqs = [SessionRequest("a", np.arange(4) % CFG.vocab_size, 20),
                SessionRequest("b", np.arange(5) % CFG.vocab_size, 20)]
        ref, res = self._assert_identical(reqs, n_slots=2, max_len=32,
                                          paged=True, page_size=4,
                                          n_pages=1 + 7)
        assert res.preemptions > 0, "pool was sized to force preemption"
        assert res.preemptions == ref.preemptions

    def test_chunked_prefill_identity(self):
        self._assert_identical(_requests(4), n_slots=2, max_len=32,
                               paged=True, page_size=4, prefill_chunk=4)

    def test_step_kv_blocks_traced_and_below_virtual(self):
        """The scheduler's per-step live-block trace (what the fused
        kernel walks) stays below the constant virtual view the gather
        route materialises."""
        res = self.fused.generate_continuous(
            _requests(4), n_slots=3, max_len=32, paged=True, page_size=8)
        assert res.step_kv_blocks and len(res.step_kv_blocks) == \
            res.decode_steps
        virtual_blocks = 3 * (-(-32 // 8))
        assert max(res.step_kv_blocks) <= virtual_blocks
        assert min(res.step_kv_blocks) >= 1

    def test_compiled_once_through_churn(self):
        """StepProgram-style guard: two admission waves through one
        fused-backend paged scheduler — exhaustion, reclaim, backfill —
        and still exactly ONE compiled decode step (page residency and
        block tables are pure data)."""
        sched = SlotScheduler(self.fused.model, self.params, n_slots=2,
                              max_len=32, paged=True, page_size=8,
                              n_pages=5)
        for r in _requests(4):
            sched.submit(r)
        sched.run()
        assert sched.step_cache_size() == 1
        for r in _requests(3, base_len=5, base_new=4):
            sched.submit(SessionRequest(r.session_id + "w2", r.prompt,
                                        r.max_new_tokens))
        sched.run()
        assert sched.step_cache_size() == 1
        assert sched.free_pages == 4
