"""Paged KV cache: slot -> block-table -> page-pool indirection.

Token identity is the contract: the same session mix served through the
paged scheduler — at full backing, oversubscribed, chunk-prefilled, or
preempted — must emit exactly the tokens the contiguous slotted
scheduler emits, with the paged decode step compiled exactly once
through churn, page exhaustion, and reclaim.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.models import attention as attn
from repro.serving import (BlockAllocator, DecodeEngine, SessionRequest,
                           SlotScheduler, jit_cache_size)

KEY = jax.random.PRNGKey(11)
CFG = get_config("qwen2.5-3b").reduced()


def _engine(cfg=CFG):
    m = Model(cfg)
    return DecodeEngine(m, m.init(KEY))


def _requests(n, cfg=CFG, base_len=4, base_new=3):
    """n sessions with mixed prompt lengths and token budgets."""
    reqs = []
    for i in range(n):
        k = jax.random.fold_in(KEY, 100 + i)
        prompt = np.asarray(
            jax.random.randint(k, (base_len + 2 * i,), 0, cfg.vocab_size))
        reqs.append(SessionRequest(f"s{i}", prompt, base_new + i % 4))
    return reqs


class TestBlockAllocator:
    def test_free_list_lifecycle(self):
        a = BlockAllocator(5)          # page 0 reserved
        assert a.n_free == 4
        got = a.alloc(3)
        assert len(got) == 3 and 0 not in got
        assert a.n_free == 1
        assert a.alloc(2) is None      # under-supplied: no change
        assert a.n_free == 1
        a.release(got)
        assert a.n_free == 4

    def test_garbage_page_never_handed_out(self):
        a = BlockAllocator(4)
        assert sorted(a.alloc(3)) == [1, 2, 3]

    def test_double_free_rejected(self):
        a = BlockAllocator(4)
        (p,) = a.alloc(1)
        a.release([p])
        with pytest.raises(AssertionError):
            a.release([p])


class TestPagedCache:
    def test_layout(self):
        m = Model(CFG)
        cache = m.init_cache(3, 32, paged=True, page_size=8)
        L, n_pages, hkv, page, hd = cache["k"].shape
        assert (L, page, hkv, hd) == (CFG.n_layers, 8, CFG.n_kv_heads,
                                      CFG.head_dim)
        assert n_pages == 1 + 3 * 4            # garbage + full backing
        assert cache["block_table"].shape == (3, 4)
        assert cache["pos"].shape == (3,)

    def test_oversubscribed_pool_shrinks_memory(self):
        m = Model(CFG)
        full = m.init_cache(4, 64, paged=True, page_size=8)
        over = m.init_cache(4, 64, paged=True, page_size=8, n_pages=9)
        assert over["k"].size < full["k"].size / 3

    def test_int8_paged_adds_scale_pools(self):
        # paged + int8 is now supported: scale slabs ride parallel pools
        # sharing the block table (one f32 scale per (token, head))
        cache = Model(CFG).init_cache(3, 32, paged=True, page_size=8,
                                      kv_dtype=jnp.int8)
        assert cache["k"].dtype == jnp.int8
        assert cache["k_scale"].shape == cache["k"].shape[:-1]
        assert cache["k_scale"].dtype == jnp.float32
        assert cache["v_scale"].shape == cache["v"].shape[:-1]

    def test_rejects_unsupported(self):
        with pytest.raises(ValueError):
            Model(CFG).init_cache(2, 32, paged=True, kv_quant="fp4")
        with pytest.raises(NotImplementedError):
            Model(CFG.replace(sliding_window=8)).init_cache(2, 32, paged=True)
        with pytest.raises(NotImplementedError):
            Model(get_config("mamba2-2.7b").reduced()).init_cache(
                2, 32, paged=True)

    def test_step_program_rejects_paged(self):
        m = Model(CFG)
        params = m.init(KEY)
        cache = m.init_cache(2, 32, paged=True, page_size=8)
        with pytest.raises(NotImplementedError):
            m.step_program(params, cache)

    def test_paged_view_gathers_block_table(self):
        # (n_pages, Hkv, page, hd): page p holds tokens 2p, 2p + 1
        pool = jnp.arange(5 * 1 * 2 * 1, dtype=jnp.float32).reshape(5, 1, 2, 1)
        bt = jnp.array([[3, 1], [0, 0]], jnp.int32)
        view = np.asarray(attn.paged_view(pool, bt))
        assert view.shape == (2, 4, 1, 1)
        np.testing.assert_array_equal(view[0, :, 0, 0], [6, 7, 2, 3])
        np.testing.assert_array_equal(view[1, :, 0, 0], [0, 1, 0, 1])


class TestPagedPrefill:
    def _paged_cache(self, m, n_slots=2, max_len=32, page=8):
        cache = m.init_cache(n_slots, max_len, paged=True, page_size=page)
        bt = np.zeros((n_slots, -(-max_len // page)), np.int32)
        bt[0] = np.arange(1, bt.shape[1] + 1)      # slot 0 fully backed
        cache["block_table"] = jnp.asarray(bt)
        return cache

    def test_whole_prompt_matches_contiguous_prefill(self):
        m = Model(CFG)
        params = m.init(KEY)
        toks = jax.random.randint(KEY, (1, 11), 0, CFG.vocab_size)
        cache = self._paged_cache(m)
        lp, cache = m.prefill_into_slot(params, {"tokens": toks}, cache,
                                        jnp.int32(0))
        ref = m.init_cache(2, 32, slotted=True)
        lr, _ = m.prefill_into_slot(params, {"tokens": toks}, ref,
                                    jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lp, np.float32),
                                   np.asarray(lr, np.float32), atol=2e-2)
        np.testing.assert_array_equal(np.asarray(cache["pos"]), [11, 0])

    def test_chunked_equals_whole_prompt(self):
        """Feeding a prompt chunk-by-chunk (page-aligned chunks) must
        reproduce the one-shot prefill bit-for-bit: same last-position
        logits, same pool contents, same positions."""
        m = Model(CFG)
        params = m.init(KEY)
        toks = jax.random.randint(jax.random.fold_in(KEY, 7), (1, 19), 0,
                                  CFG.vocab_size)
        c1 = self._paged_cache(m)
        l1, c1 = m.prefill_into_slot(params, {"tokens": toks}, c1,
                                     jnp.int32(0))
        c2 = self._paged_cache(m)
        for start in (0, 8, 16):
            chunk = toks[:, start:start + 8]
            l2, c2 = m.prefill_chunk_into_slot(params, {"tokens": chunk},
                                               c2, jnp.int32(0),
                                               jnp.int32(start))
        np.testing.assert_allclose(np.asarray(l1, np.float32),
                                   np.asarray(l2, np.float32), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(c1["pos"]),
                                      np.asarray(c2["pos"]))
        np.testing.assert_allclose(np.asarray(c1["k"], np.float32),
                                   np.asarray(c2["k"], np.float32),
                                   atol=1e-5)


class TestPagedEquivalence:
    def _contiguous_ref(self, eng, reqs, n_slots=3, max_len=32):
        return eng.generate_continuous(reqs, n_slots=n_slots,
                                       max_len=max_len)

    def test_full_backing_matches_contiguous(self):
        eng = _engine()
        reqs = _requests(6)
        ref = self._contiguous_ref(eng, reqs)
        res = eng.generate_continuous(reqs, n_slots=3, max_len=32,
                                      paged=True, page_size=8)
        assert res.step_cache_size == 1
        assert res.preemptions == 0
        for r in reqs:
            np.testing.assert_array_equal(
                ref.tokens_for(r.session_id), res.tokens_for(r.session_id),
                err_msg=f"{r.session_id} diverged under paging")

    def test_oversubscribed_pool_token_identity(self):
        """The acceptance case: a pool holding fewer tokens than the
        contiguous n_slots*max_len reservation serves a workload whose
        summed KV footprint exceeds the pool — eviction reclaim keeps it
        flowing — and the greedy streams are identical."""
        eng = _engine()
        reqs = _requests(6)
        n_slots, max_len, page, n_pages = 3, 32, 8, 7
        pool_tokens = (n_pages - 1) * page
        assert pool_tokens < n_slots * max_len          # oversubscribed
        footprint = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
        assert footprint > pool_tokens                  # needs reclaim
        ref = self._contiguous_ref(eng, reqs)
        res = eng.generate_continuous(reqs, n_slots=n_slots,
                                      max_len=max_len, paged=True,
                                      page_size=page, n_pages=n_pages)
        assert res.step_cache_size == 1
        for r in reqs:
            np.testing.assert_array_equal(
                ref.tokens_for(r.session_id), res.tokens_for(r.session_id),
                err_msg=f"{r.session_id} diverged oversubscribed")

    def test_chunked_prefill_token_identity(self):
        eng = _engine()
        reqs = _requests(5)
        ref = self._contiguous_ref(eng, reqs)
        res = eng.generate_continuous(reqs, n_slots=3, max_len=32,
                                      paged=True, page_size=4,
                                      prefill_chunk=4)
        assert res.step_cache_size == 1
        for r in reqs:
            np.testing.assert_array_equal(
                ref.tokens_for(r.session_id), res.tokens_for(r.session_id),
                err_msg=f"{r.session_id} diverged chunk-prefilled")

    def test_preemption_token_identity(self):
        """Decode outgrowing the pool preempts the youngest session
        (pages reclaimed, session requeued + re-prefilled from prompt +
        generated prefix); its stream must be unchanged."""
        eng = _engine()
        reqs = [SessionRequest("a", np.arange(4) % CFG.vocab_size, 20),
                SessionRequest("b", np.arange(5) % CFG.vocab_size, 20)]
        ref = eng.generate_continuous(reqs, n_slots=2, max_len=32)
        res = eng.generate_continuous(reqs, n_slots=2, max_len=32,
                                      paged=True, page_size=4,
                                      n_pages=1 + 7)
        assert res.preemptions > 0, "pool was sized to force preemption"
        assert res.step_cache_size == 1
        for r in reqs:
            np.testing.assert_array_equal(
                ref.tokens_for(r.session_id), res.tokens_for(r.session_id),
                err_msg=f"{r.session_id} diverged through preemption")

    def test_compiled_once_through_churn_and_reclaim(self):
        """Two admission waves through one oversubscribed paged
        scheduler: exhaustion, reclaim, backfill — and still exactly one
        compiled decode step."""
        eng = _engine()
        sched = SlotScheduler(eng.model, eng.params, n_slots=2, max_len=32,
                              paged=True, page_size=8, n_pages=5)
        for r in _requests(4):
            sched.submit(r)
        sched.run()
        assert sched.step_cache_size() == 1
        for r in _requests(3, base_len=5, base_new=4):
            sched.submit(SessionRequest(r.session_id + "w2", r.prompt,
                                        r.max_new_tokens))
        sched.run()
        assert sched.step_cache_size() == 1
        assert sched.free_pages == 4           # everything reclaimed
        assert sched.free_slots == [0, 1]


class TestPagedSchedulerInvariants:
    def test_admission_gated_on_free_pages(self):
        """Two sessions that cannot coexist in the pool are serialised:
        the second admits only after the first's pages are reclaimed."""
        eng = _engine()
        reqs = [SessionRequest("a", np.arange(16) % CFG.vocab_size, 5),
                SessionRequest("b", np.arange(16) % CFG.vocab_size, 5)]
        sched = SlotScheduler(eng.model, eng.params, n_slots=2, max_len=32,
                              paged=True, page_size=4, n_pages=1 + 5)
        for r in reqs:
            sched.submit(r)
        res = sched.run()
        a, b = res.sessions["a"], res.sessions["b"]
        assert b.admitted_tick >= a.finished_tick
        assert res.preemptions == 0            # gating, not preemption

    def test_submit_rejects_session_larger_than_pool(self):
        eng = _engine()
        sched = SlotScheduler(eng.model, eng.params, n_slots=1, max_len=32,
                              paged=True, page_size=4, n_pages=3)
        with pytest.raises(AssertionError):
            sched.submit(SessionRequest("x", np.arange(8), 8))

    def test_event_log_replay(self):
        """Replaying admit/preempt/finish, occupancy and page accounting
        stay consistent (a preempted session's re-admit is legal)."""
        eng = _engine()
        sched = SlotScheduler(eng.model, eng.params, n_slots=2, max_len=32,
                              paged=True, page_size=4, n_pages=1 + 7)
        reqs = [SessionRequest("a", np.arange(4) % CFG.vocab_size, 18),
                SessionRequest("b", np.arange(5) % CFG.vocab_size, 18),
                SessionRequest("c", np.arange(6) % CFG.vocab_size, 6)]
        for r in reqs:
            sched.submit(r)
        res = sched.run()
        occupancy = {}
        for ev in res.events:
            kind, sid, slot = ev[0], ev[1], ev[2]
            if kind == "admit":
                assert slot not in occupancy
                occupancy[slot] = sid
            elif kind in ("finish", "preempt"):
                assert occupancy.pop(slot) == sid
        assert not occupancy
        assert len(res.sessions) == 3

    def test_paged_requires_full_jit(self):
        eng = _engine()
        with pytest.raises(NotImplementedError):
            SlotScheduler(eng.model, eng.params, n_slots=2, max_len=32,
                          paged=True, dispatch_mode="stage_jit")

    def test_prefill_chunk_must_be_page_aligned(self):
        eng = _engine()
        with pytest.raises(AssertionError):
            SlotScheduler(eng.model, eng.params, n_slots=2, max_len=32,
                          paged=True, page_size=8, prefill_chunk=12)


class TestJitCacheSize:
    """The recompile guard counts one executable per input signature."""

    def test_counts_compiled_executables(self):
        f = jax.jit(lambda x: x + 1)
        f(jnp.ones((2,)))
        f(jnp.zeros((2,)))
        assert jit_cache_size(f) == 1

    def test_degrades_to_none_without_the_hook(self):
        """A second input shape is a second executable: the count is
        exact, never None."""
        f = jax.jit(lambda x: x + 1)
        f(jnp.ones((2,)))
        f(jnp.ones((3,)))
        assert jit_cache_size(f) == 2
