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
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.kernels.paged_decode_attention.ops import (paged_decode_attention,
                                                      serving_traffic_bytes,
                                                      traffic_bytes,
                                                      walk_blocks)
from repro.kernels.paged_decode_attention.paged_decode_attention import (
    paged_decode_attention_pallas, pages_per_block)
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


def walk_case(page, max_blocks, lengths, seed=0):
    """A block table whose lanes hold ``lengths`` tokens on shuffled
    pages.  Returns the table, the lengths and the pages no lane reads:
    the garbage page 0, every page no table row holds, and the pages of
    a lane's row past its last live page."""
    B = len(lengths)
    n_pages = 1 + B * max_blocks
    rng = np.random.RandomState(seed)
    perm = iter(rng.permutation(np.arange(1, n_pages)))
    bt = np.zeros((B, max_blocks), np.int32)
    live = set()
    for b, n in enumerate(lengths):
        for i in range(min(-(-n // page), max_blocks)):
            bt[b, i] = next(perm)
            live.add(int(bt[b, i]))
        # pages past the live ones: allocated to the lane, not yet read
        for i in range(min(-(-n // page), max_blocks), max_blocks - 1, 3):
            bt[b, i] = next(perm)
    dead = np.asarray(sorted(set(range(n_pages)) - live))
    return (jnp.asarray(bt), jnp.asarray(lengths, jnp.int32), n_pages,
            dead)


# (page, max_blocks, lengths): at page 16 a compute block is 512 tokens;
# lengths 0, 1, page-1, one block, one block + 1 and the whole table;
# max_blocks not a multiple of the block's pages (70 = 2 x 32 + 6); a
# length 100 tokens past its table, on a lane whose next lane is live,
# attends over its own row alone.  Page 4 takes the page grid (see
# test_pool_takes_the_walk_or_the_page_grid) under the same contract
WALKS = [(16, 70, (0, 1, 15, 512, 513, 70 * 16)),
         (8, 100, (0, 1, 7, 512, 513, 100 * 8)),
         (4, 200, (0, 1, 3, 512, 513, 200 * 4)),
         (16, 4, (0, 1, 15, 64)),                # the table is one block
         (16, 40, (40 * 16 + 100, 513, 0, 1))]


class TestKernelVsRef:
    @pytest.mark.parametrize("page,max_blocks,lengths", WALKS,
                             ids=["page16", "page8", "page4", "one-block",
                                  "past-table"])
    def test_block_walk_matches_ref_with_nan_behind_every_dead_page(
            self, capsys, page, max_blocks, lengths):
        """Each lane walks ``ceil(len / bk)`` blocks and copies only its
        live pages: with NaN in the garbage page, in every page no lane
        holds and in the allocated pages past each length, the output
        still equals the reference over the clean pool, to the last
        row of a partial block."""
        Hq, Hkv, hd = 8, 2, 32
        bt, ln, n_pages, dead = walk_case(page, max_blocks, lengths)
        ppb = pages_per_block(page, max_blocks)
        assert max(lengths) > ppb * page or max_blocks <= ppb
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (len(lengths), Hq, hd), jnp.float32)
        k_pool, v_pool = _rand_pool(ks[1], n_pages, page, Hkv, hd)
        ref = paged_decode_attention_ref(q, k_pool, v_pool, bt, ln)
        # the TPU interpreter: VMEM starts as NaN, DMAs land on their
        # wait, a read out of bounds raises, and a DMA racing a read of
        # its buffer prints a race
        out = paged_decode_attention_pallas(
            q, k_pool.at[dead].set(jnp.nan), v_pool.at[dead].set(jnp.nan),
            bt, ln, interpret=pltpu.InterpretParams(detect_races=True))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        assert all(bool(jnp.all(out[b] == 0))      # length-0 lanes
                   for b, n in enumerate(lengths) if n == 0)
        assert "RACE" not in capsys.readouterr().out

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

    def test_walk_blocks_bounds_the_walk_of_each_step(self):
        """From the summed live pages of a step, ``walk_blocks`` bounds
        the compute blocks the lanes walk: one partial block a lane, and
        never more than the whole table."""
        page, max_blocks, lengths = WALKS[0]
        ppb = pages_per_block(page, max_blocks)
        steps = [lengths, (max_blocks * page,) * len(lengths)]
        pages = [sum(-(-n // page) for n in s) for s in steps]
        walked = [sum(-(-n // (ppb * page)) for n in s) for s in steps]
        assert ppb == 32 and walked == [8, 18]
        # (137 + 6 x 31) // 32 = 10, and the whole table's 6 x 3 = 18
        assert walk_blocks(pages, page, max_blocks, len(lengths)) == 14.0
        assert walk_blocks(pages[1:], page, max_blocks, len(lengths)) == 18

    def test_int8_fused_traffic_is_the_floor(self):
        """Under int8 the fused route reads live codes and scales once
        at stored width; the gather route sits above that floor."""
        kw = dict(n_slots=4, max_blocks=70, n_layers=2, kv_quant="int8")
        tb = traffic_bytes(100, 16, 2, 128, **kw)
        assert tb["fused"] == tb["floor"] == 2 * 100 * 16 * 2 * 2 * (128 + 4)
        assert tb["gather_sdpa"] > tb["floor"]

    @pytest.mark.parametrize("page,quantized,grid",
                             [(16, False, (2,)), (8, False, (2,)),
                              (4, False, (2, 3)), (12, False, (2, 3)),
                              (16, True, (2, 3))],
                             ids=["page16", "page8", "page4", "page12",
                                  "int8"])
    def test_pool_takes_the_walk_or_the_page_grid(self, page, quantized,
                                                  grid):
        """A model-dtype pool whose pages span whole 8-row tiles takes
        the walk, one program per lane; an int8 pool, or pages the chip
        cannot place in a block, take one grid step per (lane, page)."""
        q = jnp.zeros((2, 8, 32), jnp.float32)
        pool = jnp.zeros((7, 2, page, 32), jnp.int8 if quantized
                         else jnp.float32)
        scales = [jnp.ones((7, 2, page), jnp.float32)] * 2 * quantized
        bt, ln = jnp.ones((2, 3), jnp.int32), jnp.ones((2,), jnp.int32)
        jaxpr = jax.make_jaxpr(paged_decode_attention_pallas)(
            q, pool, pool, bt, ln, *scales)
        eqns = list(jaxpr.eqns)
        while eqns:
            e = eqns.pop()
            if e.primitive.name == "pallas_call":
                assert e.params["grid_mapping"].grid == grid
                return
            eqns += [x for v in e.params.values()
                     for x in getattr(getattr(v, "jaxpr", None), "eqns", ())]
        pytest.fail("no pallas_call in the traced kernel")

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
