"""Batch-1 / batched streaming decode engine — the paper's workload.

The engine is built around the paper's own conclusion: the decode step
must be ONE compiled program (the CUDA-Graphs-equivalent ``full_jit``
mode), with the KV cache donated so steps run allocation-free.  For the
dispatch A/B experiments the engine can be opened up to ``stage_jit`` /
``eager`` execution of the same math (core.dispatch).

Generation offers two drivers:
  step-streamed — one host dispatch per token (what a Python serving
                  loop does; pays the launch tax once per token)
  fused-loop    — ``lax.scan`` over N tokens inside ONE program (the
                  TPU-idiomatic schedule: zero per-token host work;
                  this is "CUDA Graphs over the whole generation")
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.dispatch import StepProgram
from repro.core.spans import Phases
from repro.models.model import Model
from repro.quant import quantize_tree
from repro.serving.programs import jit_cache_size
from repro.serving.sampling import sample


@dataclasses.dataclass
class GenerationResult:
    tokens: jnp.ndarray           # (B, n_new)
    step_times_s: List[float]     # per-token wall times (step-streamed only)
    tokens_per_s: float


class DecodeEngine:
    def __init__(self, model: Model, params, *, quant_path: str = "bf16",
                 kv_dtype=None, donate_cache: bool = True):
        self.model = model
        self.cfg: ArchConfig = model.cfg
        self.params = quantize_tree(params, quant_path) if quant_path != "bf16" else params
        self.quant_path = quant_path
        self.kv_dtype = kv_dtype
        # host time of the streamed loop: repro.dispatch / repro.sync
        self.phases = Phases()
        donate = (1,) if donate_cache else ()
        self._prefill = jax.jit(model.prefill)
        self._step = jax.jit(model.decode_step, donate_argnums=donate)
        # the fused-generation driver: same multi-step program family as
        # the scheduler's horizon-K macro-ticks (one executable per
        # distinct horizon)
        self._steps_fused = jax.jit(
            model.decode_steps,
            static_argnames=("horizon", "temperature", "top_k", "eos_id"),
            donate_argnums=donate)

    # -------------------------------------------------------------- API
    def step_cache_size(self) -> int:
        """Executables compiled for the streamed decode step (the
        recompile guard: 1 for a run at one batch and cache length)."""
        return jit_cache_size(self._step)

    def new_cache(self, batch: int, max_len: int):
        return self.model.init_cache(batch, max_len, kv_dtype=self.kv_dtype)

    def prefill(self, batch: Dict, max_len: int):
        B = next(iter(batch.values())).shape[0]
        cache = self.new_cache(B, max_len)
        logits, cache = self._prefill(self.params, batch, cache)
        return logits, cache

    def _token_shape(self, ids: jnp.ndarray) -> jnp.ndarray:
        if self.cfg.n_codebooks and ids.ndim == 2:
            return ids[:, None, :]          # (B, K) -> (B, 1, K)
        if ids.ndim == 1:
            return ids[:, None]
        return ids

    def generate_streamed(self, batch: Dict, *, max_len: int, n_new: int,
                          temperature: float = 0.0, top_k: int = 0,
                          seed: int = 0, timed: bool = False) -> GenerationResult:
        """One host dispatch per token (the paper's streaming workload):
        prefill, then ``decode_streamed`` from the filled cache.

        The generation wall is always timed (``tokens_per_s`` is real
        whether or not per-step instrumentation is on); ``timed=True``
        additionally records each token's gap for percentile reporting."""
        logits, cache = self.prefill(batch, max_len)
        key = jax.random.PRNGKey(seed)
        out, times = [], []
        tok = sample(logits[:, -1], key, temperature=temperature, top_k=top_k)
        out.append(tok)
        t_gen = t0 = time.perf_counter()
        # staticcheck: disable=prng-discipline -- decode_streamed folds key with the step index before each draw (the loop it replaced did the same), so its draws are disjoint from tok0's
        for tok, cache in self.decode_streamed(
                cache, tok, key, n_new - 1, temperature=temperature,
                top_k=top_k):
            if timed:
                t1 = time.perf_counter()
                times.append(t1 - t0)
                t0 = t1
            out.append(tok)
        jax.block_until_ready(tok)
        wall = time.perf_counter() - t_gen
        tokens = jnp.stack(out, axis=1)
        tps = (n_new - 1) / wall if n_new > 1 and wall > 0 else float("nan")
        return GenerationResult(tokens, times, tps)

    def decode_streamed(self, cache, tok: jnp.ndarray, key: jnp.ndarray,
                        n_steps: int, *, temperature: float = 0.0,
                        top_k: int = 0
                        ) -> Iterator[Tuple[jnp.ndarray, Dict]]:
        """Decode up to ``n_steps`` tokens from a filled ``cache`` whose
        last sampled token is ``tok`` (B,), one host dispatch per token.

        Per token: fold ``key`` with the step index, run the compiled
        step, sample, wait for the token on the device (spanned
        ``repro.dispatch`` and ``repro.sync``).  Yields ``(token, cache)``
        after each step; the token stays on the device.  The step
        donates the cache it is given, so a caller that stops early
        continues from the last cache yielded."""
        for i in range(n_steps):
            with self.phases("dispatch"):
                key = jax.random.fold_in(key, i)
                logits, cache = self._step(self.params, cache,
                                           self._token_shape(tok))
                tok = sample(logits[:, -1], key, temperature=temperature,
                             top_k=top_k)
            with self.phases("sync"):
                jax.block_until_ready(tok)
            yield tok, cache

    def generate_fused(self, batch: Dict, *, max_len: int, n_new: int,
                       seed: int = 0, temperature: float = 0.0,
                       top_k: int = 0) -> GenerationResult:
        """N tokens inside one compiled program — zero per-token host
        dispatch, the beyond-CUDA-Graphs schedule available on an
        AOT-compiled stack.  Runs the SAME multi-step program
        (``Model.decode_steps``) the continuous scheduler's horizon-K
        macro-ticks dispatch, with the horizon spanning the whole
        generation and every lane active throughout."""
        logits, cache = self.prefill(batch, max_len)
        key = jax.random.PRNGKey(seed)
        tok0 = sample(logits[:, -1], key, temperature=temperature,
                      top_k=top_k)
        t0 = time.perf_counter()
        # staticcheck: disable=prng-discipline -- decode_steps fold_ins key per scan step, so its draws are disjoint from tok0's; re-deriving here would change golden token streams
        toks, _ = self._steps_fused(self.params, cache,
                                    self._token_shape(tok0), key, None,
                                    horizon=n_new - 1,
                                    temperature=temperature, top_k=top_k)
        toks = jax.block_until_ready(toks)
        dt = time.perf_counter() - t0
        tokens = jnp.concatenate([tok0[:, None], toks], axis=1)
        return GenerationResult(tokens, [], (n_new - 1) / dt)

    def generate_continuous(self, sessions, *, n_slots: int, max_len: int,
                            temperature: float = 0.0, top_k: int = 0,
                            seed: int = 0, dispatch_mode: str = "full_jit",
                            paged: bool = False, page_size: int = 16,
                            n_pages: Optional[int] = None,
                            prefill_chunk: Optional[int] = None,
                            steps_per_tick: int = 1,
                            eos_id: Optional[int] = None,
                            timed: bool = True,
                            prefix_cache: bool = False,
                            adaptive_k: bool = False,
                            min_steps_per_tick: int = 1,
                            priority_preemption: bool = True,
                            virtual_step_s: float = 1e-3,
                            virtual_dispatch_s: float = 4e-3,
                            shared_programs: bool = False,
                            kv_tier: str = "none",
                            tier_policy="spill",
                            host_pages: Optional[int] = None,
                            virtual_host_copy_s: float = 5e-4,
                            fault_injector=None,
                            retry_budget: int = 2,
                            session_ttl_s: Optional[float] = None,
                            restore_patience: int = 0,
                            quarantine_budget: int = 2,
                            self_audit: bool = False,
                            logit_screen: Optional[bool] = None):
        """Continuous batching: serve ``sessions`` (SessionRequest list)
        through a fixed-capacity slotted cache — admission, per-slot
        prefill, shared batched decode, eviction, FIFO backfill.  The
        decode step is the same ONE compiled program for the whole run
        (``dispatch_mode='full_jit'``); the eager/stage_jit executors
        remain available for the dispatch-tax A/B on the live workload.
        ``paged=True`` serves out of a page pool with per-slot block
        tables instead of per-slot ``max_len`` rows — ``n_pages`` below
        full backing oversubscribes memory, ``prefill_chunk`` admits
        long prompts chunk-by-chunk between decode ticks.
        ``steps_per_tick=K > 1`` fuses K decode steps into one
        macro-tick program (on-device sampling, one token transfer per
        macro-tick) — the horizon-K launch-overhead amortisation;
        ``eos_id`` ends sessions early on sampling that token.
        ``prefix_cache=True`` (paged only) shares page-aligned prompt
        prefixes across sessions through refcounted CoW pages — matched
        runs skip prefill entirely; greedy streams stay token-identical
        to the no-sharing baseline, stochastic streams draw under
        different sampling salts (see repro.serving.scheduler).

        Sessions whose requests carry ``arrival_s > 0`` are *replayed*:
        released into the admission queue by virtual arrival time
        against the scheduler's deterministic clock (``virtual_step_s``
        per device decode step + ``virtual_dispatch_s`` launch tax per
        dispatched program) — the trace-driven load-harness mode
        (serving/trace.py builds traces and scores the SLO metrics).
        ``adaptive_k=True`` lets each macro-tick pick its horizon from
        the [min_steps_per_tick, steps_per_tick] ladder based on queue
        depth and resident budgets; ``priority_preemption=False``
        degrades page-pressure eviction to the youngest-first baseline.

        ``kv_tier='host'`` (paged only) adds a host-DRAM page tier:
        preempted sessions *park* their full KV pages host-side and
        re-admission restores them instead of re-prefilling, and
        LRU-evicted prefix pages get a second life in a host prefix
        index — placement steered by ``tier_policy``
        (prefer-device | spill | lookahead), capacity by ``host_pages``,
        virtual migration cost by ``virtual_host_copy_s`` per page.

        ``fault_injector`` (serving/faults.py) arms a seeded chaos plan
        against the run: injected copy failures retry with backoff
        (``retry_budget``) then degrade to re-prefill, poisoned logits
        quarantine their lane (``quarantine_budget`` requeues, then
        fail-closed), aborts and the ``session_ttl_s`` deadline free a
        session's slot and pages with a terminal event, and
        ``self_audit`` checks the page accounting on idle ticks.
        ``restore_patience`` holds a parked host copy that many ticks
        before re-prefill admission supersedes it.  Returns a
        ``ContinuousResult``."""
        from repro.serving.scheduler import SlotScheduler
        sched = SlotScheduler(self.model, self.params, n_slots=n_slots,
                              max_len=max_len, dispatch_mode=dispatch_mode,
                              temperature=temperature, top_k=top_k,
                              seed=seed, kv_dtype=self.kv_dtype,
                              paged=paged, page_size=page_size,
                              n_pages=n_pages, prefill_chunk=prefill_chunk,
                              steps_per_tick=steps_per_tick, eos_id=eos_id,
                              timed=timed, prefix_cache=prefix_cache,
                              adaptive_k=adaptive_k,
                              min_steps_per_tick=min_steps_per_tick,
                              priority_preemption=priority_preemption,
                              virtual_step_s=virtual_step_s,
                              virtual_dispatch_s=virtual_dispatch_s,
                              shared_programs=shared_programs,
                              kv_tier=kv_tier, tier_policy=tier_policy,
                              host_pages=host_pages,
                              virtual_host_copy_s=virtual_host_copy_s,
                              fault_injector=fault_injector,
                              retry_budget=retry_budget,
                              session_ttl_s=session_ttl_s,
                              restore_patience=restore_patience,
                              quarantine_budget=quarantine_budget,
                              self_audit=self_audit,
                              logit_screen=logit_screen)
        for req in sessions:
            sched.submit(req)
        return sched.run()

    # ------------------------------------------------- dispatch A/B hooks
    def step_program(self, cache) -> StepProgram:
        return self.model.step_program(self.params, cache)
