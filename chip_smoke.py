#!/usr/bin/env python3
"""Serve Qwen2.5-3B at its published widths on one TPU, end to end.

    python chip_smoke.py              # one TPU chip, full width
    python chip_smoke.py --reduced    # CPU rehearsal at the reduced config

One process drives the serving stack through the objects
``launch/serve.py`` builds (``Model``, ``DecodeEngine``,
``SlotScheduler``), with random weights from ``--seed``:

  batch1        batch-1 streamed decode, the paper's workload: prompt
                2048, 32 new tokens, one dispatch per token
  paged_gather  continuous serving from the paged KV pool, gather+SDPA
                route: 4 slots, 8 sessions, prompts spread over 512-1024
                tokens, 32 new tokens each, one token per dispatch (K=1)
  route_check   one decode step on paged_gather's live cache through
                both paged routes: logits must be finite and agree
                within LOGITS_TOL
  paged_fused   the same sessions through the fused block-table kernel,
                four tokens per dispatch (K=4); before it serves, the
                scheduler's own K=4 step program must hold the Pallas
                kernel (``tpu_custom_call``), not its interpreter

Each phase prints one line: tokens produced, decode-step executables
compiled (must be 1), seconds spent in the backend compiler, and the
median step wall time, named with the device it ran on.  Any failed
check exits nonzero.  Without ``--reduced`` a device that is not a TPU
is refused before any phase runs.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.launch.serve import (device_label, floor_note,  # noqa: E402
                                prompt_batch)
from repro.models.model import Model  # noqa: E402
from repro.serving import (DecodeEngine, SessionRequest,  # noqa: E402
                           SlotScheduler)

ARCH = "qwen2.5-3b"
# Max |logits_gather - logits_fused| over one paged decode step, relative
# to max |logits_gather|.  The routes differ in bf16 rounding only: the
# gather SDPA rounds its probabilities to bf16 before the PV product,
# the kernel keeps them in f32.  bf16 logits are spaced 0.4-0.8% of their
# magnitude; the routes differ by 1.0% at the reduced config and 1.9% at
# 36 layers, d_model 512 (random weights, CPU, interpret-mode kernel),
# while a dropped token, another slot's pages or swapped KV heads in the
# kernel put them 34-181% apart at the reduced config
# (tests/test_chip_smoke.py).
LOGITS_TOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    prompt: int          # batch1 prompt length
    new: int             # batch1 new tokens
    slots: int
    sessions: int
    paged_prompts: tuple  # paged phases: prompt lengths spread over (lo, hi)
    paged_new: int       # paged phases: new tokens per session
    page: int
    horizon: int         # paged_fused tokens per dispatch


FULL = Sizes(prompt=2048, new=32, slots=4, sessions=8,
             paged_prompts=(512, 1024), paged_new=32, page=16, horizon=4)
REDUCED = Sizes(prompt=32, new=8, slots=4, sessions=8,
                paged_prompts=(16, 32), paged_new=4, page=16, horizon=4)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends in the backend compiler (persistent-cache
    lookups included), read per phase as a difference."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def mark(self):
        return self.seconds, self.programs

    def since(self, mark) -> str:
        return (f"compile {self.seconds - mark[0]:.1f} s "
                f"({self.programs - mark[1]} programs)")


def device_memory() -> str:
    """Device memory in use now and at peak, as the backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "device memory not reported"
    return (f"device memory {stats['bytes_in_use'] / 1e9:.3f} GB in use, "
            f"peak {stats['peak_bytes_in_use'] / 1e9:.3f} GB of "
            f"{stats['bytes_limit'] / 1e9:.3f} GB")


def phase_line(name: str, tokens: int, step_cache_size: int, compiled: str,
               step_s, what: str, extra: str = "") -> str:
    med = float(np.median(step_s)) * 1e3
    line = (f"phase {name}: {tokens} tokens, step_cache_size "
            f"{step_cache_size}, {compiled}, median {what} {med:.3f} ms "
            f"measured on {device_label()}")
    return line + (f"; {extra}" if extra else "")


def batch1(engine: DecodeEngine, cfg, sz: Sizes, seed: int,
           clock: CompileClock) -> str:
    mark = clock.mark()
    batch = prompt_batch(cfg, 1, sz.prompt, seed)
    max_len = sz.prompt + sz.new + 1
    res = engine.generate_streamed(batch, max_len=max_len, n_new=sz.new,
                                   timed=True)
    check(res.tokens.shape == (1, sz.new), f"batch1 tokens {res.tokens.shape}")
    check(engine.step_cache_size() == 1,
          f"batch1 decode step compiled {engine.step_cache_size()}x")
    logits, _ = engine.prefill(batch, max_len)
    logits = np.asarray(logits, np.float32)
    check(bool(np.isfinite(logits).all()), "batch1 prefill logits not finite")
    check(int(np.argmax(logits[0, -1])) == int(res.tokens[0, 0]),
          "batch1 first token is not the prefill argmax")
    return phase_line("batch1", res.tokens.shape[1], engine.step_cache_size(),
                      clock.since(mark), res.step_times_s,
                      "step (dispatch to block_until_ready)",
                      floor_note(cfg, sz.prompt))


def paged_requests(cfg, sz: Sizes, seed: int) -> list:
    """``sz.sessions`` seeded prompts, lengths spread evenly over
    ``sz.paged_prompts`` (both ends included), ``sz.paged_new`` new
    tokens each."""
    rng = np.random.default_rng(seed + 1)
    lengths = np.linspace(*sz.paged_prompts, sz.sessions).round().astype(int)
    return [SessionRequest(f"session{i}",
                           rng.integers(0, cfg.vocab_size, n, np.int32),
                           sz.paged_new)
            for i, n in enumerate(lengths)]


def serve_paged(model: Model, params, reqs, sz: Sizes, *, horizon: int,
                clock: CompileClock, before_serving=None,
                after_first_tick=None):
    """Serve ``reqs`` through one paged ``SlotScheduler``.
    ``before_serving(sched)`` runs before the first tick,
    ``after_first_tick(sched)`` once every slot is admitted; each
    returns a line, printed before the phase line."""
    mark = clock.mark()
    max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 1
    sched = SlotScheduler(model, params, n_slots=sz.slots, max_len=max_len,
                          paged=True, page_size=sz.page,
                          steps_per_tick=horizon, timed=True)
    if before_serving:
        print(before_serving(sched))
    for r in reqs:
        sched.submit(r)
    sched.tick()
    if after_first_tick:
        print(after_first_tick(sched))
    res = sched.run()
    name = f"paged_{'fused' if model.decode_backend == 'pallas' else 'gather'}"
    want = sum(r.max_new_tokens for r in reqs)
    got = sum(len(s.tokens) for s in res.sessions.values())
    check(len(res.sessions) == len(reqs) and got == want,
          f"{name}: {got} of {want} tokens from {len(res.sessions)} sessions")
    check(res.step_cache_size == 1,
          f"{name}: decode step compiled {res.step_cache_size}x")
    check(all(0 <= t < model.cfg.vocab_size
              for s in res.sessions.values() for t in s.tokens),
          f"{name}: token outside the vocabulary")
    steps = [t for s in res.sessions.values() for t in s.step_times_s]
    what = ("step (dispatch to token transfer)" if horizon == 1 else
            f"per-token step (K={horizon} macro-tick wall / K)")
    return phase_line(name, got, res.step_cache_size, clock.since(mark),
                      steps, what)


def kernel_check(sched: SlotScheduler, on_tpu: bool) -> str:
    """Compile the scheduler's own horizon-K step program, the one the
    phase times, for the arguments it will be called with.  On a TPU it
    must hold the Pallas kernel; on the CPU the kernel is interpreted."""
    n = sched.n_slots
    hlo = sched._progs.steps.lower(
        sched.params, sched.cache, jnp.zeros((n, 1), jnp.int32), sched.key,
        jnp.zeros((n,), jnp.int32), horizon=sched.steps_per_tick,
        temperature=sched.temperature, top_k=sched.top_k,
        eos_id=sched.eos_id).compile().as_text()
    where = f"the K={sched.steps_per_tick} fused step program"
    if not on_tpu:
        return f"kernel_check: {where} runs the kernel in interpret mode"
    check("tpu_custom_call" in hlo,
          f"kernel_check: {where} holds no Pallas TPU kernel")
    return f"kernel_check: tpu_custom_call in {where}"


def route_check(sched: SlotScheduler, fused: Model, params) -> str:
    """One decode step on the scheduler's live paged cache through the
    gather route and the fused kernel (no donation: the cache stays the
    scheduler's)."""
    toks = np.zeros((sched.n_slots, 1), np.int32)
    for slot, sess in enumerate(sched.slots):
        check(sess is not None and sess.decoding,
              f"route_check: slot {slot} is not decoding")
        toks[slot, 0] = sess.next_input_token
    toks = jnp.asarray(toks)
    lg = np.asarray(jax.jit(sched.model.decode_step)(
        params, sched.cache, toks)[0], np.float32)
    lf = np.asarray(jax.jit(fused.decode_step)(
        params, sched.cache, toks)[0], np.float32)
    check(bool(np.isfinite(lg).all() and np.isfinite(lf).all()),
          "route_check: logits not finite")
    diff = float(np.max(np.abs(lg - lf)))
    scale = float(np.max(np.abs(lg)))
    check(diff <= LOGITS_TOL * scale,
          f"route_check: max |gather - fused| {diff:.4g} exceeds "
          f"{LOGITS_TOL} x max |logits| {scale:.4g}")
    return (f"route_check: max |logits gather - fused| {diff:.6g} "
            f"(max |logits| {scale:.6g}, tolerance {LOGITS_TOL} x that)")


def run(reduced: bool, seed: int) -> dict:
    """All phases, one printed line each; returns the device record of
    the last line."""
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not reduced and not on_tpu:
        raise SmokeFailure(f"no TPU: JAX runs on {device_label()}; "
                           f"use --reduced for a CPU rehearsal")
    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduced()
    sz = REDUCED if reduced else FULL
    clock = CompileClock()
    try:
        gather = Model(cfg)
        params = jax.block_until_ready(gather.init(jax.random.PRNGKey(seed)))
        n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        print(f"model {cfg.name}: {cfg.n_layers} layers, d_model "
              f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
              f"vocab {cfg.vocab_size}, {cfg.dtype}, seed {seed}, weights "
              f"{n_bytes / 1e9:.3f} GB; after init {device_memory()}")
        print(batch1(DecodeEngine(gather, params), cfg, sz, seed, clock))
        reqs = paged_requests(cfg, sz, seed)
        fused = Model(cfg, decode_backend="pallas")
        print(serve_paged(
            gather, params, reqs, sz, horizon=1, clock=clock,
            after_first_tick=lambda s: route_check(s, fused, params)))
        print(serve_paged(fused, params, reqs, sz, horizon=sz.horizon,
                          clock=clock,
                          before_serving=lambda s: kernel_check(s, on_tpu)))
    finally:
        clock.close()
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def smoke(reduced: bool, seed: int = 0) -> int:
    """Run every phase and print the report; returns the exit code."""
    t0 = time.perf_counter()
    try:
        device = run(reduced, seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s wall; "
          f"{device_memory()}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reduced", action="store_true",
                    help="run the phases at the reduced config (CPU "
                         "rehearsal; never a device measurement)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print(f"compile cache: {place_compile_cache()}")
    return smoke(args.reduced, args.seed)


if __name__ == "__main__":
    sys.exit(main())
