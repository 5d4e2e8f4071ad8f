"""The measured windows.  Each driver builds the program's serving
objects, warms every shape its mix will use (set-up), then serves for
exactly ``seconds`` of wall clock while recording, on the host clock,
every tick and every token, and hands the program's state back so the
caller can free it before the reference runs.

- ``engine``: batch-1 greedy decode through ``DecodeEngine``'s
  compiled step over the contiguous cache.  Per token it does what
  ``DecodeEngine.generate_streamed`` does and nothing more: fold the
  key, run the step, sample, wait for the token on the device; the
  tokens stay on the device until the segment ends.  (The engine has no
  public call that decodes from a filled cache.)  The context is
  filled once in set-up; the window decodes in segments of ``segment``
  tokens, each starting again from that same context, so no prefill or
  admission runs in the window.  The gap across a segment boundary is
  not a token gap.
- ``scheduler``: ``SlotScheduler`` over the paged pool.  A closed loop
  prefills its sessions in set-up and keeps them all decoding past the
  window; an open loop submits each request when it falls due on the
  wall clock, times it from then, and after the window keeps arriving
  and ticking until every request due in the window has its first token
  (at most ``drain_s``), then lets what was admitted finish.

Spans: ``bench.tick`` around each tick, ``bench.sync`` around the wait
for the batch-1 loop's token, ``bench.traced`` over the traced slice.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import traffic
from bench.harness.score import Served

Span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    sync_s: float                   # host blocked on the token transfer
    live: Tuple[int, ...]           # live KV length of each decoding lane


@dataclasses.dataclass
class Window:
    open_s: float = 0.0
    close_s: float = 0.0
    trace_from: Optional[float] = None
    ticks: List[Tick] = dataclasses.field(default_factory=list)
    gaps: List[Tuple[float, float]] = dataclasses.field(default_factory=list)
    # (start, end) of each token gap that counts
    ttft: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)       # (due time, seconds to first token)
    backlog_at_close: int = 0       # requests waiting when the window closed
    queue_wait: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)       # (due time, wait seconds)
    late_s: List[float] = dataclasses.field(default_factory=list)
    tokens: int = 0                 # tokens emitted inside the window
    attempted: int = 0
    failed: int = 0
    sample: List[Served] = dataclasses.field(default_factory=list)
    notes: List[str] = dataclasses.field(default_factory=list)

    def in_window(self, t: float) -> bool:
        return self.open_s <= t <= self.close_s


class Tracer:
    """Starts the profiler ``trace_s`` before the window closes (when
    tracing) and stops it at the close."""

    def __init__(self, directory: Optional[str], trace_s: float):
        self.directory, self.trace_s = directory, trace_s
        self.span: Optional[Span] = None
        self.started_at: Optional[float] = None

    def poll(self, now: float, close: float) -> None:
        if self.directory and self.started_at is None \
                and now >= close - self.trace_s:
            jax.profiler.start_trace(self.directory)
            self.started_at = time.perf_counter()
            self.span = Span("bench.traced")
            self.span.__enter__()

    def stop(self) -> None:
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            jax.profiler.stop_trace()


def _settle() -> None:
    """End of set-up: collect, and move what survives out of the
    collector's way, so the window's collections stay short."""
    gc.collect()
    gc.freeze()


# ------------------------------------------------------------- batch-1
def engine_cell(model, params, mix: Dict, seed: int, seconds: float,
                tracer: Tracer, mark_open: Callable[[], None]):
    from repro.serving import DecodeEngine, sample

    ctx, seg = mix["context"], mix["segment"]
    vocab = model.cfg.vocab_size
    engine = DecodeEngine(model, params)
    prompt = traffic.prompt_ids(seed, vocab, ctx)
    logits, filled = engine.prefill({"tokens": jnp.asarray(prompt)[None]},
                                    ctx + seg)
    key0 = jax.random.PRNGKey(0)    # generate_streamed's default seed
    tok0 = sample(logits[:, -1], key0, temperature=0.0)
    step = engine._step             # the engine's compiled decode step
    state = {"cache": filled}

    def segment(n_steps: int, w: Optional[Window]):
        """Decode up to ``n_steps`` from the filled context, per token as
        ``generate_streamed``; returns the served tokens and whether the
        window closed first."""
        cache = dict(state["cache"], pos=jnp.asarray(ctx, jnp.int32))
        key, tok, out, last = key0, tok0, [tok0], None
        closed = False
        for i in range(n_steps):
            t0 = time.perf_counter()
            with Span("bench.tick"):
                key = jax.random.fold_in(key, i)
                logits, cache = step(engine.params, cache,
                                     engine._token_shape(tok))
                tok = sample(logits[:, -1], key, temperature=0.0)
                t1 = time.perf_counter()
                with Span("bench.sync"):
                    jax.block_until_ready(tok)
            t2 = time.perf_counter()
            out.append(tok)
            if w is not None:
                w.ticks.append(Tick(t0, t2, t2 - t1, (ctx + i + 1,)))
                if last is not None:
                    w.gaps.append((last, t2))
                last = t2
                tracer.poll(t2, w.close_s)
                if t2 >= w.close_s:
                    closed = True
                    break
        state["cache"] = cache
        return [int(t[0]) for t in jax.device_get(out)], closed

    segment(2, None)                # compiles the step, fold and sampling
    _settle()
    w = Window()
    mark_open()
    w.open_s = time.perf_counter()
    w.close_s = w.open_s + seconds
    served: List[List[int]] = []
    with Span("bench.window"):
        closed = False
        while not closed:
            toks, closed = segment(seg, w)
            w.attempted += 1
            if not closed:
                served.append(toks)
    tracer.stop()
    w.gaps = [g for g in w.gaps if g[1] <= w.close_s]
    w.tokens = len(w.gaps)
    w.trace_from = tracer.started_at
    if not served:                  # a window shorter than one segment
        served.append(toks)
    pick = int(np.random.default_rng(seed).integers(len(served)))
    w.sample = [Served(f"segment{pick}", prompt,
                       np.asarray(served[pick], np.int32))]
    w.notes.append(f"window: {w.attempted} segments of {seg} tokens from "
                   f"context {ctx}, {len(served)} complete, {w.tokens} "
                   f"token gaps")
    return w, (engine, state, filled, logits)


# ----------------------------------------------------------- scheduler
def scheduler_cell(model, params, mix: Dict, seed: int, seconds: float,
                   tracer: Tracer, mark_open: Callable[[], None]):
    from repro.serving import SessionRequest, SlotScheduler

    cfg = model.cfg
    open_loop = mix["loop"] == "open"
    reqs = traffic.requests(mix, seed, cfg.vocab_size, seconds)
    max_prompt = max(traffic.support(mix["prompt"]))
    max_out = max(traffic.support(mix["output"]))
    page = mix["page"]
    n_pages = None                  # full backing: every slot at max_len
    if mix.get("pool") == "sessions":
        # as many pages as the closed loop's sessions can ever hold
        n_pages = 1 + sum(-(-(r.prompt.size + r.max_new_tokens) // page)
                          for r in reqs)
    sched = SlotScheduler(model, params, n_slots=mix["slots"],
                          max_len=max_prompt + max_out, paged=True,
                          page_size=page, n_pages=n_pages,
                          prefill_chunk=mix.get("prefill_chunk"),
                          steps_per_tick=mix.get("steps_per_tick", 1),
                          seed=seed & 0xFFFFFFFF, timed=True)
    sessions = {}
    first_at: Dict[str, float] = {}
    due_at: Dict[str, float] = {}
    admitted: Dict[str, float] = {}

    def tick(w: Optional[Window]) -> None:
        live = tuple(s.pos + 1 for s in sched.slots
                     if s is not None and s.decoding)
        sync0 = sched.host_sync_s
        t0 = time.perf_counter()
        with Span("bench.tick"):
            events = sched.tick()
        t1 = time.perf_counter()
        for ev in events:
            if ev[0] == "admit":
                admitted.setdefault(ev[1], t0)
            elif ev[0] == "token":
                sid = ev[1]
                prev = sessions.get(sid)
                if w is not None and prev is not None \
                        and w.in_window(prev) and w.in_window(t1):
                    w.gaps.append((prev, t1))
                sessions[sid] = t1
                first_at.setdefault(sid, t1)
                if w is not None and w.in_window(t1):
                    w.tokens += 1
        if w is not None:
            w.ticks.append(Tick(t0, t1, sched.host_sync_s - sync0, live))

    # ---- set-up: every shape the window will use
    if open_loop:
        for i, plen in enumerate(traffic.support(mix["prompt"])):
            sched.submit(SessionRequest(
                f"warm_{i}", traffic.prompt_ids(seed + i, cfg.vocab_size,
                                                plen), 2))
        sched.run()
    else:
        for r in reqs:
            sched.submit(SessionRequest(r.rid, r.prompt, r.max_new_tokens))
        while not all(s is not None and s.decoding and len(s.tokens) >= 3
                      for s in sched.slots):
            tick(None)
    _settle()

    w = Window()
    mark_open()
    w.open_s = time.perf_counter()
    w.close_s = w.open_s + seconds
    pending = list(reqs) if open_loop else []
    window_due: List[str] = []
    closed = False
    with Span("bench.window"):
        while True:
            now = time.perf_counter()
            while pending and w.open_s + pending[0].due_s <= now:
                r = pending.pop(0)
                due = w.open_s + r.due_s
                sched.submit(SessionRequest(r.rid, r.prompt,
                                            r.max_new_tokens))
                due_at[r.rid] = due
                w.late_s.append(now - due)
                if due <= w.close_s:
                    window_due.append(r.rid)
            if now >= w.close_s:
                if not closed:
                    closed = True
                    w.backlog_at_close = len(sched.waiting)
                tracer.stop()
                if all(r in first_at for r in window_due) or \
                        now >= w.close_s + mix.get("drain_s", 0):
                    break
            tracer.poll(now, w.close_s)
            if sched.waiting or any(s is not None for s in sched.slots):
                tick(w)
            elif pending:
                time.sleep(max(0.0, min(w.open_s + pending[0].due_s,
                                        w.close_s) - time.perf_counter()))
            else:
                time.sleep(1e-3)
    w.trace_from = tracer.started_at
    if open_loop:
        # what was admitted finishes; nothing new arrives
        sched.waiting.clear()
        t_end = time.perf_counter() + mix.get("drain_s", 0)
        while any(s is not None for s in sched.slots) \
                and time.perf_counter() < t_end:
            tick(None)
        w.attempted = len(window_due)
        got = [r for r in window_due if r in first_at]
        w.failed = w.attempted - len(got)
        w.ttft = [(due_at[r], first_at[r] - due_at[r]) for r in got]
        w.queue_wait = [(due_at[r], admitted[r] - due_at[r]) for r in got
                        if r in admitted]
        done = {s.sid: s for s in sched.finished}
        pool = [done[r] for r in got if r in done]
        w.notes.append(f"window: {len(window_due)} requests due, "
                       f"{len(got)} served a first token, "
                       f"{len(pool)} finished; {w.tokens} tokens")
    else:
        w.attempted = len(reqs)
        pool = [s for s in sched.slots if s is not None]
        w.failed = w.attempted - len(pool)
        w.notes.append(f"window: {len(pool)} sessions live, {w.tokens} "
                       f"tokens, {len(w.ticks)} ticks")
    w.sample = _sample(pool, mix, seed)
    return w, (sched,)


def _sample(pool, mix: Dict, seed: int) -> List[Served]:
    """The longest request, and more drawn from the seed, up to
    ``score_requests``; of each, the last ``score_tokens`` served
    tokens (all of them when unset)."""
    if not pool:
        return []
    longest = max(pool, key=lambda s: (len(s.request.prompt) + len(s.tokens),
                                       s.sid))
    rest = sorted((s for s in pool if s is not longest), key=lambda s: s.sid)
    rng = np.random.default_rng(seed)
    k = min(len(rest), mix["score_requests"] - 1)
    picked = [longest] + [rest[i] for i in
                          sorted(rng.choice(len(rest), k, replace=False))]
    out = []
    for s in picked:
        toks = np.asarray(s.tokens, np.int32)
        keep = mix.get("score_tokens") or len(toks)
        out.append(Served(s.sid, np.asarray(s.request.prompt, np.int32),
                          toks, max(0, len(toks) - keep)))
    return out


DRIVERS = {"engine": engine_cell, "scheduler": scheduler_cell}
