"""Serving launcher — batch-1 streaming decode, the paper's workload,
plus the continuous-batching multi-session mode (slotted KV cache).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
      --prompt-len 32 --new-tokens 64 --quant int4_fused --timed

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
      --continuous --slots 4 --sessions 10 --timed

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
      --paged --trace bursty --steps-per-tick 8 --adaptive-k --slo-json
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core import floor as fl
from repro.core.hardware import chip_for_device_kind
from repro.launch.compile_cache import place_compile_cache
from repro.models.model import Model
from repro.serving import DecodeEngine, SessionRequest
from repro.training.data import DataLoader


def device_label() -> str:
    """The device JAX runs on, as every timing line names it."""
    dev = jax.devices()[0]
    return f"{dev.platform} {dev.device_kind}"


def floor_note(cfg, ctx: int) -> str:
    """The batch-1 analytic step floor of ``cfg`` at context ``ctx`` on
    the device JAX runs on (peaks looked up by ``device_kind``; an
    unknown TPU raises).  Off-TPU there is no device floor to compare a
    wall time with."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return "device floor: not measured"
    chip = chip_for_device_kind(dev.device_kind)
    fc = fl.floor_cell(cfg, chip, ctx)
    return f"{chip.name} analytic floor {fc.t_floor_ms:.2f} ms"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--quant", default="bf16",
                    choices=["bf16", "int8_dequant", "int8_fused",
                             "int4_dequant", "int4_fused"])
    ap.add_argument("--weights", default=None,
                    choices=["bf16", "int8", "int4", "int8_dequant",
                             "int8_fused", "int4_dequant", "int4_fused"],
                    help="weight quantisation path (alias for --quant; "
                         "bare 'int8'/'int4' select the fused "
                         "realised-savings path)")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="KV cache quantisation: int8 stores codes + "
                         "per-(token, head) f32 scales — on --paged the "
                         "scales ride parallel pool slabs sharing the "
                         "block table, and --decode-backend pallas "
                         "dequantises inside the fused kernel's block "
                         "loads (realised traffic cut); the gather "
                         "route materialises a dequantised view "
                         "(bnb-style, stored-only cut)")
    ap.add_argument("--mode", default="streamed", choices=["streamed", "fused"])
    ap.add_argument("--decode-backend", default="sdpa",
                    choices=["sdpa", "math", "split_kv", "pallas"],
                    help="decode attention route; with --paged, 'pallas' "
                         "runs the fused block-table kernel (pages read "
                         "in place, no gathered view; interpret mode on "
                         "CPU), anything else the gather+SDPA reference")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--timed", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # continuous batching (slotted KV cache, multi-session churn)
    ap.add_argument("--continuous", action="store_true",
                    help="serve --sessions sessions of mixed prompt/target "
                         "lengths through --slots cache slots")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--dispatch", default="full_jit",
                    choices=["eager", "stage_jit", "full_jit"])
    ap.add_argument("--steps-per-tick", type=int, default=1,
                    help="horizon K: fuse K decode steps into ONE "
                         "compiled macro-tick program (on-device "
                         "sampling, one token transfer per macro-tick) "
                         "— amortises the per-token Python + dispatch + "
                         "sync launch tax by ~K; K=1 is the classic "
                         "one-dispatch-per-token loop.  Requires "
                         "--dispatch full_jit.  Sweet spot: 4-16 "
                         "(above that, mid-horizon finishes waste "
                         "device steps and admission latency grows)")
    # paged KV cache (slot->block-table->page-pool indirection)
    ap.add_argument("--paged", action="store_true",
                    help="serve out of a paged KV cache: a page pool + "
                         "per-slot block tables instead of per-slot "
                         "max_len rows (implies --continuous)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--pages", type=int, default=None,
                    help="total pool pages incl. the garbage sentinel; "
                         "below 1 + slots*ceil(max_len/page_size) the "
                         "pool is oversubscribed (default: full backing)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="admit prompts in chunks of this many tokens "
                         "(multiple of --page-size), interleaved with "
                         "decode ticks")
    # prefix sharing (copy-on-write KV pages over the block table)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share page-aligned prompt prefixes across "
                         "sessions: matched pages are refcounted and "
                         "aliased into the new slot's block table "
                         "(prefill skipped for the match, CoW copy "
                         "before any write could touch a shared page); "
                         "implies --paged")
    # host-DRAM KV page tier (serving/memory/tiers.py)
    ap.add_argument("--kv-tier", default="none", choices=["none", "host"],
                    help="with --paged: add a host-DRAM page tier — "
                         "preempted sessions park their full KV pages "
                         "host-side and re-admission restores them "
                         "instead of re-prefilling; LRU-evicted prefix "
                         "pages spill into a host prefix index "
                         "(implies --paged)")
    ap.add_argument("--tier-policy", default="spill",
                    choices=["prefer-device", "spill", "lookahead"],
                    help="placement/migration policy for --kv-tier host: "
                         "prefer-device never spills (the control arm), "
                         "spill migrates exactly on eviction, lookahead "
                         "additionally pre-copies the predicted next "
                         "victim's cold pages on idle ticks")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="host pool capacity in pages (default: one full "
                         "device pool)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical tokens to every "
                         "session's prompt (the physical-AI fleet "
                         "workload: one system prompt / scene preamble "
                         "replayed across sessions) — what "
                         "--prefix-cache deduplicates")
    # trace-driven load replay (serving/trace.py)
    ap.add_argument("--trace", default=None,
                    choices=["poisson", "bursty"],
                    help="replay a seeded arrival trace instead of the "
                         "all-at-once session wave: requests are "
                         "released into the admission queue by virtual "
                         "arrival time and the run reports per-class "
                         "TTFT / per-token latency percentiles and "
                         "goodput-under-SLO on the scheduler's "
                         "deterministic virtual clock (implies "
                         "--continuous; --sessions sets the request "
                         "count)")
    ap.add_argument("--trace-seed", type=int, default=13,
                    help="trace generator seed (same seed -> "
                         "byte-identical trace)")
    ap.add_argument("--rate", type=float, default=25.0,
                    help="mean arrival rate of the trace, requests per "
                         "virtual second")
    ap.add_argument("--adaptive-k", action="store_true",
                    help="let each macro-tick pick its horizon from the "
                         "[1, --steps-per-tick] halving ladder by load "
                         "(ends ticks at completions when sessions "
                         "queue, at arrivals when a slot is free); "
                         "requires --steps-per-tick >= 2")
    ap.add_argument("--no-priority-preemption", action="store_true",
                    help="page-pressure eviction picks the youngest "
                         "session regardless of priority (the FIFO "
                         "baseline) instead of "
                         "lowest-priority-youngest")
    ap.add_argument("--slo-json", action="store_true",
                    help="with --trace: print the full SLO report as "
                         "JSON instead of the one-line summary")
    # fault injection + graceful degradation (serving/faults.py)
    ap.add_argument("--fault-plan", default=None,
                    help="with --trace: arm a chaos plan against the "
                         "replay — 'mixed' generates a seeded plan over "
                         "every fault kind (--chaos-seed; same seed -> "
                         "byte-identical schedule), anything else is "
                         "read as a fault-plan file (plan_to_text "
                         "format).  Injected copy failures retry with "
                         "backoff then degrade to re-prefill, poisoned "
                         "logits quarantine their lane, aborts free the "
                         "session's slot and pages with a terminal "
                         "event")
    ap.add_argument("--chaos-seed", type=int, default=7,
                    help="seed for --fault-plan mixed")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="retries per failed host-tier copy before the "
                         "restore degrades to re-prefill (backoff is "
                         "charged to the virtual clock)")
    ap.add_argument("--session-ttl", type=float, default=None,
                    help="per-session deadline in virtual seconds since "
                         "arrival; overdue sessions are expired and "
                         "their slot/pages freed")
    ap.add_argument("--restore-patience", type=int, default=0,
                    help="ticks a parked host copy is held while the "
                         "page gate can't cover its restore before "
                         "re-prefill admission supersedes it")
    args = ap.parse_args()
    if args.weights:
        args.quant = {"int8": "int8_fused",
                      "int4": "int4_fused"}.get(args.weights, args.weights)
    if args.trace:
        args.continuous = True
    if args.prefix_cache:
        args.paged = True
    if args.kv_tier != "none":
        args.paged = True
    if args.paged:
        args.continuous = True

    place_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, decode_backend=args.decode_backend)
    params = model.init(jax.random.PRNGKey(args.seed))
    import jax.numpy as jnp
    engine = DecodeEngine(
        model, params, quant_path=args.quant,
        kv_dtype=jnp.int8 if args.kv_quant == "int8" else None)

    if args.trace:
        return serve_trace(engine, cfg, args)
    if args.continuous:
        return serve_continuous(engine, cfg, args)

    batch = prompt_batch(cfg, args.batch, args.prompt_len, args.seed)
    max_len = args.prompt_len + args.new_tokens + 1

    if args.mode == "fused":
        res = engine.generate_fused(batch, max_len=max_len,
                                    n_new=args.new_tokens,
                                    temperature=args.temperature,
                                    seed=args.seed)
    else:
        res = engine.generate_streamed(batch, max_len=max_len,
                                       n_new=args.new_tokens,
                                       temperature=args.temperature,
                                       timed=args.timed)
    print(f"generated {res.tokens.shape} tokens; {res.tokens_per_s:.1f} tok/s "
          f"on {device_label()}")
    if args.timed and res.step_times_s:
        import numpy as np
        p50 = float(np.median(res.step_times_s)) * 1e3
        print(f"p50 step {p50:.2f} ms on {device_label()} "
              f"({floor_note(cfg, args.prompt_len)})")
        print(phase_line(engine.phases.totals))
    print("first tokens:", res.tokens[0, :12].tolist())


def phase_line(totals) -> str:
    """The host's own time per phase (``core/spans.py``), phases that
    ran only."""
    parts = [f"{name} {sec * 1e3:.1f}" for name, sec in totals.items()
             if sec > 0]
    return "host ms by phase: " + (", ".join(parts) or "none")


def prompt_batch(cfg, batch: int, prompt_len: int, seed: int):
    """Seeded synthetic prompts (batch, prompt_len) for streamed decode."""
    out = next(DataLoader(cfg, batch=batch, seq_len=prompt_len, seed=seed))
    out.pop("labels", None)
    return out


def mixed_requests(cfg, n_sessions: int, *, base_prompt: int,
                   base_new: int, seed: int, shared_prefix: int = 0):
    """Deterministic session mix: prompt lengths base..~2x base, token
    budgets base_new..~2x base_new — enough spread to exercise churn.
    ``shared_prefix`` prepends that many identical tokens to every
    prompt (the prefix-sharing workload)."""
    key = jax.random.PRNGKey(seed + 1)
    common = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 10_000), (shared_prefix,), 0,
        cfg.vocab_size)) if shared_prefix else None
    reqs = []
    for i in range(n_sessions):
        k = jax.random.fold_in(key, i)
        plen = base_prompt + (i * 7) % (base_prompt + 1)
        n_new = base_new + (i * 5) % (base_new + 1)
        prompt = np.asarray(jax.random.randint(k, (plen,), 0,
                                               cfg.vocab_size))
        if common is not None:
            prompt = np.concatenate([common, prompt])
        reqs.append(SessionRequest(f"session{i}", prompt, n_new))
    return reqs


def serve_trace(engine: DecodeEngine, cfg, args):
    """Trace-driven load replay: generate a seeded arrival trace,
    release its requests by virtual arrival time through the continuous
    scheduler, and report the SLO metrics (TTFT / per-token latency
    percentiles, goodput-under-SLO) per session class."""
    import json

    from repro.serving import generate_trace, slo_report
    from repro.serving.trace import bursty_config, poisson_config
    mk = bursty_config if args.trace == "bursty" else poisson_config
    tcfg = mk(seed=args.trace_seed, n_requests=args.sessions,
              vocab_size=cfg.vocab_size, rate_rps=args.rate)
    trace = generate_trace(tcfg)
    max_len = trace.max_len() + 1
    injector = None
    if args.fault_plan:
        from repro.serving.faults import (FaultInjector, FaultPlanConfig,
                                          generate_fault_plan,
                                          plan_from_text, validate_plan)
        sids = [r.session_id for r in trace.requests]
        if args.fault_plan == "mixed":
            horizon = round(max(r.arrival_s for r in trace.requests)
                            + 0.25, 6)
            plan = generate_fault_plan(
                FaultPlanConfig(seed=args.chaos_seed, n_faults=12,
                                horizon_s=horizon), session_ids=sids)
        else:
            with open(args.fault_plan) as fh:
                plan = plan_from_text(fh.read())
            validate_plan(plan)
        injector = FaultInjector(plan)
    res = engine.generate_continuous(
        trace.requests, n_slots=args.slots, max_len=max_len,
        temperature=args.temperature, seed=args.seed,
        dispatch_mode=args.dispatch, paged=args.paged,
        page_size=args.page_size, n_pages=args.pages,
        prefill_chunk=args.prefill_chunk,
        steps_per_tick=args.steps_per_tick, timed=args.timed,
        prefix_cache=args.prefix_cache, adaptive_k=args.adaptive_k,
        priority_preemption=not args.no_priority_preemption,
        kv_tier=args.kv_tier, tier_policy=args.tier_policy,
        host_pages=args.host_pages,
        fault_injector=injector, retry_budget=args.retry_budget,
        session_ttl_s=args.session_ttl,
        restore_patience=args.restore_patience,
        self_audit=injector is not None)
    rep = slo_report(res, trace.classes)
    if args.slo_json:
        print(json.dumps(rep, indent=2, allow_nan=False))
        return
    print(f"replayed {args.trace} trace (seed {args.trace_seed}, "
          f"{len(trace.requests)} requests at {args.rate:g} req/s) through "
          f"{args.slots} slots, steps_per_tick={args.steps_per_tick}"
          f"{' adaptive' if args.adaptive_k else ''}: "
          f"{res.dispatches} decode dispatches, "
          f"{res.preemptions} preemptions, "
          f"virtual makespan {rep['makespan_s']:.3f}s")
    if res.kv_tier != "none":
        print(f"kv tier ({res.tier_policy}): {res.pages_spilled} spilled / "
              f"{res.pages_restored} restored pages, "
              f"{res.tier_restores} parked restores, "
              f"{res.host_prefix_hits} host prefix hits")
    if injector is not None:
        fc = " ".join(f"{k}:{v}" for k, v in res.fault_counts.items())
        print(f"chaos plan ({args.fault_plan}, seed {args.chaos_seed}): "
              f"{res.faults_injected} faults fired"
              f"{' (' + fc + ')' if fc else ''}")
        print(f"recovery: {res.save_retries}/{res.restore_retries} "
              f"save/restore retries "
              f"({res.retry_backoff_s * 1e3:.1f} ms virtual backoff), "
              f"{res.degraded_restores} degraded restores, "
              f"{res.corrupt_blobs} checksum rejects, "
              f"{res.quarantines} quarantines; sessions "
              f"{res.aborted_sessions} aborted / "
              f"{res.failed_sessions} failed / "
              f"{res.expired_sessions} expired")
    if rep["ttft"] is not None and rep["tpot"] is not None:
        print(f"ttft p50/p95/p99 {rep['ttft']['p50']:.4f}/"
              f"{rep['ttft']['p95']:.4f}/{rep['ttft']['p99']:.4f} s, "
              f"tpot p50/p95/p99 {rep['tpot']['p50']:.4f}/"
              f"{rep['tpot']['p95']:.4f}/{rep['tpot']['p99']:.4f} s "
              f"(virtual)")
    for name, c in rep["classes"].items():
        print(f"  class {name}: {c['sessions']} sessions, "
              f"slo_frac {c['slo_frac']:.2f} "
              f"(ttft<={c['slo_ttft_s']:g}s, tpot_p95<={c['slo_tpot_s']:g}s), "
              f"goodput {c['goodput_tok_s']:.1f} tok/s")
    dropped = (f", {rep['failed_sessions']} dropped"
               if rep.get("failed_sessions") else "")
    print(f"goodput under SLO: {rep['goodput_tok_s']:.1f} tok/s "
          f"({rep['slo_sessions']}/{rep['sessions']} sessions in SLO"
          f"{dropped}, "
          f"{rep.get('tokens_per_s_virtual', 0.0):.1f} tok/s served)")
    if res.adaptive_k:
        hist = " ".join(f"K{k}:{v}" for k, v in
                        sorted(res.horizon_hist.items()))
        print(f"adaptive horizon histogram: {hist}")


def serve_continuous(engine: DecodeEngine, cfg, args):
    reqs = mixed_requests(cfg, args.sessions, base_prompt=args.prompt_len,
                          base_new=args.new_tokens, seed=args.seed,
                          shared_prefix=args.shared_prefix)
    max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 1
    res = engine.generate_continuous(
        reqs, n_slots=args.slots, max_len=max_len,
        temperature=args.temperature, seed=args.seed,
        dispatch_mode=args.dispatch, paged=args.paged,
        page_size=args.page_size, n_pages=args.pages,
        prefill_chunk=args.prefill_chunk,
        steps_per_tick=args.steps_per_tick, timed=args.timed,
        prefix_cache=args.prefix_cache, kv_tier=args.kv_tier,
        tier_policy=args.tier_policy, host_pages=args.host_pages)
    n_tok = sum(len(s.tokens) for s in res.sessions.values())
    layout = "paged" if args.paged else "contiguous"
    backend = engine.model.decode_backend
    print(f"served {len(res.sessions)} sessions through {args.slots} slots "
          f"({args.dispatch}, {layout}, attn={backend}): {n_tok} tokens in "
          f"{res.ticks} ticks / {res.dispatches} decode dispatches, "
          f"{res.tokens_per_s:.1f} tok/s aggregate on {device_label()}")
    if args.steps_per_tick > 1:
        dec_tok = n_tok - len(res.sessions)   # first tokens come from prefill
        print(f"horizon-K: steps_per_tick={args.steps_per_tick}, "
              f"{dec_tok / max(res.dispatches, 1):.1f} tokens per dispatch")
    if args.paged:
        max_blocks = -(-max_len // args.page_size)
        full = 1 + args.slots * max_blocks
        pages = args.pages or full
        print(f"paged: page_size={args.page_size} pages={pages} "
              f"(full backing {full}, "
              f"oversubscription x{(full - 1) / max(pages - 1, 1):.2f}), "
              f"preemptions={res.preemptions}")
        if args.prefix_cache:
            # denominator = prefill work this run would have dispatched
            # without sharing (saved + dispatched) — preempted sessions
            # re-match their own prefix on resume, so hits can exceed
            # the session count and saved can exceed the prompt bytes
            total = res.prefix_tokens_saved + res.prefill_tokens
            print(f"prefix cache: {res.prefix_hits} admission hits "
                  f"({len(reqs)} sessions), prefill tokens "
                  f"{res.prefill_tokens} dispatched / "
                  f"{res.prefix_tokens_saved} shared "
                  f"({res.prefix_tokens_saved / max(total, 1):.0%} of "
                  f"prefill work skipped), "
                  f"{res.cow_copies} CoW page cop"
                  f"{'y' if res.cow_copies == 1 else 'ies'}")
        if res.kv_tier != "none":
            print(f"kv tier ({res.tier_policy}): "
                  f"{res.pages_spilled} pages spilled / "
                  f"{res.pages_restored} restored, "
                  f"{res.tier_restores} parked-session restores, "
                  f"{res.host_prefix_hits} host prefix hits, "
                  f"{res.host_pages_used} host pages resident")
        if res.step_kv_blocks:
            from repro.kernels.paged_decode_attention.ops import (
                serving_traffic_bytes, walk_blocks)
            from repro.kernels.paged_decode_attention.paged_decode_attention \
                import PAGE_ROWS
            tb = serving_traffic_bytes(res.step_kv_blocks, cfg,
                                       page_size=args.page_size,
                                       n_slots=args.slots,
                                       max_blocks=max_blocks,
                                       kv_quant=args.kv_quant)
            route = "fused-in-place" if backend == "pallas" else "gather+sdpa"
            moved = tb["fused"] if backend == "pallas" else tb["gather_sdpa"]
            quant_note = (f", kv_quant={args.kv_quant} "
                          f"floor {tb['floor'] / 1024:.1f} KiB"
                          if args.kv_quant != "none" else "")
            print(f"per-step KV traffic ({route}): {moved / 1024:.1f} KiB "
                  f"(fused would move {tb['fused'] / 1024:.1f}, gather "
                  f"{tb['gather_sdpa'] / 1024:.1f}{quant_note})")
            if (backend == "pallas" and args.kv_quant == "none"
                    and args.page_size % PAGE_ROWS == 0):
                w = walk_blocks(res.step_kv_blocks, args.page_size,
                                max_blocks, args.slots)
                print(f"fused walk: at most {w:.1f} compute blocks a "
                      f"layer-step")
    if args.quant != "bf16" or args.kv_quant != "none":
        from repro.quant import tree_weight_traffic
        wb = tree_weight_traffic(engine.params)
        print(f"quantised serving: weights={args.quant} "
              f"kv={args.kv_quant}; per-step weight stream "
              f"{wb / 1024:.1f} KiB")
    compiled = (f"compiled {res.step_cache_size}x"
                if res.step_cache_size is not None else
                "compile count n/a (staged/eager executors)")
    print(f"decode step {compiled}, "
          f"{res.launches_per_step} host launch(es) per step")
    if args.timed:
        print(phase_line(res.host_phase_s))
        for sid, s in res.sessions.items():
            if not s.step_times_s:
                continue
            p50 = float(np.median(s.step_times_s)) * 1e3
            p95 = float(np.percentile(s.step_times_s, 95)) * 1e3
            print(f"  {sid}: {len(s.tokens)} tokens, slot {s.slot}, "
                  f"ticks {s.admitted_tick}-{s.finished_tick}, "
                  f"step p50 {p50:.2f} ms p95 {p95:.2f} ms")
    first = next(iter(res.sessions.values()))
    print("first session tokens:", first.tokens[:12].tolist())


if __name__ == "__main__":
    main()
