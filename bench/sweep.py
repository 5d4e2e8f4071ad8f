#!/usr/bin/env python3
"""Find the knee of an open-loop mix: the highest offered rate at which
the queue does not grow.

    python3 bench/sweep.py --config qwen2.5-3b --traffic serve_fleet \
        --rates 6,8,10,12,14 --seconds 20

In one process, one window per rate (the mix with only ``rate_per_s``
changed), lowest rate first.  The mix need not belong to a cell yet.
One JSON line per rate: requests due, TTFT p50 and p95, queue wait p95,
the median TTFT of the first and the last third of the window, requests
waiting at the close, tokens per second, the generator's lateness and
the widest logit gap of the sampled requests.  Past the knee the last
third waits longer than the first and a backlog stands at the close.
A cell's rate is then fixed in its mix at four fifths of the knee; the
benchmark's runs never search.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1])]
    from bench import run
    run.place_compile_cache()
    from bench.harness import readers, spec, stats
    name = f"{args.config}.{args.traffic}"
    wl = {"name": name, "config": args.config, "traffic": args.traffic,
          "chips": 1}
    mix = spec.traffic(args.traffic)
    for rate in [float(r) for r in args.rates.split(",")]:
        t = time.perf_counter()
        cell = run.run_cell(name, args.seed, args.seconds, False, wl=wl,
                            mix=dict(mix, rate_per_s=rate), limits={})
        w = cell.run.window
        ttft = sorted(w.ttft)
        third = max(1, len(ttft) // 3)
        med = (lambda xs: 1e3 * statistics.median(x for _, x in xs)
               if xs else None)
        print(json.dumps({
            "rate_per_s": rate, "due": w.attempted, "failed": w.failed,
            "ttft_p50_ms": 1e3 * stats.percentile([x for _, x in ttft], 50),
            "ttft_p95_ms": readers.ttft_p95_ms(cell.run),
            "queue_wait_p95_ms": readers.queue_wait_p95_ms(cell.run),
            "ttft_first_third_p50_ms": med(ttft[:third]),
            "ttft_last_third_p50_ms": med(ttft[-third:]),
            "waiting_at_close": w.backlog_at_close,
            "tokens_per_s": readers.tokens_per_s(cell.run),
            "late_max_ms": 1e3 * max(w.late_s) if w.late_s else None,
            "max_logit_gap": cell.readings["max_logit_gap"],
            "s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
