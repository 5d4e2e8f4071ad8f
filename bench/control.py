#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/control.py --workload <name> --seeds 1-12 \
        --control-seeds 1-3 --seconds <s>

In one process, for each seed: a run of the cell with a short window
(long enough to finish the mix's longest requests), and the comparison
of what it served with the plain reference.  For the control seeds also
the fp8 control: the reference computed with float8 e4m3 operands, read
at the same positions.  One JSON line per seed, then a summary: the
program's largest reading over its seeds (the lower reading) and the
control's smallest (the upper reading), per number.  The cell needs no
limits yet: these readings set them.  The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def _seeds(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed-base", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1])]
    from bench import run
    run.place_compile_cache()
    ctrl = set(_seeds(args.control_seeds)) if args.control_seeds else set()
    lower, upper = {}, {}
    t_all = time.perf_counter()
    for s in _seeds(args.seeds):
        seed = args.seed_base + s
        t = time.perf_counter()
        cell = run.run_cell(args.workload, seed, args.seconds, False,
                            limits={}, control=s in ctrl)
        result, read = cell.result, cell.readings
        line = {"workload": args.workload, "seed": seed,
                "attempted": result["attempted"], "failed": result["failed"],
                **read, "s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
        for k, v in read.items():
            if k.startswith("control."):
                k = k[len("control."):]
                upper[k] = min(upper.get(k, v), v)
            else:
                lower[k] = max(lower.get(k, v), v)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper,
                      "wall_s": time.perf_counter() - t_all}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
