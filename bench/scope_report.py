#!/usr/bin/env python3
"""Where a cell's decode step spends its device time, by the program's
own scopes, and what its host was doing while the device idled.

    python3 bench/scope_report.py --workload <cell> --seed <n> --seconds <s>
        [--trace-s <s>] [--layers <n>] [--keep <file.xplane.pb.gz>]

Runs the cell's window as ``bench/run.py --trace 1`` does (same driver,
weights and traced slice, no scoring), then reads the trace with
``bench/harness/scopes.py`` and prints one JSON object: device ms per
decode step by bucket, the step program's ms per step and the share of
it no op covers, idle ms per step by ``repro.*`` host span, and the
readings of ``kv_write_ms``, ``scan_io_ms``, ``mlp_roofline`` and
``host_bound_idle``, and how long the trace and its op map took to
read.  ``--layers`` cuts the configuration's depth and
``--keep`` keeps the trace, gzipped: together they record a small
fixture for the tests.  Without an accelerator it exits 2.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def report(workload: str, seed: int, seconds: float, *, trace_s=None,
           layers=None, keep=None, require_accelerator=True,
           cfg=None, mix=None) -> dict:
    import jax

    from bench import run as bench_run
    from bench.harness import drivers, peaks, profile, readers, scopes
    from bench.harness import spec, weights
    from repro.models.model import Model

    bm = spec.benchmark()
    wl = spec.workload(bm, workload)
    if require_accelerator:
        bench_run.check_devices(wl["chips"])
    cfg = dict(cfg or spec.config(bm, wl["config"]))
    mix = dict(mix or spec.traffic(wl["traffic"]))
    if layers:
        cfg["num_hidden_layers"] = layers
    if trace_s:
        mix["trace_s"] = trace_s
    kind = jax.devices()[0].device_kind
    model = Model(spec.program_config(cfg),
                  decode_backend=mix.get("decode_backend", "sdpa"))
    params = weights.make_params(model.abstract_params(), seed)
    trace_dir = bench_run.TRACE_DIR / f"{workload}.scopes"
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = drivers.Tracer(str(trace_dir), mix.get("trace_s", 3))
    window, state = drivers.DRIVERS[mix["driver"]](
        model, params, mix, seed, seconds, tracer, lambda: None)
    del state
    path = profile.newest_xplane(str(trace_dir))
    if keep:
        with open(path, "rb") as src, gzip.open(keep, "wb") as dst:
            shutil.copyfileobj(src, dst)
    t = time.perf_counter()
    ev = profile.read_xplane(path)
    read_s = time.perf_counter() - t
    t = time.perf_counter()
    ops = scopes.read_ops(path)
    ops_s = time.perf_counter() - t
    run = types.SimpleNamespace(
        cfg=cfg, mix=mix, window=window, events=ev,
        peaks=peaks.PEAKS.get(kind), reduction=profile.reduce(ev),
        scopes=ops)
    shutil.rmtree(trace_dir, ignore_errors=True)

    red = run.reduction
    steps = sum(n for k, n in red.program_runs.items()
                if scopes.STEP_PROGRAM in k)
    prog_s = sum(s for k, s in red.program_s.items()
                 if scopes.STEP_PROGRAM in k)
    buckets = scopes.step_buckets(run) or {}
    window_s, idle = scopes.idle_by_phase(ev)
    per_step = (lambda s: 1e3 * s / steps) if steps else (lambda s: None)
    out = {
        "workload": workload, "device": kind,
        "layers": cfg["num_hidden_layers"], "steps": steps,
        "window_s": window_s, "busy_s": red.busy_s,
        "step_ms": per_step(prog_s),
        "uncovered_share": (1 - sum(buckets.values()) / prog_s
                            if prog_s else None),
        "bucket_ms": {k: per_step(v) for k, v in buckets.items()},
        "idle_ms_by_span": {k or "none": per_step(v)
                            for k, v in sorted(idle.items())},
        "trace_read_s": read_s, "op_map_read_s": ops_s,
        "host_ms_per_tick": readers.host_ms_per_tick(run),
        "device_idle": readers.device_idle(run),
    }
    for name in ("kv_write_ms", "scan_io_ms", "mlp_roofline",
                 "host_bound_idle"):
        out[name] = getattr(scopes, name)(run)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-s", type=float)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    from bench import run as bench_run
    bench_run.place_compile_cache()
    try:
        out = report(args.workload, args.seed, args.seconds,
                     trace_s=args.trace_s, layers=args.layers,
                     keep=args.keep)
    except bench_run.NoAccelerator as e:
        print(f"scope_report: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
