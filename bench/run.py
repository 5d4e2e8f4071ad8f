#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
Set-up makes the weights from the seed on the device, builds the
program's serving objects and warms every shape the mix uses; then the
window serves for ``--seconds`` of wall clock.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
last seconds of the window are traced and the result carries its
per-layer metrics, the device's busy time and a breakdown.  After the
window, a sample of what was served is compared with the configuration's
plain reference, which decides ``correct``.

Diagnostics go to standard error, the numbers compared and their limits
last.  The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks`` (each compared number with its
limit).  Without an accelerator, or with fewer chips than the cell asks
for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")     # libtpu logs to /tmp
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class CellResult:
    result: Dict            # the last line
    diag: List[str]         # diagnostics, in order
    checks: List[str]       # the numbers compared, each with its limit
    readings: Dict          # every reading, the control's included
    run: "RunRecord"


class RunRecord:
    """What the metric readers read."""

    def __init__(self, cfg, mix, peaks):
        self.cfg, self.mix, self.peaks = cfg, mix, peaks
        self.window = None
        self.reduction = None
        self.setup_s: Optional[float] = None


class Watch:
    """Compiles and garbage collections, with the host time of each."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.compiles: List[float] = []
        self.gc: List[tuple] = []
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        gc.callbacks.append(self._on_gc)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.compiles.append(time.perf_counter())

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc.append((self._gc_t0, time.perf_counter(),
                            info["generation"]))

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        gc.callbacks.remove(self._on_gc)


def place_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set
    (JAX reads it itself), else ``.jax_cache`` in the checkout, a fixed
    path.  Every program is kept, however fast it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator("no accelerator: JAX runs on the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX finds "
                            f"{len(devs)}")
    return devs


def _gap_lines(w) -> List[str]:
    by_end = {t.end: t for t in w.ticks}
    top = sorted(w.gaps, key=lambda g: g[0] - g[1])[:5]
    out = []
    for a, b in top:
        t = by_end.get(b)
        tick = (f" (tick {1e3 * (t.end - t.start):.2f} sync "
                f"{1e3 * t.sync_s:.2f})" if t else "")
        out.append(f"{1e3 * (b - a):.2f}@{b - w.open_s:.2f}{tick}")
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             cfg: Optional[Dict] = None, mix: Optional[Dict] = None,
             limits: Optional[Dict] = None, require_accelerator=True,
             control: bool = False, wl: Optional[Dict] = None):
    """One run of a cell, as a ``CellResult``.  ``cfg``, ``mix`` and
    ``limits`` stand in for the cell's own files, ``wl`` for its entry in
    ``BENCHMARK.json`` (a cell that is not there yet), and
    ``require_accelerator=False`` skips the look for a chip (tests at a
    small size on the CPU).  ``control`` adds the readings of the fp8
    reference at the same positions (``bench/control.py``)."""
    import jax

    from bench.harness import drivers, peaks as peaks_mod, profile, score
    from bench.harness import spec, weights
    from repro.models.model import Model

    bm = spec.benchmark()
    wl = wl or spec.workload(bm, workload)
    devs = (check_devices(wl["chips"]) if require_accelerator
            else jax.devices())
    cfg = cfg or spec.config(bm, wl["config"])
    mix = mix or spec.traffic(wl["traffic"])
    limits = limits if limits is not None else spec.limits(workload)
    kind = devs[0].device_kind
    peaks = (peaks_mod.peaks_for(kind) if kind in peaks_mod.PEAKS
             else None)
    if require_accelerator and peaks is None:
        peaks_mod.peaks_for(kind)            # raises: no peaks, no run
    diag = [f"start-up: {time.perf_counter() - T0:.2f} s to the first "
            f"device ({kind} x{len(devs)}); compile cache "
            f"{jax.config.jax_compilation_cache_dir}"]
    run = RunRecord(cfg, mix, peaks)
    watch = Watch()
    trace_dir = str(TRACE_DIR / workload) if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = drivers.Tracer(trace_dir, mix.get("trace_s", 3))

    model = Model(spec.program_config(cfg),
                  decode_backend=mix.get("decode_backend", "sdpa"))
    t = time.perf_counter()
    params = jax.block_until_ready(
        weights.make_params(model.abstract_params(), seed))
    diag.append(f"set-up: weights {time.perf_counter() - t:.2f} s")

    def mark_open():
        run.setup_s = time.perf_counter() - T0

    window, state = drivers.DRIVERS[mix["driver"]](
        model, params, mix, seed, seconds, tracer, mark_open)
    run.window = window
    stats = devs[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    del state
    gc.collect()
    watch.close()

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    breakdown = None
    if trace_dir and window.trace_from is not None:
        t = time.perf_counter()
        ev = profile.read_xplane(profile.newest_xplane(trace_dir))
        if ev.ops:
            run.reduction = profile.reduce(ev)
            device["busy_s"] = run.reduction.busy_s
            device["window_s"] = run.reduction.window_s
            breakdown = {"device_ops": run.reduction.device_ops(),
                         "idle_gaps": run.reduction.idle_gaps()}
        shutil.rmtree(trace_dir, ignore_errors=True)
        diag.append(f"trace: read in {time.perf_counter() - t:.1f} s")

    kind_key = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(bm, workload, kind_key):
        value = spec.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    w = window
    diag.extend(w.notes)
    diag.append(f"set-up: {run.setup_s:.2f} s in all")
    use = resource.getrusage(resource.RUSAGE_SELF)
    diag.append(f"host cpu: user {use.ru_utime:.1f} s, system "
                f"{use.ru_stime:.1f} s in {time.perf_counter() - T0:.1f} s "
                f"of wall; {use.ru_nivcsw} involuntary context switches")
    diag.append("compiles in window: "
                f"{sum(w.open_s <= c <= w.close_s for c in watch.compiles)}")
    if w.gaps:
        diag.append("longest token gaps (ms at s into the window): "
                    + ", ".join(_gap_lines(w)))
    in_win = [g for g in watch.gc if w.open_s <= g[0] <= w.close_s]
    diag.append(f"gc in window: {len(in_win)} collections, "
                f"{1e3 * sum(b - a for a, b, _ in in_win):.2f} ms in all, "
                f"{sum(g[2] == 2 for g in in_win)} of generation 2")
    if w.late_s:
        diag.append(f"generator late: median "
                    f"{1e3 * sorted(w.late_s)[len(w.late_s) // 2]:.3f} ms, "
                    f"max {1e3 * max(w.late_s):.3f} ms")

    t = time.perf_counter()
    served, ctrl = score.served_and_control_gaps(
        spec.reference(cfg), cfg, params, w.sample, control=control)
    read = score.readings(served) if served.size else \
        {"max_logit_gap": float("inf"), "mean_logit_gap": float("inf")}
    readings = dict(read)
    if ctrl.size:
        readings.update({f"control.{k}": v
                         for k, v in score.readings(ctrl).items()})
    diag.append(f"scored {served.size} served tokens of {len(w.sample)} "
                f"requests in {time.perf_counter() - t:.1f} s")
    diag.append("readings: " + ", ".join(f"{k} {v!r}"
                                         for k, v in read.items()))
    ok, checks = score.judge(read, limits)
    ok = ok and served.size > 0
    result = {"correct": bool(ok), "attempted": w.attempted,
              "failed": w.failed, "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    check_lines = [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
                   for k, c in checks.items()]
    return CellResult(result, diag, check_lines, readings, run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import jax  # noqa: F401
        from bench.harness import spec
        wl = spec.workload(spec.benchmark(), args.workload)
        place_compile_cache()
        check_devices(wl["chips"])
        cell = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in cell.diag + cell.checks:
        print(line, file=sys.stderr)
    print(json.dumps(cell.result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
