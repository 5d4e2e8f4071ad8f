"""The arithmetic behind the per-layer metrics.  Each metric under
``bench/metrics`` is a file of its own that binds one of these to its
name; each returns None where its run has nothing for it to read.

Host-clock readings use the ticks of the window before the traced slice
began (tracing slows the host); device readings use the traced slice.
"""
from __future__ import annotations

from typing import Optional

from bench.harness import counts, stats

STEP_PROGRAM = "decode_step"          # in the name of the decode-step program
PAGED_KERNEL = "paged_decode_attention"


def _untraced(run):
    end = run.window.trace_from or run.window.close_s
    return [t for t in run.window.ticks
            if run.window.open_s <= t.start and t.end <= end]


def _traced(run):
    if run.window.trace_from is None:
        return []
    return [t for t in run.window.ticks
            if t.start >= run.window.trace_from and t.end <= run.window.close_s
            and t.live]


def host_ms_per_tick(run) -> Optional[float]:
    """Mean host time per tick, less the time blocked on the token
    transfer (an open loop's idle waits between ticks are no tick)."""
    ticks = _untraced(run)
    if not ticks:
        return None
    return stats.mean([t.end - t.start - t.sync_s for t in ticks]) * 1e3


def queue_wait_p95_ms(run) -> Optional[float]:
    """p95 of due-to-admission waits of requests due before the traced
    slice."""
    end = run.window.trace_from or run.window.close_s
    waits = [w for due, w in run.window.queue_wait if due <= end]
    if not waits:
        return None
    return stats.percentile(waits, 95) * 1e3


def _step_device_s(run) -> Optional[float]:
    red = run.reduction
    if red is None:
        return None
    runs = sum(n for k, n in red.program_runs.items() if STEP_PROGRAM in k)
    secs = sum(s for k, s in red.program_s.items() if STEP_PROGRAM in k)
    return secs / runs if runs else None


def step_mfu(run) -> Optional[float]:
    """Roofline time of the mean traced decode step (larger of its
    FLOPs and its bytes at peak) over the step program's mean device
    time, in percent."""
    ticks, dev = _traced(run), _step_device_s(run)
    if not ticks or not dev:
        return None
    roof = stats.mean([counts.roofline_s(counts.step_flops(run.cfg, t.live),
                                         counts.step_bytes(run.cfg, t.live),
                                         run.peaks) for t in ticks])
    return 100.0 * roof / dev


def paged_attn_roofline(run) -> Optional[float]:
    """Roofline time of the paged kernel's live-KV reads per step over
    its traced time per step, in percent."""
    ticks, red = _traced(run), run.reduction
    if not ticks or red is None:
        return None
    kern = red.kernel_s(PAGED_KERNEL)
    runs = sum(n for k, n in red.program_runs.items() if STEP_PROGRAM in k)
    if kern <= 0 or not runs:
        return None
    roof = stats.mean([counts.roofline_s(
        counts.attention_flops(run.cfg, t.live),
        counts.attention_kv_bytes(run.cfg, t.live), run.peaks)
        for t in ticks])
    return 100.0 * roof / (kern / runs)


def device_idle(run) -> Optional[float]:
    red = run.reduction
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


# ------------------------------------------------------ end to end
def _gaps(run):
    return [b - a for a, b in run.window.gaps]


def itl_mean_ms(run) -> Optional[float]:
    g = _gaps(run)
    return stats.mean(g) * 1e3 if g else None


def itl_p95_ms(run) -> Optional[float]:
    g = _gaps(run)
    return stats.percentile(g, 95) * 1e3 if g else None


def ttft_p95_ms(run) -> Optional[float]:
    t = [x for _, x in run.window.ttft]
    return stats.percentile(t, 95) * 1e3 if t else None


def tokens_per_s(run) -> Optional[float]:
    w = run.window
    return w.tokens / (w.close_s - w.open_s) if w.tokens else None


def setup_s(run) -> Optional[float]:
    return run.setup_s
