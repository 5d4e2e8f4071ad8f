"""Where the decode step's device time goes, by the program's own names.

The program names the parts of its decode step with ``jax.named_scope``
(``repro.core.spans.SCOPES``) and the phases of its host loop with
``repro.*`` trace spans.  A TPU trace keeps each XLA op's scope path as
the ``tf_op`` stat of the op's event metadata.  ``ProfileData`` does not
expose event-metadata stats, so ``read_ops`` walks the ``.xplane.pb``
in protobuf wire format: only the ``/device:TPU:*`` planes' metadata,
the host plane's bytes skipped.

Buckets: each op of the decode-step program goes to the innermost model
scope in its path; an op with none goes to ``scan`` when its path sits
directly under ``while/body`` (the layer scan's slicing of weights and
cache in, and its stacking of the cache out), else to ``other``.  Every
op self time of the program lands in exactly one bucket.

Host: each device idle gap in the traced window is charged to the
innermost ``repro.*`` span at its middle.

The readers take a run that carries, besides the ``Reduction``, the
trace's op map (``run.scopes``, from ``read_ops``) and its events
(``run.events``); each returns None where the run has no such map, or
the trace no scoped op or span (a program older than its scopes).
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, Iterator, Optional, Tuple

from bench.harness import counts, profile
from bench.harness.readers import STEP_PROGRAM
from repro.core.spans import SCOPES, SPAN_PREFIX

BUCKETS = SCOPES + ("scan", "other")
SYNC_SPAN = SPAN_PREFIX + "sync"


@dataclasses.dataclass(frozen=True)
class Op:
    tf_op: str          # scope path, "" where the trace gives none
    hlo: str            # the op's HLO text (its operand shapes)


# ------------------------------------------------------------ wire walk
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; a length-delimited value is
    its (start, end) in ``b``."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(b: bytes, span):
    """(key, value span) of one map entry."""
    key = val = None
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_ops(b: bytes, lo: int, hi: int) -> Dict[str, Op]:
    stat_names: Dict[int, str] = {}
    metas = []
    for f, v in _fields(b, lo, hi):
        if f == 5:                          # XPlane.stat_metadata
            key, val = _map_value(b, v)
            for f2, v2 in _fields(b, *val):
                if f2 == 2:
                    stat_names[key] = _text(b, v2)
        elif f == 4:                        # XPlane.event_metadata
            metas.append(_map_value(b, v)[1])
    modules: Dict[int, str] = {}            # program id -> program name
    raw = []
    for span in metas:
        name, stats = "", {}
        for f, v in _fields(b, *span):
            if f == 2:
                name = _text(b, v)
            elif f == 5:                    # XEventMetadata.stats
                sid = val = None
                for f2, v2 in _fields(b, *v):
                    if f2 == 1:
                        sid = v2
                    elif f2 == 5:           # str_value
                        val = _text(b, v2)
                    elif f2 == 7:           # ref_value: a stat name
                        val = stat_names.get(v2, "")
                    elif f2 in (3, 4):      # uint64 / int64
                        val = v2
                stats[stat_names.get(sid, "")] = val
        m = re.fullmatch(r"(.*)\((\d+)\)", name)
        if m and "program_id" not in stats:
            modules[int(m.group(2))] = m.group(1)
        elif "program_id" in stats:
            raw.append((stats["program_id"], name, stats.get("tf_op", "")))
    ops: Dict[str, Op] = {}
    for pid, name, tf_op in raw:
        prog = modules.get(pid)
        if prog is None:
            continue
        tf_op = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
        ops.setdefault(f"{prog}/{profile._op_name(name)}", Op(tf_op, name))
    return ops


def read_ops(path: str) -> Dict[str, Op]:
    """Every XLA op of the trace's TPU planes, keyed ``program/op`` as
    ``profile.read_xplane`` and ``Reduction`` key them."""
    with open(path, "rb") as fh:
        b = fh.read()
    ops: Dict[str, Op] = {}
    for f, v in _fields(b, 0, len(b)):
        if f != 1:                          # XSpace.planes
            continue
        name = ""
        for f2, v2 in _fields(b, *v):
            if f2 == 2:
                name = _text(b, v2)
                break
        if name.startswith("/device:TPU:") and "CUSTOM" not in name:
            for key, op in _plane_ops(b, *v).items():
                ops.setdefault(key, op)
    return ops


# ------------------------------------------------------------- buckets
def bucket(tf_op: str) -> str:
    parts = tf_op.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    if parts[-3:-1] == ["while", "body"]:
        return "scan"
    return "other"


def step_buckets(run) -> Optional[Dict[str, float]]:
    """Self seconds of the decode-step program's ops in the traced
    window, by bucket; None without an op map or a scoped op."""
    ops, red = getattr(run, "scopes", None), run.reduction
    if not ops or red is None:
        return None
    out = dict.fromkeys(BUCKETS, 0.0)
    for key, sec in red.op_self_s.items():
        if STEP_PROGRAM in key.split("/", 1)[0]:
            op = ops.get(key)
            out[bucket(op.tf_op if op else "")] += sec
    if not any(out[s] for s in SCOPES):
        return None
    return out


def _steps(run) -> int:
    return sum(n for k, n in run.reduction.program_runs.items()
               if STEP_PROGRAM in k)


def _per_step_ms(run, name: str) -> Optional[float]:
    b = step_buckets(run)
    steps = _steps(run) if b else 0
    return 1e3 * b[name] / steps if steps else None


def mlp_weight_bytes(cfg: Dict) -> int:
    """Bytes of every layer's MLP weights (gate, up, down)."""
    return (cfg["num_hidden_layers"] * 3 * cfg["hidden_size"]
            * cfg["intermediate_size"] * counts.dtype_bytes(cfg))


# ------------------------------------------------------------- readers
def kv_write_ms(run) -> Optional[float]:
    """Device time per decode step in the cache writes (``kv_write``)."""
    return _per_step_ms(run, "kv_write")


def scan_io_ms(run) -> Optional[float]:
    """Device time per decode step in the layer scan's own slicing and
    stacking (``scan``)."""
    return _per_step_ms(run, "scan")


def mlp_roofline(run) -> Optional[float]:
    """MLP weight bytes per step at peak HBM bandwidth over the ``mlp``
    device time per step, in percent."""
    ms = _per_step_ms(run, "mlp")
    if not ms:
        return None
    floor_s = mlp_weight_bytes(run.cfg) / run.peaks.hbm_bytes_per_s
    return 100.0 * floor_s / (ms * 1e-3)


def idle_by_phase(ev: profile.TraceEvents,
                  window: Optional[Tuple[float, float]] = None
                  ) -> Tuple[float, Dict[str, float]]:
    """The traced window's length, and its device idle seconds by the
    innermost ``repro.*`` span at each gap's middle (``""`` where the
    host was in none)."""
    w0, w1 = window or profile.window_of(ev)
    busy = profile.union([(max(s, w0), min(e, w1)) for _, _, s, e in ev.ops
                          if e > w0 and s < w1])
    spans = [(s, e, n) for n, s, e in ev.host if n.startswith(SPAN_PREFIX)
             and e > w0 and s < w1]
    idle: Dict[str, float] = collections.defaultdict(float)
    t = w0
    for s, e in busy + [(w1, w1)]:
        if s > t:
            mid = (t + s) / 2
            cover = [(b - a, n) for a, b, n in spans if a <= mid < b]
            idle[min(cover)[1] if cover else ""] += s - t
        t = max(t, e)
    return w1 - w0, dict(idle)


def host_bound_idle(run) -> Optional[float]:
    """Share of the traced window in which the device idles while the
    host is in a ``repro.*`` span other than ``repro.sync``, in
    percent."""
    ev = getattr(run, "events", None)
    if ev is None or not ev.ops or not any(
            n.startswith(SPAN_PREFIX) for n, _, _ in ev.host):
        return None
    window_s, idle = idle_by_phase(ev)
    held = sum(v for k, v in idle.items() if k and k != SYNC_SPAN)
    return 100.0 * held / window_s if window_s > 0 else None
