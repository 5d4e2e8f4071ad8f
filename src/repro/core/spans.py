"""Names for what the program does, as a profiler trace shows it.

Two kinds, one module, so a reader of the trace imports the names it
looks for from here:

- **Device scopes** (``scope``): ``jax.named_scope`` around each part of
  a decoder layer and around the parts of a step outside the layer scan.
  A scope is trace-time metadata: it names the HLO ops it covers
  (their ``op_name``, which a TPU trace carries as ``tf_op``) and
  changes nothing that XLA builds.  A fusion carries the name of its
  root op, so each scope holds its residual add: the add is what the
  last matmul of the part fuses into.
- **Host phases** (``Phases``): each ``with phases("sync"):`` is a
  ``jax.profiler.TraceAnnotation`` named ``repro.sync``, on the device
  trace's clock, and adds its own time (less that of phases nested in
  it) to ``phases.totals["sync"]``.  With no trace being taken a span
  costs a few hundred nanoseconds; there is no switch.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax

# device scopes, in the order a decode step runs them
SCOPES = ("embed", "qkv", "kv_write", "attend", "attn_out", "mlp", "head",
          "sample")
# host phases of a serving tick, in the order a tick runs them
PHASES = ("tick", "admit", "prefill", "pages", "dispatch", "sync", "emit")
SPAN_PREFIX = "repro."


def scope(name: str):
    """``jax.named_scope`` for one of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; scopes are {SCOPES}")
    return jax.named_scope(name)


class Phases:
    """Host time per phase of a loop, each phase also a trace span.

    ``totals`` holds each phase's own seconds: a phase opened inside
    another (a prefill chunk run by an admission) is charged to the
    inner one only, so the totals add up to the time spent inside the
    outermost phases."""

    def __init__(self):
        self.totals: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self._open: List["_Phase"] = []

    def __call__(self, name: str) -> "_Phase":
        if name not in self.totals:
            raise ValueError(f"unknown phase {name!r}; phases are {PHASES}")
        return _Phase(self, name)

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)


class _Phase:
    __slots__ = ("phases", "name", "span", "t0", "inner", "seconds")

    def __init__(self, phases: Phases, name: str):
        self.phases, self.name = phases, name
        self.inner = 0.0           # seconds of phases nested in this one
        self.seconds = 0.0         # wall seconds, once closed

    def __enter__(self) -> "_Phase":
        self.span = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name)
        self.span.__enter__()
        self.phases._open.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self.t0
        opened = self.phases._open
        opened.pop()
        self.phases.totals[self.name] += self.seconds - self.inner
        if opened:
            opened[-1].inner += self.seconds
        self.span.__exit__(*exc)
