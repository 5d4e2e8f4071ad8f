"""Compiled-program registry for the continuous-batching scheduler.

One place owns every ``jax.jit`` wrapper the scheduler dispatches —
prefill (whole-prompt or chunked), the CoW page copy, the tier's
save/restore page movers, and the decode step (single or horizon-K
fused).  Pulled out of scheduler.py so program wiring (what compiles,
what donates, what is shared) is separable from scheduling policy.

``shared_programs``: A/B drivers that build many schedulers over ONE
model (e.g. table13's arm sweep) pay a full recompile per instance,
because each jax.jit wrapper carries its own trace cache.  Opting in
parks the wrappers on the model so every scheduler over it reuses the
same compiled executables — donation is per call, so sharing the
callable is safe.  The scheduler's ``step_cache_size()`` then reports
a delta since its construction, keeping the "one executable per
(backend, K)" recompile guard meaningful per instance.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.models.model import Model


def jit_cache_size(fn) -> int:
    """Compiled-executable count of a ``jax.jit`` callable (the
    recompile guard).  ``_cache_size()`` is the per-callable executable
    cache's own count; a jax without it fails here, loudly, rather than
    leaving the guard blind."""
    return fn._cache_size()


class SchedulerPrograms:
    """The jit wrappers one ``SlotScheduler`` dispatches.

    Attributes are ``None`` when the configuration doesn't use them:
    ``prefill_chunk``/``copy_page`` exist only paged, ``save_pages``/
    ``restore_pages`` only with the host KV tier, ``prefill_slot`` only
    contiguous, and exactly one of ``step`` (K=1) / ``steps``
    (horizon-K fused) under ``full_jit`` — both ``None`` for the
    stage/eager dispatch A/B, whose executor the scheduler builds
    itself (it needs the live cache)."""

    def __init__(self, model: Model, *, paged: bool, kv_tier: str,
                 dispatch_mode: str, steps_per_tick: int,
                 shared_programs: bool):
        if shared_programs:
            _shared = model.__dict__.setdefault("_shared_sched_jits", {})

            def _jit(name, make):
                if name not in _shared:
                    _shared[name] = make()
                return _shared[name]
        else:
            def _jit(name, make):
                return make()

        self.prefill_chunk = self.copy_page = None
        self.save_pages = self.restore_pages = None
        self.prefill_slot = None
        self.step = self.steps = None
        if paged:
            self.prefill_chunk = _jit(
                "prefill_chunk",
                lambda: jax.jit(model.prefill_chunk_into_slot,
                                donate_argnums=(2,)))
            self.copy_page = _jit(
                "copy_page",
                lambda: jax.jit(model.copy_kv_page, donate_argnums=(0,)))
            if kv_tier == "host":
                # one gather / one scatter program per pow-2 run length
                # (save_kv_blobs pads with the garbage page); the save
                # must NOT donate — the pool stays live under it
                self.save_pages = _jit(
                    "save_kv_pages", lambda: jax.jit(model.save_kv_pages))
                self.restore_pages = _jit(
                    "restore_kv_pages",
                    lambda: jax.jit(model.restore_kv_pages,
                                    donate_argnums=(0,)))
        else:
            self.prefill_slot = _jit(
                "prefill_slot",
                lambda: jax.jit(model.prefill_into_slot,
                                donate_argnums=(2,)))
        if dispatch_mode == "full_jit":
            # the production hot path: the whole step is one program,
            # cache donated so steps run allocation-free.  With
            # steps_per_tick > 1 it is the horizon-K multi-step scan —
            # ONE executable per (backend, K); lanes that finish
            # mid-horizon are masked off on device.
            if steps_per_tick > 1:
                self.steps = _jit(
                    "decode_steps",
                    lambda: jax.jit(
                        model.decode_steps,
                        static_argnames=("horizon", "temperature",
                                         "top_k", "eos_id"),
                        donate_argnums=(1,)))
            else:
                self.step = _jit(
                    "decode_step",
                    lambda: jax.jit(model.decode_step,
                                    donate_argnums=(1,)))

    def raw_step_cache_size(self) -> Optional[int]:
        if self.steps is not None:
            return jit_cache_size(self.steps)
        if self.step is not None:
            return jit_cache_size(self.step)
        return None
