"""Device scopes and host phases (``repro.core.spans``).

The scopes are how a TPU trace says which part of a layer an op belongs
to; the phases are how it says what the host was doing while the
device idled.  These tests pin both down on the CPU at a reduced size:
which programs carry which scopes, that the cache write is named
``kv_write``, that a tick under the profiler emits its ``repro.*`` spans
nested and in order, and that the phase totals are the host timings the
scheduler reports.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import spans
from repro.models import Model
from repro.serving import DecodeEngine, SessionRequest, SlotScheduler

KEY = jax.random.PRNGKey(5)
CFG = get_config("qwen2.5-3b").reduced()
LAYER_SCOPES = set(spans.SCOPES) - {"sample"}


@pytest.fixture(scope="module")
def model_params():
    m = Model(CFG)
    return m, m.init(KEY)


def _hlo(fn, *args, **static):
    """Compiled HLO text of ``fn`` jitted over ``args``, metadata kept."""
    lowered = jax.jit(fn, static_argnames=tuple(static)).lower(*args,
                                                               **static)
    return lowered.compile().as_text()


def _scopes_in(hlo: str):
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        found |= set(name.split("/")) & set(spans.SCOPES)
    return found


def _programs(m, params):
    single = m.init_cache(1, 32)
    slotted = m.init_cache(2, 32, slotted=True)
    paged = m.init_cache(2, 32, paged=True, page_size=8)
    toks = jnp.zeros((2, 1), jnp.int32)
    chunk = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    return {
        "contiguous": (_hlo(m.decode_step, params, single, toks[:1]),
                       LAYER_SCOPES),
        "slotted": (_hlo(m.decode_step, params, slotted, toks),
                    LAYER_SCOPES),
        "paged": (_hlo(m.decode_step, params, paged, toks), LAYER_SCOPES),
        "horizon": (_hlo(m.decode_steps, params, paged, toks, KEY,
                         jnp.full((2,), 3, jnp.int32), horizon=3),
                    set(spans.SCOPES)),
        "prefill_chunk": (_hlo(m.prefill_chunk_into_slot, params, chunk,
                               paged, jnp.int32(0), jnp.int32(0)),
                          LAYER_SCOPES),
    }


def test_every_scope_names_ops_in_the_programs(model_params):
    seen = set()
    for name, (hlo, want) in _programs(*model_params).items():
        assert _scopes_in(hlo) == want, name
        seen |= want
    assert seen == set(spans.SCOPES)


def test_cache_writes_carry_kv_write(model_params):
    """Every update of a K/V array (a dynamic-update-slice or, batched
    or paged, a scatter into an array of rank 3 or more) is
    ``kv_write``, save the layer scan stacking its cache output, which
    is no model scope's."""
    for prog, (hlo, _) in _programs(*model_params).items():
        ups = re.findall(r"= \w+\[\d+,\d+,[\d,]*\]\S* "
                         r"(?:dynamic-update-slice|scatter)\(.*"
                         r"op_name=\"([^\"]*)\"", hlo)
        writes = [n for n in ups if "/kv_write/" in n]
        stack = [n for n in ups if n.endswith("while/body/"
                                               "dynamic_update_slice")]
        assert writes and len(writes) + len(stack) == len(ups), (prog, ups)


def test_scope_rejects_unknown_names():
    with pytest.raises(ValueError):
        spans.scope("attention")
    with pytest.raises(ValueError):
        spans.Phases()("decode")


def test_phase_totals_are_own_time():
    clock = iter([0.0, 1.0, 3.0, 4.0])     # tick in, prefill in/out, out
    phases = spans.Phases()
    real = spans.time.perf_counter
    spans.time.perf_counter = lambda: next(clock)
    try:
        with phases("tick") as tick:
            with phases("prefill") as pf:
                pass
    finally:
        spans.time.perf_counter = real
    assert (tick.seconds, pf.seconds) == (4.0, 2.0)
    assert phases.totals["prefill"] == 2.0
    assert phases.totals["tick"] == 2.0      # its own time, not 4
    assert sum(phases.totals.values()) == 4.0


def _session(i, n_prompt=9, n_new=12):
    prompt = np.asarray(jax.random.randint(jax.random.fold_in(KEY, i),
                                           (n_prompt,), 0, CFG.vocab_size))
    return SessionRequest(f"s{i}", prompt, n_new)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(spans.SPAN_PREFIX)]
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("k", [1, 2], ids=["single", "horizon"])
def test_tick_spans_nest_in_phase_order(model_params, tmp_path, k):
    m, params = model_params
    sched = SlotScheduler(m, params, n_slots=2, max_len=32, paged=True,
                          page_size=8, prefill_chunk=8, steps_per_tick=k)
    sched.submit(_session(0))
    for _ in range(3):               # compile prefill, decode, sampling
        sched.tick()
    sched.submit(_session(1))        # admitted and prefilled in the trace
    with jax.profiler.trace(str(tmp_path)):
        sched.tick()
    got = _host_spans(str(tmp_path))
    ticks = [e for e in got if e[0] == "repro.tick"]
    assert len(ticks) == 1
    _, t0, t1 = ticks[0]
    inner = [e for e in got if e[0] != "repro.tick"]
    assert all(t0 <= s and e <= t1 for _, s, e in inner)
    first = []
    for name, _, _ in inner:
        if name not in first:
            first.append(name)
    assert first == [spans.SPAN_PREFIX + p for p in spans.PHASES[1:]]


@pytest.mark.parametrize("k", [1, 2], ids=["single", "horizon"])
def test_host_timings_are_the_phase_totals(model_params, k):
    m, params = model_params
    sched = SlotScheduler(m, params, n_slots=2, max_len=32, paged=True,
                          page_size=8, prefill_chunk=8, steps_per_tick=k)
    for i in range(3):
        sched.submit(_session(i))
    res = sched.run()
    assert res.host_dispatch_s == res.host_phase_s["dispatch"] > 0
    assert res.host_sync_s == res.host_phase_s["sync"] > 0
    assert sched.host_dispatch_s == sched.phases.totals["dispatch"]
    assert sched.host_sync_s == sched.phases.totals["sync"]
    assert set(res.host_phase_s) == set(spans.PHASES)
    assert all(v >= 0 for v in res.host_phase_s.values())
    # own times: together they are the time spent inside ticks
    assert sum(res.host_phase_s.values()) <= res.wall_s


def test_decode_streamed_continues_a_filled_cache(model_params):
    m, params = model_params
    eng = DecodeEngine(m, params)
    batch = {"tokens": jax.random.randint(KEY, (1, 6), 0, CFG.vocab_size)}
    want = eng.generate_streamed(batch, max_len=16, n_new=6).tokens
    from repro.serving import sample
    logits, cache = eng.prefill(batch, 16)
    key = jax.random.PRNGKey(0)
    tok = sample(logits[:, -1], key)
    d0, s0 = eng.phases.totals["dispatch"], eng.phases.totals["sync"]
    got = [tok] + [t for t, _ in eng.decode_streamed(cache, tok, key, 5)]
    np.testing.assert_array_equal(np.stack(got, axis=1), np.asarray(want))
    assert eng.phases.totals["dispatch"] > d0
    assert eng.phases.totals["sync"] > s0
