"""The reduction from a profiler trace to device numbers, on a small
trace recorded on a TPU v5e (a paged decode step of a reduced model,
with the host's bench.* spans) and on events made by hand."""
import tiny  # noqa: F401

import gzip
import pathlib
import shutil

import pytest

from bench.harness import profile

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "tpu_decode.xplane.pb.gz"


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return profile.read_xplane(str(path))


def test_recorded_trace_reads(events):
    assert events.n_devices == 1
    assert len(events.ops) > 1000 and len(events.programs) > 100
    assert any(n == "bench.window" for n, _, _ in events.host)
    tagged = [o for o in events.ops if o[0] != "?"]
    assert len(tagged) / len(events.ops) > 0.99
    assert {o[0] for o in events.ops} >= {"jit_decode_step"}


def test_recorded_trace_reduces(events):
    red = profile.reduce(events)
    w0, w1 = profile.window_of(events)
    assert red.window_s == pytest.approx(w1 - w0)
    assert 0 < red.busy_s < red.window_s
    # every idle second is charged to something the host was doing
    assert sum(red.idle_by_host.values()) == \
        pytest.approx(red.window_s - red.busy_s, rel=1e-9)
    top = red.device_ops()
    assert len(top) == 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    assert top[0][0] == "jit_decode_step/paged_decode_attention_pallas.11"
    assert red.kernel_s("paged_decode_attention") == pytest.approx(top[0][1])
    assert sum(red.op_self_s.values()) <= sum(red.op_total_s.values()) + 1e-12
    assert red.program_runs["jit_decode_step"] == 18
    gaps = dict(red.idle_gaps())
    assert any(k.startswith("bench.tick") for k in gaps)


def _ev(ops, host, programs=None):
    programs = programs or [("jit_step", 0.0, 10.0)]
    return profile.TraceEvents(
        [("jit_step", n, s, e) for n, s, e in ops], programs, host, 1)


def test_hand_made_events():
    ev = _ev(ops=[("while", 1.0, 5.0), ("fusion", 2.0, 3.0),
                  ("fusion.1", 3.0, 4.0), ("copy", 6.0, 7.0)],
             host=[("bench.traced", 0.0, 10.0), ("bench.tick", 0.0, 6.0),
                   ("np.asarray(jax.Array)", 5.0, 6.0),
                   ("bench.tick", 6.0, 10.0)])
    red = profile.reduce(ev)
    assert red.window_s == 10.0 and red.busy_s == 5.0
    assert red.op_self_s == {"jit_step/while": 2.0, "jit_step/fusion": 1.0,
                             "jit_step/fusion.1": 1.0, "jit_step/copy": 1.0}
    assert red.op_total_s["jit_step/while"] == 4.0
    # idle [0,1] and [7,10] in ticks, [5,6] in the token transfer
    assert red.idle_by_host == {"bench.tick": 4.0,
                                "bench.tick/np.asarray(jax.Array)": 1.0}


def test_idle_attribution_by_gap_middle():
    ev = _ev(ops=[("a", 0.0, 1.0), ("b", 3.0, 4.0)],
             host=[("bench.traced", 0.0, 4.0), ("bench.tick", 0.5, 3.5),
                   ("np.asarray(jax.Array)", 1.5, 2.5)])
    red = profile.reduce(ev)
    assert red.busy_s == 2.0
    assert red.idle_by_host == {"bench.tick/np.asarray(jax.Array)": 2.0}


def test_union_and_nesting():
    assert profile.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    st = profile.self_times([("p", "outer", 0.0, 10.0),
                             ("p", "inner", 2.0, 5.0),
                             ("p", "overlap", 9.0, 12.0)])
    assert st["p/inner"] == 3.0 and st["p/overlap"] == 3.0
    assert st["p/outer"] == 7.0
