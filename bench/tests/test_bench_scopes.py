"""The decode step's device time by the program's scopes, and the host's
idle time by its phases (``bench/harness/scopes.py``), on a trace
recorded on a TPU v5e and on events made by hand.

``tpu_decode`` is a paged decode step of a program that had no scopes
yet: the wire reader must join its ops, and the readers must read
nothing from it.  That every op reading an MLP weight carries the
``mlp`` scope is checked on the compiled program
(``tests/test_tpu_compile.py``).
"""
import tiny  # noqa: F401

import gzip
import pathlib
import shutil
import types

import pytest

from bench.harness import peaks, profile, scopes, spec
from repro.core import floor, spans

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def _unzip(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(FIXTURES / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def _run(path):
    cfg = spec.config(spec.benchmark(), "qwen2.5-3b")
    ev = profile.read_xplane(path)
    return types.SimpleNamespace(
        cfg=cfg, events=ev, reduction=profile.reduce(ev),
        scopes=scopes.read_ops(path), peaks=peaks.peaks_for("TPU v5 lite"))


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    return _run(_unzip("tpu_decode", tmp_path_factory))


# -------------------------------------------------- the wire reader
def test_wire_reader_joins_the_trace_ops(unscoped):
    keys = {f"{p}/{n}" for p, n, _, _ in unscoped.events.ops}
    assert keys <= set(unscoped.scopes)
    progs = {k.split("/", 1)[0] for k in unscoped.scopes}
    assert {"jit_decode_step", "jit__threefry_fold_in",
            "jit_prefill_chunk_into_slot"} <= progs
    for prog in ("jit_decode_step", "jit__threefry_fold_in",
                 "jit_prefill_chunk_into_slot"):
        tagged = [o.tf_op for k, o in unscoped.scopes.items()
                  if k.startswith(prog + "/") and o.tf_op]
        assert tagged, prog
        name = prog[len("jit_"):]
        assert all(t.startswith(f"jit({name})") for t in tagged
                   if t.startswith("jit(")), prog
    op = unscoped.scopes["jit_decode_step/paged_decode_attention_pallas.11"]
    assert op.hlo.startswith("%paged_decode_attention_pallas.11 = ")


def test_readers_read_nothing_from_a_program_without_scopes(unscoped):
    assert scopes.step_buckets(unscoped) is None
    for name in ("kv_write_ms", "scan_io_ms", "mlp_roofline",
                 "host_bound_idle"):
        assert getattr(scopes, name)(unscoped) is None, name
    assert scopes.kv_write_ms(types.SimpleNamespace(
        reduction=unscoped.reduction)) is None      # no op map at all


# ---------------------------------------------------------- buckets
@pytest.mark.parametrize("tf_op,want", [
    ("jit(decode_step)/while/body/closed_call/mlp/jit(silu)/mul", "mlp"),
    ("jit(decode_step)/while/body/closed_call/attend/qkv/add", "qkv"),
    ("jit(decode_step)/while/body/closed_call/kv_write/scatter",
     "kv_write"),
    ("jit(decode_step)/while/body/dynamic_update_slice", "scan"),
    ("jit(decode_step)/while/body/dynamic_slice", "scan"),
    ("jit(decode_step)/while/body/closed_call/concatenate", "other"),
    ("jit(decode_step)/while", "other"),
    ("jit(decode_step)/head/dot_general", "head"),
    ("jit(decode_steps)/while/body/sample/argmax", "sample"),
    ("", "other"),
])
def test_bucket_is_the_innermost_scope(tf_op, want):
    assert scopes.bucket(tf_op) == want


def test_buckets_are_the_program_scopes_and_two_more():
    assert scopes.BUCKETS[:len(spans.SCOPES)] == spans.SCOPES
    assert scopes.BUCKETS[-2:] == ("scan", "other")


def test_mlp_bytes_are_the_floors_per_layer_mlp():
    cfg = spec.config(spec.benchmark(), "qwen2.5-3b")
    prog = spec.program_config(cfg)
    per_layer = floor._dense_mlp_params(prog.d_model, prog.d_ff,
                                        prog.mlp_gated)
    assert scopes.mlp_weight_bytes(cfg) == prog.n_layers * per_layer * 2
    assert scopes.mlp_weight_bytes(cfg) == 4_869_586_944     # 5.95 ms at 819 GB/s


# ------------------------------------------------------- by hand
def _ev(ops, host, program="jit_step"):
    return profile.TraceEvents([(program, n, s, e) for n, s, e in ops],
                               [(program, 0.0, 10.0)], host, 1)


def _hand_run():
    """Two decode steps of a 36-layer Qwen2.5-3B, by hand: each an MLP
    fusion, a cache write, the scan's re-stacking and an op no scope
    names, inside the layer loop's ``while``."""
    ops, names = [], {
        "while.1": "jit(decode_step)/while",
        "fusion.100": "jit(decode_step)/while/body/closed_call/mlp/dot_general",
        "scatter.3": "jit(decode_step)/while/body/closed_call/kv_write/scatter",
        "copy_dynamic-update-slice_fusion.4":
            "jit(decode_step)/while/body/dynamic_update_slice",
        "copy.112": ""}
    for t in (0.0, 0.01):
        ops += [("while.1", t, t + 0.009),
                ("fusion.100", t + 0.001, t + 0.007),
                ("scatter.3", t + 0.007, t + 0.0075),
                ("copy_dynamic-update-slice_fusion.4", t + 0.0075, t + 0.0085),
                ("copy.112", t + 0.009, t + 0.0095)]
    ev = _ev(ops, [("bench.traced", 0.0, 0.02)], program="jit_decode_step")
    ev.programs[:] = [("jit_decode_step", 0.0, 0.0098),
                      ("jit_decode_step", 0.01, 0.0198)]
    cfg = spec.config(spec.benchmark(), "qwen2.5-3b")
    return types.SimpleNamespace(
        cfg=cfg, events=ev, reduction=profile.reduce(ev),
        peaks=peaks.peaks_for("TPU v5 lite"),
        scopes={f"jit_decode_step/{n}": scopes.Op(t, f"%{n} = ...")
                for n, t in names.items()})


def test_hand_made_steps_bucket_every_op_once():
    run = _hand_run()
    b = scopes.step_buckets(run)
    assert sum(b.values()) == pytest.approx(
        sum(run.reduction.op_self_s.values()), rel=1e-12)
    assert b["mlp"] == pytest.approx(0.012)
    assert b["kv_write"] == pytest.approx(0.001)
    assert b["scan"] == pytest.approx(0.002)
    # the loop's own time (9 ms less its children) and the unnamed copy
    assert b["other"] == pytest.approx(2 * (0.001 + 0.0005 + 0.0005))
    assert scopes.kv_write_ms(run) == pytest.approx(0.5)
    assert scopes.scan_io_ms(run) == pytest.approx(1.0)
    # 4.87 GB at 819 GB/s is 5.946 ms, over 6 ms of mlp a step
    assert scopes.mlp_roofline(run) == pytest.approx(
        100 * 4_869_586_944 / 819e9 / 0.006)


def test_idle_goes_to_the_innermost_span_at_its_middle():
    ev = _ev(ops=[("a", 1.0, 2.0), ("b", 4.0, 5.0), ("c", 8.0, 9.0)],
             host=[("bench.traced", 0.0, 10.0),
                   ("repro.tick", 0.0, 6.0), ("repro.sync", 2.0, 4.0),
                   ("repro.tick", 6.0, 10.0), ("repro.emit", 6.0, 6.5),
                   ("repro.admit", 6.5, 7.5),
                   ("np.asarray(jax.Array)", 2.5, 3.5)])
    window_s, idle = scopes.idle_by_phase(ev)
    assert window_s == 10.0
    # [0,1] tick, [2,4] sync, [5,8] admit (middle 6.5), [9,10] tick
    assert idle == {"repro.tick": 2.0, "repro.sync": 2.0,
                    "repro.admit": 3.0}
    run = types.SimpleNamespace(events=ev)
    assert scopes.host_bound_idle(run) == pytest.approx(50.0)


def test_idle_outside_every_span_is_not_host_bound():
    ev = _ev(ops=[("a", 0.0, 4.0)],
             host=[("bench.traced", 0.0, 10.0), ("repro.sync", 4.0, 5.0)])
    _, idle = scopes.idle_by_phase(ev)
    assert idle == {"": 6.0}
    assert scopes.host_bound_idle(types.SimpleNamespace(events=ev)) == 0.0


def test_scope_report_runs_a_cell_on_the_cpu(tmp_path):
    """The report's whole path at a small size: the scheduler's window,
    its traced slice kept, the trace read.  The CPU trace has no device
    ops, so every device reading is None."""
    from bench import scope_report
    keep = tmp_path / "t.xplane.pb.gz"
    out = scope_report.report(tiny.CELLS["closed"], 2 ** 31 + 5, 1.5,
                              require_accelerator=False, cfg=tiny.CONFIG,
                              mix=tiny.MIXES["closed"], keep=str(keep))
    assert keep.stat().st_size > 0
    assert out["steps"] == 0 and out["step_ms"] is None
    assert out["host_ms_per_tick"] > 0
    assert out["kv_write_ms"] is None and out["host_bound_idle"] is None
