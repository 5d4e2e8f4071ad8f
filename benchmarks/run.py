"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.py).
``--quick`` shrinks session counts for CI-speed runs; the default run is
the paper-faithful protocol (N=10 sessions on the headline A/B).
``--json <path>`` additionally writes every emitted row (with the
derived ``k=v`` pairs parsed into typed fields) plus per-table status
and wall time to a machine-readable file, so the perf trajectory
(``BENCH_*.json``) can be tracked across PRs.

Every selected table runs even if an earlier one fails; any failure
makes the process exit nonzero (with a ``# FAILED`` line per broken
table), so a CI stage over a sweep can never silently pass.  Exit codes
distinguish the failure class: 3 when every failed table tripped one of
its own inline assertions (a metric regression — the harness ran fine),
1 when any table crashed outright.  ``--list`` prints the table ids
with one-line descriptions and exits 0.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time
import traceback


DESCRIPTIONS = {
    "table1": "analytic R_floor matrix across archs and chips",
    "table2": "dispatch-mode A/B: eager vs stage_jit vs full_jit tax",
    "table4": "batch-size sweep: decode latency vs batched throughput",
    "table6": "decode attention backends: sdpa / math / split_kv / pallas",
    "table7": "weight quantisation matrix: dequant vs fused kernels",
    "table8": "roofline accounting: bytes moved vs model footprint",
    "fig9": "cost-of-inference ladder across optimisation stages",
    "table9": "continuous batching vs sequential serving",
    "table10": "paged KV: oversubscription, chunked prefill, preemption",
    "table11": "launch overhead: horizon-K fused macro-tick amortisation",
    "table12": "prefix sharing: CoW page dedup across sessions",
    "table13": "SLO metrics under trace load: fixed-K vs adaptive-K",
    "table14": "host-DRAM KV tier: park/restore vs re-prefill",
    "table15": "quantised KV pages + int4 weights: realised vs analytic "
               "traffic per route",
    "table16": "fault injection + graceful degradation: chaos replay A/B",
}


_MODULE_RE = re.compile(r"(table\d+|fig\d+)_\w+\.py$")


def registry_audit(suite_names=None, description_names=None,
                   module_dir=None):
    """staticcheck-style self-audit of the table registry: every
    ``table*.py`` / ``fig*.py`` module must be registered with a
    description, and the three views (modules on disk, ``DESCRIPTIONS``,
    the ``suites`` dict) must agree.  Returns a list of human-readable
    problem lines — empty means consistent.  Each view is optional so
    ``--list`` can audit without importing the suite modules."""
    problems = []
    descs = set(DESCRIPTIONS if description_names is None
                else description_names)
    module_dir = module_dir or os.path.dirname(os.path.abspath(__file__))
    ids = {m.group(1) for f in os.listdir(module_dir)
           if (m := _MODULE_RE.match(f))}
    for mid in sorted(ids - descs):
        problems.append(f"{mid}: module file exists but has no entry in "
                        f"DESCRIPTIONS (--list would omit it)")
    for mid in sorted(descs - ids):
        problems.append(f"{mid}: described in --list but no matching "
                        f"benchmark module file")
    if suite_names is not None:
        suites = set(suite_names)
        for name in sorted(suites - descs):
            problems.append(f"{name}: registered suite has no --list "
                            f"description")
        for name in sorted(descs - suites):
            problems.append(f"{name}: described in --list but not in the "
                            f"suites registry")
    return problems


def _report_audit(problems) -> None:
    for p in problems:
        print(f"# registry: {p}", flush=True)
    print(f"# FAILED: benchmark registry out of sync "
          f"({len(problems)} problem(s))", flush=True)


def main() -> None:
    if "--list" in sys.argv:
        for name, desc in DESCRIPTIONS.items():
            print(f"{name:8s} {desc}")
        problems = registry_audit()
        if problems:
            _report_audit(problems)
            sys.exit(2)
        return
    quick = "--quick" in sys.argv
    only = None
    json_path = None
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a.startswith("--only="):
            only = a.split("=", 1)[1]
        elif a.startswith("--json="):
            json_path = a.split("=", 1)[1]
        elif a == "--json":
            if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
                print("# FAILED: --json requires a path argument",
                      flush=True)
                sys.exit(2)
            json_path = argv[i + 1]

    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    from benchmarks import (common, fig9_cost_ladder, table1_rfloor_matrix,
                            table2_dispatch_ab, table4_batch_sweep,
                            table6_attention_backends, table7_quant_matrix,
                            table8_accounting, table9_continuous_batching,
                            table10_paged_kv, table11_launch_overhead,
                            table12_prefix_sharing, table13_slo_load,
                            table14_kv_tiering, table15_quant_serving,
                            table16_fault_recovery)
    suites = {
        "table1": table1_rfloor_matrix.run,
        "table2": lambda: table2_dispatch_ab.run(quick=quick),
        "table4": lambda: table4_batch_sweep.run(quick=quick),
        "table6": lambda: table6_attention_backends.run(quick=quick),
        "table7": lambda: table7_quant_matrix.run(quick=quick),
        "table8": table8_accounting.run,
        "fig9": fig9_cost_ladder.run,
        "table9": lambda: table9_continuous_batching.run(quick=quick),
        "table10": lambda: table10_paged_kv.run(quick=quick),
        "table11": lambda: table11_launch_overhead.run(quick=quick),
        "table12": lambda: table12_prefix_sharing.run(quick=quick),
        "table13": lambda: table13_slo_load.run(quick=quick),
        "table14": lambda: table14_kv_tiering.run(quick=quick),
        "table15": lambda: table15_quant_serving.run(quick=quick),
        "table16": lambda: table16_fault_recovery.run(quick=quick),
    }
    problems = registry_audit(suites)
    if problems:
        _report_audit(problems)
        sys.exit(2)
    if only is not None and only not in suites:
        print(f"# FAILED: unknown table {only!r} "
              f"(have: {', '.join(suites)})", flush=True)
        sys.exit(2)
    t0 = time.time()
    failed, crashed = [], []
    report = {"quick": quick, "only": only, "tables": {}}
    for name, fn in suites.items():
        if only and name != only:
            continue
        common.take_results()            # drop stray rows from prior table
        t_table = time.time()
        ok = True
        try:
            fn()
        except AssertionError:
            traceback.print_exc()
            print(f"# FAILED (assertion): {name}", flush=True)
            failed.append(name)
            ok = False
        except Exception:
            traceback.print_exc()
            print(f"# FAILED: {name}", flush=True)
            failed.append(name)
            crashed.append(name)
            ok = False
        report["tables"][name] = {
            "ok": ok,
            "seconds": round(time.time() - t_table, 3),
            "rows": common.take_results(),
        }
    report["total_s"] = round(time.time() - t0, 3)
    report["failed"] = failed
    if json_path:
        with open(json_path, "w") as f:
            # allow_nan=False: a NaN/Inf anywhere in the report is a
            # bug (strict mode would emit invalid JSON silently) — fail
            # the run loudly instead
            json.dump(report, f, indent=2, allow_nan=False)
        print(f"# wrote {json_path}", flush=True)
    for name, entry in report["tables"].items():
        print(f"# {name}: {entry['seconds']:.1f}s"
              f"{'' if entry['ok'] else ' FAILED'}", flush=True)
    print(f"# total {report['total_s']:.1f}s", flush=True)
    if failed:
        print(f"# {len(failed)} table(s) failed: {', '.join(failed)}",
              flush=True)
        # 3 = every failure was an inline-assertion trip (metric
        # regression); 1 = at least one table crashed outright
        sys.exit(1 if crashed else 3)


if __name__ == "__main__":
    main()
