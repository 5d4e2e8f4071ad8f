"""Everything a cell is made of, found by name.

``BENCHMARK.json`` names the cells; a cell names its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``); each metric is read by
``bench/metrics/<metric>.py``; the limits of the comparison that decides
``correct`` are in ``bench/limits/<workload>.json``; a configuration
names its plain reference (``bench/reference/<name>.py``).  Adding a
cell, a mix or a metric adds files and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def benchmark() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def workload(bm: Dict, name: str) -> Dict:
    return _by_name(bm["workloads"], name, "workload")


def config(bm: Dict, name: str) -> Dict:
    entry = _by_name(bm["configs"], name, "configuration")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    cfg["name"] = name
    return cfg


def traffic(name: str) -> Dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def limits(workload_name: str) -> Dict[str, float]:
    return json.loads((BENCH / "limits" / f"{workload_name}.json")
                      .read_text())["limits"]


def _load(path: pathlib.Path, module: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _load(BENCH / "metrics" / f"{name}.py",
                 "bench_metric_" + name.replace(".", "_")).read


def reference(cfg: Dict):
    return _load(BENCH / "reference" / f"{cfg['reference']}.py",
                 "bench_reference_" + cfg["reference"])


def metrics_of(bm: Dict, workload_name: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bm[kind]
            if workload_name in m.get("workloads", [workload_name])]


def program_config(cfg: Dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    hq = cfg["num_attention_heads"]
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=hq, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // hq,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=bool(cfg.get("attention_bias", cfg.get("bias", False))),
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["torch_dtype"])
