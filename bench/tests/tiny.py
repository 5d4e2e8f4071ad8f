"""Small stand-ins for the cells' configurations and mixes, sized for the
CPU, and a way to run a cell at that size."""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = dict(name="tiny", reference="dense_gqa", hidden_size=128,
              intermediate_size=256, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=2, vocab_size=512,
              rms_norm_eps=1e-6, rope_theta=1e6, tie_word_embeddings=True,
              torch_dtype="bfloat16", attention_bias=True)

MIXES = {
    "engine": dict(driver="engine", context=48, segment=16, trace_s=1),
    "closed": dict(driver="scheduler", loop="closed", sessions=3, slots=3,
                   page=16, prefill_chunk=32, steps_per_tick=1,
                   decode_backend="pallas",
                   prompt=dict(dist="uniform", lo=64, hi=128, step=32),
                   output=dict(fixed=400), score_requests=2, score_tokens=8,
                   trace_s=1),
    "open": dict(driver="scheduler", loop="open", rate_per_s=6.0, drain_s=10,
                 slots=4, page=16, prefill_chunk=None, steps_per_tick=1,
                 decode_backend="pallas",
                 prompt=dict(dist="loguniform", lo=16, hi=64, step=16),
                 output=dict(dist="loguniform", lo=4, hi=8, step=1),
                 score_requests=3, trace_s=1),
}

# no cell runs an open loop yet; its driver is held to the limits of
# the Qwen2.5-3B batch-1 cell
CELLS = {"engine": "qwen2.5-3b.b1_ctx2k",
         "closed": "qwen2.5-3b.serve_long",
         "open": "qwen2.5-3b.b1_ctx2k"}


def run(kind: str, seed: int = 2 ** 31 + 11, seconds: float = 1.5,
        cfg=None, **kw):
    """One run of the cell whose driver and loop ``kind`` names, at the
    small size, with that cell's own limits."""
    from bench import run as bench_run
    return bench_run.run_cell(CELLS[kind], seed, seconds, False,
                              cfg=cfg or CONFIG, mix=MIXES[kind],
                              require_accelerator=False, **kw)
