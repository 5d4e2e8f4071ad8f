"""The control comes out not correct: the reference computed with fp8
operands, in the program's place, fails the cell's limits where the
program passes them.  At full width (d_model 2048, 16/2 heads, as
Qwen2.5-3B) with 2 layers and a 32,768-token vocabulary, on the CPU."""
import tiny

import pytest

from bench.harness import score, spec

WIDE = dict(tiny.CONFIG, hidden_size=2048, intermediate_size=1024,
            num_attention_heads=16, num_key_value_heads=2, vocab_size=32768)
MIX = dict(tiny.MIXES["engine"], context=64, segment=64)


@pytest.mark.parametrize("cell", ["qwen2.5-3b.b1_ctx2k",
                                  "qwen2.5-3b.serve_long"])
def test_fp8_control_fails_where_the_program_passes(cell):
    from bench import run as bench_run
    limits = spec.limits(cell)
    res = bench_run.run_cell("qwen2.5-3b.b1_ctx2k", 2 ** 31 + 5, 1.0, False,
                             cfg=WIDE, mix=MIX, limits=limits,
                             require_accelerator=False, control=True)
    assert res.result["correct"] is True
    ctrl = {k[len("control."):]: v for k, v in res.readings.items()
            if k.startswith("control.")}
    ok, _ = score.judge(ctrl, limits)
    assert not ok, (ctrl, limits)
