"""Bytes and operations per decode step come from each configuration's
own sizes, and agree with the paper's floor arithmetic."""
import tiny  # noqa: F401  (puts the repository on the path)

import json

import pytest

from bench.harness import counts, peaks, spec
from repro.configs import get_config
from repro.core import floor


def _cfg(name):
    """A configuration file, whether or not a cell runs it yet."""
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    return dict(cfg, name=name)


@pytest.mark.parametrize("name,ctx,gb", [("internlm2-1.8b", 16384, 5.389),
                                         ("qwen2.5-3b", 2048, 6.247)])
def test_step_bytes_match_the_floor(name, ctx, gb):
    cfg = _cfg(name)
    prog = spec.program_config(cfg)
    want = floor.weight_bytes(prog) + floor.kv_bytes(prog, ctx)
    assert counts.step_bytes(cfg, [ctx]) == want
    assert round(counts.step_bytes(cfg, [ctx]) / 1e9, 3) == gb


@pytest.mark.parametrize("name,kv_heads,per_token", [
    ("internlm2-1.8b", 8, 98304), ("qwen2.5-3b", 2, 36864)])
def test_kv_bytes_follow_each_configs_kv_heads(name, kv_heads, per_token):
    cfg = _cfg(name)
    assert cfg["num_key_value_heads"] == kv_heads
    assert counts.kv_bytes_per_token(cfg) == per_token
    assert counts.step_bytes(cfg, [100, 50]) - counts.weight_bytes(cfg) \
        == 150 * per_token


@pytest.mark.parametrize("name", ["internlm2-1.8b", "qwen2.5-3b"])
def test_program_config_is_the_registered_model(name):
    mine, theirs = spec.program_config(_cfg(name)), get_config(name)
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "qkv_bias", "tie_embeddings",
              "rope_theta", "dtype", "family"):
        assert getattr(mine, f) == getattr(theirs, f), f


def test_step_flops_and_roofline():
    cfg = _cfg("qwen2.5-3b")
    n = counts.param_count(cfg)
    f = counts.step_flops(cfg, [2048])
    # two per streamed matmul weight, the embedding lookup excluded
    assert 1.9 * n < f < 2.2 * n
    p = peaks.peaks_for("TPU v5 lite")
    t = counts.roofline_s(f, counts.step_bytes(cfg, [2048]), p)
    assert t == pytest.approx(6.247e9 / 819e9, rel=1e-3)   # bytes bound


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
