"""``chip_smoke.py`` rehearsed on the CPU, the device-kind table, and
where the persistent compilation cache goes.

The smoke script's phases run here at the reduced config with the
Pallas kernels in interpret mode: this checks the paths, the checks and
the report's shape, never a device time.  Its route check must fail a
fused kernel with a fault planted in it.  Without ``--reduced`` the
script must refuse a device that is not a TPU before any phase runs.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from repro.configs import get_config
from repro.core.hardware import TPU_V5E, chip_for_device_kind
from repro.kernels.paged_decode_attention import ops as pda_ops
from repro.launch import compile_cache
from repro.models.model import Model
from repro.serving import SlotScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_reduced_phases_run_and_end_in_the_device_record(capsys):
    assert chip_smoke.smoke(reduced=True, seed=0) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for phase in ("phase batch1:", "phase paged_gather:", "route_check:",
                  "phase paged_fused:"):
        line = next(ln for ln in lines if ln.startswith(phase))
        assert "measured on cpu" in line or phase == "route_check:"
        if phase.startswith("phase"):
            assert "step_cache_size 1," in line
    last = json.loads(lines[-1])
    dev = jax.devices()[0]
    assert last == {"ok": True,
                    "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": len(jax.devices())}}


@pytest.fixture(scope="module")
def live_paged_step():
    """A reduced gather-route scheduler one tick in: every slot decoding
    on its paged cache, as ``route_check`` meets it in the smoke run."""
    cfg = get_config(chip_smoke.ARCH).reduced()
    gather = Model(cfg)
    params = gather.init(jax.random.PRNGKey(0))
    reqs = chip_smoke.paged_requests(cfg, chip_smoke.REDUCED, seed=0)
    sched = SlotScheduler(gather, params, n_slots=chip_smoke.REDUCED.slots,
                          max_len=40, paged=True, page_size=16)
    for r in reqs:
        sched.submit(r)
    sched.tick()
    return sched, Model(cfg, decode_backend="pallas"), params


# faults a broken fused kernel could make, planted in its wrapper
FAULTS = {
    "newest_token_dropped": lambda q, kp, vp, bt, ln, **kw: (
        q, kp, vp, bt, ln - 1),
    "other_slots_pages": lambda q, kp, vp, bt, ln, **kw: (
        q, kp, vp, jnp.roll(bt, 1, axis=0), ln),
    "kv_heads_swapped": lambda q, kp, vp, bt, ln, **kw: (
        q, kp[:, ::-1], vp[:, ::-1], bt, ln),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_route_check_fails_a_broken_fused_kernel(live_paged_step,
                                                 monkeypatch, fault):
    sched, fused, params = live_paged_step
    kernel = pda_ops.paged_decode_attention
    monkeypatch.setattr(
        pda_ops, "paged_decode_attention",
        lambda *a, **kw: kernel(*FAULTS[fault](*a, **kw), **kw))
    with pytest.raises(chip_smoke.SmokeFailure, match="exceeds"):
        chip_smoke.route_check(sched, fused, params)


def test_full_run_refuses_a_device_that_is_not_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.smoke(reduced=False) == 1
    captured = capsys.readouterr()
    assert captured.out == ""            # no phase ran, no result line
    assert "no TPU" in captured.err


class TestDeviceKinds:
    def test_known_kind_resolves(self):
        assert chip_for_device_kind("TPU v5 lite") is TPU_V5E

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="TPU v99"):
            chip_for_device_kind("TPU v99")


class TestCompileCachePlacement:
    def test_environment_wins_and_nothing_is_set(self, monkeypatch,
                                                 tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_ignored_path_in_the_checkout(self,
                                                             monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            placed = compile_cache.place_compile_cache()
            assert jax.config.jax_compilation_cache_dir == placed
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert placed == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
