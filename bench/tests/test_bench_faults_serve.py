"""As ``test_bench_faults_b1``, through ``SlotScheduler`` and the paged
pool: the closed loop of long sessions, and the open loop."""
import tiny

import pytest

from repro.models.model import Model


@pytest.mark.parametrize("kind", ["closed", "open"])
def test_sound_run_is_correct(kind):
    assert tiny.run(kind).result["correct"] is True


@pytest.mark.parametrize("kind", ["closed", "open"])
def test_altered_token_is_caught(kind, monkeypatch):
    import repro.serving.scheduler as sched
    orig = sched.sample

    def off_by_one(logits, key, **kw):
        return (orig(logits, key, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(sched, "sample", off_by_one)
    assert tiny.run(kind).result["correct"] is False


def test_unwritten_cache_is_caught(monkeypatch):
    orig = Model.decode_step

    def stale(self, params, cache, tokens, active=None):
        logits, new = orig(self, params, cache, tokens, active)
        return logits, dict(cache, pos=new["pos"])
    monkeypatch.setattr(Model, "decode_step", stale)
    assert tiny.run("closed").result["correct"] is False
