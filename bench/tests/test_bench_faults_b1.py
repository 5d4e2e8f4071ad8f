"""The comparison that decides ``correct`` fails the batch-1 timed path
when it is broken underneath: a token altered where it is produced, or
a decode step that hands back its cache unwritten.  Small size, CPU,
the cell's own limits."""
import tiny

from repro.models.model import Model


def test_sound_run_is_correct():
    assert tiny.run("engine").result["correct"] is True


def test_altered_token_is_caught(monkeypatch):
    import repro.serving as serving
    orig = serving.sample

    def off_by_one(logits, key, **kw):
        return (orig(logits, key, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(serving, "sample", off_by_one)
    assert tiny.run("engine").result["correct"] is False


def test_unwritten_cache_is_caught(monkeypatch):
    orig = Model.decode_step

    def stale(self, params, cache, tokens, active=None):
        logits, _ = orig(self, params, cache, tokens, active)
        return logits, dict(cache, pos=cache["pos"] + 1)
    monkeypatch.setattr(Model, "decode_step", stale)
    assert tiny.run("engine").result["correct"] is False


def test_loop_folds_a_key_per_token_as_generate_streamed(monkeypatch):
    """The timed loop pays what ``generate_streamed`` pays per token: a
    key folded for each step, the warm-up's two steps included (counted
    from the engine's construction on, after the weights are made)."""
    import jax
    from repro.serving import DecodeEngine
    orig_fold, orig_init, calls = jax.random.fold_in, DecodeEngine.__init__, []

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)
        calls.append("engine")

    def counted(key, data):
        if calls:
            calls.append(data)
        return orig_fold(key, data)
    monkeypatch.setattr(DecodeEngine, "__init__", init)
    monkeypatch.setattr(jax.random, "fold_in", counted)
    cell = tiny.run("engine")
    assert cell.result["correct"] is True
    folds = calls[1:]
    assert len(folds) == len(cell.run.window.ticks) + 2
    seg = tiny.MIXES["engine"]["segment"]
    assert folds[:2] == [0, 1] and folds[2:2 + seg] == list(range(seg))
