"""Every seed offers the same work in another order."""
import tiny  # noqa: F401

import numpy as np

from bench.harness import traffic

FLEET = {"loop": "open", "rate_per_s": 5.0, "drain_s": 10,
         "prompt": {"dist": "loguniform", "lo": 128, "hi": 1024, "step": 64},
         "output": {"dist": "loguniform", "lo": 16, "hi": 64, "step": 1}}
LONG = {"loop": "closed", "sessions": 8,
        "prompt": {"dist": "uniform", "lo": 4096, "hi": 8192, "step": 512},
        "output": {"fixed": 1024}}


def _window(reqs, seconds):
    return [r for r in reqs if r.due_s <= seconds]


def test_every_seed_puts_the_same_work_in_the_window():
    a = _window(traffic.requests(FLEET, 2 ** 31 + 3, 1000, 40), 40)
    b = _window(traffic.requests(FLEET, 12, 1000, 40), 40)
    assert len(a) == len(b) == 200
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    assert abs(a[-1].due_s - b[-1].due_s) < 1e-9
    full = traffic.requests(FLEET, 12, 1000, 40)
    assert all(x.due_s < y.due_s for x, y in zip(full, full[1:]))
    assert full[-1].due_s > 40 + 9


def test_lengths_lie_on_the_warmed_support():
    sup = set(traffic.support(FLEET["prompt"]))
    assert sup == set(range(128, 1025, 64))
    for r in traffic.requests(FLEET, 5, 1000, 40):
        assert r.prompt.size in sup and 16 <= r.max_new_tokens <= 64
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000


def test_closed_loop_contexts():
    reqs = traffic.requests(LONG, 9, 92544)
    sizes = sorted(r.prompt.size for r in reqs)
    assert sizes == list(range(4608, 8193, 512))
    assert all(r.max_new_tokens == 1024 and r.due_s == 0 for r in reqs)


def test_prompts_follow_the_seed():
    a = traffic.prompt_ids(2 ** 31 + 1, 151936, 64)
    assert np.array_equal(a, traffic.prompt_ids(2 ** 31 + 1, 151936, 64))
    assert not np.array_equal(a, traffic.prompt_ids(2 ** 31 + 2, 151936, 64))


def test_fleet_mix_fills_a_window_and_warms_few_shapes():
    """The committed open-loop mix (a cell to come; bench/sweep.py reads
    it) puts at least 200 requests in a window of the benchmark's length,
    on 15 prompt lengths that set-up warms."""
    from bench.harness import spec
    mix = spec.traffic("serve_fleet")
    secs = spec.benchmark()["run_seconds"]
    reqs = traffic.requests(mix, 2 ** 31 + 77, 151936, secs)
    assert len(_window(reqs, secs)) >= 200
    assert len(traffic.support(mix["prompt"])) == 15
