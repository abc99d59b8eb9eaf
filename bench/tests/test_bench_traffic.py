"""The traffic generator: seeded, clipped as the mix states, the same sizes
for every seed, and the square wave's rate averaging to the mean."""
import bench_paths  # noqa: F401  (puts bench/ and src/ on sys.path)
import json
from pathlib import Path

import numpy as np
import pytest

from harness import traffic

MIXES = Path(__file__).resolve().parents[1] / "mixes"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "code-burst"])
def test_seeded_and_clipped(name):
    mx = mix(name)
    a = traffic.generate(mx, 2.0, 70.0, 2**31 + 11, 1000, 2048)
    b = traffic.generate(mx, 2.0, 70.0, 2**31 + 11, 1000, 2048)
    c = traffic.generate(mx, 2.0, 70.0, 5, 1000, 2048)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.prompt_len, b.prompt_len)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    # another seed: the same sizes and gaps in another order
    assert not np.array_equal(a.prompt_len, c.prompt_len)
    assert sorted(a.prompt_len) == sorted(c.prompt_len)
    assert sorted(a.output_len) == sorted(c.output_len)
    assert len(a.due) == len(c.due)
    for t in (a, c):
        assert np.all(np.diff(t.due) >= 0) and t.due[0] >= 0 and t.due[-1] < 70
        p, o = mx["prompt"], mx["output"]
        assert t.prompt_len.min() >= p["min"] and t.prompt_len.max() <= p["max"]
        assert t.output_len.min() >= o["min"] and t.output_len.max() <= o["max"]
        assert [len(x) for x in t.prompts] == list(t.prompt_len)
        assert all(x.min() >= 1 and x.max() < 1000 for x in t.prompts)


def test_clips_are_counted():
    mx = dict(mix("chat"), prompt=dict(mix("chat")["prompt"], max=300))
    t = traffic.generate(mx, 2.0, 200.0, 3, 100, 2048)
    at_max = int(np.sum(t.prompt_len == 300))      # cut, or drawn as 300
    assert 0.9 * at_max <= t.clipped["prompt_cut"] <= at_max


def test_square_wave_averages_to_the_mean():
    w = traffic.Wave(mix("code-burst")["arrivals"], 1.5)
    assert w.period == 10.0
    assert w.integral(1.0) == pytest.approx(4.5)         # 3x in the burst
    assert w.integral(6.0) - w.integral(4.0) == pytest.approx(1.5)   # 0.5x
    assert w.integral(10.0) == pytest.approx(15.0)
    assert w.integral(35.0) / 35.0 == pytest.approx(
        (3 * 15.0 + 2 * 4.5 + 3 * 0.75) / 35.0)
    lam = np.linspace(0, 40, 97)
    assert np.allclose([w.integral(t) for t in w.inverse(lam)], lam)
    with pytest.raises(ValueError):
        traffic.Wave({"phases": [[2.0, 3.0], [8.0, 1.0]]}, 1.0)


def test_arrival_counts_follow_the_wave():
    mx = mix("code-burst")
    t = traffic.generate(mx, 2.0, 100.0, 9, 1000, 2048)
    # ten periods: about 2 req/s x 100 s, most of them in the 2 s bursts
    assert 150 < len(t.due) < 250
    in_burst = np.mean((t.due % 10.0) < 2.0)
    assert 0.45 < in_burst < 0.75           # 6 of every 10 expected arrivals
