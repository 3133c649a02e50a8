"""The traffic generator: deterministic per seed, the same work for every
seed in another order, and the lengths and rates its files state."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "benchmarks"
                       / "chip"))

import traffic  # noqa: E402

SEG = 1024


def take(mix, seed, seconds=40.0, n=None):
    out = []
    for r in traffic.requests(mix, seed, seconds, SEG, 32000):
        out.append(r)
        if n is not None and len(out) >= n:
            break
    return out


@pytest.mark.parametrize("name", ["longdoc", "chat"])
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    a, b = take(mix, 2 ** 35 + 1, n=12), take(mix, 2 ** 35 + 1, n=12)
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.t_send for r in a] == [r.t_send for r in b]
    c = take(mix, 2 ** 35 + 2, n=12)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_longdoc_cycle():
    mix = traffic.load("longdoc")
    reqs = take(mix, 3, n=8)
    segs = [r.prompt.shape[0] // SEG for r in reqs]
    tails = {r.prompt.shape[0] % SEG for r in reqs}
    assert segs == [32, 64, 96, 128] * 2
    assert tails == {512}
    assert {r.max_new for r in reqs} == {16}
    assert all(r.t_send is None for r in reqs)


def test_chat_lengths_and_rate():
    mix = traffic.load("chat")
    seconds = 40.0
    a = take(mix, 11, seconds)
    b = take(mix, 12, seconds)
    n = traffic.n_requests(mix, seconds)
    assert len(a) == len(b) == n
    # the same multiset of sizes for every seed, in another order
    sa = sorted((r.prompt.shape[0], r.max_new) for r in a)
    sb = sorted((r.prompt.shape[0], r.max_new) for r in b)
    assert sa == sb
    assert [r.prompt.shape[0] for r in a] != [r.prompt.shape[0] for r in b]
    law = mix["classes"][0]["tokens"]
    p = np.array([r.prompt.shape[0] for r in a])
    assert p.min() >= law["min"] and p.max() <= law["max"]
    assert abs(np.median(p) - law["median"]) <= 0.05 * law["median"]
    new = np.array([r.max_new for r in a])
    assert new.min() >= 32 and new.max() <= 512
    t = np.array([r.t_send for r in a])
    assert t[0] == 0.0 and np.all(np.diff(t) >= 0) and t[-1] < seconds
    g = traffic.gaps(mix, n)
    assert abs(len(g) / g.sum() - mix["rate_rps"]) < 0.15 * mix["rate_rps"]
    cv = g.std() / g.mean()
    assert 1.5 < cv < 2.2


def test_warm_shapes_cover_every_prompt_length():
    for name in ("longdoc", "chat"):
        mix = traffic.load(name)
        warm = traffic.warm_shapes(mix, SEG, 40.0, 8)
        lens = {s["prompt"] for s in traffic.shapes(
            mix, SEG, traffic.n_requests(mix, 40.0))}
        assert {w["prompt"] for w in warm} == lens
        assert all(w["max_new"] >= 2 for w in warm)
