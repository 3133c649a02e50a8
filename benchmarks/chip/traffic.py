"""The one traffic generator: turns a mix file (``traffic/<mix>.json``) and a
seed into the requests of a run.

A mix file gives the loop (``closed`` with a fixed cycle of request
classes, or ``open`` at a fixed rate with gamma inter-arrivals), and the
request classes: a prompt of ``segments`` full segments plus a ``tail`` of
tokens, or a ``tokens`` length, each either a fixed list or a log-normal
law, and ``max_new`` likewise. All outputs are greedy.

Steadiness: the sizes and the inter-arrival gaps are a fixed multiset,
taken at evenly spaced quantiles of their laws (the same for every seed);
the seed permutes their order and draws the token ids. So two seeds offer
the same work in another order, and the same seed gives the same requests.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List, Optional

import numpy as np
from scipy import stats

HERE = Path(__file__).resolve().parent


@dataclass
class Req:
    idx: int
    prompt: np.ndarray          # int32 [P]
    max_new: int
    t_send: Optional[float]     # seconds after the window opens (open loop)
    cls: str


def load(name: str, root: Path = HERE) -> Dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def _quantiles(law: Dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a log-normal law given by its
    median and sigma, rounded and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    x = law["median"] * np.exp(law["sigma"] * stats.norm.ppf(u))
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def _lengths(spec, n: int) -> np.ndarray:
    if isinstance(spec, (int, float)):
        return np.full(n, int(spec), np.int64)
    if isinstance(spec, list):
        return np.resize(np.asarray(spec, np.int64), n)
    return _quantiles(spec, n)


def _class_counts(classes: List[Dict], n: int) -> List[int]:
    shares = np.asarray([c["share"] for c in classes], np.float64)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    for i in np.argsort(-shares)[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def shapes(mix: Dict, seg_len: int, n: int) -> List[Dict]:
    """The fixed multiset of ``n`` request shapes (prompt tokens, max_new,
    class), in the mix's own order, independent of the seed."""
    out = []
    for c, k in zip(mix["classes"], _class_counts(mix["classes"], n)):
        if k == 0:
            continue
        if "segments" in c:
            segs = _lengths(c["segments"], k)
            tail = _lengths(c.get("tail", 0), k)
            plen = segs * seg_len + tail
        else:
            plen = _lengths(c["tokens"], k)
        new = _lengths(c["max_new"], k)
        out += [dict(prompt=int(p), max_new=int(m), cls=c["name"])
                for p, m in zip(plen, new)]
    return out


def n_requests(mix: Dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        return sum(len(c["segments"]) if isinstance(c.get("segments"), list)
                   else 1 for c in mix["classes"])
    return max(1, int(math.ceil(mix["rate_rps"] * seconds)))


def gaps(mix: Dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (s): evenly spaced quantiles of a gamma law
    with the mix's rate and coefficient of variation."""
    cv = mix["interarrival"]["cv"]
    shape = 1.0 / cv ** 2
    u = (np.arange(n) + 0.5) / n
    return stats.gamma.ppf(u, shape, scale=1.0 / (mix["rate_rps"] * shape))


def requests(mix: Dict, seed: int, seconds: float, seg_len: int,
             vocab: int) -> Iterator[Req]:
    """The run's requests. Closed loop: the cycle repeated without end (the
    client stops sending when the window closes). Open loop: every request
    due in [0, seconds), with its send time."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
    n = n_requests(mix, seconds)
    sh = shapes(mix, seg_len, n)

    def make(i, s, t):
        p = rng.integers(0, vocab, s["prompt"]).astype(np.int32)
        return Req(i, p, s["max_new"], t, s["cls"])

    if mix["loop"] == "closed":
        i = 0
        while True:
            for s in sh:
                yield make(i, s, None)
                i += 1
    order = rng.permutation(n)
    g = gaps(mix, n)[rng.permutation(n)]
    t = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    for i in range(n):
        if t[i] >= seconds:
            break
        yield make(i, sh[order[i]], float(t[i]))


def warm_shapes(mix: Dict, seg_len: int, seconds: float,
                chunk: int) -> List[Dict]:
    """The request shapes the set-up serves: one per distinct prompt length
    of the mix (the program slices each prompt length with programs of its
    own, and each full-segment count splits into its own diagonal stages),
    each decoding at least one chunk."""
    sh = shapes(mix, seg_len, n_requests(mix, seconds))
    out = {}
    for s in sh:
        new = max(2, min(s["max_new"], 2 * chunk))
        if s["prompt"] not in out or out[s["prompt"]]["max_new"] < new:
            out[s["prompt"]] = dict(prompt=s["prompt"], max_new=new,
                                    cls="warm")
    return [out[k] for k in sorted(out)]
