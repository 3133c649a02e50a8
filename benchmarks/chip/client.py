"""The client side of a run: sends the traffic into
``ContinuousScheduler.run``, stamps every delivery, and turns the stamps
into the end-to-end metrics.

Open loop: each request is due at its send time; the source hands it to
the scheduler once it is due (the scheduler pulls only when it can start
one, so a request that waits for a slot waits in the source, and its
latency counts from when it was due). Closed loop: one client sends the
next request when the previous one has delivered its last token. Either
way no request is sent once ``seconds`` have passed since the window
opened, and the requests in flight are served to their end (or until
``drain_s`` more seconds have passed: what is unfinished then has failed).
"""
from __future__ import annotations

import math
import statistics
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional

import numpy as np


@dataclass
class Record:
    idx: int
    prompt_len: int
    max_new: int
    cls: str
    t_due: float                       # host clock, s
    prompt: Optional[np.ndarray] = None
    t_sent: Optional[float] = None     # when the scheduler pulled it
    deliveries: List[List[float]] = field(default_factory=list)  # [t, n]
    tokens: List[int] = field(default_factory=list)
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new

    @property
    def t_first(self) -> Optional[float]:
        return self.deliveries[0][0] if self.deliveries else None


def drive(sched, reqs: Iterable, loop: str, seconds: float,
          drain_s: float = 120.0, clock=time.perf_counter):
    """Run one window. Returns (records, t0, t_close, t_end)."""
    from repro.serve import Request, RequestError
    recs: Dict[int, Record] = {}
    it = iter(reqs)
    state = {"pending": None, "busy": False, "closed": False}
    t0 = clock()

    def source():
        while True:
            now = clock()
            if now - t0 >= seconds:
                state["closed"] = True
                return
            if loop == "closed":
                if state["busy"]:
                    yield None
                    continue
                r = next(it, None)
                if r is None:
                    state["closed"] = True
                    return
                due = now
            else:
                r = state["pending"] or next(it, None)
                if r is None:
                    state["closed"] = True
                    return
                due = t0 + r.t_send
                if due > now:
                    state["pending"] = r
                    yield None
                    continue
                state["pending"] = None
            recs[r.idx] = Record(r.idx, int(r.prompt.shape[0]), r.max_new,
                                 r.cls, due, r.prompt, clock())
            state["busy"] = True
            yield Request(r.idx, r.prompt, r.max_new)

    t_close = None
    for ev in sched.run(source()):
        now = clock()
        if state["closed"] and t_close is None:
            t_close = now
        if isinstance(ev, RequestError):
            recs[ev.req_id].error = ev.code
            state["busy"] = False
            continue
        rec = recs[ev.req_id]
        if rec.deliveries and rec.deliveries[-1][0] == ev.t_emit:
            rec.deliveries[-1][1] += 1
        else:
            rec.deliveries.append([ev.t_emit, 1])
        rec.tokens.append(int(ev.token))
        if ev.done:
            state["busy"] = False
        if t_close is not None and now - t_close > drain_s:
            break
    t_end = clock()
    return list(recs.values()), t0, (t_close or t_end), t_end


# ---------------------------------------------------------------------------
# End-to-end metrics (client side, host clock)
# ---------------------------------------------------------------------------

def _pct(values: List[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation; an infinite
    value (a failed request) sorts last."""
    v = sorted(values)
    if not v:
        return math.inf
    k = (len(v) - 1) * q / 100.0
    lo, hi = int(math.floor(k)), int(math.ceil(k))
    if math.isinf(v[hi]):
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def prefill_tok_s(recs: List[Record], t0: float) -> float:
    """Prompt tokens of every request whose first token arrived, over the
    time from the window's start to the last such arrival."""
    firsts = [r for r in recs if r.t_first is not None]
    if not firsts:
        return 0.0
    t_last = max(r.t_first for r in firsts)
    return sum(r.prompt_len for r in firsts) / max(t_last - t0, 1e-9)


def ttft_s(recs: List[Record]) -> List[float]:
    return [(r.t_first - r.t_due) if r.t_first is not None and not r.error
            else math.inf for r in recs]


def itl_ms(recs: List[Record]) -> List[float]:
    """Per output token after each request's first delivery: the time since
    the previous delivery to its request over the tokens the delivery
    carried (so the chunk size does not change the metric)."""
    out = []
    for r in recs:
        for (ta, _), (tb, n) in zip(r.deliveries, r.deliveries[1:]):
            out += [(tb - ta) / n * 1e3] * int(n)
    return out


END_TO_END = ("prefill_tok_s", "ttft_p95_s", "itl_p50_ms", "itl_p95_ms")


def end_to_end(name: str, recs: List[Record], t0: float) -> float:
    """The end-to-end metric ``name`` (one of END_TO_END) of a window."""
    if name == "prefill_tok_s":
        return prefill_tok_s(recs, t0)
    if name == "ttft_p95_s":
        return _pct(ttft_s(recs), 95)
    if name == "itl_p50_ms":
        return statistics.median(itl_ms(recs))
    if name == "itl_p95_ms":
        return _pct(itl_ms(recs), 95)
    raise KeyError(f"no end-to-end metric named {name!r}")
