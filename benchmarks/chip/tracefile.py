"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's operations inside the traced window, the host
spans open around them, and the busy and idle time.

The window is the host span ``bench.window`` that the harness opens around
the measured work, up to the last device operation in it: the window ends
when the last request's tokens are read back, right after the device work
that made them, so a device trace that stops earlier stopped recording
(the TPU profiler keeps about 5.05 M device events: some 27 s of decoding
on one TPU v5e), and the metrics are read over the part it holds. Device planes are ``/device:TPU:<n>``; an operation is
an event on a plane line named ``XLA Ops`` (all lines of the plane where
no line has that name). Each operation keeps its name, interval and its
string stats (module, op path, long name), which ``Trace.select`` and
``names.kernel_ops`` search for the program's ``jax.named_scope`` labels
and kernels. Host spans are the events of the host plane's Python threads
(the program's ``TraceAnnotation`` spans), not its runtime threads.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"


@dataclass
class Op:
    name: str
    t0: int                  # ns
    t1: int                  # ns
    device: int = 0
    leaf: bool = True        # no other operation nested inside it
    module: str = ""         # the XLA module (jitted program) it ran in
    ev: object = field(default=None, repr=False)   # its event, for stats
    _text: Optional[str] = field(default=None, repr=False)

    @property
    def dur(self) -> int:
        return self.t1 - self.t0

    @property
    def head(self) -> str:
        """Name and module, which match most operations."""
        return f"{self.name} module={self.module}" if self.module \
            else self.name

    @property
    def text(self) -> str:
        """Name, string stats and module, for matching; the stats are read
        on first use (a trace holds millions of operations)."""
        if self._text is None:
            parts = [self.name, _stats_text(self.ev) if self.ev is not None
                     else "", f"module={self.module}" if self.module else ""]
            self._text = " ".join(x for x in parts if x)
        return self._text

    @property
    def base(self) -> str:
        """The HLO instruction's name without its number: a TPU trace
        names an operation by its HLO text, ``%fusion.37 = bf16[...] ...``."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        return re.sub(r"[.\d]+$", "", head) or head


def module_base(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return name.split("(", 1)[0].strip()


def assign_modules(ops: List[Op], mods: List[Tuple[int, int, int, str]]):
    """Give each operation the module event (device, t0, t1, name) that
    encloses it in time on its device."""
    import bisect
    by_dev: Dict[int, List[Tuple[int, int, str]]] = {}
    for d, a, b, n in mods:
        by_dev.setdefault(d, []).append((a, b, n))
    starts = {}
    for d in by_dev:
        by_dev[d].sort()
        starts[d] = [m[0] for m in by_dev[d]]
    for o in ops:
        ms = by_dev.get(o.device)
        if not ms:
            continue
        i = bisect.bisect_right(starts[o.device], o.t0) - 1
        if i >= 0 and ms[i][1] >= o.t1:
            o.module = ms[i][2]


def mark_leaves(ops: List[Op]) -> None:
    """Flag the operations that enclose another one on the same device (a
    while loop around its body's operations): sums over them would count
    the time twice."""
    stack: List[Op] = []
    for o in sorted(ops, key=lambda o: (o.device, o.t0, -o.t1)):
        while stack and (stack[-1].device != o.device
                         or stack[-1].t1 <= o.t0):
            stack.pop()
        if stack and o.t1 <= stack[-1].t1:
            stack[-1].leaf = False
        stack.append(o)


@dataclass
class Span:
    name: str
    t0: int
    t1: int


@dataclass
class Trace:
    window: Tuple[int, int]
    ops: List[Op]                       # inside the window, all devices
    spans: List[Span]                   # host spans overlapping the window
    n_devices: int = 1
    lines: Dict[str, List[str]] = field(default_factory=dict)
    modules: Dict[str, int] = field(default_factory=dict)   # name -> runs
    span_end: int = 0                   # end of the harness's window span
    _span_arr: Optional[Tuple] = field(default=None, repr=False)
    source: object = field(default=None, repr=False)  # keeps events alive

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def select(self, patterns: Sequence[str]) -> List[Op]:
        """Innermost operations whose name or stats contain any of
        ``patterns``."""
        return [o for o in self.ops
                if o.leaf and (any(p in o.head for p in patterns)
                               or any(p in o.text for p in patterns))]

    def busy_intervals(self, device: Optional[int] = None):
        ops = [o for o in self.ops if device is None or o.device == device]
        return union(sorted((o.t0, o.t1) for o in ops))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        tot = 0
        for d in range(self.n_devices):
            tot += sum(b - a for a, b in self.busy_intervals(d))
        return tot * 1e-9 / max(self.n_devices, 1)

    def idle_gaps(self, device: int = 0) -> List[Tuple[int, int]]:
        gaps = []
        t = self.window[0]
        for a, b in self.busy_intervals(device):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        return gaps

    def label(self, t: int) -> str:
        """The innermost host span open at ``t`` (the first of the
        shortest), or 'host'."""
        import numpy as np
        if not self.spans:
            return "host"
        if self._span_arr is None:
            self._span_arr = (
                np.array([s.t0 for s in self.spans], np.int64),
                np.array([s.t1 for s in self.spans], np.int64))
        a, b = self._span_arr
        open_ = (a <= t) & (t <= b)
        if not open_.any():
            return "host"
        dur = np.where(open_, b - a, np.iinfo(np.int64).max)
        return self.spans[int(np.argmin(dur))].name

    def breakdown(self, n: int = 10) -> Dict[str, List]:
        by_name: Dict[str, int] = {}
        for o in self.ops:
            if o.leaf:
                by_name[o.base] = by_name.get(o.base, 0) + o.dur
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        by_label: Dict[str, int] = {}
        longest = sorted(self.idle_gaps(0), key=lambda g: g[0] - g[1])
        for a, b in longest[:500]:
            lab = self.label((a + b) // 2)
            by_label[lab] = max(by_label.get(lab, 0), b - a)
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v * 1e-9] for k, v in top],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def union(iv: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _stats_text(ev) -> str:
    parts = []
    try:
        for k, v in ev.stats:
            if isinstance(v, str):
                parts.append(f"{k}={v}")
    except Exception:
        pass
    return " ".join(parts)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` and reduce it to the harness's window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = reduce(data.planes)
    tr.source = data
    return tr


def reduce(planes) -> Trace:
    """Reduce profiler planes (each with ``name`` and ``lines``; a line with
    ``name`` and ``events``; an event with ``name``, ``start_ns``,
    ``duration_ns`` and ``stats``) to the harness's window."""
    dev_ops: List[Op] = []
    mods: List[Tuple[int, int, int, str]] = []
    spans: List[Span] = []
    lines: Dict[str, List[str]] = {}
    devices = []
    for plane in planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            devices.append(dev)
            names = [ln.name for ln in plane.lines]
            use = ["XLA Ops"] if "XLA Ops" in names else names
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    for ev in ln.events:
                        t0 = int(ev.start_ns)
                        mods.append((dev, t0, t0 + int(ev.duration_ns),
                                     module_base(ev.name)))
                if ln.name not in use:
                    continue
                for ev in ln.events:
                    t0 = int(ev.start_ns)
                    dev_ops.append(Op(ev.name, t0, t0 + int(ev.duration_ns),
                                      dev, ev=ev))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                if ln.name.startswith("tf_"):
                    continue            # runtime threads, not the program's
                for ev in ln.events:
                    if ev.duration_ns <= 0 or ev.name.startswith("$"):
                        continue
                    t0 = int(ev.start_ns)
                    spans.append(Span(ev.name, t0, t0 + int(ev.duration_ns)))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        w = (win[-1].t0, win[-1].t1)
    elif dev_ops:
        w = (min(o.t0 for o in dev_ops), max(o.t1 for o in dev_ops))
    else:
        w = (0, 0)
    ops = []
    for o in dev_ops:
        a, b = max(o.t0, w[0]), min(o.t1, w[1])
        if b > a:
            o.t0, o.t1 = a, b
            ops.append(o)
    span_end = w[1]
    if ops:
        w = (w[0], max(o.t1 for o in ops))
    spans = [s for s in spans if s.t1 >= w[0] and s.t0 <= w[1]
             and s.name != WINDOW_SPAN]
    devs = sorted(set(devices))
    remap = {d: i for i, d in enumerate(devs)}
    mods = [(remap.get(d, 0), a, b, n) for d, a, b, n in mods
            if b > w[0] and a < w[1]]
    for o in ops:
        o.device = remap.get(o.device, 0)
    assign_modules(ops, mods)
    mark_leaves(ops)
    runs: Dict[str, int] = {}
    for _, _, _, n in mods:
        runs[n] = runs.get(n, 0) + 1
    return Trace(w, ops, spans, max(len(devs), 1), lines, runs, span_end)


def describe(tr: Trace, n: int = 40) -> str:
    """A readable summary of a reduced trace: planes and lines, and the
    longest operations with their stats (for checking the matchers)."""
    out = [f"window {tr.window_s:.3f} s (of the harness's span "
           f"{(tr.span_end - tr.window[0]) * 1e-9:.3f} s), {len(tr.ops)} "
           f"ops, {len(tr.spans)} host spans, devices {tr.n_devices}"]
    for k, v in tr.lines.items():
        out.append(f"plane {k}: lines {v[:12]}")
    out.append(f"modules (runs in the window): {tr.modules}")
    seen = set()
    for o in sorted(tr.ops, key=lambda o: -o.dur):
        if o.base in seen:
            continue
        seen.add(o.base)
        out.append(f"  {o.dur / 1e6:9.3f} ms  {o.name[:70]} | "
                   f"{o.text[len(o.name):][:600]}")
        if len(seen) >= n:
            break
    return "\n".join(out)
