"""Reduce a ``jax.profiler`` trace to device busy time, idle share,
kernel time inside host annotations, and the breakdown of device ops and
idle gaps.  Part of the benchmark's yardstick: every PR reads its trace
through this file, and no PR that claims a gain can change it.

A trace is first read into plain spans (:func:`load`), then reduced by
functions over spans, which is what the tests exercise on a small
recorded trace (``bench/tests/data``).  Times are in nanoseconds on the
profiler's clock, which host annotations and device ops share.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench/window"             # the harness's own span around the trace
STEP_ANNOTATIONS = ("repro/decode", "repro/prefill_chunk")
HOST = "host"                       # a gap under no step annotation


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                    # ns
    end: float                      # ns
    device: int = 0
    detail: str = ""                # the op's framework name, if any

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: List[Span]                 # device ops, every device
    notes: List[Span]               # host annotations (steps and window)
    devices: int

    def window(self) -> Optional[Tuple[float, float]]:
        w = [s for s in self.notes if s.name == WINDOW]
        if not w:
            return None
        return min(s.start for s in w), max(s.end for s in w)

    def to_json(self) -> dict:
        """The recorded form (``bench/tests/data``), read by
        :meth:`from_json`."""
        return {"devices": self.devices,
                "ops": [[s.name, s.start, s.end, s.device, s.detail]
                        for s in self.ops],
                "notes": [[s.name, s.start, s.end] for s in self.notes]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls([Span(n, a, b, dev, det) for n, a, b, dev, det in d["ops"]],
                   [Span(n, a, b) for n, a, b in d["notes"]], d["devices"])


def _detail(ev) -> str:
    for k, v in ev.stats:
        if k in ("tf_op", "long_name", "hlo_op") and isinstance(v, str):
            return v
    return ""


def load(log_dir: str, keep=STEP_ANNOTATIONS + (WINDOW,)) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: List[Span] = []
    notes: List[Span] = []
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not lines:
                continue
            dev = devices
            devices += 1
            for ln in lines:
                for ev in ln.events:
                    if ev.duration_ns > 0:
                        ops.append(Span(ev.name, ev.start_ns, ev.end_ns, dev,
                                        _detail(ev)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in keep:
                        notes.append(Span(ev.name, ev.start_ns, ev.end_ns))
    ops.sort(key=lambda s: s.start)
    notes.sort(key=lambda s: s.start)
    return Trace(ops, notes, devices)


def _clip(spans: Iterable[Span], lo: float, hi: float):
    for s in spans:
        a, b = max(s.start, lo), min(s.end, hi)
        if b > a:
            yield a, b


def union(spans: Iterable[Span], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Disjoint, sorted intervals covered by ``spans`` inside [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted(_clip(spans, lo, hi)):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Device busy time in [lo, hi], averaged over the devices."""
    if not tr.devices:
        return 0.0
    total = 0.0
    for d in range(tr.devices):
        total += sum(b - a for a, b in
                     union((s for s in tr.ops if s.device == d), lo, hi))
    return total / tr.devices


def idle_share(tr: Trace, lo: float, hi: float) -> Optional[float]:
    if not tr.devices or hi <= lo:
        return None
    return 1.0 - busy_ns(tr, lo, hi) / (hi - lo)


def inside(notes: Sequence[Span], name: str, t: float) -> bool:
    return any(n.name == name and n.start <= t <= n.end for n in notes)


def kernel_ns(tr: Trace, pattern: str, within: str, lo: float,
              hi: float) -> Tuple[float, int]:
    """Summed device time and count of ops whose name or framework name
    matches ``pattern`` and whose midpoint lies inside a ``within`` host
    annotation, in [lo, hi]."""
    rx = re.compile(pattern)
    spans = sorted((n for n in tr.notes if n.name == within),
                   key=lambda n: n.start)
    total, count, j = 0.0, 0, 0
    for s in tr.ops:
        if not (rx.search(s.name) or rx.search(s.detail)):
            continue
        mid = 0.5 * (s.start + s.end)
        if not lo <= mid <= hi:
            continue
        while j < len(spans) and spans[j].end < mid:
            j += 1
        if j < len(spans) and spans[j].start <= mid:
            total += s.dur
            count += 1
    return total, count


def op_label(s: Span) -> str:
    """A short label of an op.  The TPU names an op by its HLO text
    (``%fusion.140 = bf16[32,22016]{1,0:T(8,128)} fusion(...)``): the
    label keeps it without layouts or the leading ``%``, cut to 100
    characters, so one instruction of one program is one label."""
    text = re.sub(r"\{[^{}]*\}", "", s.name).lstrip("%")
    return re.sub(r"\s+", " ", text)[:100]


def self_ns(tr: Trace, lo: float, hi: float) -> List[Tuple[Span, float]]:
    """(op, self time in [lo, hi]) of every op: its own time less that
    of the ops nested in it on its device (a loop's body ops lie inside
    the loop's own event)."""
    out = []
    for d in range(tr.devices):
        ops = sorted((s for s in tr.ops if s.device == d),
                     key=lambda s: (s.start, -s.end))
        stack: List[list] = []              # [span, clipped self time]
        for s in ops:
            while stack and stack[-1][0].end <= s.start:
                out.append(tuple(stack.pop()))
            own = sum(b - a for a, b in _clip([s], lo, hi))
            if stack:
                stack[-1][1] -= own
            stack.append([s, own])
        out.extend(tuple(x) for x in stack)
    return out


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """[[op, seconds], ...]: the ``n`` ops with the most self time in
    [lo, hi], summed over their events, averaged over devices."""
    acc: Dict[str, float] = {}
    for s, t in self_ns(tr, lo, hi):
        if t > 0:
            k = op_label(s)
            acc[k] = acc.get(k, 0.0) + t
    d = max(1, tr.devices)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / d * 1e-9] for k, v in best]


def gaps(tr: Trace, lo: float, hi: float, device: int = 0) -> List[Tuple[float, float]]:
    """Idle intervals of one device in [lo, hi]."""
    cov = union((s for s in tr.ops if s.device == device), lo, hi)
    out, t = [], lo
    for a, b in cov:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(tr: Trace, a: float, b: float) -> str:
    """What the host was doing in an idle gap: the step annotation that
    covers its midpoint, else ``host`` (scheduling, the client)."""
    mid = 0.5 * (a + b)
    for name in STEP_ANNOTATIONS:
        if inside(tr.notes, name, mid):
            return name
    return HOST


def idle_breakdown(tr: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """[[label, seconds], ...]: idle time of device 0 in [lo, hi] summed
    by what the host was doing, then the longest single gaps, at most
    ``n`` entries in all."""
    gs = gaps(tr, lo, hi)
    by: Dict[str, float] = {}
    for a, b in gs:
        k = label_gap(tr, a, b)
        by[k] = by.get(k, 0.0) + (b - a)
    out = [[f"total:{k}", v * 1e-9] for k, v in
           sorted(by.items(), key=lambda kv: -kv[1])]
    longest = sorted(gs, key=lambda g: g[0] - g[1])[:max(0, n - len(out))]
    out += [[f"longest:{label_gap(tr, a, b)}", (b - a) * 1e-9]
            for a, b in longest]
    return out[:n]

