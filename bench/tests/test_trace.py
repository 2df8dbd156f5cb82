"""The trace reduction (``bench/trace.py``) on a small trace recorded on
one TPU v5e (two decode steps and a chunk step of the pallas50 cell, op
names cut to 200 characters), checked against independent arithmetic: a
sweep over every op's end points for busy time, and a plain loop for
kernel time and gap labels."""
import gzip
import json
import os
import re

import pytest

from bench import run
from bench import trace as TR

DATA = os.path.join(os.path.dirname(__file__), "data", "decode_trace.json.gz")


@pytest.fixture(scope="module")
def tr():
    with gzip.open(DATA, "rt") as f:
        return TR.Trace.from_json(json.load(f))


def _sweep_busy(ops, lo, hi):
    """Busy time by an event sweep: +1 at each start, -1 at each end."""
    ev = []
    for s in ops:
        a, b = max(s.start, lo), min(s.end, hi)
        if b > a:
            ev += [(a, 1), (b, -1)]
    ev.sort(key=lambda e: (e[0], -e[1]))
    busy, depth, last = 0.0, 0, None
    for t, d in ev:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_trace_has_what_the_harness_reads(tr):
    assert tr.devices == 1
    lo, hi = tr.window()
    assert hi > lo
    names = {n.name for n in tr.notes}
    assert "repro/decode" in names
    assert any(lo <= s.start < hi for s in tr.ops)


def test_busy_and_idle_share(tr):
    lo, hi = tr.window()
    busy = TR.busy_ns(tr, lo, hi)
    assert busy == pytest.approx(_sweep_busy(tr.ops, lo, hi), rel=1e-9)
    idle = TR.idle_share(tr, lo, hi)
    assert 0.0 <= idle < 1.0
    assert idle == pytest.approx(1 - busy / (hi - lo))
    gaps = TR.gaps(tr, lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx(hi - lo - busy,
                                                        rel=1e-9)


def test_union_of_nested_and_overlapping_spans():
    sp = [TR.Span("a", 0, 10), TR.Span("b", 2, 4), TR.Span("c", 8, 15),
          TR.Span("d", 20, 30)]
    assert TR.union(sp, 0, 25) == [(0, 15), (20, 25)]
    t = TR.Trace(sp, [TR.Span(TR.WINDOW, 0, 40)], 1)
    assert TR.busy_ns(t, 0, 40) == 25
    assert TR.gaps(t, 0, 40) == [(15, 20), (30, 40)]


def test_kernel_time_inside_annotations(tr):
    lo, hi = tr.window()
    pat = run.load_metric("sparse_mm_roofline").KERNEL
    ns, n = TR.kernel_ns(tr, pat, "repro/decode", lo, hi)
    rx = re.compile(pat)
    dec = [s for s in tr.notes if s.name == "repro/decode"]
    want = [s for s in tr.ops
            if (rx.search(s.name) or rx.search(s.detail))
            and lo <= (s.start + s.end) / 2 <= hi
            and any(d.start <= (s.start + s.end) / 2 <= d.end for d in dec)]
    # seven projections in each of six layers, in each of two steps
    assert n == len(want) == 2 * 7 * 6
    assert ns == pytest.approx(sum(s.dur for s in want))
    # none of them in the chunk step, whose rows number 256
    assert all("f32[32," in s.name for s in want)


def test_breakdown_lists(tr):
    lo, hi = tr.window()
    ops = TR.top_ops(tr, lo, hi)
    assert 0 < len(ops) <= 10
    assert all(ops[i][1] >= ops[i + 1][1] for i in range(len(ops) - 1))
    assert sum(v for _, v in ops) <= (hi - lo) * 1e-9 + 1e-12
    gaps = TR.idle_breakdown(tr, lo, hi)
    assert 0 < len(gaps) <= 10
    labels = {g[0].split(":", 1)[1] for g in gaps}
    assert labels <= set(TR.STEP_ANNOTATIONS) | {TR.HOST}
    total = sum(v for k, v in gaps if k.startswith("total:"))
    assert total == pytest.approx(
        (hi - lo - TR.busy_ns(tr, lo, hi)) * 1e-9, rel=1e-9)


def test_top_ops_count_self_time():
    """A loop's event holds its body's ops: each is counted once, the
    loop by what its body leaves."""
    sp = [TR.Span("%while.3 = (s32[]{:T(128)}) while()", 0, 10),
          TR.Span("%fusion.1 = bf16[8]{0} fusion()", 1, 3),
          TR.Span("%fusion.2 = bf16[8]{0} fusion()", 4, 8),
          TR.Span("%fusion.1 = bf16[8]{0} fusion()", 12, 14)]
    t = TR.Trace(sp, [TR.Span(TR.WINDOW, 0, 20)], 1)
    got = dict(TR.top_ops(t, 0, 20))
    assert got == pytest.approx({"while.3 = (s32[]) while()": 4e-9,
                                 "fusion.1 = bf16[8] fusion()": 4e-9,
                                 "fusion.2 = bf16[8] fusion()": 4e-9})
