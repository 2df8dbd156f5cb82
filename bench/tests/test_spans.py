"""The idle attribution (``bench/spans.py``) on the recorded v5e trace
(``bench/tests/data``) with a synthetic span store at a known offset,
checked against a plain per-piece loop: the four shares sum to the
window's idle share, GC wins where it overlaps, and a store whose
clock does not line up with the trace reads nothing."""
import gzip
import json
import os
import types

import numpy as np
import pytest

from bench import run
from bench import spans as SP
from bench import trace as TR
from repro.obs.spans import SpanRecord

DATA = os.path.join(os.path.dirname(__file__), "data", "decode_trace.json.gz")
OFF = 7_000_000_000                 # note start less record start, ns
SHARES = {"engine_host": "idle_engine_host_share",
          "dispatch": "idle_dispatch_share", "gc": "idle_gc_share",
          "client": "idle_client_share"}


@pytest.fixture(scope="module")
def tr():
    with gzip.open(DATA, "rt") as f:
        return TR.Trace.from_json(json.load(f))


class Store:
    """A span store as the program keeps one: (index, record) in index
    order, each child after its parent."""

    def __init__(self):
        self.recs = []
        self.open = []

    def add(self, name, a, b, request_id=None, **args):
        parent = -1
        for i in reversed(self.open):
            r = self.recs[i][1]
            if r.start_ns + OFF <= a and b <= r.end_ns + OFF:
                parent = i
                break
        i = len(self.recs)
        self.recs.append((i, SpanRecord(name, int(a - OFF), int(b - OFF),
                                        parent, request_id, args)))
        self.open.append(i)
        return i

    def records(self):
        return list(self.recs)


def _store(tr, skew_ns=0.0):
    """Spans around the trace's three step notes, on the store's clock
    (note time less OFF), with a collection in the first idle gap."""
    st = Store()
    notes = sorted((n for n in tr.notes if n.name in SP.ALIGN),
                   key=lambda n: n.start)
    lo, hi = tr.window()
    gap = TR.gaps(tr, lo, hi)
    first_gap = next(g for g in gap if g[1] - g[0] > 1000)
    prev_end = lo + 200
    for k, n in enumerate(notes):
        d = k * skew_ns
        s0 = max(prev_end + 100, n.start - 400)
        nxt = notes[k + 1].start if k + 1 < len(notes) else hi - 100
        s1 = min(n.end + (nxt - n.end) * 0.8, nxt - 200)
        st.add("repro/step", s0 + d, s1 + d)
        st.add("repro/admit", s0 + 10 + d, s0 + 60 + d)
        phase = n.name
        st.add(phase + "/prepare", s0 + 70 + d, n.start - 50 + d)
        st.add(phase, n.start + d, n.end + d, request_id=k)
        mid = n.start + (n.end - n.start) * 0.3
        st.add(phase + "/launch", n.start + 20 + d, mid + d)
        st.add(phase + "/readback", mid + d, n.end - 20 + d)
        tail = n.end + 50
        if phase == "repro/prefill_chunk":
            st.add(phase + "/first_token", tail + d,
                   tail + (s1 - tail) * 0.4 + d, request_id=k)
            tail = tail + (s1 - tail) * 0.5
        st.add(phase + "/emit", tail + d, s1 - 30 + d)
        a, b = first_gap
        if s0 <= a and b <= s1:
            st.add(SP.GC, a + (b - a) * 0.25 + d, a + (b - a) * 0.75 + d,
                   generation=0, collected=1)
        prev_end = s1
    return st


def _run(tr, store):
    lo, hi = tr.window()
    eng = types.SimpleNamespace(obs=types.SimpleNamespace(spans=store))
    return types.SimpleNamespace(client=types.SimpleNamespace(eng=eng),
                                 trace=tr, trace_lo=lo, trace_hi=hi)


def _by_plain_loop(tr, store):
    """Each gap cut at every span boundary; each piece to GC if a
    collection covers its middle, else to the deepest span over it."""
    recs = dict(store.records())

    def depth(i):
        n = 0
        while recs[i].parent >= 0:
            i, n = recs[i].parent, n + 1
        return n

    moved = [(r.start_ns + OFF, r.end_ns + OFF, r.name, depth(i))
             for i, r in recs.items()]
    lo, hi = tr.window()
    out = dict.fromkeys(SP.GROUPS, 0.0)
    for a, b in TR.gaps(tr, lo, hi):
        cuts = sorted({a, b} | {t for s in moved for t in s[:2]
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            m = (x + y) / 2
            over = [s for s in moved if s[0] <= m < s[1]]
            if any(s[2] == SP.GC for s in over):
                out["gc"] += y - x
                continue
            over = [s for s in over if s[2] != SP.GC]
            name = max(over, key=lambda s: s[3])[2] if over else None
            out[SP.group(name)] += y - x
    return {g: 100.0 * v / (hi - lo) for g, v in out.items()}


def test_offset_is_found_and_shares_sum_to_idle(tr):
    st = _store(tr)
    off, spread = SP.offset(st.records(), tr.notes)
    assert off == pytest.approx(OFF) and spread == pytest.approx(0.0)
    r = _run(tr, st)
    got = {g: run.load_metric(name).read(r) for g, name in SHARES.items()}
    lo, hi = tr.window()
    idle = 100.0 * (1 - TR.busy_ns(tr, lo, hi) / (hi - lo))
    assert sum(got.values()) == pytest.approx(idle, abs=1e-6)
    want = _by_plain_loop(tr, st)
    assert got == pytest.approx(want, abs=1e-6)
    # every group is there: a GC in a gap, client time between steps,
    # dispatch inside the step spans, the engine's own time around them
    assert all(v > 0 for v in got.values()), got


def test_gc_wins_where_it_overlaps(tr):
    st = _store(tr)
    gc = [r for _, r in st.records() if r.name == SP.GC]
    assert len(gc) == 1
    lo, hi = tr.window()
    covered = sum(max(0.0, min(b, gc[0].end_ns + OFF)
                      - max(a, gc[0].start_ns + OFF))
                  for a, b in TR.gaps(tr, lo, hi))
    share = run.load_metric("idle_gc_share").read(_run(tr, st))
    assert share == pytest.approx(100.0 * covered / (hi - lo))


def test_a_skewed_store_reads_nothing(tr):
    st = _store(tr, skew_ns=150_000.0)      # drifts 150 us per step
    off, spread = SP.offset(st.records(), tr.notes)
    assert off is None and spread > SP.MAX_SPREAD_NS
    r = _run(tr, st)
    assert all(run.load_metric(n).read(r) is None for n in SHARES.values())


def _step_pairs(stalls=(), n=140, lead=60, step_ns=41_800_000.0):
    """A store of ``lead + n`` step spans (a chunk step one in five) and
    the trace's notes of the last ``n``, each ``OFF`` plus 1-8 us after
    its record; the notes at ``stalls`` (index: ns) start that much later,
    as where the host stood still between the clock read and the note."""
    rng = np.random.default_rng(0)
    records, notes = [], []
    for i in range(lead + n):
        name = "repro/prefill_chunk" if i % 5 == 0 else "repro/decode"
        a = i * step_ns + float(rng.uniform(0, 3_000_000))
        records.append((i, SpanRecord(name, int(a), int(a + step_ns / 2),
                                      -1, None, {})))
        if i >= lead:
            j = i - lead
            late = float(rng.uniform(1_000, 8_000)) + stalls.get(j, 0)
            notes.append(TR.Span(name, a + OFF + late, a + OFF + step_ns / 2))
    return records, notes


@pytest.mark.parametrize("stalls", [
    {}, {70: 5_000_000}, {3: 180_000, 101: 100_000_000}],
    ids=["none", "one-stall", "two-stalls"])
def test_a_stalled_pair_leaves_the_offset(stalls):
    records, notes = _step_pairs(stalls)
    off, spread = SP.offset(records, notes)
    # the median of the pairs, 1-8 us after OFF, wherever the stalls lie
    assert off is not None and 1_000 <= off - OFF <= 8_000
    assert spread < 8_000


def test_a_pairing_that_strays_reads_nothing():
    # a fifth of the notes stray by 0.2-3 ms: no clock offset fits them
    records, notes = _step_pairs({j: 200_000 + j * 20_000
                                  for j in range(0, 140, 5)})
    off, spread = SP.offset(records, notes)
    assert off is None and spread > SP.MAX_SPREAD_NS


def test_a_program_without_a_store_reads_nothing(tr):
    lo, hi = tr.window()
    eng = types.SimpleNamespace(obs=types.SimpleNamespace())
    r = types.SimpleNamespace(client=types.SimpleNamespace(eng=eng),
                              trace=tr, trace_lo=lo, trace_hi=hi)
    assert all(run.load_metric(n).read(r) is None for n in SHARES.values())
    # the recorded trace's kernels are anonymous: no score/mask time
    assert run.load_metric("score_mask_ms").read(r) is None


def test_groups_of_span_names():
    assert SP.group(None) == "client"
    assert SP.group(SP.GC) == "gc"
    for n in ("repro/decode", "repro/prefill_chunk/launch",
              "repro/decode/readback", "repro/prefill_chunk/first_token",
              "repro/spec_verify/launch"):
        assert SP.group(n) == "dispatch", n
    for n in ("repro/step", "repro/admit", "repro/decode/prepare",
              "repro/prefill_chunk/emit", "repro/prefix_write"):
        assert SP.group(n) == "engine_host", n
