"""The program's spans (``repro.obs.spans``): off, they cost nothing and
hook nothing; armed, every boundary of an engine step is a span nested
as the engine runs it, the garbage collector's runs are spans, tokens
and flight replays are unchanged, and a profiler capture's
``repro/clock`` mark lays the store and a Chrome export over the
capture."""
import gc
import glob
import os
import statistics
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.data import DataConfig, SyntheticLM
from repro.models import api
from repro.obs import NULL_CONTEXT, NULL_TELEMETRY, Telemetry
from repro.obs.flight import FlightRecorder
from repro.obs.flight import replay as flight_replay
from repro.obs.spans import GC_SPAN, SpanStore
from repro.serving import Engine, EngineConfig


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    return params, cfg


def _prompts(cfg, n, seq, step=0):
    return np.asarray(SyntheticLM(
        DataConfig(cfg.vocab_size, seq, n)).batch(step))


def _ecfg():
    return EngineConfig(max_slots=2, max_len=48, prefill_chunk=8)


def _serve(model, telemetry=None, on_token=None, clock=None):
    params, cfg = model
    eng = Engine(params, cfg, _ecfg(), telemetry=telemetry, clock=clock)
    eng.warmup()
    for i, n in enumerate((9, 17, 5)):
        eng.submit(_prompts(cfg, 1, n, step=i)[0], 6, on_token=on_token)
    return eng, eng.run()


def _by_index(tel):
    return dict(tel.spans.records())


# ---------------------------------------------------------------------------
# off
# ---------------------------------------------------------------------------

def test_off_path_has_no_store_and_no_hook(model):
    hooks = list(gc.callbacks)          # a dead store's hook may leave it
    tel = Telemetry()
    assert tel.spans is None and NULL_TELEMETRY.spans is None
    assert tel.annotate("repro/decode") is NULL_CONTEXT
    assert tel.annotate("repro/prefill_chunk/first_token", 3,
                        tokens=2) is NULL_CONTEXT
    eng, _ = _serve(model)
    assert eng.obs is NULL_TELEMETRY and eng.obs.spans is None
    assert [h for h in gc.callbacks if h not in hooks] == []
    # the off path keeps nothing: a thousand calls leave no block behind
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            with NULL_TELEMETRY.annotate("repro/step"):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [d for d in after.compare_to(before, "filename")
             if d.traceback[0].filename.endswith(("obs/__init__.py",
                                                  "obs/spans.py"))
             and d.size_diff > 0]
    assert not grown, grown


# ---------------------------------------------------------------------------
# armed
# ---------------------------------------------------------------------------

PARENTS = {
    "repro/step": None,
    "repro/admit": "repro/step",
    "repro/decode/prepare": "repro/step",
    "repro/decode": "repro/step",
    "repro/decode/launch": "repro/decode",
    "repro/decode/readback": "repro/decode",
    "repro/decode/emit": "repro/step",
    "repro/prefill_chunk/prepare": "repro/step",
    "repro/prefill_chunk": "repro/step",
    "repro/prefill_chunk/launch": "repro/prefill_chunk",
    "repro/prefill_chunk/readback": "repro/prefill_chunk",
    "repro/prefill_chunk/first_token": "repro/step",
    "repro/prefill_chunk/emit": "repro/step",
}


def test_armed_spans_nest_as_the_step_runs(model):
    forced = []                         # (index before, collected, after)

    def on_token(rid, tok):
        if not forced:                  # one forced collection, in a step
            before = tel.spans.opened
            n = gc.collect()
            forced.append((before, n, tel.spans.opened))

    tel = Telemetry(annotate_dispatch=True)
    hook = tel.spans._gc_hook
    assert hook in gc.callbacks
    eng, out = _serve(model, tel, on_token=on_token)
    assert all(len(t) == 6 for t in out.values())
    recs = _by_index(tel)
    names = {r.name for r in recs.values()}
    assert set(PARENTS) <= names
    for i, r in recs.items():
        assert r.end_ns >= r.start_ns
        if r.name in PARENTS:
            want = PARENTS[r.name]
            if want is None:
                assert r.parent == -1
                continue
            p = recs[r.parent]
            assert p.name == want, (r.name, p.name)
            assert p.start_ns <= r.start_ns and r.end_ns <= p.end_ns
            assert r.parent < i             # indices open in order
    steps = [r for r in recs.values() if r.name == "repro/step"]
    assert len(steps) == eng.stats.decode_steps + eng.stats.prefill_chunks
    # the chunk's spans carry the request, its first token too
    firsts = [r for r in recs.values()
              if r.name == "repro/prefill_chunk/first_token"]
    assert sorted(r.request_id for r in firsts) == [0, 1, 2]
    dec = [r for r in recs.values() if r.name == "repro/decode"]
    assert all(r.args["active"] >= 1 and r.args["rung"] == 0 for r in dec)
    # the forced collection, inside the emit that ran the callback (other
    # collections, of any generation, may run at any time)
    before, n, after = forced[0]
    mine = [r for i, r in recs.items() if before <= i < after
            and r.name == GC_SPAN and r.args["generation"] == 2]
    assert len(mine) == 1 and mine[0].args["collected"] == n
    assert recs[mine[0].parent].name.endswith("/emit")
    tel.close()
    assert hook not in gc.callbacks and tel.spans._gc_hook is None
    n = tel.spans.opened
    gc.collect()
    assert tel.spans.opened == n            # unhooked: no more GC spans


def test_tokens_and_retraces_unchanged_with_spans(model):
    e0, out0 = _serve(model)
    tel = Telemetry(annotate_dispatch=True)
    e1, out1 = _serve(model, tel)
    tel.close()
    assert out1 == out0
    assert e1.decode_retraces_after_warmup == 0
    assert e1.chunk_retraces_after_warmup == 0
    assert len(tel.spans) > 0


def test_flight_dump_with_spans_replays(model, tmp_path):
    """Spans read the clock out of band: a recording made with them on
    replays with no divergence."""
    params, cfg = model
    sink = str(tmp_path / "spans.jsonl")
    fr = FlightRecorder(sink=sink)
    tel = Telemetry(annotate_dispatch=True, flight=fr)
    with Engine(params, cfg, _ecfg(), telemetry=tel) as eng:
        for i, n in enumerate((9, 17, 5)):
            eng.submit(_prompts(cfg, 1, n, step=i)[0], 6)
        eng.run()
    assert len(tel.spans) > 0
    report = flight_replay.replay(
        sink, engine_factory=lambda clock, telemetry: Engine(
            params, cfg, _ecfg(), telemetry=Telemetry(
                annotate_dispatch=True, flight=telemetry.flight),
            clock=clock))
    assert report.ok, report.failures
    assert report.divergence is None
    assert all(v == 0 for v in report.retraces.values()), report.retraces


def test_tracer_alone_exports_the_store(model):
    """A tracer arms the store without annotations; its export holds
    the step spans on the engine track and the chunks on the
    request's."""
    tel = Telemetry(tracer=obs.SpanTracer())
    assert tel.spans is not None and not tel.spans.annotate
    _serve(model, tel)
    tel.close()
    doc = tel.tracer.to_dict()
    assert obs.validate_chrome_trace(doc) == len(tel.tracer)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs if e["tid"] == 0} >= {
        "repro/step", "repro/decode", "repro/decode/emit"}
    chunk = {e["tid"] for e in xs if e["name"] == "repro/prefill_chunk"}
    assert chunk == {1, 2, 3}               # request id + 1


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

def test_store_ring_keeps_the_newest_and_counts_drops():
    st = SpanStore(capacity=4, annotate=False)
    with st.span("outer"):
        for i in range(6):
            with st.span("inner", i, {"i": i}):
                pass
    recs = list(st.records())
    assert st.opened == 7 and st.dropped == 3
    assert [i for i, _ in recs] == [3, 4, 5, 6]
    assert [r.args["i"] for _, r in recs] == [2, 3, 4, 5]
    assert all(r.parent == 0 and r.request_id == r.args["i"]
               for _, r in recs)
    with pytest.raises(ValueError):
        SpanStore(capacity=0)


def test_store_hook_leaves_with_the_store():
    st = SpanStore(annotate=False)
    st.watch_gc()
    hook = st._gc_hook
    assert hook in gc.callbacks
    del st
    gc.collect()
    assert hook not in gc.callbacks


# ---------------------------------------------------------------------------
# one clock for operators
# ---------------------------------------------------------------------------

def test_profiler_clock_mark_lays_store_and_export_over_capture(tmp_path):
    """The capture's clock starts at the capture; the monotonic clock
    reads far beyond.  A stamp pair differs by microseconds, more where
    the thread is descheduled between a clock read and its annotation
    on a loaded machine."""
    from jax.profiler import ProfileData
    tol_ns = 2_000_000

    tel = Telemetry(tracer=obs.SpanTracer(), annotate_dispatch=True,
                    profiler=obs.ProfilerSession(str(tmp_path)))
    tel.profiler.start()
    for i in range(20):
        with tel.annotate("repro/decode", active=i):
            with tel.annotate("repro/decode/launch"):
                sum(range(2000))
    tel.close()                             # stops the capture last
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(files[-1])
    evs = [e for p in pd.planes if p.name.startswith("/host:")
           for ln in p.lines for e in ln.events]
    mark = [e for e in evs if e.name == "repro/clock"]
    assert len(mark) == 1
    mono = dict(mark[0].stats)["mono_ns"]
    clock_off = mark[0].start_ns - mono
    notes = sorted((e for e in evs if e.name == "repro/decode"),
                   key=lambda e: e.start_ns)
    assert len(notes) == 20
    assert [dict(e.stats)["active"] for e in notes] == list(range(20))
    recs = [r for _, r in tel.spans.records() if r.name == "repro/decode"]
    pair_off = statistics.median(n.start_ns - r.start_ns
                                 for n, r in zip(notes, recs))
    assert abs(pair_off - clock_off) < tol_ns
    # a Chrome event's ts, through the export's origin, lands on its note
    doc = tel.tracer.to_dict()
    origin = doc["otherData"]["mono_origin_ns"]
    xs = sorted((e for e in doc["traceEvents"]
                 if e["name"] == "repro/decode"), key=lambda e: e["ts"])
    for e, n in zip(xs, notes):
        at = origin + e["ts"] * 1e3 + clock_off
        assert abs(at - n.start_ns) < tol_ns
