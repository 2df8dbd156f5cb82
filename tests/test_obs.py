"""repro.obs telemetry: instrument semantics, exposition/trace-schema
validity, null-path zero-cost guarantees, clock discipline, and the
engine-level contracts — bit-identical tokens with telemetry on vs off,
retrace-free dispatch annotations, and event-log attribution for forced
rung switches, spec rollbacks and prefix evictions."""
import json
import math
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.data import DataConfig, SyntheticLM
from repro.models import api
from repro.obs import (NULL_CONTEXT, NULL_TELEMETRY, EventLog, Histogram,
                       MetricsRegistry, SpanTracer, Telemetry, log_buckets,
                       parse_exposition, serve_metrics,
                       validate_chrome_trace, validate_exposition)
from repro.serving import Engine, EngineConfig, SLOConfig, SpecConfig
from repro.serving.metrics import EngineStats, RingBuffer, percentile
from repro.sparsity import PolicyLadder


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    return params, cfg


@pytest.fixture(scope="module")
def ladder(model):
    params, cfg = model
    return PolicyLadder.uniform(params, cfg, (0.0, 0.5))


def _prompts(cfg, n, seq, step=0):
    return np.asarray(SyntheticLM(
        DataConfig(cfg.vocab_size, seq, n)).batch(step))


def _engine(params, cfg, sp=None, telemetry=None, ladder=None, **kw):
    defaults = dict(max_slots=4, max_len=32, prefill_chunk=8)
    defaults.update(kw)
    return Engine(params, cfg, EngineConfig(**defaults), sp,
                  ladder=ladder, telemetry=telemetry)


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

def test_histogram_exact_whole_run():
    h = Histogram()
    assert h.count == 0 and math.isnan(h.quantile(50))
    for v in (1e-4, 1e-3, 1e-2, 1e-2, 10.0, 100.0):
        h.observe(v)
    assert h.count == 6
    assert h.sum == pytest.approx(110.0211)
    assert h.cumulative()[-1] == h.count
    # 100.0 overflows the 10s top bound into the +Inf slot
    assert h.counts[-1] == 1
    # quantile reports the selected bucket's upper bound, clamped to the
    # last finite bound for overflow
    assert h.quantile(100) == h.bounds[-1]
    assert h.quantile(0) >= 1e-4

    with pytest.raises(ValueError, match="increasing"):
        Histogram((1.0, 1.0))
    with pytest.raises(ValueError, match="increasing"):
        Histogram(())


def test_histogram_unit_buckets_exact():
    """Unit-width integer buckets (the accepted-per-verify layout) make
    nearest-rank quantiles exact, not just bucket-resolved."""
    h = Histogram(tuple(float(i) for i in range(9)))
    data = [0, 1, 1, 2, 2, 2, 3, 5, 8]
    for v in data:
        h.observe(v)
    for p in (0, 25, 50, 75, 95, 100):
        assert h.quantile(p) == percentile(data, p)


def test_histogram_never_windows():
    """A ring percentile silently becomes windowed past capacity; the
    histogram keeps the whole run."""
    ring = RingBuffer(capacity=16)
    hist = Histogram()
    for v in [5.0] * 100 + [1e-4] * 16:     # old mass: 5s, recent: 100us
        ring.append(v)
        hist.observe(v)
    assert percentile(ring, 95) == pytest.approx(1e-4)   # window-blind
    assert hist.quantile(95) >= 5.0                      # whole-run
    assert hist.count == 116 and len(ring) == 16


def test_log_buckets_and_counter_gauge():
    bs = log_buckets(1e-3, 1.0, per_decade=3)
    assert bs[0] == pytest.approx(1e-3) and bs[-1] == pytest.approx(1.0)
    assert list(bs) == sorted(bs) and len(bs) == 10
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc(); c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7); g.set(-2)
    assert g.value == -2
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")


def test_registry_render_roundtrip():
    reg = MetricsRegistry()
    reg.counter("x_total", "a counter").inc(3)
    reg.gauge("y").set(1.5)
    h = reg.histogram("z_seconds", bounds=(0.1, 1.0))
    h.observe(0.05); h.observe(0.5); h.observe(99.0)
    text = reg.render()
    assert validate_exposition(text) > 0
    types, samples = parse_exposition(text)
    assert types == {"x_total": "counter", "y": "gauge",
                     "z_seconds": "histogram"}
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["x_total"] == [({}, 3.0)]
    assert by_name["z_seconds_count"] == [({}, 3.0)]
    les = {ls["le"]: v for ls, v in by_name["z_seconds_bucket"]}
    assert les == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}   # cumulative


def test_validate_exposition_rejects_malformed():
    with pytest.raises(ValueError, match="no samples"):
        validate_exposition("")
    with pytest.raises(ValueError, match="TYPE"):
        validate_exposition("orphan 1\n")
    bad_hist = ("# TYPE h histogram\n"
                'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
                "h_sum 1\nh_count 3\n")
    with pytest.raises(ValueError, match="not monotone"):
        validate_exposition(bad_hist)
    missing_inf = ("# TYPE h histogram\n"
                   'h_bucket{le="1"} 1\nh_sum 1\nh_count 1\n')
    with pytest.raises(ValueError, match=r"\+Inf"):
        validate_exposition(missing_inf)


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------

def test_clock_monotonic_and_wall():
    a, b = obs.now(), obs.now()
    assert b >= a
    import time
    assert abs(obs.to_wall(obs.now()) - time.time()) < 1.0


# ---------------------------------------------------------------------------
# tracer / event log
# ---------------------------------------------------------------------------

def test_tracer_schema_and_thread_names():
    tr = SpanTracer()
    t0 = obs.now()
    tr.thread_name(3, "req 2")
    tr.thread_name(3, "renamed")            # first name wins, no dup M
    tr.complete("decode_step", t0, t0 + 1e-3, active=2, rung=1)
    tr.instant("finish", tid=3, reason="eos")
    tr.counter("engine_load", queue_depth=4, occupancy=2)
    doc = tr.to_dict()
    assert validate_chrome_trace(doc) == len(tr)
    names = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(names) == 2                  # engine tid 0 + tid 3, once
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert span["dur"] == pytest.approx(1e3)     # us
    assert span["args"] == {"active": 2, "rung": 1}
    # exported file parses back through the same validator
    json.dumps(doc)


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({})
    with pytest.raises(ValueError, match="non-empty"):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError, match="bad phase"):
        validate_chrome_trace({"traceEvents": [
            {"ph": "?", "name": "x", "pid": 1, "tid": 0, "ts": 0}]})
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 0,
             "dur": -1}]})


def test_event_log_ring_sink_and_filter(tmp_path):
    sink = tmp_path / "events.jsonl"
    with EventLog(capacity=4, sink=str(sink)) as ev:
        for i in range(10):
            ev.emit("tick", i=i)
        ev.emit("rung_switch", from_rung=0, to_rung=1, reason="tpot")
    assert ev.count == 11 and len(ev) == 4          # ring kept the tail
    assert [e["i"] for e in ev.events("tick")] == [7, 8, 9]
    sw = ev.events("rung_switch")[0]
    assert sw["reason"] == "tpot" and "t" in sw
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert len(lines) == 11                         # sink got everything
    assert lines[-1]["kind"] == "rung_switch"


def test_event_log_sink_rotation_preserves_ring(tmp_path):
    """A byte-capped sink rotates to <path>.1 instead of growing without
    bound — and rotation must never drop events from the in-memory ring
    view (the ring is capacity-bounded, not byte-bounded)."""
    sink = tmp_path / "events.jsonl"
    with EventLog(capacity=64, sink=str(sink), max_sink_bytes=512) as ev:
        for i in range(40):
            ev.emit("tick", i=i)
        assert ev.sink_rotations >= 1
        assert sink.stat().st_size <= 512
        rotated = tmp_path / "events.jsonl.1"
        assert rotated.exists()
        # the live file + the rotation hold a contiguous tail of events
        recent = [json.loads(ln)["i"]
                  for ln in rotated.read_text().splitlines()
                  + sink.read_text().splitlines()]
        assert recent == list(range(40 - len(recent), 40))
        # the ring view is untouched by rotation: all 40, in order
        assert [e["i"] for e in ev.events("tick")] == list(range(40))
        assert ev.count == 40

    # rotation needs a log-owned path sink — a file handle can't be
    # renamed out from under its owner
    with pytest.raises(ValueError, match="path sink"):
        EventLog(sink=open(tmp_path / "h.jsonl", "w"), max_sink_bytes=10)
    with pytest.raises(ValueError, match=">= 0"):
        EventLog(sink=str(sink), max_sink_bytes=-1)


def test_event_log_rotation_boundary_exact(tmp_path):
    """Rotation happens strictly *before* the write that would overflow
    the budget: no line is ever split across the rotation, a write that
    lands exactly at the cap does not rotate, and every event appears
    exactly once across <path>.1 + <path>."""
    sink = tmp_path / "events.jsonl"
    # fixed-width payloads (explicit t) make every line the same length
    with EventLog(capacity=256, sink=str(sink)) as probe:
        probe.emit("e", t=0.0, i="0000")
    line_len = len((tmp_path / "events.jsonl").read_bytes())

    sink = tmp_path / "boundary.jsonl"
    rotated = tmp_path / "boundary.jsonl.1"
    with EventLog(capacity=256, sink=str(sink),
                  max_sink_bytes=3 * line_len) as ev:
        for i in range(3):                      # fills the file exactly
            ev.emit("e", t=0.0, i=f"{i:04d}")
        assert ev.sink_rotations == 0           # at the cap, not over it
        assert sink.stat().st_size == 3 * line_len
        ev.emit("e", t=0.0, i="0003")           # would overflow: rotates
        assert ev.sink_rotations == 1
        assert rotated.stat().st_size == 3 * line_len
        assert sink.stat().st_size == line_len  # whole line, new file
        for i in range(4, 9):                   # drive a second rotation
            ev.emit("e", t=0.0, i=f"{i:04d}")
        assert ev.sink_rotations == 2

    # both files parse end to end; the union is a contiguous, duplicate-
    # free tail (earlier history was dropped with the replaced .1 —
    # the documented disk budget, never a torn or double-written line)
    tail = [json.loads(ln)["i"] for ln in
            rotated.read_text().splitlines() + sink.read_text().splitlines()]
    assert tail == [f"{i:04d}" for i in range(9 - len(tail), 9)]
    assert len(set(tail)) == len(tail)
    # the ring still holds everything, unaffected by disk rotation
    assert [e["i"] for e in ev.events("e")] == [f"{i:04d}" for i in range(9)]


def test_event_log_oversized_line_still_recorded(tmp_path):
    """A single event bigger than the whole byte budget is still
    written intact (rotated onto a fresh file that then exceeds the
    cap) — bounding disk truncates history (older lines leave with the
    replaced ``.1``), never an individual line."""
    sink = tmp_path / "big.jsonl"
    with EventLog(capacity=8, sink=str(sink), max_sink_bytes=64) as ev:
        ev.emit("small", t=0.0, i=0)
        ev.emit("big", t=0.0, blob="x" * 300)   # rotates, then overflows
        ev.emit("small", t=0.0, i=1)            # rotates the big line out
    assert ev.sink_rotations == 2
    lines = [json.loads(ln) for ln in
             (tmp_path / "big.jsonl.1").read_text().splitlines()
             + sink.read_text().splitlines()]
    # the disk holds a contiguous tail with the oversized line whole
    assert [e["kind"] for e in lines] == ["big", "small"]
    assert len(lines[0]["blob"]) == 300 and lines[1]["i"] == 1
    # the ring saw everything regardless
    assert [e["kind"] for e in ev.events()] == ["small", "big", "small"]


# ---------------------------------------------------------------------------
# null path
# ---------------------------------------------------------------------------

def test_null_telemetry_is_allocation_free():
    assert not NULL_TELEMETRY.enabled
    assert NULL_TELEMETRY.tracer is None and NULL_TELEMETRY.events is None
    # annotate returns the one shared reusable null context, not a fresh
    # object per call — the hot path allocates nothing when disabled
    assert NULL_TELEMETRY.annotate("repro/decode") is NULL_CONTEXT
    assert NULL_TELEMETRY.annotate("x") is NULL_TELEMETRY.annotate("y")
    with NULL_TELEMETRY.annotate("a"), NULL_TELEMETRY.annotate("b"):
        pass                                    # reentrant
    NULL_TELEMETRY.close()                      # harmless


def test_engine_defaults_to_null_telemetry(model):
    params, cfg = model
    eng = _engine(params, cfg)
    assert eng.obs is NULL_TELEMETRY
    with pytest.raises(TypeError, match="Telemetry"):
        Engine(params, cfg, EngineConfig(max_slots=2, max_len=32,
                                         prefill_chunk=8),
               telemetry="yes")


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_telemetry_parity_and_artifacts(model, tmp_path):
    """Full telemetry changes no tokens, keeps annotated decode
    retrace-free, and produces valid exposition + trace artifacts."""
    params, cfg = model
    prompts = [_prompts(cfg, 1, n)[0] for n in (9, 17, 5, 13)]

    def run(tel):
        eng = _engine(params, cfg, telemetry=tel)
        eng.warmup()
        for p in prompts:
            eng.submit(p, 8)
        return eng, eng.run()

    e0, out0 = run(None)
    tel = Telemetry.full(events_sink=str(tmp_path / "events.jsonl"))
    e1, out1 = run(tel)
    assert out1 == out0, "telemetry must only observe"
    assert e1.decode_retraces_after_warmup == 0

    # exposition: validates, and counters match the engine's stats
    text = e1.metrics_exposition()
    assert validate_exposition(text) > 0
    _, samples = parse_exposition(text)
    flat = {n: v for n, ls, v in samples if not ls}
    assert flat["repro_requests_finished_total"] == e1.stats.finished
    assert flat["repro_decode_tokens_total"] == e1.stats.decode_tokens
    assert flat["repro_tpot_seconds_count"] == e1.stats.tpot_hist.count
    assert flat["repro_decode_retraces_after_warmup_total"] == 0

    # trace: schema-valid, per-request lifecycle present on its track
    # (closed first: the garbage collector's spans stop with the hook)
    tel.close()
    path = tmp_path / "trace.json"
    tel.tracer.export(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == len(tel.tracer)
    assert doc["otherData"]["mono_origin_ns"] == tel.tracer.origin_ns
    for rid in range(len(prompts)):
        kinds = [e["name"] for e in doc["traceEvents"]
                 if e.get("tid") == rid + 1 and e["ph"] in ("i", "X")]
        assert kinds[0] == "submit" and "finish" in kinds
        assert "repro/prefill_chunk" in kinds and "first_token" in kinds
    assert any(e["name"] == "repro/decode" and e["ph"] == "X"
               and e["tid"] == 0 for e in doc["traceEvents"])

    # snapshot v4+ fields (v5 added the admission/preemption block,
    # v6 the quality-probe block, v7 the flight block — both absent
    # here: no QualityMonitor or FlightRecorder armed)
    snap = e1.snapshot()
    assert snap["schema_version"] == 7
    assert "quality_probes" not in snap
    assert "flight_records" not in snap
    assert snap["telemetry_spans"] == len(tel.tracer)
    assert snap["tpot_p95_s"] >= snap["tpot_p50_s"]
    assert "tpot_p95_window_s" in snap


def test_summary_reports_both_estimators(model):
    s = EngineStats()
    for v in (0.01, 0.02, 0.03):
        s.observe_tpot(v)
    out = s.summary()
    assert out["tpot_p95_s"] == pytest.approx(
        s.tpot_hist.quantile(95), rel=1e-3)
    assert out["tpot_p95_window_s"] == pytest.approx(
        percentile(s.tpot_s, 95), rel=1e-3)
    assert s.tpot_percentile(95) == s.tpot_hist.quantile(95)


def test_forced_rung_switch_lands_in_event_log(model, ladder):
    """An unmeetable SLO forces escalation; the event log records the
    switch with the controller's reason."""
    params, cfg = model
    tel = Telemetry(events=EventLog())
    eng = Engine(params, cfg, EngineConfig(
        max_slots=2, max_len=32, prefill_chunk=8,
        slo=SLOConfig(tpot_p95=1e-9, dwell=1)), ladder=ladder,
        telemetry=tel)
    eng.warmup()
    eng.submit(_prompts(cfg, 1, 9)[0], 12)
    eng.run()
    switches = tel.events.events("rung_switch")
    assert switches, "unmeetable SLO never escalated"
    sw = switches[0]
    assert sw["from_rung"] == 0 and sw["to_rung"] == 1
    assert sw["reason"] == "tpot"
    assert eng.controller.snapshot()["tpot_estimator"] == "ewma"
    assert eng.decode_retraces_after_warmup == 0
    # compile events recorded during warmup, none flagged post-warmup
    compiles = tel.events.events("compile")
    assert compiles and all(not c["post_warmup"] for c in compiles)


def test_spec_rollback_lands_in_event_log(model, ladder):
    """Force every draft to disagree with the verifier (shifted draft
    logits), so each spec round must roll back drafted KV — and the
    event log must record it with slot/request attribution."""
    import jax.numpy as jnp

    params, cfg = model
    tel = Telemetry(events=EventLog(), tracer=SpanTracer())
    eng = Engine(params, cfg, EngineConfig(
        max_slots=2, max_len=32, prefill_chunk=8,
        spec=SpecConfig(gamma=2, drafter_rung=1)), ladder=ladder,
        telemetry=tel)
    # drafting routes through eng._dstep; verify uses its own executable,
    # so rolling the draft logits breaks only the drafts (argmax + 1 mod
    # vocab never matches the verifier) — acceptance is exactly zero
    real_dstep = eng._dstep

    def shifted(params, tokens, positions, caches, sp, weights, *, policy):
        logits, caches = real_dstep(params, tokens, positions, caches,
                                    sp, weights, policy=policy)
        return jnp.roll(logits, 1, axis=-1), caches

    eng._dstep = shifted
    eng.submit(_prompts(cfg, 1, 9)[0], 10)
    out = eng.run()
    rb = tel.events.events("kv_rollback")
    assert rb, "zero acceptance produced no rollback events"
    ev = rb[0]
    assert ev["slot"] == 0 and ev["request"] == 0 and ev["gamma"] == 2
    assert ev["accepted"] == 0 and ev["committed"] == 1
    assert ev["tokens"] == ev["gamma"] + 1 - ev["committed"] == 2
    assert len(out[0]) == 10
    # spec phases land as engine-track spans
    names = {e["name"] for e in tel.tracer.to_dict()["traceEvents"]
             if e["ph"] == "X"}
    assert {"repro/spec_draft", "repro/spec_verify",
            "repro/spec_rollback"} <= names


def test_prefix_eviction_lands_in_event_log(model):
    """A tiny cached-token budget forces LRU eviction on publish; the
    event carries segment accounting."""
    params, cfg = model
    tel = Telemetry(events=EventLog(), tracer=SpanTracer())
    eng = Engine(params, cfg, EngineConfig(
        max_slots=2, max_len=32, prefill_chunk=8, prefix_cache=True,
        prefix_cache_tokens=16), telemetry=tel)
    for step in (0, 1):                     # two unrelated prompts
        eng.submit(_prompts(cfg, 1, 16, step=step)[0], 4)
        eng.run()
    evs = tel.events.events("prefix_evict")
    assert evs, "over-budget publishes never evicted"
    assert evs[0]["segments"] >= 1
    assert 8 <= sum(e["tokens"] for e in evs) <= 16
    assert evs[0]["cached_tokens"] <= 16
    assert evs[0]["trigger_request"] == 1
    # the admission consult is traced whether it hits or misses
    lookups = [e for e in tel.tracer.events
               if e.get("name") == "prefix_lookup"]
    assert lookups and lookups[0]["args"]["hit"] is False


def test_metrics_http_endpoint(model):
    params, cfg = model
    eng = _engine(params, cfg)
    eng.submit(_prompts(cfg, 1, 9)[0], 4)
    eng.run()
    server = serve_metrics(eng.metrics_exposition, port=0)
    try:
        url = f"http://127.0.0.1:{server.server_port}/metrics"
        with urllib.request.urlopen(url) as resp:
            assert resp.status == 200
            assert "0.0.4" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert validate_exposition(body) > 0
        assert "repro_decode_tokens_total" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.server_port}/nope")
    finally:
        server.shutdown()
