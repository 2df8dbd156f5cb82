"""Gateway HTTP/SSE front door, engine lifecycle (close / reset_ids /
context manager), and serve-CLI flag validation."""
import dataclasses
import http.client
import json
import socket
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced, serving_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.serve import build_parser, validate_args, validate_rungs
from repro.models import api
from repro.serving import Engine, EngineConfig, SchedulerConfig
from repro.serving.gateway import Gateway


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    return params, cfg


def _prompts(cfg, n, seq, step=0):
    return np.asarray(SyntheticLM(
        DataConfig(cfg.vocab_size, seq, n)).batch(step))


def _engine(params, cfg, **kw):
    defaults = dict(max_slots=2, max_len=32, prefill_chunk=8)
    defaults.update(kw)
    return Engine(params, cfg, EngineConfig(**defaults), None)


@pytest.fixture(scope="module")
def gateway(model):
    params, cfg = model
    eng = _engine(params, cfg,
                  scheduler=SchedulerConfig(max_queue=8, preemption=True))
    gw = Gateway(eng, port=0)
    port = gw.start()
    yield gw, eng, port
    gw.stop()


def _request(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request(method, path,
                     body=json.dumps(body) if body is not None else None,
                     headers={"Content-Type": "application/json"}
                     if body is not None else {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------

def test_health(gateway):
    _, _, port = gateway
    status, _, body = _request(port, "GET", "/v1/health")
    assert status == 200
    health = json.loads(body)
    assert health["status"] == "ok"
    assert {"queue_depth", "occupancy", "suspended", "rung"} <= set(health)


def test_generate_non_streaming(gateway, model):
    _, eng, port = gateway
    _, cfg = model
    prompt = [int(t) for t in _prompts(cfg, 1, 10)[0]]
    status, _, body = _request(port, "POST", "/v1/generate", {
        "prompt": prompt, "max_new_tokens": 5, "priority": "interactive"})
    assert status == 200
    out = json.loads(body)
    assert len(out["tokens"]) == 5
    assert out["finish_reason"] == "max_tokens"
    assert out["usage"] == {"prompt_tokens": 10, "completion_tokens": 5}


def test_generate_streaming_sse_framing(gateway, model):
    """Raw-socket SSE request: chunked transfer framing, one event per
    token, a done event carrying usage, then the [DONE] sentinel."""
    _, _, port = gateway
    _, cfg = model
    prompt = [int(t) for t in _prompts(cfg, 1, 8, step=3)[0]]
    payload = json.dumps({"prompt": prompt, "max_new_tokens": 3,
                          "stream": True}).encode()
    req = (b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
           b"Content-Type: application/json\r\n"
           b"Content-Length: " + str(len(payload)).encode()
           + b"\r\n\r\n" + payload)
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        s.sendall(req)
        raw = b""
        while b"0\r\n\r\n" not in raw:
            chunk = s.recv(65536)
            if not chunk:
                break
            raw += chunk
    head, _, rest = raw.partition(b"\r\n\r\n")
    assert b"HTTP/1.1 200" in head
    assert b"Transfer-Encoding: chunked" in head
    assert b"Content-Type: text/event-stream" in head
    # de-chunk
    body, buf = b"", rest
    while buf:
        size, _, buf = buf.partition(b"\r\n")
        n = int(size, 16)
        if n == 0:
            break
        body += buf[:n]
        buf = buf[n + 2:]
    events = [e for e in body.decode().split("\n\n") if e.strip()]
    assert events[-1] == "data: [DONE]"
    parsed = [json.loads(e[len("data: "):]) for e in events[:-1]]
    tokens = [e for e in parsed if "token" in e]
    assert [e["index"] for e in tokens] == [0, 1, 2]
    done = parsed[-1]
    assert done["done"] is True
    assert done["usage"]["completion_tokens"] == 3


def test_metrics_exposition_validates(gateway):
    _, _, port = gateway
    status, headers, body = _request(port, "GET", "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    text = body.decode()
    assert obs.validate_exposition(text) > 0
    # the admission/preemption families are exported when a
    # SchedulerConfig is armed
    for name in ("repro_preemptions_total", "repro_queue_wait_seconds",
                 "repro_suspended_requests"):
        assert name in text


def test_debug_flight_404_without_recorder(gateway):
    _, _, port = gateway
    status, _, body = _request(port, "GET", "/v1/debug/flight")
    assert status == 404
    assert "flight-record" in json.loads(body)["error"]


def test_debug_flight_serves_ring_and_dumps(model, tmp_path):
    """With a recorder armed the endpoint returns the ring snapshot and
    triggers an http-reason black-box dump on every hit."""
    from repro.obs import Telemetry
    from repro.obs.flight import FLIGHT_SCHEMA_VERSION, FlightRecorder

    params, cfg = model
    fr = FlightRecorder(dump_dir=str(tmp_path / "dumps"))
    eng = Engine(params, cfg,
                 EngineConfig(max_slots=2, max_len=32, prefill_chunk=8),
                 None, telemetry=Telemetry(flight=fr))
    gw = Gateway(eng, port=0)
    port = gw.start()
    try:
        status, _, body = _request(port, "POST", "/v1/generate", {
            "prompt": _prompts(cfg, 1, 8)[0].tolist(),
            "max_new_tokens": 4})
        assert status == 200
        status, _, body = _request(port, "GET", "/v1/debug/flight")
        assert status == 200
        snap = json.loads(body)
        assert snap["flight_schema_version"] == FLIGHT_SCHEMA_VERSION
        assert snap["count"] > 0 and snap["complete"]
        kinds = {r["k"] for r in snap["records"]}
        assert {"header", "submit", "clock", "finish"} <= kinds
        assert snap["dump_path"].endswith("flight-http-0.jsonl")
        assert (tmp_path / "dumps" / "flight-http-0.jsonl").exists()
    finally:
        gw.stop()


def test_concurrent_metrics_scrapes_under_decode(gateway, model):
    """GET /metrics from several threads while a generation is decoding:
    every scrape returns a valid exposition and the generation finishes
    untouched (the registry renders from live engine state, so scrapes
    must tolerate the state mutating mid-decode)."""
    _, _, port = gateway
    _, cfg = model
    prompt = [int(t) for t in _prompts(cfg, 1, 10, step=7)[0]]
    samples, gen_out, errors = [], [], []

    def scrape():
        try:
            status, _, body = _request(port, "GET", "/metrics")
            assert status == 200
            samples.append(obs.validate_exposition(body.decode()))
        except Exception as e:        # surface in the main thread
            errors.append(e)

    def generate():
        try:
            status, _, body = _request(port, "POST", "/v1/generate",
                                       {"prompt": prompt,
                                        "max_new_tokens": 16})
            assert status == 200
            gen_out.append(json.loads(body)["tokens"])
        except Exception as e:
            errors.append(e)

    g = threading.Thread(target=generate)
    g.start()
    scrapers = [threading.Thread(target=scrape) for _ in range(6)]
    for s in scrapers:
        s.start()
        time.sleep(0.01)     # spread the scrapes across the decode window
    for s in scrapers:
        s.join(timeout=60)
    g.join(timeout=120)
    assert not errors, errors
    assert len(samples) == 6 and all(n > 0 for n in samples)
    assert len(gen_out) == 1 and len(gen_out[0]) == 16


def test_validation_errors_are_400(gateway):
    _, _, port = gateway
    for bad in ({}, {"prompt": []}, {"prompt": [1.5]},
                {"prompt": [1], "max_new_tokens": 0},
                {"prompt": [1], "priority": "vip"}):
        status, _, body = _request(port, "POST", "/v1/generate", bad)
        assert status == 400, f"payload {bad} not rejected"
        assert "error" in json.loads(body)
    status, _, _ = _request(port, "GET", "/nope")
    assert status == 404


def test_drain_closes_engine(model):
    """stop() drains in-flight work, shuts the listener, and closes the
    engine (telemetry flushed)."""
    params, cfg = model
    eng = _engine(params, cfg)
    gw = Gateway(eng, port=0)
    port = gw.start()
    prompt = [int(t) for t in _prompts(cfg, 1, 8)[0]]
    status, _, _ = _request(port, "POST", "/v1/generate",
                            {"prompt": prompt, "max_new_tokens": 2})
    assert status == 200
    gw.stop()
    assert eng._closed
    with pytest.raises(ConnectionRefusedError):
        socket.create_connection(("127.0.0.1", port), timeout=2)


# ---------------------------------------------------------------------------
# engine lifecycle
# ---------------------------------------------------------------------------

def test_close_flushes_trace_sink_and_is_idempotent(model, tmp_path):
    params, cfg = model
    sink = str(tmp_path / "trace.json")
    tel = obs.Telemetry(tracer=obs.SpanTracer(), trace_sink=sink)
    with Engine(params, cfg,
                EngineConfig(max_slots=2, max_len=32, prefill_chunk=8),
                None, telemetry=tel) as eng:
        eng.submit(_prompts(cfg, 1, 8)[0], 3)
        eng.run()
    with open(sink) as f:
        assert obs.validate_chrome_trace(json.load(f)) > 0
    eng.close()                               # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(_prompts(cfg, 1, 8)[0], 3)


def test_close_flushes_on_exception(model, tmp_path):
    params, cfg = model
    sink = str(tmp_path / "trace.json")
    tel = obs.Telemetry(tracer=obs.SpanTracer(), trace_sink=sink)
    with pytest.raises(RuntimeError, match="boom"), \
            Engine(params, cfg,
                   EngineConfig(max_slots=2, max_len=32, prefill_chunk=8),
                   None, telemetry=tel) as eng:
        eng.submit(_prompts(cfg, 1, 8)[0], 3)
        eng.run()
        raise RuntimeError("boom")
    with open(sink) as f:
        json.load(f)                          # exported despite the raise


def test_reset_ids_gives_fresh_namespace(model):
    """reset_ids() restarts request ids at 0 (per-rep benchmark replays
    key cross-engine parity on the id); busy engines refuse."""
    params, cfg = model
    eng = _engine(params, cfg)
    prompts = _prompts(cfg, 2, 8)
    first = eng.submit(prompts[0], 2)
    assert first.request.request_id == 0
    with pytest.raises(RuntimeError, match="busy engine"):
        eng.reset_ids()
    eng.run()
    eng.reset_ids()
    again = eng.submit(prompts[1], 2)
    assert again.request.request_id == 0
    eng.run()


# ---------------------------------------------------------------------------
# serve CLI validation (build_parser + validate_args, no process spawn)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("argv,msg", [
    (["--spec-gamma", "2"], "needs --ladder"),
    (["--spec-adaptive"], "--spec-gamma"),
    (["--ladder", "x.npz", "--spec-gamma", "2", "--slo-tpot-p95", "0.1"],
     "conflicts"),
    (["--rung", "3"], "needs --ladder"),
    (["--sparsity", "1.5"], "sparsity"),
    (["--gen", "0"], "--gen"),
    (["--max-queue", "-1"], "--max-queue"),
    (["--gateway", "--legacy"], "engine path"),
    (["--gateway", "--metrics-out", "m.jsonl"], "owns the engine loop"),
    (["--gateway", "--metrics-port", "9090"], "already serves /metrics"),
    (["--gateway-port", "9999"], "need --gateway"),
    (["--preemption", "--legacy"], "engine path"),
    (["--quality-probe-rate", "1.5"], "quality-probe-rate"),
    (["--quality-probe-rate", "-0.1"], "quality-probe-rate"),
    (["--quality-probe-rate", "0.5", "--legacy"], "engine path"),
    (["--quality-drift-threshold", "0.3"], "quality-probe-rate > 0"),
    (["--quality-probe-rate", "0.5", "--quality-drift-threshold", "1.0"],
     "quality-drift-threshold must be in"),
    (["--quality-probe-rate", "0.5", "--quality-drift-threshold", "0.0"],
     "quality-drift-threshold must be in"),
    (["--flight-record", "f.jsonl", "--flight-ring", "0"],
     "--flight-ring must be > 0"),
    (["--flight-record", "f.jsonl", "--flight-ring", "-8"],
     "--flight-ring must be > 0"),
    (["--flight-ring", "1024"], "needs --flight-record"),
    (["--flight-dump-dir", "/tmp"], "needs --flight-record"),
    (["--flight-record", "f.jsonl", "--legacy"], "engine path"),
    (["--layers", "-1"], "--layers"),
])
def test_serve_cli_rejects_bad_flags(argv, msg):
    args = build_parser().parse_args(argv)
    with pytest.raises(SystemExit, match=msg):
        validate_args(args)


def test_serve_cli_flight_dump_dir_must_be_writable_dir(tmp_path):
    not_dir = tmp_path / "plainfile"
    not_dir.write_text("x")
    args = build_parser().parse_args(
        ["--flight-record", "f.jsonl", "--flight-dump-dir", str(not_dir)])
    with pytest.raises(SystemExit, match="not a directory"):
        validate_args(args)


def test_serve_cli_accepts_good_flags(tmp_path):
    for argv in ([], ["--gateway", "--max-queue", "8", "--preemption"],
                 ["--ladder", "x.npz", "--rung", "1"],
                 ["--ladder", "x.npz", "--spec-gamma", "2",
                  "--spec-drafter", "1"],
                 ["--quality-probe-rate", "0.25"],
                 ["--quality-probe-rate", "1.0",
                  "--quality-drift-threshold", "0.3"],
                 ["--flight-record"],          # bounded ring, no sink
                 ["--flight-record", "f.jsonl", "--flight-ring", "1024",
                  "--flight-dump-dir", str(tmp_path)],
                 ["--gateway", "--flight-record", "f.jsonl"]):
        validate_args(build_parser().parse_args(argv))


def test_serve_cli_builds_published_widths_unless_reduced():
    """``--reduced`` is opt-in and ``--layers`` cuts depth only: the
    default run is the published config, and a cut keeps every width."""
    args = build_parser().parse_args([])
    assert (args.reduced, args.layers) == (False, 0)
    full = get_config("llama31_8b")
    assert serving_config(args.arch, tiny=args.reduced,
                          layers=args.layers) == full
    cut = serving_config("llama31_8b", layers=12)
    assert cut == dataclasses.replace(full, num_layers=12)
    assert serving_config("llama31_8b", tiny=True) == reduced(full)
    with pytest.raises(ValueError, match=r"layers must be in \[1, 32\]"):
        serving_config("llama31_8b", layers=33)


def test_serve_cli_rung_range_checked_against_ladder():
    args = build_parser().parse_args(["--ladder", "x.npz", "--rung", "3"])
    with pytest.raises(SystemExit, match="out of range"):
        validate_rungs(args, num_rungs=2)
    args = build_parser().parse_args(
        ["--ladder", "x.npz", "--spec-gamma", "2", "--spec-drafter", "5"])
    with pytest.raises(SystemExit, match="spec-drafter 5 out of range"):
        validate_rungs(args, num_rungs=2)
    validate_rungs(build_parser().parse_args(
        ["--ladder", "x.npz", "--rung", "1"]), num_rungs=2)
