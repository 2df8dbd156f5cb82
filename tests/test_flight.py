"""Flight recorder: zero-cost null path, capture validation, ReplayClock
divergence taxonomy, record→replay bit-identity round trips (controller +
preemption, speculative decoding), incomplete-dump refusal, dump
triggers, the injected-divergence CLI report, and the no-raw-time lint
over the serving tree."""
import glob
import json
import os

import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced, serving_config
from repro.data import DataConfig, SyntheticLM
from repro.models import api
from repro.obs import NULL_TELEMETRY, ReplayClock, ReplayDivergence, Telemetry
from repro.obs.flight import FLIGHT_SCHEMA_VERSION, FlightRecorder
from repro.obs.flight import replay as flight_replay
from repro.serving import Engine, EngineConfig, SchedulerConfig
from repro.serving.controller import SLOConfig
from repro.serving.spec import SpecConfig
from repro.sparsity import PolicyLadder


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    return params, cfg


@pytest.fixture(scope="module")
def ladder(model):
    params, cfg = model
    return PolicyLadder.uniform(params, cfg, [0.0, 0.5, 0.7])


def _prompts(cfg, n, seq, step=0):
    return np.asarray(SyntheticLM(
        DataConfig(cfg.vocab_size, seq, n)).batch(step))


# ---------------------------------------------------------------------------
# null path + construction validation
# ---------------------------------------------------------------------------

def test_null_path_is_allocation_free(model):
    """With no recorder armed the engine keeps the exact module-level
    singletons — the hot path branches on ``is None`` and never builds
    per-call objects."""
    params, cfg = model
    assert NULL_TELEMETRY.flight is None
    eng = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=24, prefill_chunk=8), None)
    assert eng.obs is NULL_TELEMETRY
    assert eng.clock is obs.SYSTEM_CLOCK


def test_recorder_validation_and_double_attach(model):
    params, cfg = model
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)
    with pytest.raises(ValueError, match="max_dumps"):
        FlightRecorder(max_dumps=-1)
    with pytest.raises(TypeError):
        Engine(params, cfg, EngineConfig(
            max_slots=1, max_len=24, prefill_chunk=8), None,
            clock=object())
    fr = FlightRecorder()
    eng = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=24, prefill_chunk=8), None,
        telemetry=Telemetry(flight=fr))
    assert eng.clock is not obs.SYSTEM_CLOCK     # recording wrapper
    with pytest.raises(RuntimeError, match="already attached"):
        Engine(params, cfg, EngineConfig(
            max_slots=1, max_len=24, prefill_chunk=8), None,
            telemetry=Telemetry(flight=fr))


def test_replay_clock_divergence_taxonomy():
    """Exhausted stream, kind mismatch, and site mismatch each raise a
    ReplayDivergence whose ``detail`` names what desynchronized."""
    clock = ReplayClock([{"k": "clock", "t": 1.5, "s": "decode.t0"}])
    assert clock.now("decode.t0") == 1.5
    assert clock.exhausted
    with pytest.raises(ReplayDivergence, match="exhausted") as exc:
        clock.now("decode.t1")
    assert exc.value.detail["expected"] is None
    assert exc.value.detail["got"] == {"k": "clock", "s": "decode.t1"}

    clock = ReplayClock([{"k": "submit", "prompt": [1]}])
    with pytest.raises(ReplayDivergence, match="'submit' record") as exc:
        clock.now("decode.t0")
    assert exc.value.detail["expected"]["k"] == "submit"
    assert clock.cursor == 0                 # divergence consumes nothing

    clock = ReplayClock([{"k": "clock", "t": 1.5, "s": "decode.t0"}])
    with pytest.raises(ReplayDivergence, match="decode.t0") as exc:
        clock.now("prefill_chunk.t0")
    detail = exc.value.detail
    assert detail["expected"]["s"] == "decode.t0"
    assert detail["got"]["s"] == "prefill_chunk.t0"


# ---------------------------------------------------------------------------
# record → replay round trips
# ---------------------------------------------------------------------------

def _controller_ecfg():
    return EngineConfig(
        max_slots=2, max_len=96, prefill_chunk=16,
        slo=SLOConfig(tpot_p95=1e-9, max_queue=2),
        scheduler=SchedulerConfig(max_queue=8, preemption=True))


def _record_controller_run(model, ladder, sink, dump_dir=None):
    """The incident scenario: an impossible TPOT SLO forces rung
    escalation while an interactive arrival preempts a best-effort
    decoder."""
    params, cfg = model
    fr = FlightRecorder(sink=sink, dump_dir=dump_dir)
    prompts = _prompts(cfg, 3, 20)
    with Engine(params, cfg, _controller_ecfg(), ladder=ladder,
                telemetry=Telemetry(flight=fr)) as eng:
        for i in range(2):
            eng.submit(prompts[i], 24, priority="best-effort")
        for _ in range(10):
            eng.step()
        eng.submit(prompts[2], 12, priority="interactive")
        while eng.scheduler.has_work():
            eng.step()
    return fr


def test_controller_preemption_replays_bit_identical(model, ladder,
                                                     tmp_path):
    params, cfg = model
    sink = str(tmp_path / "controller.jsonl")
    fr = _record_controller_run(model, ladder, sink)
    kinds = {r["kind"] for r in fr.records("decision")}
    assert "rung_switch" in kinds, "scenario must exercise the controller"
    assert "preempt" in kinds and "resume" in kinds

    report = flight_replay.replay(
        sink, engine_factory=lambda clock, telemetry: Engine(
            params, cfg, _controller_ecfg(), ladder=ladder,
            telemetry=telemetry, clock=clock))
    assert report.ok, report.failures
    assert report.divergence is None
    assert report.requests == 3 and report.tokens > 0
    assert all(v == 0 for v in report.retraces.values()), report.retraces


def test_header_reconstruction_replays_without_factory(model, ladder,
                                                       tmp_path):
    """No factory passed: the engine is rebuilt purely from the header
    (arch/reduced/seed/ladder meta + serialized EngineConfig) — the
    path the CLI takes on a foreign dump."""
    sink = str(tmp_path / "controller.jsonl")
    ladder_path = str(tmp_path / "ladder.npz")
    ladder.save(ladder_path)
    params, cfg = model
    fr = FlightRecorder(sink=sink, meta={
        "arch": "llama31_8b", "reduced": True, "seed": 0,
        "ladder_path": ladder_path})
    prompts = _prompts(cfg, 1, 20)
    with Engine(params, cfg, _controller_ecfg(), ladder=ladder,
                telemetry=Telemetry(flight=fr)) as eng:
        eng.submit(prompts[0], 12)
        while eng.scheduler.has_work():
            eng.step()
    report = flight_replay.replay(sink)
    assert report.ok, report.failures


def test_header_reconstruction_keeps_the_depth_cut(tmp_path):
    """A run served at a cut depth (``serve --layers N``) records N in
    its header, and the replay rebuilds that depth: a full-depth rebuild
    would diverge on the first token."""
    cfg = serving_config("llama31_8b", tiny=True, layers=1)
    params = api.init_model(cfg, 0)
    sink = str(tmp_path / "cut.jsonl")
    fr = FlightRecorder(sink=sink, meta={
        "arch": "llama31_8b", "reduced": True, "layers": 1, "seed": 0})
    with Engine(params, cfg, EngineConfig(
            max_slots=1, max_len=48, prefill_chunk=8),
            telemetry=Telemetry(flight=fr)) as eng:
        eng.submit(_prompts(cfg, 1, 20)[0], 8)
        while eng.scheduler.has_work():
            eng.step()
    report = flight_replay.replay(sink)
    assert report.ok, report.failures
    assert report.tokens == 8


def test_spec_round_replays_bit_identical(model, ladder, tmp_path):
    params, cfg = model
    sink = str(tmp_path / "spec.jsonl")
    ecfg = EngineConfig(
        max_slots=2, max_len=96, prefill_chunk=16,
        spec=SpecConfig(gamma=2, drafter_rung=1, verifier_rung=0,
                        adaptive=True))
    fr = FlightRecorder(sink=sink)
    prompts = _prompts(cfg, 2, 20)
    with Engine(params, cfg, ecfg, ladder=ladder,
                telemetry=Telemetry(flight=fr)) as eng:
        for i in range(2):
            eng.submit(prompts[i], 16)
        while eng.scheduler.has_work():
            eng.step()
    assert fr.records("finish"), "spec scenario recorded no finishes"

    report = flight_replay.replay(
        sink, engine_factory=lambda clock, telemetry: Engine(
            params, cfg, ecfg, ladder=ladder,
            telemetry=telemetry, clock=clock))
    assert report.ok, report.failures
    assert report.retraces.get("verify") == 0


def test_injected_divergence_cli_reports_structured_diff(model, ladder,
                                                         tmp_path,
                                                         capsys):
    """``--inject-divergence`` corrupts one recorded token; the CLI must
    exit 1 and name the request/token/record that diverged."""
    params, cfg = model
    sink = str(tmp_path / "one.jsonl")
    ladder_path = str(tmp_path / "ladder.npz")
    ladder.save(ladder_path)
    fr = FlightRecorder(sink=sink, meta={
        "arch": "llama31_8b", "reduced": True, "seed": 0,
        "ladder_path": ladder_path})
    prompts = _prompts(cfg, 1, 20)
    with Engine(params, cfg, _controller_ecfg(), ladder=ladder,
                telemetry=Telemetry(flight=fr)) as eng:
        eng.submit(prompts[0], 12)
        while eng.scheduler.has_work():
            eng.step()

    rc = flight_replay.main([sink, "--inject-divergence"])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert not report["ok"]
    div = report["divergence"]
    assert div is not None
    assert {"record", "request", "token_index",
            "recorded_token", "replayed_token"} <= set(div)


# ---------------------------------------------------------------------------
# dumps
# ---------------------------------------------------------------------------

def test_incomplete_ring_dump_is_refused(model, ladder, tmp_path):
    """A dump whose ring overflowed is marked incomplete and the loader
    refuses it — a partial history cannot gate bit-identity."""
    dump_dir = str(tmp_path / "dumps")
    fr = _record_controller_run(model, ladder, sink=None,
                                dump_dir=dump_dir)
    assert fr.capacity == 4096 and fr.dropped == 0
    # shrink a copy of the history into a 8-record ring and dump it
    small = FlightRecorder(capacity=8, dump_dir=dump_dir)
    small._attached = True
    for rec in fr.records():
        small._append(rec)
    assert small.dropped > 0
    path = small.dump("manual")
    prologue = json.loads(open(path).readline())
    assert prologue["complete"] is False
    with pytest.raises(ValueError, match="incomplete"):
        flight_replay.load_recording(path)


def test_dump_triggers_slo_breach_and_exception(model, ladder, tmp_path):
    """The impossible SLO's first escalation auto-dumps (slo_breach);
    a crashed driving loop dumps on the way out (exception)."""
    params, cfg = model
    dump_dir = str(tmp_path / "dumps")
    fr = _record_controller_run(model, ladder, sink=None,
                                dump_dir=dump_dir)
    reasons = {os.path.basename(p).split("-")[1] for p in fr.dumps}
    assert "slo_breach" in reasons, fr.dumps

    fr2 = FlightRecorder(dump_dir=dump_dir)
    prompts = _prompts(cfg, 1, 20)
    with pytest.raises(RuntimeError, match="boom"), \
            Engine(params, cfg, _controller_ecfg(), ladder=ladder,
                   telemetry=Telemetry(flight=fr2)) as eng:
        eng.submit(prompts[0], 12)
        eng.step()
        raise RuntimeError("boom")
    assert any("flight-exception-" in p for p in fr2.dumps)
    assert glob.glob(os.path.join(dump_dir, "flight-exception-*.jsonl"))


def test_sink_is_sealed_and_versioned(model, ladder, tmp_path):
    sink = str(tmp_path / "sealed.jsonl")
    _record_controller_run(model, ladder, sink)
    records = [json.loads(ln) for ln in open(sink)]
    assert records[0]["k"] == "header"
    assert records[0]["flight_schema_version"] == FLIGHT_SCHEMA_VERSION
    assert records[-1] == {"k": "end", "count": len(records) - 1,
                           "complete": True}


# ---------------------------------------------------------------------------
# no raw time reads in the serving tree (satellite lint)
# ---------------------------------------------------------------------------

def test_no_raw_time_calls_in_serving_tree():
    """Every serving-path timestamp must flow through the engine clock
    (``repro.obs.clock``) or the recorder can't capture it.  The old
    grep-level lint graduated into the ``no-raw-time`` AST pass of
    ``repro.analysis`` (which also covers ``from time import ...``
    aliasing and the ``*_ns`` variants, and scans ALL of ``src/`` plus
    ``benchmarks/`` and ``examples/``, not just the serving tree);
    this thin wrapper keeps the invariant in the tier-1 suite.
    ``time.sleep`` is fine — it advances no clocks."""
    from repro.analysis import run_ast_passes
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    findings = run_ast_passes(root, rules=["no-raw-time"])
    assert not findings, "\n".join(f.format() for f in findings)
