"""Sparsity quality observability (``repro.obs.quality``): shadow dense
probes, reconstruction error vs calibration baselines, saliency drift
attribution, roofline counters, and the quality-aware controller hint."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.data import DataConfig, SyntheticLM
from repro.models import api
from repro.serving import Engine, EngineConfig, SLOConfig
from repro.serving.controller import AdaptiveController
from repro.sparsity import PolicyLadder


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    return params, cfg


@pytest.fixture(scope="module")
def ladder(model):
    params, cfg = model
    return PolicyLadder.uniform(
        params, cfg, (0.0, 0.5),
        dense_phases=("prefill_dense", "prefill_sparse"))


def _prompts(cfg, n, seq, step=0):
    return np.asarray(SyntheticLM(
        DataConfig(cfg.vocab_size, seq, n)).batch(step))


def _engine(params, cfg, ladder=None, telemetry=None, rung=0, **kw):
    defaults = dict(max_slots=2, max_len=32, prefill_chunk=8,
                    initial_rung=rung)
    defaults.update(kw)
    return Engine(params, cfg, EngineConfig(**defaults), None,
                  ladder=ladder, telemetry=telemetry)


# ---------------------------------------------------------------------------
# config + stride
# ---------------------------------------------------------------------------

def test_quality_config_validation():
    for kw, msg in [(dict(probe_rate=0.0), "probe_rate"),
                    (dict(probe_rate=1.5), "probe_rate"),
                    (dict(drift_threshold=1.0), "drift_threshold"),
                    (dict(drift_threshold=0.0), "drift_threshold"),
                    (dict(drift_alpha=0.0), "drift_alpha"),
                    (dict(topk=0), "topk"),
                    (dict(recon_every=-1), "recon_every"),
                    (dict(recon_window=0), "recon_window"),
                    (dict(saliency_topk=0), "saliency_topk")]:
        with pytest.raises(ValueError, match=msg):
            obs.QualityConfig(**kw)
    with pytest.raises(TypeError, match="not both"):
        obs.QualityMonitor(obs.QualityConfig(), probe_rate=0.5)


def test_probe_stride_is_deterministic():
    q = obs.QualityMonitor(probe_rate=0.5)
    assert not q.should_probe()          # inert until attach() arms it
    q.armed = True
    assert [q.should_probe() for _ in range(6)] \
        == [True, False, True, False, True, False]
    assert q.retraces_after_warmup is None   # no warm baseline yet


# ---------------------------------------------------------------------------
# null path: monitor off must cost (and change) nothing
# ---------------------------------------------------------------------------

def test_null_path_off_by_default(model):
    params, cfg = model
    assert obs.NULL_TELEMETRY.quality is None
    eng = _engine(params, cfg)
    eng.submit(_prompts(cfg, 1, 8)[0], 3)
    eng.run()
    snap = eng.snapshot()
    assert snap["schema_version"] == 7
    assert not any(k.startswith("quality_") for k in snap)
    assert eng.probe_retraces_after_warmup is None
    assert "repro_quality_probes_total" not in eng.metrics_exposition()


# ---------------------------------------------------------------------------
# shadow probes
# ---------------------------------------------------------------------------

def test_probe_parity_dense_agreement_and_roofline(model, ladder):
    """Probing at the dense rung: tokens identical to a probe-free run,
    agreement exactly 1.0 (the probe IS the serving policy), zero probe
    retraces, roofline counters captured for every rung."""
    params, cfg = model
    prompts = _prompts(cfg, 2, 8)

    def run(telemetry):
        eng = _engine(params, cfg, ladder=ladder, telemetry=telemetry)
        eng.warmup()
        for p in prompts:
            eng.submit(p, 6)
        return eng, eng.run()

    tel = obs.Telemetry(quality=obs.QualityMonitor(probe_rate=1.0,
                                                   recon_every=0))
    q = tel.quality
    eng, out = run(tel)
    _, ref = run(None)
    assert out == ref                    # probes never alter served tokens
    assert q.probes > 0 and q.probe_tokens > 0
    assert eng.probe_retraces_after_warmup == 0
    assert eng.decode_retraces_after_warmup == 0

    snap = eng.snapshot()
    assert snap["schema_version"] == 7
    assert snap["quality_probes"] == q.probes
    assert snap["quality_agreement_mean"] == 1.0
    assert snap["quality_topk_overlap_mean"] >= 0.75
    assert snap["quality_recon_mean"] is None    # recon_every=0 disables

    # roofline counters: decode captured per rung at attach()
    assert ("decode", 0) in q.roofline and ("decode", 1) in q.roofline
    assert all(c["flops"] >= 0 and c["bytes"] >= 0
               for c in q.roofline.values())
    util = q.decode_utilization(1e-3, "TPU v5 lite")
    assert set(util) == {0, 1} and all(u >= 0 for u in util.values())
    assert q.decode_utilization(0.0, "TPU v5 lite") == {}
    with pytest.raises(ValueError, match="no published peaks"):
        q.decode_utilization(1e-3, "cpu")


def test_sparse_rung_recon_baseline_and_exposition(model, ladder):
    """Probing at the sparse rung with injected calibration baselines:
    parity holds, the recon pass runs and reports the live-vs-baseline
    ratio, and the repro_quality_* families reach the exposition."""
    params, cfg = model
    L = cfg.num_layers
    # the uniform ladder's tau = -inf keeps every channel through the
    # recon pass's Eq. 5 mask (live error exactly 0); a rung-1 threshold
    # that no channel reaches stands in for a calibrated one
    sps = list(ladder.sps)
    sps[1] = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.full_like(a, jnp.inf)
        if path[-1].key == "tau" else a, sps[1])
    ladder = dataclasses.replace(ladder, sps=tuple(sps))
    with_base = dataclasses.replace(ladder, baselines={
        "recon": np.full((2, L), 1e-8),
        "channels": tuple(tuple(np.arange(4, dtype=np.int64)
                                for _ in range(L)) for _ in range(2))})
    prompts = _prompts(cfg, 2, 8, step=1)

    tel = obs.Telemetry(quality=obs.QualityMonitor(
        probe_rate=1.0, recon_every=1, recon_window=8, saliency_topk=4))
    q = tel.quality
    eng = _engine(params, cfg, ladder=with_base, telemetry=tel, rung=1)
    eng.warmup()
    for p in prompts:
        eng.submit(p, 6)
    out = eng.run()

    plain = _engine(params, cfg, ladder=ladder, rung=1)
    plain.warmup()
    for p in prompts:
        plain.submit(p, 6)
    assert out == plain.run()            # bit-identical probes-on vs off

    assert q.recon_passes > 0
    assert q.recon_baseline_mean(1) == pytest.approx(1e-8)
    snap = eng.snapshot()
    assert snap["quality_recon_mean"] is not None
    assert snap["quality_recon_vs_baseline"] > 0
    assert eng.probe_retraces_after_warmup == 0

    expo = eng.metrics_exposition()
    assert obs.validate_exposition(expo) > 0
    for family in ("repro_quality_probes_total",
                   "repro_quality_probe_agreement_rung1",
                   "repro_quality_recon_error_rung1",
                   "repro_quality_recon_baseline_rung1",
                   "repro_quality_roofline_flops_decode_rung1",
                   "repro_quality_pressure"):
        assert family in expo, f"{family} missing from exposition"


def test_forced_saliency_drift_event_attribution(model, ladder):
    """Re-baselining a block to channels live traffic never selects must
    fire exactly one attributed saliency_drift event (transition edge,
    not one per pass) and raise the pressure gauge."""
    params, cfg = model
    tel = obs.Telemetry(
        events=obs.EventLog(capacity=128),
        quality=obs.QualityMonitor(probe_rate=1.0, recon_every=1,
                                   recon_window=8, saliency_topk=8,
                                   drift_threshold=0.9, drift_alpha=1.0))
    q = tel.quality
    eng = _engine(params, cfg, ladder=ladder, telemetry=tel, rung=1)
    eng.warmup()
    eng.submit(_prompts(cfg, 1, 8, step=2)[0], 6)
    eng.run()
    assert q.recon_passes > 0
    # (the untrained model's window-to-window saliency jitter may trip
    # the tight 0.9 threshold on its own; the forced-drift assertions
    # below are relative to this baseline)
    n0 = q.drift_events
    ev0 = len(tel.events.events("saliency_drift"))

    live = q.saliency_ref[(1, 0)]
    disjoint = np.setdiff1d(np.arange(cfg.d_model), live)[:8]
    q.seed_reference(1, 0, disjoint)     # clears the key's EWMA + state
    eng.submit(_prompts(cfg, 1, 8, step=3)[0], 6)
    eng.run()

    assert q.drift_events > n0
    assert q.pressure > 0.0
    new = tel.events.events("saliency_drift")[ev0:]
    b0 = [e for e in new if e["block"] == 0]
    assert len(b0) == 1                  # edge-triggered, not per-pass
    assert b0[0]["rung"] == 1 and b0[0]["overlap"] < 0.9
    assert eng.snapshot()["quality_drift_events"] == q.drift_events


# ---------------------------------------------------------------------------
# ladder artifact v4
# ---------------------------------------------------------------------------

def test_ladder_v4_baselines_roundtrip_and_backcompat(model, ladder,
                                                      tmp_path):
    params, cfg = model
    L = cfg.num_layers
    recon = np.arange(2 * L, dtype=float).reshape(2, L) + 1e-6
    channels = tuple(tuple(np.arange(d, d + 4, dtype=np.int64)
                           for d in range(L)) for _ in range(2))
    lad = dataclasses.replace(ladder,
                              baselines={"recon": recon,
                                         "channels": channels})
    p = str(tmp_path / "ladder.npz")
    lad.save(p)
    l2 = PolicyLadder.load(p)
    assert np.allclose(l2.baselines["recon"], recon)
    for per_a, per_b in zip(channels, l2.baselines["channels"]):
        for a, b in zip(per_a, per_b):
            assert np.array_equal(a, b)

    # a ladder without baselines round-trips to None, still at v4
    plain = str(tmp_path / "plain.npz")
    ladder.save(plain)
    assert PolicyLadder.load(plain).baselines is None

    # pre-v4 back-compat: rewrite the meta at version 3 without quality
    z = np.load(p, allow_pickle=False)
    meta = json.loads(str(z["__meta__"]))
    meta["version"] = 3
    meta.pop("quality")
    arrays = {k: z[k] for k in z.files
              if k != "__meta__" and not k.startswith("qc")}
    with open(p, "wb") as f:
        np.savez(f, __meta__=np.array(json.dumps(meta)), **arrays)
    assert PolicyLadder.load(p).baselines is None


# ---------------------------------------------------------------------------
# quality-aware controller hint
# ---------------------------------------------------------------------------

def test_controller_quality_deescalation():
    slo = SLOConfig(tpot_p95=1.0, dwell=1, quality_aware=True)
    ctl = AdaptiveController(2, slo, initial_rung=1)
    rung = ctl.update([0.01], queue_depth=0, quality_pressure=0.5)
    assert rung == 0
    assert ctl.quality_deescalations == 1
    assert ctl.transitions[-1][3] == "quality"
    assert ctl.snapshot()["quality_deescalations"] == 1


def test_controller_quality_hint_never_overrides_slo():
    # a violated TPOT target escalates even under maximal drift pressure
    slo = SLOConfig(tpot_p95=0.001, dwell=1, quality_aware=True)
    ctl = AdaptiveController(3, slo, initial_rung=1)
    assert ctl.update([0.1], queue_depth=0, quality_pressure=1.0) == 2
    assert ctl.quality_deescalations == 0
    # queued work blocks the hint: de-escalating would slow the drain
    ctl2 = AdaptiveController(
        2, SLOConfig(tpot_p95=1.0, dwell=1, quality_aware=True),
        initial_rung=1)
    assert ctl2.update([0.01], queue_depth=3,
                       quality_pressure=1.0) == 1
    assert ctl2.quality_deescalations == 0
    # without quality_aware the pressure signal is ignored entirely
    ctl3 = AdaptiveController(2, SLOConfig(tpot_p95=1.0, dwell=1),
                              initial_rung=1)
    assert ctl3.update([0.9], queue_depth=0, quality_pressure=1.0) == 1
    assert ctl3.quality_deescalations == 0
    assert "quality_deescalations" not in ctl3.snapshot()
