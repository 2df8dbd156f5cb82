"""Sparsity *quality* observability: how much accuracy is the active
rung costing on live traffic, right now?

The serving stack's other telemetry (metrics/trace/events) observes
latency and throughput; the :class:`AdaptiveController` is blind to
quality — it will happily park at the sparsest rung as long as TPOT
holds.  WiSparse's own quality machinery (Eq. 6 block reconstruction
error, weight-aware channel saliency) runs once at calibration time and
is never measured again, even though saliency statistics drift when the
serving distribution stops matching the calibration set.  The
:class:`QualityMonitor` closes that loop with four probes, all riding
the engine's existing compile-once discipline:

1. **Shadow dense probes** — a configurable fraction of decode steps is
   re-run through a dense single-token verify executable (PR 4's
   ``mode="verify"`` machinery with a window of one) *before* the real
   decode dispatch.  The probe writes dense K/V only at each slot's
   current position, which the immediately following serving-policy
   decode overwrites — so served tokens and cache state are bit-exactly
   those of a probe-free run.  Per-rung token-agreement and top-k
   logit-overlap histograms come out the other end.
2. **Online block reconstruction error** — the exact Eq. 6 metric from
   ``core/calibration.py`` evaluated on a window of recently served
   tokens: one dense unstacked forward collects every block's dense
   input/output, each block re-runs under the active rung's sp tree with
   the paper's per-token ``mask`` numerics, and the per-block MSE is
   exported as histograms and compared against the calibration-time
   baselines a v4 ladder artifact carries.
3. **Saliency drift detection** — per (block, rung) EWMA Jaccard overlap
   between the live top-k saliency channel set (``|x| * g^alpha`` on the
   block input, the calibration scoring rule) and the calibration-time
   set from the ladder artifact (first live observation seeds the
   reference when the artifact predates v4).  Crossing below the
   threshold emits a ``saliency_drift`` event with (block, rung)
   attribution and raises the ``pressure`` gauge the controller can read
   as an advisory de-escalation hint (``SLOConfig.quality_aware``).
4. **Per-rung roofline counters** — at ``warmup()`` every rung's
   decode/chunk (and spec verify) executable is AOT-lowered and its
   ``cost_analysis()`` FLOPs/bytes captured
   (:func:`repro.launch.roofline.executable_costs`), exported as gauges
   plus an achieved-vs-roofline decode utilization estimate.

Zero-cost when off: ``NULL_TELEMETRY.quality is None`` and the engine's
only hot-path touch is one ``is not None`` check.  Retrace-free when on:
the probe and reconstruction executables are jitted once and precompiled
by :meth:`attach` (called from ``Engine.warmup()``); their trace
counters are baselined exactly like the engine's
(``retraces_after_warmup``).  Spec engines never run the plain decode
step, so they expose roofline counters but do not probe.

Module import stays light (stdlib + numpy + ``obs.metrics``); jax and
the model stack load lazily at :meth:`attach`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.obs import clock
from repro.obs.metrics import Histogram, log_buckets

# dedicated Chrome-trace track for quality probes (requests own tids
# request_id+1; this sits far above any realistic request count)
QUALITY_TID = 999_983

# [0, 1] fractions (agreement, top-k overlap) at 1/16 resolution —
# exact means via _sum/_count, bounded exposition cardinality
FRACTION_BUCKETS = tuple(i / 16 for i in range(17))

# Eq. 6 block MSEs span many decades; one bucket per decade
RECON_BUCKETS = log_buckets(1e-9, 1e3, per_decade=1)


@dataclasses.dataclass(frozen=True)
class QualityConfig:
    """Quality-probe tuning.

    probe_rate       fraction of decode steps shadow-probed, in (0, 1]
                     (deterministic stride — no RNG on the hot path).
    topk             k for the probe's logit-overlap metric.
    drift_threshold  EWMA Jaccard overlap below which a block is
                     drifting, in (0, 1).
    drift_alpha      EWMA smoothing for the per-(block, rung) overlap.
    recon_every      run the reconstruction/saliency pass on every Nth
                     probe (it costs a full window forward; 0 disables).
    recon_window     token window for the reconstruction pass; sampled
                     from the live request with the longest history
                     (skipped until one has at least this many tokens).
    saliency_topk    channel-set size for the live-vs-calibration
                     Jaccard overlap.
    """

    probe_rate: float = 0.05
    topk: int = 8
    drift_threshold: float = 0.5
    drift_alpha: float = 0.2
    recon_every: int = 4
    recon_window: int = 16
    saliency_topk: int = 32

    def __post_init__(self):
        if not 0.0 < self.probe_rate <= 1.0:
            raise ValueError(
                f"probe_rate must be in (0, 1], got {self.probe_rate}")
        if not 0.0 < self.drift_threshold < 1.0:
            raise ValueError(
                f"drift_threshold must be in (0, 1), "
                f"got {self.drift_threshold}")
        if not 0.0 < self.drift_alpha <= 1.0:
            raise ValueError(
                f"drift_alpha must be in (0, 1], got {self.drift_alpha}")
        if self.topk < 1:
            raise ValueError(f"topk must be >= 1, got {self.topk}")
        if self.recon_every < 0:
            raise ValueError(
                f"recon_every must be >= 0, got {self.recon_every}")
        if self.recon_window < 1:
            raise ValueError(
                f"recon_window must be >= 1, got {self.recon_window}")
        if self.saliency_topk < 1:
            raise ValueError(
                f"saliency_topk must be >= 1, got {self.saliency_topk}")


# ---------------------------------------------------------------------------
# shared helpers (the calibration side of the ladder uses these too, so
# live scores and stored baselines are computed by the same rule)
# ---------------------------------------------------------------------------

def rep_saliency_leaf(sp_d, d_model: int):
    """First sparsifiable leaf of a per-depth sp dict whose ``g`` norms
    live on the block-input channel axis -> (g, alpha) as numpy, or
    ``None`` when the block has no such leaf.  Deterministic (sorted
    walk), so calibration and serving always pick the same leaf."""
    def walk(node):
        if not isinstance(node, dict):
            return None
        if "g" in node and "alpha" in node:
            g = np.asarray(node["g"], np.float32)
            if g.ndim == 1 and g.shape[0] == d_model:
                return g, float(np.asarray(node["alpha"]))
            return None
        for k in sorted(node):
            found = walk(node[k])
            if found is not None:
                return found
        return None
    return walk(sp_d)


def saliency_channels(x_mean_abs: np.ndarray, g: np.ndarray, alpha: float,
                      k: int) -> np.ndarray:
    """Top-k channel indices of the WiSparse saliency score
    ``|x| * max(g, 1e-12)^alpha`` (sorted, for stable set compares)."""
    scores = np.asarray(x_mean_abs, np.float32) \
        * np.maximum(np.asarray(g, np.float32), 1e-12) ** float(alpha)
    k = min(int(k), scores.shape[0])
    return np.sort(np.argpartition(-scores, k - 1)[:k]).astype(np.int64)


def unstack_sp(cfg, sp):
    """Stacked group sp tree -> per-depth sp list (inverse of
    ``repro.core.unstacked.restack_sp``; trace-safe — slicing works on
    tracers and concrete arrays alike)."""
    import jax
    per_depth = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        gsp = sp[gi]
        for r in range(reps):
            for j in range(len(pattern)):
                per_depth.append(jax.tree_util.tree_map(
                    lambda a, r=r: a[r], gsp[f"l{j}"]))
    return per_depth


def _jaccard(a: np.ndarray, b: np.ndarray) -> float:
    union = np.union1d(a, b)
    if union.size == 0:
        return 1.0
    return float(np.intersect1d(a, b).size) / float(union.size)


# ---------------------------------------------------------------------------

class QualityMonitor:
    """Live sparsity-quality probes for one engine.

    Construct with a :class:`QualityConfig` (or kwargs), hand it to the
    engine via ``Telemetry(quality=...)``; ``Engine.warmup()`` calls
    :meth:`attach`, which builds and precompiles the probe executables
    and captures the roofline counters.  Until then the monitor is inert
    (``armed`` is False and ``should_probe`` always says no)."""

    def __init__(self, cfg: Optional[QualityConfig] = None, **kw):
        if cfg is None:
            cfg = QualityConfig(**kw)
        elif kw:
            raise TypeError("pass a QualityConfig or kwargs, not both")
        self.cfg = cfg
        self.armed = False
        self._stride = max(1, int(round(1.0 / cfg.probe_rate)))
        self._step_idx = 0
        # probe counters/aggregates
        self.probes = 0
        self.probe_tokens = 0
        self.recon_passes = 0
        self.drift_events = 0
        self.pressure = 0.0
        self.agreement_hists: Tuple[Histogram, ...] = ()
        self.overlap_hists: Tuple[Histogram, ...] = ()
        self.recon_hists: Tuple[Histogram, ...] = ()
        # per-(rung, block) saliency state
        self.saliency_ref: Dict[Tuple[int, int], np.ndarray] = {}
        self.saliency_ewma: Dict[Tuple[int, int], float] = {}
        self._drifting: Dict[Tuple[int, int], bool] = {}
        # calibration-time baselines (from a v4 ladder artifact)
        self.recon_baseline: Optional[np.ndarray] = None   # (rungs, blocks)
        self.recon_last: Optional[np.ndarray] = None       # (blocks,)
        self.recon_ratio: Optional[float] = None
        # roofline counters: (phase, rung) -> {"flops", "bytes"}
        self.roofline: Dict[Tuple[str, int], Dict[str, float]] = {}
        # executables (built at attach)
        self._vstep = None
        self._rstep = None
        self._ref_sp = None
        self._ref_policy = None
        self._g_alpha = None            # [rung][depth] -> (g, alpha) | None
        self._probe_traces = 0
        self._recon_traces = 0
        self._warm: Optional[Tuple[int, int]] = None
        self._named_track = False
        self._probe_span: Optional[Tuple[float, float]] = None

    # ------------------------------------------------------------------
    # wiring
    # ------------------------------------------------------------------
    def attach(self, engine) -> None:
        """Build + precompile the probe executables against ``engine``
        and capture the per-rung roofline counters.  Called from
        ``Engine.warmup()`` on an idle engine (the precompile dispatches
        write only scratch/overwritten cache positions, exactly like the
        rest of warmup)."""
        import jax
        import jax.numpy as jnp

        from repro.launch.roofline import executable_costs
        from repro.models import api
        from repro.sparsity import SparsityPolicy

        R = engine.num_rungs
        if len(self.agreement_hists) != R:
            self.agreement_hists = tuple(
                Histogram(FRACTION_BUCKETS) for _ in range(R))
            self.overlap_hists = tuple(
                Histogram(FRACTION_BUCKETS) for _ in range(R))
            self.recon_hists = tuple(
                Histogram(RECON_BUCKETS) for _ in range(R))

        # -- dense reference for shadow probes --------------------------
        # ladder engines probe against rung 0 (the quality reference the
        # ladder was calibrated to); if rung 0 itself is not dense, or
        # the engine runs a fixed policy, a plain dense policy over the
        # same sp tree is the reference.
        self._ref_sp = engine._rung_sp[0]
        ref_pol = engine._rung_phases[0][2]
        if not ref_pol.is_dense:
            ref_pol = SparsityPolicy.dense().for_phase("decode")
        self._ref_policy = ref_pol

        verify = api.make_verify_step(engine.cfg)

        def _probe(params, tokens, positions, caches, sp, weights, *,
                   policy):
            self._probe_traces += 1          # runs only while tracing
            engine._record_compile("quality_probe")
            return verify(params, tokens, positions, caches, sp, weights,
                          policy=policy)

        self._vstep = jax.jit(_probe, static_argnames=("policy",),
                              donate_argnums=(3,))

        S = engine.ecfg.max_slots
        t1 = jnp.zeros((S, 1), jnp.int32)
        p1 = jnp.full((S,), engine.pool_len - 1, jnp.int32)
        w1 = jnp.zeros((S, 1), jnp.float32)
        out, engine.pool.caches = self._vstep(
            engine.params, t1, p1, engine.pool.caches, self._ref_sp, w1,
            policy=self._ref_policy)
        out.block_until_ready()

        # -- reconstruction / saliency executable -----------------------
        # one jit covers every rung: the sp tree is a *traced* argument
        # and ladder rungs share one sp schema.
        self._rstep = None
        self._g_alpha = None
        if self.cfg.recon_every > 0 and all(
                sp is not None for sp in engine._rung_sp):
            from repro.core import unstacked as U
            cfg = engine.cfg
            mask_pol = SparsityPolicy.uniform("mask")

            def _recon(params, tokens, sp):
                self._recon_traces += 1
                engine._record_compile("quality_recon")
                layers = U.unstack_layers(cfg, params)
                per_depth = unstack_sp(cfg, sp)
                _, block_io = U.forward_unstacked(
                    params, cfg, tokens, layers=layers,
                    collect_block_inputs=True)
                y_last = U.block_forward(layers[-1], block_io[-1], cfg,
                                         None, None)
                refs = list(block_io[1:]) + [y_last]
                errs, feats = [], []
                for d, dl in enumerate(layers):
                    x_in = block_io[d]
                    y = U.block_forward(dl, x_in, cfg, per_depth[d], None,
                                        policy=mask_pol)
                    errs.append(jnp.mean(jnp.square(
                        y.astype(jnp.float32)
                        - refs[d].astype(jnp.float32))))
                    feats.append(jnp.mean(
                        jnp.abs(x_in.astype(jnp.float32)), axis=(0, 1)))
                return jnp.stack(errs), jnp.stack(feats)

            self._rstep = jax.jit(_recon)
            tok = jnp.zeros((1, self.cfg.recon_window), jnp.int32)
            errs, feats = self._rstep(engine.params, tok,
                                      engine._rung_sp[0])
            errs.block_until_ready()
            # host-side (g, alpha) of each block's representative leaf,
            # per rung — the live saliency scoring inputs
            self._g_alpha = []
            for sp in engine._rung_sp:
                per_depth = unstack_sp(cfg, sp)
                self._g_alpha.append([
                    rep_saliency_leaf(
                        jax.tree_util.tree_map(np.asarray, sp_d),
                        cfg.d_model)
                    for sp_d in per_depth])

        # -- calibration baselines from the ladder artifact (v4) --------
        ladder = getattr(engine, "ladder", None)
        qb = getattr(ladder, "baselines", None) if ladder is not None \
            else None
        if qb is not None:
            recon = qb.get("recon")
            if recon is not None:
                self.recon_baseline = np.asarray(recon, np.float64)
            channels = qb.get("channels")
            if channels is not None:
                for r, per_block in enumerate(channels):
                    for d, ch in enumerate(per_block):
                        ch = np.asarray(ch, np.int64)
                        if ch.size:
                            self.saliency_ref[(r, d)] = ch

        # -- per-rung roofline counters (AOT: lower + compile only; no
        # execution, so cache donation never actually happens) ----------
        t0 = jnp.zeros((S,), jnp.int32)
        inactive = jnp.zeros((S,), jnp.float32)
        C = engine.ecfg.prefill_chunk
        for r, ((pd, _ps, dec), sp) in enumerate(
                zip(engine._rung_phases, engine._rung_sp)):
            compiled = engine._dstep.lower(
                engine.params, t0, p1, engine.pool.caches, sp, inactive,
                policy=dec).compile()
            flops, byts = executable_costs(compiled)
            self.roofline[("decode", r)] = {"flops": flops, "bytes": byts}
            if engine.prefill_strategy == "chunked":
                compiled = engine._cstep.lower(
                    engine.params, jnp.zeros((1, C), jnp.int32),
                    jnp.zeros((1,), jnp.int32), jnp.int32(0),
                    engine.pool.caches, sp, jnp.zeros((C,), jnp.float32),
                    policy=pd).compile()
                flops, byts = executable_costs(compiled)
                self.roofline[("chunk", r)] = {"flops": flops,
                                               "bytes": byts}
        if engine.spec_decoder is not None:
            sd = engine.spec_decoder
            _, _, ver_pol = engine._rung_phases[sd.verifier_rung]
            ver_sp = engine._rung_sp[sd.verifier_rung]
            for g in engine.ecfg.spec.gammas():
                compiled = sd._vstep.lower(
                    engine.params, jnp.zeros((S, g + 1), jnp.int32),
                    jnp.full((S,), engine.pool_len - (g + 1), jnp.int32),
                    engine.pool.caches, ver_sp,
                    jnp.zeros((S, g + 1), jnp.float32),
                    policy=ver_pol).compile()
                flops, byts = executable_costs(compiled)
                self.roofline[(f"verify{g}", sd.verifier_rung)] = {
                    "flops": flops, "bytes": byts}

        self._warm = (self._probe_traces, self._recon_traces)
        self.armed = True

    @property
    def retraces_after_warmup(self) -> Optional[int]:
        """Probe + recon (re)traces since :meth:`attach`; the quality
        invariant is that this stays 0 under live probing."""
        if self._warm is None:
            return None
        return (self._probe_traces - self._warm[0]) \
            + (self._recon_traces - self._warm[1])

    # ------------------------------------------------------------------
    # hot path (engine._decode_step)
    # ------------------------------------------------------------------
    def should_probe(self) -> bool:
        """Deterministic stride sampling over decode steps."""
        if not self.armed:
            return False
        hit = self._step_idx % self._stride == 0
        self._step_idx += 1
        return hit

    def run_probe(self, engine, tokens, positions, active) -> np.ndarray:
        """Shadow dense probe for one decode step, run *before* the real
        dispatch: a window-1 dense verify whose K/V writes land exactly
        on the positions the immediately following serving-policy decode
        overwrites — served tokens and cache are bit-identical to a
        probe-free run.  Returns host logits (slots, vocab)."""
        import jax.numpy as jnp
        t0 = clock.now()
        out, engine.pool.caches = self._vstep(
            engine.params, jnp.asarray(tokens).reshape(-1, 1),
            jnp.asarray(positions), engine.pool.caches, self._ref_sp,
            jnp.asarray(active, jnp.float32).reshape(-1, 1),
            policy=self._ref_policy)
        probe = np.asarray(out[:, 0])            # syncs the dispatch
        self._probe_span = (t0, clock.now())
        return probe

    def observe(self, engine, probe: np.ndarray, logits, nxt: np.ndarray,
                active: np.ndarray, t: float) -> None:
        """Score one probed step (post real-decode, host side): per-rung
        agreement and top-k overlap, plus — every ``recon_every`` probes
        — the reconstruction/saliency pass."""
        slots = np.nonzero(np.asarray(active) > 0)[0]
        if slots.size == 0:
            return
        rung = engine.rung
        self.probes += 1
        self.probe_tokens += int(slots.size)
        serving = np.asarray(logits)
        k = min(self.cfg.topk, probe.shape[-1])
        agree = 0
        overlap = 0.0
        for s in slots:
            if int(np.argmax(probe[s])) == int(nxt[s]):
                agree += 1
            pa = np.argpartition(-probe[s], k - 1)[:k]
            sa = np.argpartition(-serving[s], k - 1)[:k]
            overlap += np.intersect1d(pa, sa).size / k
        agreement = agree / slots.size
        overlap /= slots.size
        self.agreement_hists[rung].observe(agreement)
        self.overlap_hists[rung].observe(overlap)
        tr = engine.obs.tracer
        if tr is not None:
            if not self._named_track:
                tr.thread_name(QUALITY_TID, "quality")
                self._named_track = True
            span = self._probe_span or (t, t)
            tr.complete("quality_probe", span[0], span[1],
                        tid=QUALITY_TID, rung=rung,
                        agreement=round(agreement, 4),
                        topk_overlap=round(overlap, 4),
                        slots=int(slots.size))
        if self._rstep is not None and self.cfg.recon_every > 0 \
                and self.probes % self.cfg.recon_every == 0:
            self._recon_pass(engine, rung, t)

    # ------------------------------------------------------------------
    # reconstruction + saliency drift
    # ------------------------------------------------------------------
    def _live_window(self, engine) -> Optional[np.ndarray]:
        """The last ``recon_window`` tokens of the live request with the
        longest prompt+generated history (fixed shape keeps the recon
        executable retrace-free); None until one is long enough."""
        W = self.cfg.recon_window
        best = None
        for rs in engine.scheduler.decoding.values():
            n = rs.request.prompt_len + len(rs.tokens)
            if n >= W and (best is None or n > best[0]):
                best = (n, rs)
        if best is None:
            return None
        rs = best[1]
        seq = np.concatenate([np.asarray(rs.request.prompt, np.int32),
                              np.asarray(rs.tokens, np.int32)])
        return seq[-W:].reshape(1, W)

    def _recon_pass(self, engine, rung: int, t: float) -> None:
        import jax.numpy as jnp
        window = self._live_window(engine)
        if window is None:
            return
        errs, feats = self._rstep(engine.params, jnp.asarray(window),
                                  engine._rung_sp[rung])
        errs = np.asarray(errs, np.float64)
        feats = np.asarray(feats, np.float32)
        self.recon_passes += 1
        self.recon_last = errs
        for e in errs:
            self.recon_hists[rung].observe(float(e))
        if self.recon_baseline is not None \
                and rung < self.recon_baseline.shape[0]:
            base = float(np.mean(self.recon_baseline[rung]))
            self.recon_ratio = float(np.mean(errs)) / max(base, 1e-12)
        self._saliency_pass(engine, rung, feats, t)

    def _saliency_pass(self, engine, rung: int, feats: np.ndarray,
                       t: float) -> None:
        cfg = self.cfg
        ga = self._g_alpha[rung] if self._g_alpha is not None else None
        if ga is None:
            return
        for d in range(feats.shape[0]):
            if d >= len(ga) or ga[d] is None:
                continue
            g, alpha = ga[d]
            live = saliency_channels(feats[d], g, alpha, cfg.saliency_topk)
            key = (rung, d)
            ref = self.saliency_ref.get(key)
            if ref is None:
                # no calibration baseline (pre-v4 artifact / uniform
                # ladder): the first live observation is the reference
                self.saliency_ref[key] = live
                self.saliency_ewma[key] = 1.0
                continue
            jac = _jaccard(live, ref)
            a = cfg.drift_alpha
            prev = self.saliency_ewma.get(key)
            ewma = jac if prev is None else (1 - a) * prev + a * jac
            self.saliency_ewma[key] = ewma
            below = ewma < cfg.drift_threshold
            if below and not self._drifting.get(key, False):
                self.drift_events += 1
                ev = engine.obs.events
                if ev is not None:
                    ev.emit("saliency_drift", t=t, block=d, rung=rung,
                            overlap=round(ewma, 4),
                            threshold=cfg.drift_threshold)
                tr = engine.obs.tracer
                if tr is not None:
                    tr.instant("saliency_drift", t=t, tid=QUALITY_TID,
                               block=d, rung=rung,
                               overlap=round(ewma, 4))
                fr = engine.obs.flight
                if fr is not None:
                    # drift edge is a black-box trigger (see
                    # FlightRecorder.decision)
                    fr.decision("saliency_drift", block=d, rung=rung,
                                overlap=round(ewma, 4),
                                threshold=cfg.drift_threshold)
            self._drifting[key] = below
        self._update_pressure(rung)

    def _update_pressure(self, rung: int) -> None:
        """Quality pressure in [0, 1]: how far below the drift threshold
        the active rung's worst block EWMA sits (0 = no drift)."""
        thr = self.cfg.drift_threshold
        worst = 0.0
        for (r, _d), ewma in self.saliency_ewma.items():
            if r == rung:
                worst = max(worst, (thr - ewma) / thr)
        self.pressure = float(np.clip(worst, 0.0, 1.0))

    def seed_reference(self, rung: int, block: int,
                       channels: np.ndarray) -> None:
        """Install a saliency reference channel set for (rung, block) —
        what loading a v4 ladder does; exposed for tests and for
        operators re-baselining a drifted deployment."""
        self.saliency_ref[(rung, block)] = \
            np.sort(np.asarray(channels, np.int64))
        self.saliency_ewma.pop((rung, block), None)
        self._drifting.pop((rung, block), None)

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def recon_baseline_mean(self, rung: int) -> Optional[float]:
        if self.recon_baseline is None \
                or rung >= self.recon_baseline.shape[0]:
            return None
        return float(np.mean(self.recon_baseline[rung]))

    def decode_utilization(self, measured_step_s: float,
                           device_kind: str) -> Dict[int, float]:
        """Per-rung achieved-vs-roofline decode utilization: the
        executable's roofline step time (max of compute and memory
        terms, at ``device_kind``'s published peaks — an unknown kind
        raises) over the measured mean decode step latency.  One
        measured mean covers all rungs — a per-rung latency split would
        need per-rung timing state the hot path deliberately doesn't
        keep."""
        from repro.launch import constants as C
        peaks = C.peaks(device_kind)
        out: Dict[int, float] = {}
        if measured_step_s <= 0:
            return out
        for (phase, r), cost in self.roofline.items():
            if phase != "decode":
                continue
            ideal = max(cost["flops"] / peaks.flops_bf16,
                        cost["bytes"] / peaks.hbm_bw)
            out[r] = ideal / measured_step_s
        return out

    def snapshot(self) -> dict:
        def hist_mean(hists):
            count = sum(h.count for h in hists)
            if not count:
                return None
            return round(sum(h.sum for h in hists) / count, 6)
        out = {
            "quality_probes": self.probes,
            "quality_probe_tokens": self.probe_tokens,
            "quality_agreement_mean": hist_mean(self.agreement_hists),
            "quality_topk_overlap_mean": hist_mean(self.overlap_hists),
            "quality_recon_mean": hist_mean(self.recon_hists),
            "quality_drift_events": self.drift_events,
            "quality_pressure": round(self.pressure, 4),
        }
        if self.recon_ratio is not None:
            out["quality_recon_vs_baseline"] = round(self.recon_ratio, 4)
        return out
