"""Continuous-batching inference engine.

One engine instance owns: the slot KV pool (fixed shapes, so the batched
decode step compiles once and never retraces), the priority scheduler, and
the jitted phase steps.  Sparsity is phase-aware per the paper's §5.1 recipe:
prefill chunks in the first ``prefill_dense_frac`` of the prompt run dense
and later chunks plus all decode steps run under the configured
:class:`SparsityPolicy`.  The policy is a hashable *static* jit argument —
an explicit value, not ambient state — so each (phase, policy) pair owns
its executable, and two engines with different policies can run
interleaved (or on separate threads) without ever sharing or leaking a
trace.

Adaptive serving: instead of one policy the engine can serve a
:class:`repro.sparsity.PolicyLadder` — a calibrated family of policies at
ascending sparsity budgets.  With an :class:`SLOConfig` an
:class:`AdaptiveController` switches the decode/prefill-sparse phases
between rungs as load changes.  Every rung's executables are precompiled
at engine start, and because compilation is keyed on the static (phase,
policy) pair while rung sp trees share one schema, a rung switch is
retrace-free (``decode_retraces_after_warmup`` asserts this).

Prefill strategies:
  * "chunked": fixed-size chunks written straight into the pool slot via
    ``mode="chunk"`` forwards (jit-stable across prompt lengths; plain
    full-attention archs only).
  * "whole":   the legacy whole-prompt prefill (batched over same-length
    requests) + pool insertion; supports every cached arch (local windows,
    SSM) at the cost of one executable per prompt length.

Speculative decoding: with ``EngineConfig.spec`` the decode action runs
draft/verify rounds instead of single batched steps — a sparse ladder
rung drafts gamma tokens per slot, the verifier rung checks them in one
batched multi-token forward, and the KV pool rolls rejected drafts back
(``repro.serving.spec``).  Output tokens are identical to verifier-only
decode; warmup() additionally precompiles a verify executable per
reachable gamma so gamma/drafter switches stay retrace-free.

Prefix caching: with ``EngineConfig.prefix_cache`` completed prefills
are published into a radix tree over prompt token ids
(``repro.serving.prefix_cache``) and admissions that share a cached
prefix copy it into their slot and chunk-prefill only the un-cached
suffix — the single largest TTFT lever under shared-system-prompt
traffic.  Requires chunked prefill and *prefix-deterministic* prefill
policies (validated eagerly at construction: dense or per-token
``mask`` backends, identical across rungs and prompt lengths), which is
what makes a cache-hit generation bit-identical to cold prefill.

Admission control + preemption: with ``EngineConfig.scheduler`` the
engine enforces a bounded admission queue (``submit`` raises
:class:`repro.serving.scheduler.QueueFull` with a retry estimate — the
gateway's 429), per-request queue-wait deadlines
(``FinishReason.EXPIRED``), strict-priority + per-tenant-WFQ admission
order, and — when ``SchedulerConfig.preemption`` is set — suspension of
a strictly less important decoding victim to host memory
(``SlotKVPool.suspend``/``resume``) so an interactive arrival gets its
slot immediately.  Preemption happens only at the admission boundary,
where every slot's KV length equals the request's committed position
(spec rounds commit + roll back entirely inside their step), so a
resumed request's remaining generation is bit-identical to an
unpreempted run; the chunk-quantized suspend/resume executables are
precompiled by :meth:`Engine.warmup`.

Telemetry: ``Engine(..., telemetry=repro.obs.Telemetry(...))`` arms
per-request span tracing (Chrome trace JSON), the structured event log
(rung switches with controller reasons, gamma changes, prefix
evictions, KV rollbacks, compile/retrace records) and the program's
spans (``repro.obs.spans``), each a JAX profiler annotation: ``repro/step``
around a whole step, ``repro/admit``, and for each phase its numpy
``prepare``, then the phase span (``repro/decode``,
``repro/prefill_chunk``) around its ``launch`` and ``readback``, the
chunk's ``first_token``, and the ``emit`` of its tokens.  Telemetry only *observes* host-side state —
tokens are bit-identical with it on or off — and the default
``NULL_TELEMETRY`` costs nothing: every emit site is an ``is not
None`` check and ``annotate()`` returns a shared null context.

Quality probes: ``Telemetry(quality=QualityMonitor(...))`` additionally
arms live sparsity-quality observability (``repro.obs.quality``) —
sampled shadow dense probes (run *before* the real decode dispatch, so
served tokens and KV stay bit-identical), online Eq. 6 reconstruction
error vs the ladder's calibration baselines and saliency-drift events.
Both
quality executables precompile at warmup
(``probe_retraces_after_warmup`` stays 0), and with
``SLOConfig.quality_aware`` the controller reads the drift-pressure
gauge as an advisory de-escalation hint.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import api
from repro.models.model import sparse_weight_feeds
from repro.obs import NULL_TELEMETRY, Telemetry
from repro.serving.controller import AdaptiveController, SLOConfig
from repro.serving.kv_pool import SlotKVPool
from repro.serving.metrics import EngineStats
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import (FinishReason, Priority, Request,
                                   RequestState, Status)
from repro.serving.scheduler import QueueFull, Scheduler, SchedulerConfig
from repro.serving.spec import SpecConfig, SpecDecoder
from repro.sparsity import PolicyLadder, SparsityPolicy

_CHUNKABLE_MIXERS = ("attn", "global")

# Engine.snapshot() JSONL format version.  v1 (implicit, pre-versioned):
# load/latency/rung fields.  v2: adds "schema_version" itself plus the
# speculative-decoding fields (spec_gamma, spec_drafter_rung,
# spec_accept_ewma, spec_accept_rate) when spec decoding is armed.
# v3: adds the prefix-cache fields (prefix_hit_rate, prefix_tokens_saved,
# prefix_cached_tokens, prefix_segments) when the prefix cache is armed.
# v4: tpot_p50_s/tpot_p95_s switch from windowed ring-buffer percentiles
# to exact whole-run histogram quantiles, tpot_p95_window_s keeps the
# windowed estimate explicitly, and telemetry_events/telemetry_spans
# report live sink depths when telemetry is armed.
# v5: adds the admission-control/preemption fields (suspended,
# preemptions, resumes, rejected, expired, queue_wait_p95_s) when an
# explicit SchedulerConfig is armed; "queue_depth" still counts only
# queued (unadmitted) requests — suspended requests report separately.
# v6: adds the quality-probe fields (quality_probes, quality_probe_tokens,
# quality_agreement_mean, quality_topk_overlap_mean, quality_recon_mean,
# quality_recon_vs_baseline, quality_drift_events, quality_pressure) when
# a QualityMonitor is armed, and quality_deescalations in the controller
# section when SLOConfig.quality_aware is set.
# v7: adds the flight-recorder fields (flight_records, flight_dropped,
# flight_dumps) when a FlightRecorder is armed; "t" is documented as an
# out-of-band wall read (never part of a flight recording's replayed
# clock stream).
SNAPSHOT_SCHEMA_VERSION = 7


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """``policy`` is the engine's execution policy (validated eagerly at
    construction; ``None`` means dense).  Ladder serving ignores it — the
    rung policies come from the ladder passed to :class:`Engine`.

    ``slo`` enables the adaptive controller (requires a ladder);
    ``initial_rung`` is the rung a ladder engine starts on (and stays on
    when no SLO is configured — a pinned rung).

    ``spec`` arms self-speculative decoding (requires a ladder: the
    drafter and verifier are rungs).  The engine then serves at the
    verifier rung and its decode actions run draft/verify rounds —
    token-identical output to verifier-only decode, fewer verifier
    passes per token (``repro.serving.spec``).

    ``prefix_cache`` arms radix-tree KV prefix reuse
    (``repro.serving.prefix_cache``): completed prefills publish into
    the tree, admissions sharing a cached prefix skip straight to the
    un-cached suffix.  ``prefix_cache_tokens`` bounds the cached
    physical tokens (0 = unbounded; LRU eviction of unpinned leaves).
    Needs chunked prefill and prefix-deterministic prefill policies —
    validated eagerly at engine construction."""
    max_slots: int = 8
    max_len: int = 512
    prefill_chunk: int = 32
    policy: Optional[SparsityPolicy] = None
    prefill_dense_frac: float = 0.5  # §5.1: first fraction of prompt dense
    prefill_strategy: str = "auto"   # auto|chunked|whole
    eos_id: Optional[int] = None     # default per-request EOS
    slo: Optional[SLOConfig] = None  # adaptive serving objectives
    initial_rung: int = 0            # ladder rung at engine start
    spec: Optional[SpecConfig] = None  # self-speculative decoding
    prefix_cache: bool = False       # radix-tree KV prefix reuse
    prefix_cache_tokens: int = 0     # cached-token budget (0 = unbounded)
    scheduler: Optional[SchedulerConfig] = None  # admission + preemption
    #                                  policy; None = unbounded FIFO-
    #                                  equivalent defaults

    def __post_init__(self):
        pol = self.policy
        if pol is None:
            pol = SparsityPolicy.dense()
        elif not isinstance(pol, SparsityPolicy):
            raise TypeError(
                f"policy must be a SparsityPolicy, got {type(pol)!r}")
        object.__setattr__(self, "policy", pol)
        if self.slo is not None and not isinstance(self.slo, SLOConfig):
            raise TypeError(f"slo must be an SLOConfig, got {type(self.slo)!r}")
        if self.spec is not None and not isinstance(self.spec, SpecConfig):
            raise TypeError(
                f"spec must be a SpecConfig, got {type(self.spec)!r}")
        if self.scheduler is not None and not isinstance(
                self.scheduler, SchedulerConfig):
            raise TypeError(
                f"scheduler must be a SchedulerConfig, "
                f"got {type(self.scheduler)!r}")
        if self.initial_rung < 0:
            raise ValueError(
                f"initial_rung must be >= 0, got {self.initial_rung}")
        if not 0 <= self.prefill_dense_frac <= 1:
            raise ValueError(
                f"prefill_dense_frac must be in [0, 1], "
                f"got {self.prefill_dense_frac}")
        if self.prefill_strategy not in ("auto", "chunked", "whole"):
            raise ValueError(
                f"unknown prefill_strategy {self.prefill_strategy!r}")
        if self.prefix_cache_tokens < 0:
            raise ValueError(
                f"prefix_cache_tokens must be >= 0, "
                f"got {self.prefix_cache_tokens}")
        if self.prefix_cache and self.prefill_strategy == "whole":
            raise ValueError(
                "prefix_cache needs chunked prefill: whole-prompt "
                "prefill cannot start at a matched prefix length")


def make_engine_steps(cfg: ModelConfig, on_decode_trace=None,
                      on_chunk_trace=None):
    """The engine's three jitted step executables — slot decode, chunked
    prefill, whole-prompt prefill — with the canonical static-arg and
    donation configuration.  This is the ONE place that configuration
    lives: the :class:`Engine` serves through these exact jits, and the
    ``repro.analysis`` jaxpr passes lower the same ones, so a donation
    or static-arg regression here is caught by the lint without the two
    sites drifting apart.

    ``on_decode_trace`` / ``on_chunk_trace`` run inside the traced
    function body — i.e. only while XLA is (re)tracing — which is how
    the engine counts retraces; each gets the traced program's
    :func:`~repro.models.model.sparse_weight_feeds`.

    The pool caches are donated back into themselves each step (no copy
    on TPU; XLA falls back to copying where donation is unsupported).
    ``policy`` is static: it must stay a frozen, hashable
    :class:`SparsityPolicy` or every step becomes a cache miss."""
    slot_decode = api.make_slot_decode_step(cfg)
    chunk_step = api.make_chunk_prefill_step(cfg)
    prefill_step = api.make_prefill_step(cfg)

    def _decode(params, tokens, positions, caches, sp, active, *,
                policy):
        if on_decode_trace is not None:
            on_decode_trace(sparse_weight_feeds(params, cfg, sp, policy))
        return slot_decode(params, tokens, positions, caches, sp,
                           active, policy=policy)

    def _chunk(params, tokens, offset, slot, caches, sp, weights, *,
               policy):
        if on_chunk_trace is not None:
            on_chunk_trace(sparse_weight_feeds(params, cfg, sp, policy))
        return chunk_step(params, tokens, offset, slot, caches, sp,
                          weights, policy=policy)

    def _prefill(params, tokens, sp, *, policy):
        return prefill_step(params, {"tokens": tokens}, sp,
                            policy=policy)

    dstep = jax.jit(_decode, static_argnames=("policy",),
                    donate_argnums=(3,))
    cstep = jax.jit(_chunk, static_argnames=("policy",),
                    donate_argnums=(4,))
    pstep = jax.jit(_prefill, static_argnames=("policy",))
    return dstep, cstep, pstep


class Engine:
    def __init__(self, params, cfg: ModelConfig, ecfg: EngineConfig,
                 sp=None, *, ladder: Optional[PolicyLadder] = None,
                 telemetry: Optional[Telemetry] = None, clock=None):
        if cfg.family in ("encdec", "vlm"):
            raise NotImplementedError(
                f"serving engine supports token-only models, not {cfg.family}")
        if telemetry is None:
            telemetry = NULL_TELEMETRY
        elif not isinstance(telemetry, Telemetry):
            raise TypeError(
                f"telemetry must be a repro.obs.Telemetry, "
                f"got {type(telemetry)!r}")
        self.obs = telemetry
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.ladder = ladder
        if ladder is not None:
            if not isinstance(ladder, PolicyLadder):
                raise TypeError(
                    f"ladder must be a PolicyLadder, got {type(ladder)!r}")
            if sp is not None:
                raise ValueError(
                    "pass either a ladder (which carries per-rung sp "
                    "trees) or a flat sp tree, not both")
            if not 0 <= ecfg.initial_rung < len(ladder):
                raise ValueError(
                    f"initial_rung {ecfg.initial_rung} outside the "
                    f"{len(ladder)}-rung ladder")
            self._rung_policies = list(ladder.policies)
            self._rung_sp = list(ladder.sps)
        else:
            if ecfg.slo is not None:
                raise ValueError(
                    "EngineConfig.slo needs a PolicyLadder: the controller "
                    "switches rungs, a single policy has none")
            if ecfg.initial_rung != 0:
                raise ValueError(
                    f"initial_rung={ecfg.initial_rung} needs a "
                    "PolicyLadder; a fixed-policy engine has only rung 0")
            self._rung_policies = [ecfg.policy]
            self._rung_sp = [sp]
        # per-rung per-phase static policies, derived once so equal
        # phases reuse equal (hash-equal) jit cache keys
        self._rung_phases = [
            (pol.for_phase("prefill_dense"), pol.for_phase("prefill_sparse"),
             pol.for_phase("decode")) for pol in self._rung_policies]
        self._rung = ecfg.initial_rung if ladder is not None else 0
        # injected clock: every engine time read goes through
        # self.clock.now(site).  Default is the shared SYSTEM_CLOCK
        # singleton (`is`-identity testable, zero-cost); a flight
        # recorder wraps it so each observation is captured, and replay
        # substitutes a ReplayClock feeding recorded stamps back.
        if clock is None:
            clock = obs.SYSTEM_CLOCK
        elif not hasattr(clock, "now"):
            raise TypeError(
                f"clock must expose now(site), got {type(clock)!r}")
        self.clock = clock
        if self.obs.flight is not None:
            self.clock = self.obs.flight.attach_engine(self)
        self.controller = None
        if ecfg.slo is not None:
            self.controller = AdaptiveController(
                len(self._rung_policies), ecfg.slo,
                initial_rung=self._rung)
        mixers = {m for m, _ in cfg.layer_kinds()}
        chunkable = mixers <= set(_CHUNKABLE_MIXERS)
        if ecfg.spec is not None:
            if ladder is None:
                raise ValueError(
                    "EngineConfig.spec needs a PolicyLadder: the drafter "
                    "and verifier are ladder rungs")
            if ecfg.slo is not None:
                raise ValueError(
                    "spec and slo are mutually exclusive: the spec "
                    "controller adapts gamma/drafter from acceptance, and "
                    "the verifier rung is pinned")
            if not chunkable:
                raise ValueError(
                    "speculative decoding needs plain-attention mixers "
                    f"(got {mixers}): the verify forward reuses the "
                    "chunked write-in-place path and rollback needs "
                    "full-length caches")
            if ecfg.spec.drafter_rung >= len(ladder):
                raise ValueError(
                    f"drafter_rung {ecfg.spec.drafter_rung} outside the "
                    f"{len(ladder)}-rung ladder")
            if ecfg.initial_rung != ecfg.spec.verifier_rung:
                raise ValueError(
                    "a spec engine serves at the verifier rung; set "
                    f"initial_rung == verifier_rung "
                    f"({ecfg.spec.verifier_rung})")
            ver_pol = self._rung_phases[ecfg.spec.verifier_rung][2]
            if not ver_pol.is_dense:
                raise ValueError(
                    f"verifier rung {ecfg.spec.verifier_rung} decodes "
                    "under a sparse policy; the token-parity guarantee "
                    "needs a dense verifier — shared top-k saliency "
                    "depends on the call's token rows, so a multi-token "
                    "verify forward and single-token decode would pick "
                    "different channel sets and diverge")
        # the pool holds slack past max_len: pad tokens of a request's
        # final prefill chunk land in [max_len, pool_len-1), and the last
        # position is scratch — inactive slots in a decode step must still
        # write *somewhere*, and every real position (< max_len) may
        # belong to a mid-prefill prompt span that a garbage write would
        # corrupt.  Scratch is beyond every reachable position, so the
        # decode valid-mask never admits it.  Spec decoding needs the
        # slack to also fit a (gamma+1)-token verify window (inactive-slot
        # windows and draft overshoot past a request's budget both land
        # there).
        slack = ecfg.prefill_chunk
        if ecfg.spec is not None:
            slack = max(slack, ecfg.spec.max_gamma + 1)
        self.pool_len = ecfg.max_len + slack
        self.pool = SlotKVPool(cfg, ecfg.max_slots, self.pool_len)
        self.scheduler = Scheduler(ecfg.scheduler)
        self._preemptible = (ecfg.scheduler is not None
                             and ecfg.scheduler.preemption)
        if self._preemptible and not self.pool.can_cache_prefix:
            raise ValueError(
                "preemption needs full-length self-attention caches: "
                "suspend/resume snapshots slice the kv_seq axis by "
                "absolute position (same precondition as the prefix "
                "cache and rollback)")
        self.stats = EngineStats()
        self.states: Dict[int, RequestState] = {}
        self._next_id = 0
        self._closed = False
        self._decode_traces = 0      # python-side retrace counter
        self._chunk_traces = 0
        self._warm_traces: Optional[int] = None
        # per step program ("decode", "chunk", "verify"): how its sparse
        # projections read their weights, recorded while it traces
        self.sparse_weight_feeds: Dict[str, Dict[str, int]] = {}

        if ecfg.prefill_strategy == "auto":
            self.prefill_strategy = "chunked" if chunkable else "whole"
        else:
            if ecfg.prefill_strategy == "chunked" and not chunkable:
                raise ValueError(
                    f"chunked prefill needs plain-attention mixers, got {mixers}")
            self.prefill_strategy = ecfg.prefill_strategy

        self.prefix_cache: Optional[PrefixCache] = None
        if ecfg.prefix_cache:
            if self.prefill_strategy != "chunked":
                raise ValueError(
                    "prefix_cache needs the chunked prefill strategy "
                    f"(this arch resolved to {self.prefill_strategy!r}): "
                    "rolling-window/SSM caches cannot resume mid-prompt")
            # bit-exact reuse needs every rung's *effective* prefill
            # policy to be independent of the prompt length and
            # prefix-deterministic — otherwise a cached prefix would
            # differ from what a cold prefill of the reusing request
            # would have computed.  A multi-rung engine must prefill
            # *dense*: rung sp trees differ, so even the per-token
            # "mask" backend would make cached KV rung-dependent.
            effective = [self._effective_prefill_policy(r)
                         for r in range(len(self._rung_phases))]
            if len(effective) > 1:
                if not all(p.is_dense for p in effective):
                    raise ValueError(
                        "prefix_cache on a ladder engine needs every "
                        "rung to prefill dense (a prefix cached at one "
                        "rung seeds requests served at any rung, and "
                        "rung sp trees differ); build the ladder with "
                        "dense_phases=('prefill_dense', 'prefill_sparse')")
            elif not effective[0].prefix_deterministic():
                raise ValueError(
                    f"prefix_cache needs a prefix-deterministic prefill "
                    f"policy (per-token backends 'off'/'mask'), got "
                    f"{effective[0].backend!r}: shared top-k saliency "
                    "depends on the call's token rows, so cached KV "
                    "would bake in the donor request's chunking and "
                    "break the token-parity guarantee")
            self.prefix_cache = PrefixCache(
                self.pool, ecfg.prefill_chunk, ecfg.prefix_cache_tokens,
                stats_fn=lambda: self.stats, obs_fn=lambda: self.obs)

        def _on_decode_trace(feeds):
            self._decode_traces += 1        # runs only while tracing
            self._record_feeds("decode", feeds)
            self._record_compile("decode")

        def _on_chunk_trace(feeds):
            self._chunk_traces += 1
            self._record_feeds("chunk", feeds)
            self._record_compile("prefill_chunk")

        self._dstep, self._cstep, self._pstep = make_engine_steps(
            cfg, on_decode_trace=_on_decode_trace,
            on_chunk_trace=_on_chunk_trace)

        self.spec_decoder: Optional[SpecDecoder] = None
        if ecfg.spec is not None:
            self.spec_decoder = SpecDecoder(self, ecfg.spec)

        if self.controller is not None or self.spec_decoder is not None \
                or self.prefix_cache is not None or self._preemptible \
                or self.obs.quality is not None:
            self.warmup()

    # ------------------------------------------------------------------
    # ladder rungs
    # ------------------------------------------------------------------
    @property
    def rung(self) -> int:
        return self._rung

    @property
    def num_rungs(self) -> int:
        return len(self._rung_policies)

    @property
    def policy(self) -> SparsityPolicy:
        """The currently active rung's policy."""
        return self._rung_policies[self._rung]

    @property
    def sp(self):
        return self._rung_sp[self._rung]

    def set_rung(self, i: int) -> None:
        if not 0 <= i < self.num_rungs:
            raise ValueError(f"rung {i} outside [0, {self.num_rungs})")
        self._rung = i

    def _effective_prefill_policy(self, rung: int) -> SparsityPolicy:
        """The one policy every prefill chunk of ``rung`` runs under —
        well-defined only when the §5.1 phase split cannot produce
        prompt-length-dependent KV (the prefix-cache precondition)."""
        pd, ps, _ = self._rung_phases[rung]
        f = self.ecfg.prefill_dense_frac
        if f >= 1.0:
            return pd
        if f <= 0.0:
            return ps
        if pd != ps:
            raise ValueError(
                f"prefix_cache with prefill_dense_frac={f} needs rung "
                f"{rung}'s prefill_dense and prefill_sparse phase "
                "policies to be equal: the dense/sparse boundary scales "
                "with the prompt length, so a cached prefix would carry "
                "a different phase split than a cold prefill of the "
                "reusing request (set prefill_dense_frac to 0 or 1, or "
                "make both phases dense)")
        return pd

    def warmup(self) -> None:
        """Precompile every rung's decode (and chunked-prefill) phase
        executables — plus, under spec decoding, the verifier's verify
        executable for every reachable draft length gamma, and, under
        prefix caching, the segment extract/copy executable for every
        quantized prefix length — then zero the post-warmup retrace
        baseline.  Only valid on an idle engine: the
        warmup chunk writes garbage into slot 0's cache prefix, which is
        harmless *before* any admission (the slot's real prefill
        overwrites it) but would corrupt a live request.  Rung and gamma
        switches after this never trace
        (``decode_retraces_after_warmup`` stays 0) — except whole-prompt
        prefill executables, which are keyed on prompt length and cannot
        be precompiled here; on "whole"-strategy archs (SSM/local
        mixers) a rung switch can still compile a fresh prefill, decode
        stays retrace-free."""
        if self.scheduler.has_work() or self.pool.num_occupied:
            raise RuntimeError(
                "warmup() on a busy engine would corrupt live KV state; "
                "call it before submitting requests")
        S = self.ecfg.max_slots
        C = self.ecfg.prefill_chunk
        tokens = jnp.zeros((S,), jnp.int32)
        positions = jnp.full((S,), self.pool_len - 1, jnp.int32)
        inactive = jnp.zeros((S,), jnp.float32)
        for (pd, ps, dec), sp in zip(self._rung_phases, self._rung_sp):
            logits, self.pool.caches = self._dstep(
                self.params, tokens, positions, self.pool.caches, sp,
                inactive, policy=dec)
            logits.block_until_ready()
            if self.prefill_strategy == "chunked":
                for pol in (pd, ps):
                    logits, self.pool.caches = self._cstep(
                        self.params, jnp.zeros((1, C), jnp.int32),
                        jnp.zeros((1,), jnp.int32), jnp.int32(0),
                        self.pool.caches, sp, jnp.zeros((C,), jnp.float32),
                        policy=pol)
                    logits.block_until_ready()
        if self.spec_decoder is not None:
            sd = self.spec_decoder
            _, _, ver_pol = self._rung_phases[sd.verifier_rung]
            ver_sp = self._rung_sp[sd.verifier_rung]
            for g in self.ecfg.spec.gammas():
                logits, self.pool.caches = sd._vstep(
                    self.params, jnp.zeros((S, g + 1), jnp.int32),
                    jnp.full((S,), self.pool_len - (g + 1), jnp.int32),
                    self.pool.caches, ver_sp,
                    jnp.zeros((S, g + 1), jnp.float32), policy=ver_pol)
                logits.block_until_ready()
        if self.prefix_cache is not None:
            # segment extract/copy executables for every reachable
            # quantized length — the first hit/publish must not stall
            # live traffic on a compile.  Suspend/resume reuse the same
            # executables at the same quantized lengths, so this sweep
            # covers preemption too.
            self.prefix_cache.warm(self.ecfg.max_len - 1)
        elif self._preemptible:
            # no prefix cache, but preemption still needs the chunk-
            # quantized extract/write executables precompiled so a
            # serving-time suspend/resume never stalls on a trace
            self.pool.warm_segments(self.ecfg.prefill_chunk,
                                    self.ecfg.max_len - 1)
        if self.obs.quality is not None:
            # builds + precompiles the shadow-probe and reconstruction
            # executables — before the retrace baseline below, so those
            # compiles count as warmup, and live probing never traces
            self.obs.quality.attach(self)
        self._warm_traces = (
            self._decode_traces, self._chunk_traces,
            self.spec_decoder._verify_traces
            if self.spec_decoder is not None else 0,
            self.pool._segment_traces)

    @property
    def decode_retraces_after_warmup(self) -> Optional[int]:
        """Decode (re)traces since :meth:`warmup`; None before warmup.
        The adaptive-serving invariant is that this stays 0 no matter how
        often the controller switches rungs (draft steps included — they
        run through the same decode executable at the drafter rung)."""
        if self._warm_traces is None:
            return None
        return self._decode_traces - self._warm_traces[0]

    @property
    def chunk_retraces_after_warmup(self) -> Optional[int]:
        """Chunked-prefill (re)traces since :meth:`warmup`; None before
        warmup.  Stays 0: warmup compiles every rung's dense and sparse
        prefill phase."""
        if self._warm_traces is None:
            return None
        return self._chunk_traces - self._warm_traces[1]

    @property
    def verify_retraces_after_warmup(self) -> Optional[int]:
        """Spec verify (re)traces since :meth:`warmup`; None before warmup
        or without spec decoding.  Stays 0 across gamma switches — every
        reachable gamma's verify executable precompiles at warmup."""
        if self._warm_traces is None or self.spec_decoder is None:
            return None
        return self.spec_decoder._verify_traces - self._warm_traces[2]

    @property
    def probe_retraces_after_warmup(self) -> Optional[int]:
        """Quality probe/recon (re)traces since :meth:`warmup`; None
        without an armed :class:`repro.obs.quality.QualityMonitor`.
        Stays 0 under live probing — both quality executables precompile
        at warmup with the shapes the hot path uses."""
        q = self.obs.quality
        if q is None or not q.armed:
            return None
        return q.retraces_after_warmup

    @property
    def segment_retraces_after_warmup(self) -> Optional[int]:
        """Segment extract/write (re)traces since :meth:`warmup`; None
        before warmup.  Covers both prefix-cache hits/publishes and
        preemption suspend/resume — warmup precompiles every
        chunk-quantized length, so this stays 0 under live traffic."""
        if self._warm_traces is None:
            return None
        return self.pool._segment_traces - self._warm_traces[3]

    # ------------------------------------------------------------------
    # telemetry plumbing
    # ------------------------------------------------------------------
    def _record_feeds(self, program: str, feeds: Dict[str, int]) -> None:
        """Called while ``program`` traces: keep how its sparse
        projections read their weights — ``in_place`` from the layer
        stack, or ``sliced`` through a per-layer copy — for
        :attr:`sparse_weight_feeds`.  A trace with no sparse projection
        (a dense phase) records nothing."""
        if any(feeds.values()):
            self.sparse_weight_feeds[program] = dict(feeds)

    def _record_compile(self, phase: str) -> None:
        """Called from inside the jitted wrappers — runs only while XLA
        is (re)tracing, so every emission is one compile record.  A
        compile after warmup is a retrace (the bug the
        ``decode_retraces_after_warmup == 0`` invariant guards), flagged
        so the event log shows *which* executable broke the discipline."""
        ev = self.obs.events
        if ev is not None:
            ev.emit("compile", phase=phase, rung=self._rung,
                    post_warmup=self._warm_traces is not None)

    def metrics_exposition(self) -> str:
        """This engine's live stats in Prometheus text-exposition format
        (built per call, off the hot path — see
        :func:`repro.obs.metrics.engine_registry`)."""
        return obs.engine_exposition(self)

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, eos_id: Optional[int] = None,
               arrival_time: Optional[float] = None,
               on_token=None, *, priority: Priority = Priority.STANDARD,
               tenant: str = "default",
               queue_deadline_s: Optional[float] = None,
               on_finish=None) -> RequestState:
        if self._closed:
            raise RuntimeError("submit() on a closed engine")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or prompt.size >= self.ecfg.max_len:
            raise ValueError(
                f"prompt length {prompt.size} outside (0, {self.ecfg.max_len})")
        priority = (Priority.parse(priority) if isinstance(priority, str)
                    else Priority(priority))
        if queue_deadline_s is not None and queue_deadline_s <= 0:
            raise ValueError(
                f"queue_deadline_s must be positive, got {queue_deadline_s}")
        fr = self.obs.flight
        if fr is not None:
            # submit-intent first, then its clock read(s), then the
            # decision — the replay driver re-issues the call verbatim
            # when it meets this record at the shared cursor
            fr.record_submit(prompt, max_new_tokens, eos_id, arrival_time,
                             priority, tenant, queue_deadline_s)
        if not self.scheduler.can_accept():
            self.stats.rejected += 1
            retry = self._retry_after()
            if self.obs.events is not None:
                self.obs.events.emit(
                    "reject", reason="queue_full",
                    queue_depth=self.scheduler.queue_depth,
                    retry_after_s=round(retry, 3))
            if fr is not None:
                fr.decision("reject", reason="queue_full",
                            queue_depth=self.scheduler.queue_depth,
                            retry_after_s=round(retry, 3))
            raise QueueFull(
                f"admission queue at capacity "
                f"({self.scheduler.cfg.max_queue})", retry_after=retry)
        max_new = min(max_new_tokens, self.ecfg.max_len - prompt.size)
        req = Request(self._next_id, prompt, max_new,
                      eos_id if eos_id is not None else self.ecfg.eos_id,
                      self._now("submit.arrival") if arrival_time is None
                      else arrival_time,
                      priority=priority, tenant=tenant,
                      queue_deadline_s=queue_deadline_s)
        self._next_id += 1
        rs = RequestState(req, on_token=on_token, on_finish=on_finish)
        self.states[req.request_id] = rs
        self.scheduler.enqueue(rs)
        self.stats.submitted += 1
        tr = self.obs.tracer
        if tr is not None:
            tr.thread_name(req.request_id + 1, f"req {req.request_id}")
            tr.instant("submit", tid=req.request_id + 1,
                       request=req.request_id, prompt_len=req.prompt_len,
                       max_new_tokens=max_new, priority=priority.name.lower(),
                       tenant=tenant)
        return rs

    def _retry_after(self) -> float:
        """Polite-client 429 hint: roughly how long until queued work
        ahead drains — queued requests × observed mean tokens-per-request
        × mean inter-token gap, floored at 1s (and at 1s before any
        traffic has calibrated the means)."""
        s = self.stats
        tokens_per_req = s.decode_tokens / s.finished if s.finished else 0.0
        gap = s.tpot_s.mean if s.tpot_s.count else 0.0
        return max(1.0, self.scheduler.queue_depth * tokens_per_req * gap)

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def step(self) -> str:
        """Admit (expiring, resuming and preempting as the scheduler
        config allows), then run one scheduler-chosen phase step."""
        with self.obs.annotate("repro/step"):
            with self.obs.annotate("repro/admit"):
                self._admit()
            self.stats.sample(self.scheduler.queue_depth,
                              self.pool.num_occupied)
            if self.obs.tracer is not None:
                self.obs.tracer.counter(
                    "engine_load", queue_depth=self.scheduler.queue_depth,
                    occupancy=self.pool.num_occupied)
            action = self.scheduler.next_action()
            if action == "prefill":
                if self.prefill_strategy == "chunked":
                    self._prefill_chunk(self.scheduler.prefill_head())
                else:
                    self._prefill_whole(self.scheduler.prefill_group())
            elif action == "decode":
                if self.spec_decoder is not None:
                    self.spec_decoder.step()
                else:
                    self._decode_step()
        return action

    def run(self) -> Dict[int, List[int]]:
        """Drive until idle; returns {request_id: generated tokens}."""
        while self.scheduler.has_work():
            self.step()
        return {rid: rs.tokens for rid, rs in self.states.items()}

    # ------------------------------------------------------------------
    # admission, preemption, resume
    # ------------------------------------------------------------------
    def _admit(self) -> None:
        """One admission pass: expire deadline-missed queued requests,
        then fill free slots — resuming suspended requests and admitting
        queued ones in priority order, suspending a strictly less
        important decoding victim when preemption is armed and the pool
        is full.  Runs before every phase step, i.e. always at a
        committed KV boundary (see the module docstring)."""
        sched = self.scheduler
        now = self._now("admit.sweep")
        for rs in sched.expire(now):
            self._expire(rs, now)
        while True:
            rs_s = sched.peek_resume()
            head_p = sched.head_priority()
            if rs_s is None and head_p is None:
                return
            # a suspended request outranks a queued one of the same
            # class: it arrived earlier and already holds partial work
            take_suspended = rs_s is not None and (
                head_p is None or rs_s.request.priority <= head_p)
            target_p = rs_s.request.priority if take_suspended else head_p
            if self.pool.num_free == 0:
                victim = (sched.pick_victim(target_p)
                          if self._preemptible else None)
                if victim is None:
                    return
                self._preempt(victim)
            if take_suspended:
                self._resume(sched.pop_resume())
            else:
                self._admit_queued(sched.pop_admit(), now)

    def _expire(self, rs: RequestState, now: float) -> None:
        req = rs.request
        rs.finish_reason = FinishReason.EXPIRED
        rs.finish_time = now
        rs.status = Status.FINISHED
        self.stats.expired += 1
        waited = now - req.arrival_time
        if self.obs.events is not None:
            self.obs.events.emit(
                "reject", reason="deadline", request=req.request_id,
                waited_s=round(waited, 4),
                deadline_s=req.queue_deadline_s)
        if self.obs.tracer is not None:
            self.obs.tracer.instant(
                "expire", tid=req.request_id + 1, waited_s=waited)
        fr = self.obs.flight
        if fr is not None:
            fr.decision("reject", reason="deadline", request=req.request_id,
                        waited_s=round(waited, 4),
                        deadline_s=req.queue_deadline_s)
            fr.finish(req.request_id, rs.finish_reason.value,
                      rs.tokens, rs.token_rungs)
        rs.finished()

    def _admit_queued(self, rs: RequestState, now: float) -> None:
        rs.slot = self.pool.alloc()
        if self.prefix_cache is not None:
            self.prefix_cache.admit(rs)     # hit: cursor jumps past the
        rs.status = Status.PREFILL          # cached prefix
        self.scheduler.prefilling.append(rs)
        self.stats.observe_queue_wait(max(0.0, now - rs.request.arrival_time))
        if self.obs.tracer is not None:
            self.obs.tracer.instant(
                "admit", tid=rs.request.request_id + 1, slot=rs.slot,
                cached_prefix=rs.next_offset,
                priority=rs.request.priority.name.lower())

    def _preempt(self, victim: RequestState) -> None:
        """Suspend a decoding victim: snapshot its KV state to host
        memory at a chunk-quantized length (warmup-precompiled — no
        trace) and free the slot.  Admission-boundary only: the slot's
        KV length equals the victim's committed position, which is what
        makes the later resume bit-identical."""
        t = self._now("preempt")
        req = victim.request
        slot = victim.slot
        seg = self.pool.suspend(slot, self.ecfg.prefill_chunk)
        if seg.length != victim.position:
            raise RuntimeError(
                f"preempt: slot {slot} KV length {seg.length} != request "
                f"{req.request_id} position {victim.position}; suspension "
                "must happen at a committed boundary")
        self.scheduler.suspend(victim)      # pops decoding via the slot
        self.pool.free(slot)
        victim.suspended = seg
        victim.suspend_time = t
        victim.preemptions += 1
        victim.slot = -1
        self.stats.preemptions += 1
        if self.obs.events is not None:
            self.obs.events.emit(
                "preempt", t=t, request=req.request_id, slot=slot,
                kv_length=seg.length, kv_phys=seg.phys,
                priority=req.priority.name.lower(),
                tokens_done=len(victim.tokens))
        if self.obs.tracer is not None:
            self.obs.tracer.instant(
                "preempt", t=t, tid=req.request_id + 1, slot=slot,
                kv_length=seg.length)
        fr = self.obs.flight
        if fr is not None:
            fr.decision("preempt", request=req.request_id, slot=slot,
                        kv_length=seg.length,
                        tokens_done=len(victim.tokens))

    def _resume(self, rs: RequestState) -> None:
        """Restore a suspended request into a freshly allocated slot:
        write the host-side segment back (same precompiled executable
        set) and rejoin the decoding set at the exact committed
        position — generation continues bit-identically."""
        t = self._now("resume")
        req = rs.request
        slot = self.pool.alloc()
        self.pool.resume(rs.suspended, slot)
        kv_length = rs.suspended.length
        rs.suspended = None
        rs.slot = slot
        rs.status = Status.DECODE
        self.scheduler.decoding[slot] = rs
        self.stats.resumes += 1
        suspended_s = None
        if rs.suspend_time is not None:
            suspended_s = t - rs.suspend_time
            self.stats.observe_preempted(suspended_s)
            rs.suspend_time = None
        if self.obs.events is not None:
            self.obs.events.emit(
                "resume", t=t, request=req.request_id, slot=slot,
                kv_length=kv_length,
                suspended_s=None if suspended_s is None
                else round(suspended_s, 4))
        if self.obs.tracer is not None:
            self.obs.tracer.instant(
                "resume", t=t, tid=req.request_id + 1, slot=slot,
                kv_length=kv_length)
        fr = self.obs.flight
        if fr is not None:
            fr.decision("resume", request=req.request_id, slot=slot,
                        kv_length=kv_length)

    # ------------------------------------------------------------------
    # phases
    # ------------------------------------------------------------------
    def _phase_policy(self, offset: int, prompt_len: int) -> SparsityPolicy:
        """§5.1: chunks starting before the dense boundary run dense."""
        pd, ps, _ = self._rung_phases[self._rung]
        dense_end = int(np.ceil(prompt_len * self.ecfg.prefill_dense_frac))
        return pd if offset < dense_end else ps

    def _emit(self, rs: RequestState, token: int) -> None:
        rs.emit(token)
        if self.ladder is not None:
            rs.token_rungs.append(self._rung)
        self.stats.decode_tokens += 1

    def _prefill_chunk(self, rs: RequestState) -> None:
        C = self.ecfg.prefill_chunk
        req = rs.request
        rid = req.request_id
        off = rs.next_offset
        real = min(C, req.prompt_len - off)
        with self.obs.annotate("repro/prefill_chunk/prepare"):
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :real] = req.prompt[off:off + real]
            weights = np.zeros((C,), np.float32)
            weights[:real] = 1.0
            policy = self._phase_policy(off, req.prompt_len)
        t0 = self._now("prefill_chunk.t0")
        with self.obs.annotate("repro/prefill_chunk", rid, slot=rs.slot,
                               offset=off, tokens=real, rung=self._rung):
            with self.obs.annotate("repro/prefill_chunk/launch"):
                logits, self.pool.caches = self._cstep(
                    self.params, jnp.asarray(chunk),
                    jnp.full((1,), off, jnp.int32),
                    jnp.int32(rs.slot), self.pool.caches, self.sp,
                    jnp.asarray(weights), policy=policy)
            with self.obs.annotate("repro/prefill_chunk/readback"):
                logits.block_until_ready()
        t1 = self._now("prefill_chunk.t1")
        dt = t1 - t0
        self.stats.prefill_time += dt
        self.stats.observe_prefill_step(dt)
        self.stats.prefill_chunks += 1
        self.stats.prefill_tokens += real
        rs.next_offset = off + real
        self.pool.lengths[rs.slot] = rs.next_offset
        if rs.done_prefill:
            if self.prefix_cache is not None:
                # release the admission pin and cache this prompt's
                # prefix before decode can extend the slot
                self.prefix_cache.publish(rs)
            with self.obs.annotate("repro/prefill_chunk/first_token", rid):
                first = int(np.asarray(jnp.argmax(logits[0, real - 1])))
            with self.obs.annotate("repro/prefill_chunk/emit"):
                self._start_decode(rs, first)

    def _prefill_whole(self, group: List[RequestState]) -> None:
        P = group[0].request.prompt_len
        tokens = np.stack([rs.request.prompt for rs in group])
        # whole-prompt prefill can't split tokens by phase: any dense
        # fraction > 0 makes the whole prompt dense (the conservative
        # accuracy choice, matching the legacy serve path)
        pd, ps, _ = self._rung_phases[self._rung]
        policy = ps if self.ecfg.prefill_dense_frac <= 0.0 else pd
        t0 = self._now("prefill_whole.t0")
        with self.obs.annotate("repro/prefill_whole", prompt_len=P,
                               batch=len(group), rung=self._rung):
            logits, caches = self._pstep(self.params, jnp.asarray(tokens),
                                         self.sp, policy=policy)
            logits.block_until_ready()
        t1 = self._now("prefill_whole.t1")
        dt = t1 - t0
        self.stats.prefill_time += dt
        self.stats.observe_prefill_step(dt)
        self.stats.prefill_chunks += 1
        self.stats.prefill_tokens += P * len(group)
        first = np.asarray(jnp.argmax(logits, axis=-1))
        for b, rs in enumerate(group):
            self.pool.insert(caches, b, rs.slot, P)
            rs.next_offset = P
            self._start_decode(rs, int(first[b]))

    def _start_decode(self, rs: RequestState, first_token: int) -> None:
        rs.first_token_time = self._now("first_token")
        rs.last_token_time = rs.first_token_time
        self.stats.observe_ttft(
            rs.first_token_time - rs.request.arrival_time)
        if self.obs.tracer is not None:
            self.obs.tracer.instant(
                "first_token", t=rs.first_token_time,
                tid=rs.request.request_id + 1, slot=rs.slot,
                ttft_s=rs.first_token_time - rs.request.arrival_time)
        self._emit(rs, first_token)
        self.scheduler.to_decode(rs)
        self._maybe_finish(rs, first_token)

    def _decode_step(self) -> None:
        S = self.ecfg.max_slots
        decoding = self.scheduler.decoding
        with self.obs.annotate("repro/decode/prepare"):
            tokens = np.zeros((S,), np.int32)
            # inactive slots write their garbage token at the scratch
            # position (see pool_len above); their logits are ignored
            # host-side and their saliency weight is zero
            positions = np.full((S,), self.pool_len - 1, np.int32)
            active = np.zeros((S,), np.float32)
            for slot, rs in decoding.items():
                tokens[slot] = rs.last_token
                positions[slot] = rs.position
                active[slot] = 1.0
        _, _, dec_policy = self._rung_phases[self._rung]
        # shadow dense quality probe (sampled): runs *before* the real
        # decode so its K/V writes land exactly on the positions the
        # serving-policy step below overwrites — served tokens and cache
        # are bit-identical to a probe-free run, and the probe stays
        # outside the timed decode region so step stats are unchanged
        q = self.obs.quality
        probe = None
        if q is not None and q.should_probe():
            probe = q.run_probe(self, tokens, positions, active)
        t0 = self._now("decode.t0")
        with self.obs.annotate("repro/decode", active=len(decoding),
                               rung=self._rung):
            with self.obs.annotate("repro/decode/launch"):
                logits, self.pool.caches = self._dstep(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(positions), self.pool.caches, self.sp,
                    jnp.asarray(active), policy=dec_policy)
            with self.obs.annotate("repro/decode/readback"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
        t1 = self._now("decode.t1")
        self.stats.decode_time += t1 - t0
        self.stats.observe_decode_step(t1 - t0)
        self.stats.decode_steps += 1
        with self.obs.annotate("repro/decode/emit"):
            self._decode_emit(decoding, nxt, logits, active, probe, t1)

    def _decode_emit(self, decoding, nxt, logits, active, probe,
                     t1: float) -> None:
        """A decode step's host side after its readback: each slot's
        token (the client's callbacks), commit and finish, the quality
        probe's observation and the controller."""
        q = self.obs.quality
        gaps = []
        for slot, rs in list(decoding.items()):
            tok = int(nxt[slot])
            if rs.last_token_time is not None:
                gaps.append(t1 - rs.last_token_time)
                self.stats.observe_tpot(gaps[-1])
            rs.last_token_time = t1
            self._emit(rs, tok)
            self.pool.commit(slot, 1)
            self._maybe_finish(rs, tok)
        if q is not None and probe is not None:
            q.observe(self, probe, logits, nxt, active, t1)
        if self.controller is not None:
            be_frac = None
            if self.controller.slo.priority_aware:
                be_frac = (sum(
                    1 for rs in decoding.values()
                    if rs.request.priority == Priority.BEST_EFFORT
                ) / len(decoding)) if decoding else 0.0
            qp = None
            if self.controller.slo.quality_aware and q is not None \
                    and q.armed:
                qp = q.pressure
            new_rung = self.controller.update(
                gaps, queue_depth=self.scheduler.queue_depth,
                occupancy=self.pool.num_occupied,
                best_effort_frac=be_frac, quality_pressure=qp)
            if new_rung != self._rung:
                old = self._rung
                self.set_rung(new_rung)
                tr = self.controller.transitions[-1] \
                    if self.controller.transitions else None
                reason = tr[3] if tr is not None else None
                if self.obs.events is not None:
                    self.obs.events.emit(
                        "rung_switch", t=t1, from_rung=old,
                        to_rung=new_rung, reason=reason,
                        controller_step=self.controller.step,
                        queue_depth=self.scheduler.queue_depth)
                if self.obs.tracer is not None:
                    self.obs.tracer.instant(
                        "rung_switch", t=t1, from_rung=old,
                        to_rung=new_rung, reason=reason)
                fr = self.obs.flight
                if fr is not None:
                    fr.decision("rung_switch", from_rung=old,
                                to_rung=new_rung, reason=reason,
                                controller_step=self.controller.step,
                                queue_depth=self.scheduler.queue_depth)

    def _maybe_finish(self, rs: RequestState, token: int) -> None:
        req = rs.request
        if req.eos_id is not None and token == req.eos_id:
            rs.finish_reason = FinishReason.EOS
        elif len(rs.tokens) >= req.max_new_tokens:
            rs.finish_reason = FinishReason.MAX_TOKENS
        else:
            return
        rs.finish_time = self._now("finish")
        if self.obs.tracer is not None:
            self.obs.tracer.instant(
                "finish", t=rs.finish_time,
                tid=req.request_id + 1, slot=rs.slot,
                reason=rs.finish_reason.value,
                tokens=len(rs.tokens))
        fr = self.obs.flight
        if fr is not None:
            fr.finish(req.request_id, rs.finish_reason.value,
                      rs.tokens, rs.token_rungs)
        self.scheduler.finish(rs)
        self.pool.free(rs.slot)
        self.stats.finished += 1
        rs.finished()

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """One metrics record (JSONL-friendly): engine load, latency
        signals and — under a controller — rung state.  Versioned via
        ``schema_version`` (see :data:`SNAPSHOT_SCHEMA_VERSION`) so
        downstream metric consumers can detect format changes."""
        s = self.stats
        out = {
            "schema_version": SNAPSHOT_SCHEMA_VERSION,
            # raw out-of-band read, NOT self._now(): observability reads
            # must never consume records from a ReplayClock stream
            "t": obs.now(),
            "queue_depth": self.scheduler.queue_depth,
            "occupancy": self.pool.num_occupied,
            "submitted": s.submitted,
            "finished": s.finished,
            "decode_steps": s.decode_steps,
            "decode_tokens": s.decode_tokens,
            "decode_tps": round(s.decode_tps, 1),
            # v4: whole-run exact-histogram quantiles (bucket upper
            # bounds); *_window_s keeps the old recent-window estimate
            "tpot_p50_s": None if not s.tpot_hist
            else round(s.tpot_hist.quantile(50), 6),
            "tpot_p95_s": None if not s.tpot_hist
            else round(s.tpot_hist.quantile(95), 6),
            "tpot_p95_window_s": None if not s.tpot_s
            else round(s.window_tpot_p95(), 6),
        }
        if self.ladder is not None:
            out["rung"] = self._rung
            out["budget"] = self.ladder.budgets[self._rung]
        if self.controller is not None:
            out.update(self.controller.snapshot())
        if self.spec_decoder is not None:
            out.update(self.spec_decoder.snapshot())
            out["spec_accept_rate"] = round(
                s.spec_accepted_tokens / max(1, s.spec_draft_tokens), 4)
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.snapshot())
        if self.ecfg.scheduler is not None:
            out["suspended"] = len(self.scheduler.suspended)
            out["preemptions"] = s.preemptions
            out["resumes"] = s.resumes
            out["rejected"] = s.rejected
            out["expired"] = s.expired
            out["queue_wait_p95_s"] = None if not s.queue_wait_hist \
                else round(s.queue_wait_hist.quantile(95), 6)
        if self.obs.enabled:
            if self.obs.events is not None:
                out["telemetry_events"] = self.obs.events.count
            if self.obs.tracer is not None:
                out["telemetry_spans"] = len(self.obs.tracer)
        if self.obs.quality is not None and self.obs.quality.armed:
            out.update(self.obs.quality.snapshot())
        if self.obs.flight is not None:
            fr = self.obs.flight
            out["flight_records"] = fr.count
            out["flight_dropped"] = fr.dropped
            out["flight_dumps"] = len(fr.dumps)
        return out

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def reset_ids(self) -> None:
        """Restart this engine's request-id namespace at 0 and drop
        finished request states.  Benchmark reps reuse warm engines while
        replaying the same trace, and parity checks key on request id —
        resetting per rep keeps ids aligned across engines and reps.
        Only valid on an idle engine (no queued, in-flight or suspended
        requests)."""
        if self.scheduler.has_work() or self.pool.num_occupied:
            raise RuntimeError(
                "reset_ids() on a busy engine would orphan live requests")
        self._next_id = 0
        self.states = {}

    def close(self) -> None:
        """Flush and close the engine's telemetry sinks (event log,
        trace export, profiler session) so artifacts are never
        truncated.  Idempotent; further ``submit`` calls raise, but
        existing state stays readable.  Prefer the context-manager form
        (``with Engine(...) as eng:``) so sinks close even when the
        driving loop raises."""
        if self._closed:
            return
        self._closed = True
        self.obs.close()

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None and self.obs.flight is not None:
            # black-box trigger: the driving loop died — dump the ring
            # before the sinks close so the incident is capturable
            self.obs.flight.dump("exception")
        self.close()
        return False

    # ------------------------------------------------------------------
    def _now(self, site: str = "") -> float:
        """One engine clock read, tagged with its consuming call site —
        the flight recorder logs the tag next to each observation so a
        replay divergence names the exact site that desynchronized."""
        return self.clock.now(site)

    @property
    def decode_traces(self) -> int:
        """How many times the batched decode step has (re)traced."""
        return self._decode_traces
