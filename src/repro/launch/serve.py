"""Serving CLI: a thin driver over the continuous-batching engine
(``repro.serving``).

    PYTHONPATH=src python -m repro.launch.serve --arch llama31_8b --reduced \
        --sparsity 0.5 --prompt-len 64 --gen 32 --batch 4

``--arch`` builds the architecture at its published widths; ``--reduced``
swaps in the tiny same-family config (CPU runs) and ``--layers N`` keeps
only the first N layers.  The engine compiles every serving step before
the first request (``Engine.warmup``), and the summary names the
platform and device kind that served.

Implements the paper's serving recipe: sparsify (by default) only half of
the prefill tokens and all decode tokens (§5.1), with the per-token mask
backend for accuracy-faithful numerics or the batched top-k backends for
TPU-shaped execution.  Greedy decoding over the slot-pool KV-cache path;
``--legacy`` runs the original static-batch loop (kept as the numerics
reference — the engine matches it token-for-token for equal-length
prompts under the whole-prompt prefill strategy).

Adaptive serving: ``--ladder plan.npz`` loads a calibrated
``PolicyLadder`` artifact (see ``repro.sparsity.calibrate_ladder`` /
``examples/calibrate_and_serve.py``) and ``--slo-tpot-p95`` arms the
feedback controller that moves between rungs under load; ``--rung`` pins
one rung instead.  ``--metrics-out`` appends JSONL engine/controller
snapshots while the engine runs.

Speculative decoding: ``--spec-gamma N`` (with ``--ladder``) drafts N
tokens per verify at the ``--spec-drafter`` rung and verifies at the
pinned ``--rung`` — token-identical output to plain decode at that rung,
fewer verifier passes per token.  The verifier rung must decode dense
(rung 0 of a calibrated ladder); the engine rejects sparse verifiers,
whose shared top-k saliency would break the parity guarantee.
``--spec-adaptive`` lets the acceptance EWMA tune gamma at runtime.

Prefix caching: ``--prefix-cache`` arms radix-tree KV reuse across
requests sharing a prompt prefix (``repro.serving.prefix_cache``) —
admissions copy the matched prefix into their slot and prefill only the
un-cached suffix.  ``--prefix-cache-tokens N`` bounds the cached tokens
(LRU eviction; 0 = unbounded).  Requires chunked prefill and a
prefix-deterministic prefill policy (dense or ``mask``) — the engine
validates and the hit path stays token-identical to cold prefill.

Gateway: ``--gateway`` serves the asyncio HTTP/1.1 + SSE front door
(``repro.serving.gateway``) on ``--gateway-host``/``--gateway-port``
instead of replaying synthetic prompts — ``POST /v1/generate``
(streaming and non-streaming), ``GET /v1/health``, ``GET /metrics``.
``--max-queue`` bounds the admission queue (rejects surface as HTTP 429
with ``Retry-After``) and ``--preemption`` lets a more important
arrival suspend the least-important decoding request to host memory,
resuming it bit-identically once a slot frees up.  SIGTERM/Ctrl-C
stops accepting connections and drains in-flight requests.

Observability (``repro.obs``): ``--metrics-out`` appends JSONL
snapshots by default; ``--metrics-format prom`` instead rewrites the
file with a Prometheus text-exposition dump (textfile-collector style),
and ``--metrics-port`` serves the same text live at
``http://127.0.0.1:PORT/metrics``.  ``--trace-out`` writes a Chrome
trace-event JSON of per-request spans (load it in Perfetto or
``chrome://tracing``), ``--events-out`` streams the structured event
log (rung/gamma switches with reasons, prefix evictions, KV rollbacks,
compile records) as JSONL, and ``--profile-dir`` captures a JAX
profiler trace of the whole run.  Tokens are bit-identical with
telemetry on or off.

Quality monitoring: ``--quality-probe-rate R`` (R in (0, 1]) arms the
:class:`repro.obs.QualityMonitor` — it samples that fraction of decode
steps through a shadow dense probe (token agreement + top-k logit
overlap vs the dense reference), measures online block reconstruction
error against calibration baselines and watches saliency drift per
(block, rung).  Probes never
alter served tokens.  ``--quality-drift-threshold`` tunes the EWMA
saliency-overlap level below which a ``saliency_drift`` event fires.

Flight recorder (``repro.obs.flight``): ``--flight-record`` captures
every nondeterministic engine input (request submissions + clock
observations) and resulting decision into a bounded in-memory ring —
black-box mode, dumped on trigger (engine exception, SLO-breach
escalation, saliency-drift edge, SIGUSR1, or the gateway's
``GET /v1/debug/flight``) into ``--flight-dump-dir``.  Give
``--flight-record PATH`` to also stream the complete recording as JSONL
to PATH; that file replays bit-identically via
``python -m repro.obs.flight.replay PATH``.  ``--flight-ring`` sizes
the ring.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import serving_config
from repro.core import pipeline as wis_pipeline
from repro.data import DataConfig, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.models import api, model as M
from repro.sparsity import PolicyLadder, SparsityPolicy


def _pad_caches(cfg, caches, batch, total_len):
    import repro.models.params as P
    schema = api.cache_schema(cfg, batch, total_len)
    target = P.abstract_params(schema, cfg.dtype)

    def fit(src, dst):
        if src.shape == dst.shape:
            return src.astype(dst.dtype)
        pads = [(0, d - s) for s, d in zip(src.shape, dst.shape)]
        return jnp.pad(src, pads).astype(dst.dtype)

    return jax.tree_util.tree_map(fit, caches, target)


def generate(params, cfg, prompts, gen_tokens: int, sp_stacked=None, *,
             prefill_sparse_frac: float = 0.5, policy=None):
    """prompts: (B, P) int32.  Returns (B, gen_tokens) greedy tokens.

    ``policy``: the SparsityPolicy for the sparse phases (None = the
    paper-exact ``mask`` backend, which is dense-equivalent without
    calibrated thresholds in ``sp_stacked``)."""
    if policy is None:
        policy = SparsityPolicy.uniform("mask")
    B, P = prompts.shape
    total = P + gen_tokens

    # paper §5.1: sparsify only half the prefill tokens -> run the first
    # half dense, the second half sparse (per-token thresholds make this a
    # pure mask toggle; we approximate by prefilling dense, which is the
    # conservative accuracy choice, when no split point is given)
    prefill_sparse = prefill_sparse_frac >= 1.0
    logits, caches = M.forward(
        params, cfg, tokens=prompts, mode="prefill",
        sp=sp_stacked if prefill_sparse else None,
        policy=policy.for_phase(
            "prefill_sparse" if prefill_sparse else "prefill_dense"))
    caches = _pad_caches(cfg, caches, B, total)

    decode_policy = policy.for_phase("decode")
    decode = jax.jit(lambda p, b, sp: M.forward(
        p, cfg, tokens=b["tokens"], mode="decode", caches=b["caches"],
        positions=b["positions"], sp=sp, policy=decode_policy))

    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [toks]
    for i in range(gen_tokens - 1):
        positions = jnp.full((B,), P + i, jnp.int32)
        logits, caches = decode(
            params, {"tokens": toks, "caches": caches,
                     "positions": positions}, sp_stacked)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(toks)
    return jnp.stack(out, axis=1)


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI parser — exposed (with :func:`validate_args`) so
    tests can drive flag validation without spawning a process."""
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("--arch", default="llama31_8b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU runs) instead of "
                         "the published widths")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers (0 = all); widths "
                         "stay as configured")
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--mode", default="mask",
                    choices=["mask", "topk_shared", "topk_block", "pallas"])
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to submit")
    ap.add_argument("--calib-quick", action="store_true",
                    help="tiny-budget WiSparse calibration (CPU demo)")
    ap.add_argument("--legacy", action="store_true",
                    help="static-batch reference loop instead of the engine")
    ap.add_argument("--max-slots", type=int, default=0,
                    help="KV pool slots (0 = batch size)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="KV pool length (0 = prompt+gen)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk size (chunked strategy)")
    ap.add_argument("--prefill-strategy", default="auto",
                    choices=["auto", "chunked", "whole"])
    ap.add_argument("--sensitive-backend", default=None,
                    choices=["off", "mask"],
                    help="mixed per-block policy: run this backend on the "
                         "most sensitive blocks of a calibrated plan "
                         "(requires --calib-quick)")
    ap.add_argument("--sensitive-frac", type=float, default=0.25,
                    help="fraction of blocks treated as sensitive")
    ap.add_argument("--ladder", default=None,
                    help="PolicyLadder npz artifact for adaptive serving "
                         "(overrides --sparsity/--mode)")
    ap.add_argument("--rung", type=int, default=0,
                    help="ladder rung to start on (and to pin, without "
                         "--slo-tpot-p95)")
    ap.add_argument("--slo-tpot-p95", type=float, default=0.0,
                    help="target p95 inter-token latency in seconds; > 0 "
                         "arms the adaptive controller (needs --ladder)")
    ap.add_argument("--slo-max-queue", type=int, default=8,
                    help="queued requests beyond which the controller "
                         "escalates")
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="speculative decoding: draft tokens per verify "
                         "(> 0 arms spec decode; needs --ladder)")
    ap.add_argument("--spec-drafter", type=int, default=1,
                    help="ladder rung that drafts (must be sparser than "
                         "the verifier rung pinned by --rung)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="tune gamma from the acceptance EWMA at runtime")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="reuse KV across requests sharing a prompt "
                         "prefix (radix tree over token ids; needs "
                         "chunked prefill + dense/mask prefill policy)")
    ap.add_argument("--prefix-cache-tokens", type=int, default=0,
                    help="cached-token budget for --prefix-cache "
                         "(LRU eviction; 0 = unbounded)")
    ap.add_argument("--metrics-out", default=None,
                    help="write engine/controller metrics to this file "
                         "while serving (format per --metrics-format)")
    ap.add_argument("--metrics-every", type=int, default=16,
                    help="engine steps between metrics writes")
    ap.add_argument("--metrics-format", default="jsonl",
                    choices=["jsonl", "prom"],
                    help="--metrics-out format: append JSONL snapshots, "
                         "or rewrite a Prometheus text-exposition dump "
                         "(textfile-collector style)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="serve live Prometheus exposition at "
                         "http://127.0.0.1:PORT/metrics (0 = off)")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request spans as Chrome trace-event "
                         "JSON (Perfetto-loadable) to this file")
    ap.add_argument("--events-out", default=None,
                    help="stream the structured event log (rung/gamma "
                         "switches, evictions, rollbacks, compiles) as "
                         "JSONL to this file")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a JAX profiler trace of the run into "
                         "this directory")
    ap.add_argument("--quality-probe-rate", type=float, default=0.0,
                    help="sample this fraction of decode steps through a "
                         "shadow dense probe (token agreement, recon "
                         "error, saliency drift; "
                         "0 = off)")
    ap.add_argument("--quality-drift-threshold", type=float, default=None,
                    help="EWMA saliency-overlap level below which a "
                         "saliency_drift event fires, in (0, 1) (needs "
                         "--quality-probe-rate > 0; default 0.5)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve the HTTP/1.1 + SSE API front door "
                         "(repro.serving.gateway) instead of replaying "
                         "synthetic prompts; SIGTERM/Ctrl-C drains "
                         "in-flight requests before exiting")
    ap.add_argument("--gateway-host", default="127.0.0.1",
                    help="gateway listen address (needs --gateway)")
    ap.add_argument("--gateway-port", type=int, default=8080,
                    help="gateway listen port (0 = ephemeral; needs "
                         "--gateway)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: reject new submissions "
                         "(HTTP 429 + Retry-After through the gateway) "
                         "beyond this many queued requests (0 = unbounded)")
    ap.add_argument("--preemption", action="store_true",
                    help="suspend the least-important decoding request to "
                         "host memory when a more important arrival needs "
                         "its KV slot; the victim resumes bit-identically")
    ap.add_argument("--flight-record", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="arm the flight recorder (repro.obs.flight): "
                         "bare = black-box ring only; with PATH, also "
                         "stream the complete recording as JSONL to PATH "
                         "(replayable via python -m repro.obs.flight.replay)")
    ap.add_argument("--flight-ring", type=int, default=4096,
                    help="flight-recorder ring capacity in records "
                         "(needs --flight-record)")
    ap.add_argument("--flight-dump-dir", default=None,
                    help="directory for triggered black-box dumps "
                         "(exception / SLO breach / saliency drift / "
                         "SIGUSR1 / GET /v1/debug/flight; needs "
                         "--flight-record)")
    return ap


def validate_args(args) -> None:
    """Fail fast on bad flag combinations, before any model work.

    Every check here is driven purely by the parsed namespace; rung
    range checks need the loaded ladder and live in
    :func:`validate_rungs`.  Raises ``SystemExit`` with a message that
    names the offending flag and what to change."""
    if not 0.0 <= args.sparsity <= 1.0:
        raise SystemExit(f"--sparsity must be in [0, 1], got {args.sparsity}")
    if args.layers < 0:
        raise SystemExit(f"--layers must be >= 0, got {args.layers}")
    for name in ("prompt-len", "gen", "batch", "chunk"):
        v = getattr(args, name.replace("-", "_"))
        if v <= 0:
            raise SystemExit(f"--{name} must be > 0, got {v}")
    if args.rung < 0:
        raise SystemExit(f"--rung must be >= 0, got {args.rung}")
    if args.max_queue < 0:
        raise SystemExit(f"--max-queue must be >= 0, got {args.max_queue}")
    if args.sensitive_backend is not None and not args.calib_quick:
        raise SystemExit("--sensitive-backend needs a calibrated plan: "
                         "add --calib-quick")
    if args.slo_tpot_p95 > 0 and args.ladder is None:
        raise SystemExit("--slo-tpot-p95 needs --ladder: the controller "
                         "switches between ladder rungs")
    if args.rung != 0 and args.ladder is None:
        raise SystemExit("--rung needs --ladder: a fixed-policy engine "
                         "has only rung 0")
    if args.spec_gamma > 0:
        if args.ladder is None:
            raise SystemExit("--spec-gamma needs --ladder: the drafter "
                             "and verifier are ladder rungs")
        if args.slo_tpot_p95 > 0:
            raise SystemExit("--spec-gamma conflicts with --slo-tpot-p95: "
                             "spec decoding pins the verifier rung")
    elif args.spec_adaptive or args.spec_drafter != 1:
        raise SystemExit("--spec-drafter/--spec-adaptive need "
                         "--spec-gamma > 0 to arm speculative decoding")
    if args.prefix_cache and args.legacy:
        raise SystemExit("--prefix-cache needs the engine path, not "
                         "--legacy")
    if args.legacy and (args.trace_out or args.events_out
                        or args.metrics_port or args.metrics_out):
        raise SystemExit("telemetry flags (--trace-out/--events-out/"
                         "--metrics-*) need the engine path, not --legacy")
    if args.prefix_cache_tokens and not args.prefix_cache:
        raise SystemExit("--prefix-cache-tokens needs --prefix-cache to "
                         "arm the prefix cache")
    if args.quality_probe_rate < 0 or args.quality_probe_rate > 1:
        raise SystemExit(
            f"--quality-probe-rate must be in (0, 1], or 0 to disable "
            f"probing, got {args.quality_probe_rate}")
    if args.quality_probe_rate > 0 and args.legacy:
        raise SystemExit("--quality-probe-rate needs the engine path, "
                         "not --legacy")
    if args.quality_drift_threshold is not None:
        if args.quality_probe_rate <= 0:
            raise SystemExit("--quality-drift-threshold needs "
                             "--quality-probe-rate > 0 to arm the "
                             "quality monitor")
        if not 0.0 < args.quality_drift_threshold < 1.0:
            raise SystemExit(
                f"--quality-drift-threshold must be in (0, 1), got "
                f"{args.quality_drift_threshold}")
    if args.gateway:
        if args.legacy:
            raise SystemExit("--gateway needs the engine path, not "
                             "--legacy")
        if args.metrics_out:
            raise SystemExit("--gateway owns the engine loop; drop "
                             "--metrics-out and scrape GET /metrics "
                             "instead")
        if args.metrics_port:
            raise SystemExit("--gateway already serves /metrics on its "
                             "own port; drop --metrics-port")
        if args.gateway_port < 0:
            raise SystemExit(f"--gateway-port must be >= 0 "
                             f"(0 = ephemeral), got {args.gateway_port}")
    elif (args.gateway_host != "127.0.0.1" or args.gateway_port != 8080):
        raise SystemExit("--gateway-host/--gateway-port need --gateway "
                         "to start the API front door")
    if (args.max_queue or args.preemption) and args.legacy:
        raise SystemExit("--max-queue/--preemption need the engine path, "
                         "not --legacy")
    if args.flight_ring <= 0:
        raise SystemExit(f"--flight-ring must be > 0, got "
                         f"{args.flight_ring}")
    if args.flight_record is not None and args.legacy:
        raise SystemExit("--flight-record needs the engine path, not "
                         "--legacy: the recorder captures the engine's "
                         "submission and clock streams")
    if args.flight_record is None:
        if args.flight_ring != 4096:
            raise SystemExit("--flight-ring needs --flight-record to arm "
                             "the flight recorder")
        if args.flight_dump_dir is not None:
            raise SystemExit("--flight-dump-dir needs --flight-record to "
                             "arm the flight recorder")
    if args.flight_dump_dir is not None:
        d = args.flight_dump_dir
        if os.path.exists(d):
            if not os.path.isdir(d):
                raise SystemExit(f"--flight-dump-dir {d!r} exists and is "
                                 "not a directory")
            if not os.access(d, os.W_OK):
                raise SystemExit(f"--flight-dump-dir {d!r} is not "
                                 "writable")


def validate_rungs(args, num_rungs: int) -> None:
    """Range-check rung-valued flags against the loaded ladder."""
    if not 0 <= args.rung < num_rungs:
        raise SystemExit(
            f"--rung {args.rung} out of range: the loaded ladder has "
            f"rungs 0..{num_rungs - 1}")
    if args.spec_gamma > 0 and not 0 <= args.spec_drafter < num_rungs:
        raise SystemExit(
            f"--spec-drafter {args.spec_drafter} out of range: the "
            f"loaded ladder has rungs 0..{num_rungs - 1}")


@dataclasses.dataclass
class ServeRun:
    """What one replay-synthetic-prompts run of :func:`main` served."""
    engine: object          # the closed repro.serving.Engine
    init_s: float           # random weights drawn from seed 0
    compile_s: float        # Engine built, every serving step compiled
    serve_s: float          # first submit -> engine drained
    tokens: int             # generated tokens over all requests


def _device() -> str:
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind}"


def main(argv=None) -> Optional[ServeRun]:
    args = build_parser().parse_args(argv)
    validate_args(args)
    use_compile_cache()

    try:
        cfg = serving_config(args.arch, tiny=args.reduced, layers=args.layers)
    except ValueError as e:
        raise SystemExit(f"--layers: {e}") from None
    t0 = obs.now()
    params = jax.block_until_ready(api.init_model(cfg, 0))
    init_s = obs.now() - t0
    print(f"initialized {cfg.name} ({cfg.num_layers} layers, "
          f"d_model {cfg.d_model}) from seed 0 in {init_s:.2f}s on "
          f"{_device()}")
    ds = SyntheticLM(DataConfig(cfg.vocab_size, args.prompt_len, args.batch))
    prompts = jnp.asarray(ds.batch(0))

    ladder = None
    if args.ladder is not None:
        ladder = PolicyLadder.load(args.ladder)
        print(f"loaded {len(ladder)}-rung ladder "
              f"(budgets {list(ladder.budgets)}) from {args.ladder}")
        validate_rungs(args, len(ladder))

    sp, policy = None, SparsityPolicy.dense()
    if ladder is None and args.sparsity > 0:
        if args.calib_quick:
            from repro.core.allocation import EvoConfig
            plan = wis_pipeline.run_pipeline(
                params, cfg, {"tokens": prompts}, args.sparsity,
                evo=EvoConfig(generations=2, offspring=4, eps=0.1),
                delta=0.25, coord_passes=0, log=print)
            sp = plan.stacked_sp
            policy = plan.to_policy(
                backend=args.mode, sensitive_backend=args.sensitive_backend,
                sensitive_frac=args.sensitive_frac)
        else:
            from repro.core.sp_schema import default_sp_stacked
            sp = default_sp_stacked(params, cfg,
                                    keep_frac=1.0 - args.sparsity)
            if args.mode == "mask":
                # mask mode needs calibrated thresholds (Eq. 7); without
                # calibration fall back to the budgeted top-k backend
                print("no calibration -> using topk_shared backend")
                args.mode = "topk_shared"
            # k_max_frac must be > 0; at 100% sparsity keep the top-k
            # backends' one-channel floor (matching the legacy mode path)
            policy = SparsityPolicy.uniform(
                args.mode, k_max_frac=max(1.0 - args.sparsity, 1e-6))

    if args.legacy:
        t0 = obs.now()
        toks = generate(params, cfg, prompts, args.gen, sp, policy=policy)
        dt = obs.now() - t0
        n = toks.size
        print(f"generated {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s on "
              f"{_device()})")
        print("sample:", np.asarray(toks[0])[:16])
        return None

    from repro.serving import (Engine, EngineConfig, SchedulerConfig,
                               SLOConfig, SpecConfig)
    from repro.serving.metrics import latency_percentiles
    slo = None
    if args.slo_tpot_p95 > 0:
        slo = SLOConfig(tpot_p95=args.slo_tpot_p95,
                        max_queue=args.slo_max_queue)
    spec = None
    if args.spec_gamma > 0:
        spec = SpecConfig(gamma=args.spec_gamma,
                          drafter_rung=args.spec_drafter,
                          verifier_rung=args.rung,
                          adaptive=args.spec_adaptive,
                          gamma_max=max(4, args.spec_gamma))
    scheduler = None
    if args.max_queue or args.preemption:
        scheduler = SchedulerConfig(max_queue=args.max_queue,
                                    preemption=args.preemption)
    ecfg = EngineConfig(
        max_slots=args.max_slots or args.batch,
        max_len=args.max_len or args.prompt_len + args.gen,
        prefill_chunk=args.chunk,
        policy=None if ladder is not None else policy,
        prefill_strategy=args.prefill_strategy,
        slo=slo, initial_rung=args.rung, spec=spec,
        prefix_cache=args.prefix_cache,
        prefix_cache_tokens=args.prefix_cache_tokens,
        scheduler=scheduler)
    telemetry = None
    flight = None
    if args.flight_record is not None:
        flight = obs.FlightRecorder(
            capacity=args.flight_ring,
            sink=args.flight_record or None,
            dump_dir=args.flight_dump_dir,
            meta={"arch": args.arch, "reduced": args.reduced,
                  "layers": args.layers, "seed": 0,
                  "ladder_path": args.ladder})
    if (args.trace_out or args.events_out or args.profile_dir
            or args.quality_probe_rate > 0 or flight is not None):
        quality = None
        if args.quality_probe_rate > 0:
            qkw = dict(probe_rate=args.quality_probe_rate)
            if args.quality_drift_threshold is not None:
                qkw["drift_threshold"] = args.quality_drift_threshold
            quality = obs.QualityMonitor(obs.QualityConfig(**qkw))
        # trace_sink makes Engine.close() (context-manager exit) export
        # the Chrome trace even when the serving loop raises
        telemetry = obs.Telemetry(
            tracer=obs.SpanTracer() if args.trace_out else None,
            events=obs.EventLog(sink=args.events_out)
            if args.events_out else None,
            annotate_dispatch=args.profile_dir is not None,
            profiler=obs.ProfilerSession(args.profile_dir)
            if args.profile_dir else None,
            quality=quality,
            flight=flight,
            trace_sink=args.trace_out)
    t0 = obs.now()
    engine = Engine(params, cfg, ecfg, sp, ladder=ladder,
                    telemetry=telemetry)
    if engine.decode_retraces_after_warmup is None:
        engine.warmup()      # not yet warmed by the engine's own options
    compile_s = obs.now() - t0
    print(f"built the engine and compiled its serving steps in "
          f"{compile_s:.2f}s on {_device()}")
    if flight is not None and hasattr(signal, "SIGUSR1"):
        # operator-triggered black-box dump: kill -USR1 <pid>
        signal.signal(signal.SIGUSR1, lambda *_: flight.dump("sigusr1"))

    if args.gateway:
        from repro.serving.gateway import Gateway
        if telemetry is not None and telemetry.profiler is not None:
            telemetry.profiler.start()
        gw = Gateway(engine, host=args.gateway_host,
                     port=args.gateway_port)
        print(f"gateway starting on http://{args.gateway_host}:"
              f"{args.gateway_port or '<ephemeral>'} "
              f"(POST /v1/generate, GET /v1/health, GET /metrics); "
              f"SIGTERM/Ctrl-C drains")
        gw.serve_forever()
        print("gateway drained; engine stats:", engine.stats.summary())
        _report_telemetry(args, telemetry)
        return None

    server = None
    if args.metrics_port:
        server = obs.serve_metrics(engine.metrics_exposition,
                                   port=args.metrics_port)
        print(f"serving metrics at "
              f"http://127.0.0.1:{server.server_port}/metrics")
    if telemetry is not None and telemetry.profiler is not None:
        telemetry.profiler.start()
    t0 = obs.now()
    for b in range(args.batch):
        engine.submit(np.asarray(prompts[b]), args.gen)
    try:
        # the context manager closes the engine (and flushes every
        # telemetry sink) even when the loop raises
        with engine:
            out = run_with_metrics(engine, args.metrics_out,
                                   args.metrics_every, args.metrics_format)
    finally:
        if server is not None:
            server.shutdown()
        _report_telemetry(args, telemetry)
    dt = obs.now() - t0
    n = sum(len(t) for t in out.values())
    print(f"generated {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s on "
          f"{_device()})")
    print("retraces after warmup: decode",
          engine.decode_retraces_after_warmup, "chunk",
          engine.chunk_retraces_after_warmup)
    print("engine stats:", engine.stats.summary())
    print("latency:", {k: round(v, 3) for k, v in
                       latency_percentiles(engine.states.values()).items()
                       if v is not None})
    if engine.controller is not None:
        print("controller:", engine.controller.snapshot())
    if engine.spec_decoder is not None:
        print("spec:", engine.spec_decoder.snapshot())
        print("verify retraces after warmup:",
              engine.verify_retraces_after_warmup)
    if engine.prefix_cache is not None:
        print("prefix cache:", engine.prefix_cache.snapshot())
    print("sample:", out[0][:16])
    return ServeRun(engine=engine, init_s=init_s, compile_s=compile_s,
                    serve_s=dt, tokens=n)


def _report_telemetry(args, telemetry) -> None:
    """Say what ``Engine.close()`` flushed (the export itself already
    happened inside close — this only reports)."""
    if telemetry is None:
        return
    if telemetry.tracer is not None:
        print(f"wrote {len(telemetry.tracer)} trace events "
              f"to {args.trace_out}")
    if telemetry.events is not None:
        print(f"logged {telemetry.events.count} events"
              + (f" to {args.events_out}" if args.events_out else ""))
    if telemetry.profiler is not None:
        print(f"wrote profiler trace to {args.profile_dir}")
    if telemetry.quality is not None and telemetry.quality.armed:
        q = telemetry.quality
        print(f"quality: {q.probes} probes ({q.probe_tokens} tokens), "
              f"{q.recon_passes} recon passes, {q.drift_events} drift "
              f"events, pressure {q.pressure:.3f}")
    if telemetry.flight is not None:
        fr = telemetry.flight
        print(f"flight: {fr.count} records ({fr.dropped} dropped from "
              f"the ring), {len(fr.dumps)} dumps"
              + (f", recording at {args.flight_record}"
                 if args.flight_record else ""))


def run_with_metrics(engine, metrics_out=None, every: int = 16,
                     fmt: str = "jsonl"):
    """Drive the engine to completion, writing metrics every ``every``
    steps (and once at the end) when ``metrics_out`` is set.

    ``fmt="jsonl"`` appends engine snapshots; ``fmt="prom"`` rewrites
    the file with the current Prometheus text exposition each time —
    the node-exporter textfile-collector pattern, scrapeable without a
    port."""
    if metrics_out is None:
        return engine.run()
    if fmt not in ("jsonl", "prom"):
        raise ValueError(f"unknown metrics format {fmt!r}")

    if fmt == "prom":
        def write(_f=None):
            with open(metrics_out, "w") as f:
                f.write(engine.metrics_exposition())
        steps = 0
        while engine.scheduler.has_work():
            engine.step()
            steps += 1
            if steps % every == 0:
                write()
        write()
        return {rid: rs.tokens for rid, rs in engine.states.items()}

    steps = 0
    with open(metrics_out, "a") as f:
        while engine.scheduler.has_work():
            engine.step()
            steps += 1
            if steps % every == 0:
                f.write(json.dumps(engine.snapshot()) + "\n")
        f.write(json.dumps(engine.snapshot()) + "\n")
    return {rid: rs.tokens for rid, rs in engine.states.items()}


if __name__ == "__main__":
    main()
