#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): start JAX, make the weights on the chip
from the seed, build the engine on the cell's rung and compile its
steps (JAX's persistent compile cache: ``repro.launch.compile_cache``),
then bring the traffic to steady state.  Then the window: ``--seconds``
of traffic from the cell's mix.  With ``--trace 0`` the last line of
standard output is the cell's end-to-end metrics; with ``--trace 1`` a
profiler trace of the window's last seconds gives its per-layer
metrics.  Either way the served tokens are then checked against the
plain reference (``bench/check.py``), and the numbers compared are
printed beside their limits, last on standard error and last in the
result line.

Runs on a TPU only: without one it exits 1 and prints no result.
``--rehearse`` runs a CPU-sized stand-in of the cell (tests only).
``--control`` puts the control (``PERF.md``), the reference at fp8, in
the program's place: its first choices at the compared positions are
judged as the served tokens are, and decide ``correct``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_S = 6.0                   # seconds of the window a trace covers
DRAIN_S = 60.0                  # wait past the close for first tokens
WARM_CAP_S = 90.0               # longest a closed loop may take to fill
OUT = os.path.join(ROOT, "bench_out")


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def say(*a) -> None:
    print(*a, flush=True)


def load_metric(name: str):
    """The reader ``bench/metrics/<name>.py``, or, for a quantity split
    by the end-to-end metric it moves (``mfu.chat``), the reader of its
    first part (``mfu.py``) where the split has no file of its own."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", name.split(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool):
    """The metrics this cell reports: end-to-end ones without a trace,
    per-layer ones with it, each kept to the cells it lists."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in names]


def memory_report(eng) -> None:
    """Each step program's compiled memory (the runtime's peak leaves
    executable temporaries out)."""
    import jax.numpy as jnp
    S, C = eng.ecfg.max_slots, eng.ecfg.prefill_chunk
    pd, ps, dec = eng._rung_phases[eng.rung]
    progs = {
        "decode": eng._dstep.lower(
            eng.params, jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32), eng.pool.caches, eng.sp,
            jnp.zeros((S,), jnp.float32), policy=dec),
    }
    for label, pol in (("chunk_dense", pd), ("chunk_sparse", ps)):
        if label == "chunk_sparse" and pol == pd:
            continue
        progs[label] = eng._cstep.lower(
            eng.params, jnp.zeros((1, C), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.int32(0), eng.pool.caches,
            eng.sp, jnp.zeros((C,), jnp.float32), policy=pol)
    for label, lowered in progs.items():
        ma = lowered.compile().memory_analysis()
        if ma is None:
            continue
        gib = 2.0 ** 30
        say(f"memory[{label}]: args {ma.argument_size_in_bytes / gib:.3f} "
            f"GiB, temp {ma.temp_size_in_bytes / gib:.3f} GiB, out "
            f"{ma.output_size_in_bytes / gib:.3f} GiB")


def main(argv=None, alter=None) -> int:
    args = parse(argv)
    from bench import cell as cellmod
    if not os.path.isdir(os.path.join(cellmod.SRC, "repro")):
        print(f"bench: no program under {cellmod.SRC}", file=sys.stderr)
        return 2
    if cellmod.SRC not in sys.path:
        sys.path.insert(0, cellmod.SRC)
    cell = cellmod.find(args.workload, rehearse=args.rehearse)
    bench = cellmod.benchmark()

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    chips = int(cell.entry["chips"])
    if not args.rehearse and (dev.platform != "tpu" or len(devices) < chips):
        print(f"bench: found {len(devices)} {dev.platform} device(s) "
              f"({dev.device_kind}); cell {cell.name} needs {chips} TPU "
              "chip(s): no result", file=sys.stderr)
        return 1
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile "
        f"cache {cache}; cell {cell.name}, seed {args.seed}, "
        f"{args.seconds:g} s, trace {args.trace}")

    from bench import check, counts, traffic as T
    from bench.client import Client, clock
    mix = cell.traffic
    cfg = cell.cfg
    sparse = cell.rung["backend"] != "off"
    pcfg = cellmod.program_config(cfg)
    params = cellmod.make_params(pcfg, cfg, args.seed)
    jax.block_until_ready(params)
    t_w = clock()
    eng = cellmod.build_engine(cell, params, annotate=bool(args.trace))
    say(f"engine built and warmed in {clock() - t_w:.2f} s "
        f"({time.perf_counter() - T_START:.2f} s since start)")
    if alter is not None:
        alter(eng)
    cl = Client(eng, mix, args.seed, cfg["vocab_size"],
                float(cell.rung.get("prefill_dense_frac", 0.5)), sparse)

    seconds = float(args.seconds)
    tracer = Tracing(args.trace, cell.name)
    if mix["loop"] == "closed":
        n_req = int(mix["draws"])
        reqs = T.Requests(mix, args.seed, cfg["vocab_size"], n_req,
                          int(mix["block"]))
        cl.closed_warm(reqs, int(mix["clients"]), WARM_CAP_S)
        q0 = eng.stats.queue_wait_s.count
        t_open = clock()
        t_close = t_open + seconds
        tracer.arm(t_close)
        tracer.tick(cl)
        cl.closed_window(reqs, t_close, on_step=lambda: tracer.tick(cl))
    else:
        lead = float(mix["lead_s"])
        span = lead + seconds
        offsets = T.open_schedule(mix, args.seed, span)
        reqs = T.Requests(mix, args.seed, cfg["vocab_size"], len(offsets),
                          int(mix["block"]))
        t0 = clock()
        dues = [t0 + o for o in offsets]
        t_open = t0 + lead
        t_close = t0 + span
        tracer.arm(t_close)
        q0 = None

        def on_step():
            nonlocal q0
            if q0 is None and clock() >= t_open:
                q0 = eng.stats.queue_wait_s.count
            tracer.tick(cl)

        cl.open_run(reqs, dues, t_close, t_close + DRAIN_S, t_open, on_step)
        q0 = q0 if q0 is not None else eng.stats.queue_wait_s.count
    tracer.stop(cl)
    t_end = clock()
    tracer.read()
    setup_s = t_open - T_START
    waits = list(eng.stats.queue_wait_s)
    n_new = eng.stats.queue_wait_s.count - q0
    waits = waits[len(waits) - min(n_new, len(waits)):] if n_new else []
    retraces = (eng.decode_retraces_after_warmup or 0) + \
        (eng.chunk_retraces_after_warmup or 0)

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(f"window {t_open:.3f}..{t_close:.3f}, stepped to {t_end:.3f}; "
        f"{len(cl.steps)} steps, {len(cl.reqs)} requests; peak memory "
        f"{peak}")
    if not args.rehearse:
        memory_report(eng)

    run = types.SimpleNamespace(
        client=cl, t_open=t_open, t_close=t_close, seconds=seconds,
        setup_s=setup_s, cell=cell, shape=counts.Shape.of(cfg),
        device_kind=dev.device_kind, queue_waits=waits,
        trace=tracer.trace, trace_lo=tracer.lo, trace_hi=tracer.hi,
        traced_steps=tracer.steps, slots=cell.serving["max_slots"],
        rehearse=args.rehearse)
    metrics = {}
    for m in cell_metrics(bench, cell.name, bool(args.trace)):
        v = load_metric(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    attempted, failed = outcome(run, mix)
    breakdown = tracer.breakdown()
    max_len = cell.serving["max_len"]
    cl.eng = None               # free the program's state for the reference
    del eng, params
    gc.collect()
    jax.clear_caches()

    gap_spec = cell.limits.get("widest_gap", {})
    rp = check.replay(cl, args.seed, sparse,
                      every=gap_spec.get("requests") == "all")
    t_ref = clock()
    got = check.widest_gap(cfg, cell.rung, args.seed, rp, max_len,
                           control=args.control)
    say(f"reference: {rp.requests} requests, {rp.tokens} served tokens "
        f"compared over {len(rp.rows.tokens)} rows in {clock() - t_ref:.1f} s")
    gap_limit = float(gap_spec.get("limit", 0.0))
    gap = got["widest_gap"]
    if args.control:
        say(f"program's widest gap {gap!r}; the control's is judged")
        gap = got["control_widest_gap"]
    compared = {
        "widest_gap": [gap, gap_limit],
        "retraces_in_window": [retraces, 0],
        "schedule_mismatches": [cl.schedule_errors, 0],
        "failed_requests": [failed, 0],
    }
    correct = (gap <= gap_limit and retraces == 0
               and cl.schedule_errors == 0 and failed == 0
               and bool(cell.limits))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if args.trace and tracer.trace is not None:
        device["busy_s"] = tracer.busy_s()
        device["window_s"] = (tracer.hi - tracer.lo) * 1e-9
    for k, (v, lim) in compared.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace and breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def outcome(run, mix):
    """(attempted, failed): requests due in the window (open loop) or
    in flight in it (closed loop); a due request with no first token by
    the end of the drain failed."""
    reqs = run.client.reqs
    if mix["loop"] == "open":
        due = [r for r in reqs if run.t_open <= r.due < run.t_close]
        return len(due), sum(1 for r in due if r.first is None)
    live = [r for r in reqs if r.sent < run.t_close and
            (r.finished_at is None or r.finished_at >= run.t_open)]
    return len(live), 0


class Tracing:
    """A profiler trace of the window's last TRACE_S seconds (``--trace
    1``), with the harness's own ``bench/window`` span around it.  It
    ends with the window, and the trace is read only after the run, so
    that exporting and reducing it stall no step inside the window."""

    def __init__(self, on: int, cell: str):
        self.on = bool(on)
        self.dir = os.path.join(OUT, f"trace-{cell}")
        self.trace = None
        self.lo = self.hi = 0.0
        self.steps = []
        self._from = self._until = None
        self._note = None
        self._done = False

    def arm(self, t_close: float) -> None:
        """Trace from TRACE_S before ``t_close`` to ``t_close``."""
        if self.on:
            self._from, self._until = t_close - TRACE_S, t_close

    def tick(self, cl) -> None:
        """Between steps: start or stop the trace when it is due."""
        from bench.client import clock
        if self._from is None or self._done:
            return
        now = clock()
        if self._note is None and now >= self._from:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._note = jax.profiler.TraceAnnotation("bench/window")
            self._note.__enter__()
            self._first = len(cl.steps)
        elif self._note is not None and now >= self._until:
            self.stop(cl)

    def stop(self, cl) -> None:
        if self._note is None:
            return
        import jax
        self._note.__exit__(None, None, None)
        self._note = None
        self._done = True
        self.steps = list(range(self._first, len(cl.steps)))
        jax.profiler.stop_trace()

    def read(self) -> None:
        """Reduce the trace written by :meth:`stop`, once the run is over."""
        if not self._done:
            return
        from bench import trace as TR
        self.trace = TR.load(self.dir)
        w = self.trace.window()
        if w is not None:
            self.lo, self.hi = w
        shutil.rmtree(self.dir, ignore_errors=True)

    def busy_s(self) -> float:
        from bench import trace as TR
        return TR.busy_ns(self.trace, self.lo, self.hi) * 1e-9

    def breakdown(self):
        if self.trace is None or not self.trace.devices:
            return None
        from bench import trace as TR
        return {"device_ops": TR.top_ops(self.trace, self.lo, self.hi),
                "idle_gaps": TR.idle_breakdown(self.trace, self.lo, self.hi)}


if __name__ == "__main__":
    sys.exit(main())
