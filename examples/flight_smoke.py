"""End-to-end flight-recorder smoke: the CI ``flight-replay-smoke`` job.

Records a short gateway run with forced rung switches (a controller
under an impossible TPOT SLO) and a preemption (interactive arrival over
a full pool of best-effort decoders), then:

1. asserts ``GET /v1/debug/flight`` serves the ring and triggers a dump,
2. drains the gateway and replays the full JSONL recording in a fresh
   process (``python -m repro.obs.flight.replay``), gating whole-trace
   token bit-identity, matching rung residency, identical decision
   streams, and zero post-warmup retraces,
3. asserts the recorded incident actually contains a ``rung_switch``
   and a ``preempt`` decision (the scenario did what it claims),
4. re-runs the replay with ``--inject-divergence`` and asserts it exits
   nonzero with a structured first-divergence report.

Run it directly::

    JAX_PLATFORMS=cpu python examples/flight_smoke.py --out-dir /tmp/flight

This process never touches JAX: a chip belongs to one process at a
time, so the ladder build, the gateway and each replay run in children
of their own.
"""
import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from repro.obs.clock import now

STARTUP_TIMEOUT_S = 300.0
DRAIN_TIMEOUT_S = 120.0
REPLAY_TIMEOUT_S = 300.0


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_healthy(port: int, deadline: float) -> None:
    url = f"http://127.0.0.1:{port}/v1/health"
    while now() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as resp:
                health = json.load(resp)
            assert health["status"] == "ok", health
            return
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(0.5)
    raise SystemExit("gateway never became healthy")


def generate(port: int, prompt, max_new: int, priority: str) -> dict:
    payload = json.dumps({"prompt": list(prompt),
                          "max_new_tokens": max_new,
                          "priority": priority}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate", data=payload,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.load(resp)


# saves the 3-rung uniform ladder the recorded engine serves with
LADDER_SCRIPT = """
import sys
from repro.configs import get_config, reduced
from repro.models import api
from repro.sparsity import PolicyLadder
cfg = reduced(get_config("llama31_8b"))
params = api.init_model(cfg, 0)
PolicyLadder.uniform(params, cfg, [0.0, 0.5, 0.7]).save(sys.argv[1])
"""


def build_ladder(path: str) -> None:
    """Build the ladder artifact in a child process."""
    subprocess.run([sys.executable, "-c", LADDER_SCRIPT, path], check=True,
                   timeout=STARTUP_TIMEOUT_S)
    print(f"ladder artifact at {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="/tmp/flight")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)
    ladder = os.path.join(args.out_dir, "ladder.npz")
    recording = os.path.join(args.out_dir, "gateway.jsonl")
    dump_dir = os.path.join(args.out_dir, "dumps")
    build_ladder(ladder)

    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--reduced",
         "--gateway", "--gateway-port", str(port), "--max-queue", "8",
         "--preemption",
         "--prompt-len", "16", "--gen", "1024", "--batch", "2", "--chunk", "8",
         "--ladder", ladder, "--slo-tpot-p95", "1e-9",
         "--flight-record", recording, "--flight-ring", "32768",
         "--flight-dump-dir", dump_dir])
    try:
        wait_healthy(port, now() + STARTUP_TIMEOUT_S)

        # two best-effort long generations fill both slots (1024 tokens
        # each keeps both decoding for seconds, so the interactive
        # arrival below reliably lands mid-decode even on fast hosts)...
        threads = [threading.Thread(
            target=generate, args=(port, range(1, 17), 1024, "best_effort"))
            for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.4)
        # ...then an interactive arrival must preempt one of them
        out = generate(port, range(20, 36), 8, "interactive")
        assert len(out["tokens"]) == 8, out
        for t in threads:
            t.join(timeout=120)

        # the debug endpoint serves the ring and triggers an http dump
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/debug/flight",
                timeout=10) as resp:
            snap = json.load(resp)
        assert snap["count"] > 0 and snap["records"], snap["count"]
        assert snap.get("dump_path"), "debug endpoint should trigger a dump"
        print(f"debug endpoint OK: {snap['count']} records, "
              f"dump at {snap['dump_path']}")
    except BaseException:
        proc.kill()
        raise
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=DRAIN_TIMEOUT_S)
    assert rc == 0, f"gateway exited {rc}, expected a clean drain (0)"

    # the incident the recording claims: rung switches + a preemption
    with open(recording) as f:
        records = [json.loads(ln) for ln in f if ln.strip()]
    kinds = {(r.get("k"), r.get("kind")) for r in records}
    assert ("decision", "rung_switch") in kinds, "no rung switch recorded"
    assert ("decision", "preempt") in kinds, "no preemption recorded"
    n_finish = sum(1 for r in records if r.get("k") == "finish")
    print(f"recorded {len(records)} records, {n_finish} finishes, "
          f"rung switches + preemption present")

    # bit-identical replay in a fresh process
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs.flight.replay", recording],
        capture_output=True, text=True, timeout=REPLAY_TIMEOUT_S)
    print(out.stdout)
    assert out.returncode == 0, f"replay failed:\n{out.stdout}{out.stderr}"
    report = json.loads(out.stdout)
    assert report["ok"] and not report["failures"], report
    assert all(v == 0 for v in report["retraces"].values()), report
    print(f"replay OK: {report['tokens']} tokens bit-identical, "
          f"retraces {report['retraces']}")

    # injected divergence must exit nonzero with a structured report
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs.flight.replay", recording,
         "--inject-divergence"],
        capture_output=True, text=True, timeout=REPLAY_TIMEOUT_S)
    assert out.returncode == 1, \
        f"injected divergence not caught (exit {out.returncode})"
    report = json.loads(out.stdout)
    div = report["divergence"]
    assert div and "record" in div and "token_index" in div, report
    print(f"divergence report OK: request {div.get('request')} token "
          f"{div.get('token_index')} at record {div['record']}")


if __name__ == "__main__":
    main()
