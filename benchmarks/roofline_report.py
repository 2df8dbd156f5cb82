"""Roofline report: renders the dry-run JSONL records into the §Roofline
table (per arch x shape x mesh: three terms, bottleneck, useful-FLOPs
ratio, MFU, memory fit)."""
from __future__ import annotations

import json
import os
from collections import defaultdict

from repro.launch import constants as C

BASE = "experiments/dryrun_baseline.jsonl"
SPARSE = "experiments/dryrun_sparse.jsonl"


def load(path):
    recs = {}
    if not os.path.exists(path):
        return recs
    with open(path) as f:
        for line in f:
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            key = (r["arch"], r["shape"], r["mesh"], r.get("sparsity", 0.0))
            recs[key] = r            # later records win (re-runs)
    return recs


def fmt_row(r):
    rl = r["roofline"]
    peak = r["memory"]["peak_bytes_estimate"] / 2**30
    hbm = C.peaks(r["device_kind"]).hbm_bytes
    fits = "OK" if peak <= hbm / 2**30 else "OVER"
    return (f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{rl['compute_s']*1e3:.2f} | {rl['memory_s']*1e3:.2f} | "
            f"{rl['collective_s']*1e3:.2f} | {rl['bottleneck']} | "
            f"{rl['useful_ratio']:.2f} | {rl['mfu']:.3f} | "
            f"{peak:.2f} {fits} |")


HEADER = ("| arch | shape | mesh | compute ms | memory ms | coll ms | "
          "bottleneck | useful | MFU | peak GiB/chip |\n"
          "|---|---|---|---|---|---|---|---|---|---|")


def render(log=print, sparsity=0.0, path=BASE):
    recs = load(path)
    log(HEADER)
    n_ok = n_err = 0
    for key in sorted(recs):
        r = recs[key]
        if key[3] != sparsity:
            continue
        if r.get("status") != "ok":
            log(f"| {key[0]} | {key[1]} | {key[2]} | FAILED: "
                f"{r.get('error', '?')[:60]} |")
            n_err += 1
            continue
        log(fmt_row(r))
        n_ok += 1
    return n_ok, n_err


def run(log=print):
    rows = []
    for name, path, sp in (
            ("baseline", BASE, 0.0), ("sparse50", SPARSE, 0.5),
            ("optimized", "experiments/dryrun_optimized.jsonl", 0.0),
            ("optimized_sparse50",
             "experiments/dryrun_optimized_sparse.jsonl", 0.5)):
        if not os.path.exists(path):
            continue
        log(f"\n== roofline {name} ==")
        ok, err = render(log, sparsity=sp, path=path)
        rows.append((f"roofline/{name}/cells_ok", 0.0, str(ok)))
        rows.append((f"roofline/{name}/cells_failed", 0.0, str(err)))
    # always-present coverage row: the artifact carries at least one row
    # even without experiment dumps, so benchmarks.compare has a
    # non-vacuous baseline to gate against
    rows.append(("roofline/reports_rendered", 0.0, str(len(rows) // 2)))
    return rows


if __name__ == "__main__":
    run()
