"""Paper Fig. 4: efficiency vs sparsity.

Left panel (FLOPs): per-token matmul FLOPs at 0-50% sparsity — the paper
reports a near-linear reduction (1.92 -> 1.03 TFLOPs at 50% on Llama-3.1
-8B); we compute the same curve analytically for the full llama31_8b
config and from the compiled sparse dry-run artifacts where available.

Right panel (throughput): wall-clock cannot be measured on CPU for a TPU
target; we report the kernel-level arithmetic (block-gather matmul FLOPs/
bytes vs dense) and the modeled decode step time from the roofline terms.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.configs import SHAPES, get_config
from repro.launch import roofline as R


def run(log=print):
    rows = []
    cfg = get_config("llama31_8b")
    n_active = R.active_matmul_params(cfg)
    dense_tf = 2 * n_active / 1e12
    for p in (0.0, 0.3, 0.4, 0.5):
        # attention projections + MLP sparsify; head stays dense
        head = cfg.vocab_size * cfg.d_model
        sparse_tf = 2 * ((n_active - head) * (1 - p) + head) / 1e12
        log(f"sparsity={p:.0%}: {sparse_tf:.3f} TFLOPs/token "
            f"({sparse_tf/dense_tf:.1%} of dense)")
        rows.append((f"fig4/flops_per_token/p{int(p*100)}", 0.0,
                     f"{sparse_tf:.4f}TF;frac={sparse_tf/dense_tf:.4f}"))

    # kernel-level: dense matmul vs block-gather at 50% kept blocks
    B, n, m, blk = 4, 2048, 2048, 128
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, n), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (n, m), jnp.float32)
    from repro.kernels import sparse_matmul as K
    idx_half = jnp.arange(0, n // blk, 2, dtype=jnp.int32)
    us_dense, _ = timed(jax.jit(lambda x: x @ w), x)
    f_sparse = jax.jit(lambda x: K.sparse_matmul_shared(
        x, w, idx_half, blk=blk, interpret=True))
    us_sparse, _ = timed(f_sparse, x)
    flops_dense = 2 * B * n * m
    flops_sparse = flops_dense // 2
    rows.append(("fig4/kernel_dense_matmul", us_dense,
                 f"flops={flops_dense}"))
    rows.append(("fig4/kernel_gather_50pct", us_sparse,
                 f"flops={flops_sparse};note=interpret-mode-CPU"))
    log(f"kernel: dense {us_dense:.0f}us vs gather@50% {us_sparse:.0f}us "
        "(interpret mode; FLOPs/bytes halve structurally)")

    # modeled decode throughput gain from the dry-run roofline artifacts
    # (prefer the optimized sweep when present)
    base_f = "experiments/dryrun_optimized.jsonl"
    sparse_f = "experiments/dryrun_optimized_sparse.jsonl"
    if not (os.path.exists(base_f) and os.path.exists(sparse_f)):
        base_f = "experiments/dryrun_baseline.jsonl"
        sparse_f = "experiments/dryrun_sparse.jsonl"
    if os.path.exists(base_f) and os.path.exists(sparse_f):
        def load(path):
            out = {}
            with open(path) as f:
                for line in f:
                    r = json.loads(line)
                    if r.get("status") == "ok":
                        out[(r["arch"], r["shape"], r["mesh"])] = r
            return out
        base, sp = load(base_f), load(sparse_f)
        for k in sorted(set(base) & set(sp)):
            if k[1].startswith("decode") and k[2] == "single":
                tb = max(base[k]["roofline"]["compute_s"],
                         base[k]["roofline"]["memory_s"])
                ts = max(sp[k]["roofline"]["compute_s"],
                         sp[k]["roofline"]["memory_s"])
                gain = tb / ts if ts > 0 else float("nan")
                rows.append((f"fig4/modeled_decode_gain/{k[0]}", 0.0,
                             f"x{gain:.2f}"))
                log(f"modeled decode mem/compute speedup {k[0]}: x{gain:.2f}")
    return rows


if __name__ == "__main__":
    run()
