#!/usr/bin/env python3
"""Bring the serving main path up on one TPU chip, at published widths.

    python chip_smoke.py

Everything runs in this one process: a chip belongs to one process at a
time.  Phases:

1. platform: the first JAX device must be a TPU.  Anywhere else the
   script names the platform it found and exits 1 without a result.
2. serve: ``repro.launch.serve.main`` driven through its argv, so the
   CLI itself is what comes up.  Llama-3.1-8B at published widths
   (d_model 4096, 32 query / 8 KV heads x 128, d_ff 14336, vocab
   128256, bf16) with depth cut to 12 of 32 layers and random weights
   from seed 0; 16 requests of 1024 prompt tokens and 64 new tokens
   each, chunked prefill, a 16-slot x 4096-position KV pool.  Once
   dense, once on the ``pallas`` backend at 50% with the default
   keep-0.5 sp tree.  Every request must get its 64 tokens, no serving
   step may retrace after warmup, and the compiled pallas decode step
   must hold the Mosaic kernel (``tpu_custom_call``).
3. logits: the served path (chunked prefill through the slot pool, then
   slot decode) against one whole-sequence float32 forward at
   ``highest`` matmul precision, and the ``pallas`` backend at
   k_max_frac=1.0 against dense.  Same widths, 2 layers: a float32 copy
   of the 12 served layers (11 GiB) does not fit next to their bf16
   weights.

Earlier lines report the device, compile seconds per phase, tokens
served, wall time and peak device memory.  The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
The persistent compile cache follows ``repro.launch.compile_cache``.
"""
import dataclasses
import functools
import gc
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ARCH = "llama31_8b"
# 12 of 32 layers: at 16 the serving steps keep a second copy of the
# KV pool as a scan temporary, and the compiler asks 17.2 GiB of the
# chip's 15.75 GiB (weights 8.5 + KV pool 4.3 + temporaries 4.5)
SERVE_LAYERS = 12
SLOTS, PROMPT_LEN, GEN, MAX_LEN, CHUNK = 16, 1024, 64, 4096, 256

CHECK_LAYERS = 2
CHECK_PROMPT, CHECK_DECODE, CHECK_CHUNK, CHECK_SLOTS = 312, 8, 128, 4

# Served bf16 path vs the float32 forward of the same (bf16-valued)
# weights.  Activations, K/V and every projection output are rounded to
# bf16 (relative rounding error up to 2^-8, about 0.2% rms) a few dozen
# times along two layers; independent roundings add as a random walk to
# about 1% of the logits' norm.  The bound leaves 4x over that.
TOL_BF16_VS_F32 = 0.04
# pallas at k_max_frac=1.0 keeps every channel block, so it computes the
# dense product; only the f32 accumulation order differs (per kept
# block in the kernel, one dot in XLA).  Where that sends one bf16
# rounding the other way, the later roundings of the two paths no
# longer coincide, and the two drift apart as far as either lies from
# the f32 forward (on a 1024-wide stand-in: 0.8% apart, each 0.9% from
# f32).  So both sit inside the same bound.
TOL_PALLAS_VS_DENSE = TOL_BF16_VS_F32


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def serve_argv(*mode_args):
    return ["--arch", ARCH, "--layers", str(SERVE_LAYERS),
            "--batch", str(SLOTS), "--max-slots", str(SLOTS),
            "--prompt-len", str(PROMPT_LEN), "--gen", str(GEN),
            "--max-len", str(MAX_LEN), "--chunk", str(CHUNK),
            "--prefill-strategy", "chunked", *mode_args]


def serve_phase(label: str, argv, dev, expect_kernel: bool) -> None:
    """One serve CLI run: checks what it served and reports it."""
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.obs import now
    from repro.serving.engine import make_engine_steps

    t0 = now()
    run = serve.main(argv)
    wall = now() - t0
    eng = run.engine
    got = [len(rs.tokens) for rs in eng.states.values()]
    check(len(got) == SLOTS and all(n == GEN for n in got),
          f"{label}: served {got} tokens per request, want {SLOTS} x {GEN}")
    retraces = (eng.decode_retraces_after_warmup,
                eng.chunk_retraces_after_warmup)
    check(retraces == (0, 0),
          f"{label}: retraces after warmup (decode, chunk) = {retraces}")
    has_kernel = None
    if expect_kernel:
        S = eng.ecfg.max_slots
        dstep, _, _ = make_engine_steps(eng.cfg)
        hlo = dstep.lower(
            eng.params, jnp.zeros((S,), jnp.int32),
            jnp.full((S,), eng.pool_len - 1, jnp.int32), eng.pool.caches,
            eng.sp, jnp.zeros((S,), jnp.float32),
            policy=eng.policy.for_phase("decode")).compile().as_text()
        has_kernel = "tpu_custom_call" in hlo
        check(has_kernel, f"{label}: compiled decode step has no "
              "tpu_custom_call: the Pallas kernel did not reach the chip")
    print(f"serve[{label}]: init {run.init_s:.2f}s, compile "
          f"{run.compile_s:.2f}s, {run.tokens} "
          f"tokens served in {run.serve_s:.3f}s on {dev.device_kind} "
          f"({run.tokens / run.serve_s:.1f} tok/s), phase wall "
          f"{wall:.2f}s, retraces after warmup {retraces}, pallas kernel "
          f"in decode: {has_kernel}, peak memory {peak_gib(dev)}")
    del run, eng
    gc.collect()


def served_logits(params, cfg, policy, sp, tokens, prompt_len: int,
                  chunk: int, slots: int):
    """Logits of every position of ``tokens`` through the engine's own
    step executables: chunked prefill of ``tokens[:prompt_len]`` into a
    non-zero pool slot, then one slot decode step per remaining token
    (teacher-forced), the other slots idle."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import make_engine_steps
    from repro.serving.kv_pool import SlotKVPool

    dstep, cstep, _ = make_engine_steps(cfg)
    total = len(tokens)
    pool_len = total + chunk
    caches = SlotKVPool(cfg, slots, pool_len).caches
    slot = slots - 1
    rows = []
    for off in range(0, prompt_len, chunk):
        real = min(chunk, prompt_len - off)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :real] = tokens[off:off + real]
        weights = np.zeros((chunk,), np.float32)
        weights[:real] = 1.0
        logits, caches = cstep(
            params, jnp.asarray(ids), jnp.full((1,), off, jnp.int32),
            jnp.int32(slot), caches, sp, jnp.asarray(weights),
            policy=policy.for_phase("prefill_sparse"))
        rows.append(np.asarray(logits[0, :real], np.float32))
    for pos in range(prompt_len, total):
        tok = np.zeros((slots,), np.int32)
        tok[slot] = tokens[pos]
        positions = np.full((slots,), pool_len - 1, np.int32)
        positions[slot] = pos
        active = np.zeros((slots,), np.float32)
        active[slot] = 1.0
        logits, caches = dstep(params, jnp.asarray(tok),
                               jnp.asarray(positions), caches, sp,
                               jnp.asarray(active),
                               policy=policy.for_phase("decode"))
        rows.append(np.asarray(logits[slot], np.float32)[None])
    return np.concatenate(rows)


def f32_logits(params, cfg, tokens):
    """One whole-sequence forward with every weight and activation in
    float32 and matmuls at ``highest`` precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      params)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: M.forward(p, cfg32, tokens=t,
                                                   mode="train"))(
            params32, jnp.asarray(tokens)[None])
        return np.asarray(logits[0], np.float32)


def rel_err(got, ref) -> float:
    """Worst position's relative L2 error of ``got`` against ``ref``."""
    import numpy as np
    num = np.linalg.norm(got - ref, axis=-1)
    den = np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)
    return float(np.max(num / den))


def logits_phase(cfg, dev) -> None:
    import numpy as np

    from repro.core.sp_schema import default_sp_stacked
    from repro.models import api
    from repro.sparsity import SparsityPolicy

    params = api.init_model(cfg, 0)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, CHECK_PROMPT + CHECK_DECODE,
                          dtype=np.int32)
    served = functools.partial(served_logits, params, cfg, tokens=tokens,
                               prompt_len=CHECK_PROMPT, chunk=CHECK_CHUNK,
                               slots=CHECK_SLOTS)
    dense = served(SparsityPolicy.dense(), None)
    ref = f32_logits(params, cfg, tokens)
    check(np.isfinite(dense).all(), "served dense logits are not finite")
    err_f32 = rel_err(dense, ref)
    del ref
    sp = default_sp_stacked(params, cfg, keep_frac=1.0)
    pallas = served(SparsityPolicy.uniform("pallas", k_max_frac=1.0), sp)
    err_pallas = rel_err(pallas, dense)
    print(f"logits[{cfg.num_layers} layers, {CHECK_PROMPT} prompt + "
          f"{CHECK_DECODE} decode positions]: served bf16 vs f32 forward "
          f"rel err {err_f32:.3e} (tol {TOL_BF16_VS_F32}); pallas "
          f"k_max_frac=1.0 vs dense rel err {err_pallas:.3e} (tol "
          f"{TOL_PALLAS_VS_DENSE}); peak memory {peak_gib(dev)}")
    check(err_f32 <= TOL_BF16_VS_F32,
          f"served logits differ from the f32 forward by {err_f32:.3e}")
    check(err_pallas <= TOL_PALLAS_VS_DENSE,
          f"pallas at k_max_frac=1.0 differs from dense by {err_pallas:.3e}")


def main() -> int:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}: run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax

    from repro.configs import serving_config
    from repro.obs import now

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU: nothing to bring up",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}")
    t0 = now()
    try:
        serve_phase("dense", serve_argv("--sparsity", "0"), dev,
                    expect_kernel=False)
        serve_phase("pallas 50%",
                    serve_argv("--mode", "pallas", "--sparsity", "0.5"), dev,
                    expect_kernel=True)
        logits_phase(serving_config(ARCH, layers=CHECK_LAYERS), dev)
    except Exception:                               # noqa: BLE001
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(f"all phases passed in {now() - t0:.1f}s on {dev.device_kind}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
