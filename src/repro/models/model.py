"""Model assembly: schema + apply for every assigned architecture family.

One generic decoder stack covers dense / MoE / SSM / hybrid archs via the
config's ``layer_pattern`` (a period of (mixer, ffn) kinds); homogeneous
periods are stacked and scanned (``lax.scan``) so HLO size and compile time
stay bounded at 95 layers.  Whisper adds an encoder stack + cross-attention;
InternVL prepends precomputed patch embeddings (frontend stub).

Modes: "train" (full seq, no cache), "prefill" (full seq, emits caches),
"decode" (one token per sequence against caches).

Execution state is explicit: ``forward`` takes a static
``SparsityPolicy`` (``repro.sparsity``) selecting the projection backend
per role / per block range (``None`` = dense), a traced ``token_weights``
row-weight vector for the serving engine's shared saliency, and a static
``aligned`` flag for the single-DUS batched decode cache write.  Nothing
on the forward path reads ambient thread-local state.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import sparse_linear
from repro.distributed.sharding import constrain
from repro.kernels.sparse_matmul import reads_in_place
from repro.models import attention as attn_lib
from repro.models.layers import apply_rope, dense, rmsnorm, rope_angles, softcap
from repro.models.mlp import mlp_apply, mlp_schema
from repro.models.moe import moe_apply, moe_schema
from repro.models.params import ParamSpec, stacked
from repro.models.ssm import mamba_apply, mamba_schema

ATTN_KINDS = ("attn", "local", "global", "attn_bidir")


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def attn_schema(cfg: ModelConfig):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": ParamSpec((d, H * hd), ("embed", "heads_flat")),
        "wk": ParamSpec((d, KV * hd), ("embed", "kv_flat")),
        "wv": ParamSpec((d, KV * hd), ("embed", "kv_flat")),
        "wo": ParamSpec((H * hd, d), ("heads_flat", "embed")),
    }


def layer_schema(cfg: ModelConfig, kind, cross: bool = False):
    mixer, ffn = kind
    s = {}
    if mixer in ATTN_KINDS:
        s["ln1"] = ParamSpec((cfg.d_model,), (None,), init="zeros")
        s["attn"] = attn_schema(cfg)
    elif mixer == "mamba":
        s["ln1"] = ParamSpec((cfg.d_model,), (None,), init="zeros")
        s["mamba"] = mamba_schema(cfg)
    if cross:
        s["ln_cross"] = ParamSpec((cfg.d_model,), (None,), init="zeros")
        s["cross"] = attn_schema(cfg)
    if ffn == "dense":
        s["ln2"] = ParamSpec((cfg.d_model,), (None,), init="zeros")
        s["mlp"] = mlp_schema(cfg)
    elif ffn == "moe":
        s["ln2"] = ParamSpec((cfg.d_model,), (None,), init="zeros")
        s["moe"] = moe_schema(cfg)
    return s


def group_schemas(cfg: ModelConfig, cross: bool = False):
    out = []
    for pattern, reps in cfg.layer_groups():
        g = {f"l{j}": layer_schema(cfg, kind, cross)
             for j, kind in enumerate(pattern)}
        out.append(stacked(g, reps, "layers"))
    return out


def model_schema(cfg: ModelConfig):
    V, D = cfg.vocab_size, cfg.d_model
    s = {
        "embed": ParamSpec((V, D), ("vocab", "embed")),
        "final_norm": ParamSpec((D,), (None,), init="zeros"),
        "groups": group_schemas(cfg, cross=(cfg.family == "encdec")),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = ParamSpec((D, V), ("embed", "vocab"))
    if cfg.family == "encdec":
        enc_pattern = (("attn_bidir", "dense"),)
        g = {f"l{j}": layer_schema(cfg, kind)
             for j, kind in enumerate(enc_pattern)}
        s["encoder"] = {
            "groups": [stacked(g, cfg.encoder_layers, "layers")],
            "final_norm": ParamSpec((D,), (None,), init="zeros"),
        }
    return s


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def attn_apply(p, x, cfg: ModelConfig, kind: str, sp=None, cache=None,
               positions=None, mode: str = "train", kv_override=None,
               slot=None, policy=None, token_weights=None,
               aligned: bool = False, role_base: str = "attn"):
    """Self- or cross-attention.  kv_override: (enc_out) for cross-attn.

    mode "chunk" is the serving engine's chunked-prefill path: x is one
    request's C-token chunk, cache holds the *whole slot pool*
    (max_slots batch dim), ``slot`` is the request's pool slot and
    ``positions`` (B,) its chunk-start offset.  The chunk's K/V are written
    in place at (slot, offset) via dynamic_update_slice and attention runs
    against the slot's full cache row, so every chunk reuses one compiled
    step regardless of prompt length or pool occupancy.  The slot's
    positions before the offset may equally be a prefix-cache copy
    (``repro.serving.prefix_cache``) rather than this request's own
    earlier chunks — the causal mask treats both identically.

    mode "verify" is the speculative-decoding verify forward: x's batch
    dim *is* the pool's slot dim, row s carrying slot s's (gamma+1)-token
    verify window starting at per-slot offset ``positions[s]``.  The same
    write-in-place machinery as "chunk", vmapped over slots, re-projects
    every window position's K/V under the verifier policy before
    attention, so whatever the drafter wrote there is overwritten and the
    committed cache prefix stays verifier-faithful."""
    sp = sp or {}
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    win = cfg.sliding_window if kind == "local" else 0
    tw = token_weights

    def proj(name, xin, row_parallel=False):
        return dense(xin, p[name], sp.get(name), row_parallel=row_parallel,
                     policy=policy, role=f"{role_base}/{name}",
                     token_weights=tw)

    # fused qkv only pays in training (merges backward dx psums); in serve
    # modes the concat of differently-sharded weight dims costs an
    # all-to-all reshard.  WiSparse needs per-projection masks (and
    # calibration needs per-projection input capture), so the sparse and
    # capture paths keep separate matmuls.
    fuse = (mode == "train" and not sp and kv_override is None
            and (policy is None or policy.capture is None))
    if not fuse:
        q = proj("wq", x).reshape(B, S, H, hd)
    if kv_override is not None:                      # cross-attention
        if mode == "decode":                         # static pre-transposed KV
            kc, vc = cache["k"], cache["v"]
            F = kc.shape[-1]
            out = attn_lib.decode_attention(
                q[:, 0], kc, vc, jnp.full((B,), F, jnp.int32))
            out = out[:, None]
        else:
            F = kv_override.shape[1]
            # encoder rows are not the step's tokens: opt out of weighting
            k = dense(kv_override, p["wk"], sp.get("wk"), policy=policy,
                      role=f"{role_base}/wk",
                      token_weights=None).reshape(B, F, KV, hd)
            v = dense(kv_override, p["wv"], sp.get("wv"), policy=policy,
                      role=f"{role_base}/wv",
                      token_weights=None).reshape(B, F, KV, hd)
            q = constrain(q, "batch", None, "heads", None)
            out = attn_lib.flash_attention(q, k, v, causal=False)
        y = proj("wo", out.reshape(B, S, H * hd), row_parallel=True)
        return y, None

    if fuse:
        # fused qkv: one matmul -> backward emits ONE dx all-reduce instead
        # of three.
        w_cat = jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=1)
        qkv = dense(x, w_cat, policy=policy, token_weights=None)
        q = qkv[..., : H * hd].reshape(B, S, H, hd)
        k = qkv[..., H * hd: (H + KV) * hd].reshape(B, S, KV, hd)
        v = qkv[..., (H + KV) * hd:].reshape(B, S, KV, hd)
    else:
        k = proj("wk", x).reshape(B, S, KV, hd)
        v = proj("wv", x).reshape(B, S, KV, hd)

    if cfg.rope_theta:
        if mode == "decode":
            cos, sin = rope_angles(positions[:, None], hd, cfg.rope_theta)
        elif mode in ("chunk", "verify"):
            cos, sin = rope_angles(positions[:, None] + jnp.arange(S)[None],
                                   hd, cfg.rope_theta)
        else:
            cos, sin = rope_angles(jnp.arange(S)[None], hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", None, "heads", None)

    if mode == "verify":
        if win:
            raise NotImplementedError(
                "speculative verify does not support local-attention "
                "layers (rolling-window caches cannot roll back)")
        kc, vc = cache["k"], cache["v"]          # pool: (S,KV,hd,T)/(S,KV,T,hd)
        kn = k.transpose(0, 2, 3, 1).astype(kc.dtype)        # (S,KV,hd,C)
        vn = v.transpose(0, 2, 1, 3).astype(vc.dtype)        # (S,KV,C,hd)

        def wk(c, n, off):                       # c: (KV,hd,T)
            return jax.lax.dynamic_update_slice(c, n, (0, 0, off))

        def wv(c, n, off):                       # c: (KV,T,hd)
            return jax.lax.dynamic_update_slice(c, n, (0, off, 0))

        kc = jax.vmap(wk)(kc, kn, positions)
        vc = jax.vmap(wv)(vc, vn, positions)
        out = attn_lib.chunk_attention(q, kc, vc, positions,
                                       attn_softcap=cfg.attn_softcap)
        y = proj("wo", out.reshape(B, S, H * hd), row_parallel=True)
        return y, {"k": kc, "v": vc}

    if mode == "chunk":
        if win:
            raise NotImplementedError(
                "chunked prefill does not support local-attention layers; "
                "use the engine's whole-prompt prefill strategy")
        kc, vc = cache["k"], cache["v"]          # pool: (S,KV,hd,T)/(S,KV,T,hd)
        off = positions[0]
        kn = k.transpose(0, 2, 3, 1).astype(kc.dtype)        # (B,KV,hd,C)
        vn = v.transpose(0, 2, 1, 3).astype(vc.dtype)        # (B,KV,C,hd)
        kc = jax.lax.dynamic_update_slice(kc, kn, (slot, 0, 0, off))
        vc = jax.lax.dynamic_update_slice(vc, vn, (slot, 0, off, 0))
        ks = jax.lax.dynamic_slice(kc, (slot, 0, 0, 0), (B,) + kc.shape[1:])
        vs = jax.lax.dynamic_slice(vc, (slot, 0, 0, 0), (B,) + vc.shape[1:])
        out = attn_lib.chunk_attention(q, ks, vs, off,
                                       attn_softcap=cfg.attn_softcap)
        y = proj("wo", out.reshape(B, S, H * hd), row_parallel=True)
        return y, {"k": kc, "v": vc}

    if mode == "decode":
        kc, vc = cache["k"], cache["v"]
        T = kc.shape[-1]
        rolling = bool(win) and win == T
        k_new, v_new = k[:, 0], v[:, 0]               # (B,KV,hd)
        out = attn_lib.decode_attention(
            q[:, 0], kc, vc, positions, k_new, v_new,
            rolling=rolling, attn_softcap=cfg.attn_softcap)
        out = out[:, None]
        nk, nv = attn_lib.cache_write_kv(
            kc, vc, k_new, v_new, positions,
            rolling=rolling, aligned=aligned)
        new_cache = {"k": nk, "v": nv}
    else:
        causal = kind != "attn_bidir"
        out = attn_lib.flash_attention(
            q, k, v, causal=causal, window=win, attn_softcap=cfg.attn_softcap)
        new_cache = None
        if mode == "prefill":
            if win and win < S:                      # rolling window cache
                ck, cv = k[:, -win:], v[:, -win:]
                # slot j of k[:, -win:] holds abs position S-win+j; roll right
                # by S%win so slot (pos % win) holds position pos
                shift = S % win
                ck = jnp.roll(ck, shift, axis=1)
                cv = jnp.roll(cv, shift, axis=1)
            else:
                ck, cv = k, v
            # decode-layout caches: K as (B,KV,hd,T), V as (B,KV,T,hd)
            new_cache = {
                "k": constrain(ck.transpose(0, 2, 3, 1),
                               "batch", "kv_heads", None, "kv_seq"),
                "v": constrain(cv.transpose(0, 2, 1, 3),
                               "batch", "kv_heads", "kv_seq", None)}
    y = proj("wo", out.reshape(B, S, H * hd), row_parallel=True)
    return y, new_cache


def layer_apply(p, x, cfg: ModelConfig, kind, sp=None, cache=None,
                positions=None, mode: str = "train", enc_out=None,
                slot=None, policy=None, token_weights=None,
                aligned: bool = False):
    """cache: per-layer dict (train/prefill) or, in decode mode,
    {"stack": <layer-stacked group cache entry>, "idx": layer-in-stack} —
    decode caches ride through xs/ys with update-only in-place writes.

    ``policy`` is the depth-resolved SparsityPolicy for this block (per-
    block ranges already folded by ``run_groups``); None runs dense."""
    if policy is None:
        policy = sparse_linear.DENSE
    mixer, ffn = kind
    sp = sp or {}
    cache = cache or {}
    decode = mode in ("decode", "chunk", "verify")
    new_cache = dict(cache) if decode else {}
    if mixer in ATTN_KINDS:
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        h, nc = attn_apply(p["attn"], h, cfg, mixer, sp.get("attn"),
                           cache.get("self"), positions, mode, slot=slot,
                           policy=policy, token_weights=token_weights,
                           aligned=aligned)
        if nc is not None:
            new_cache["self"] = nc
        x = x + h
    elif mixer == "mamba":
        if mode in ("chunk", "verify"):
            raise NotImplementedError(
                "chunked prefill / speculative verify do not support SSM "
                "layers; use the engine's whole-prompt prefill strategy")
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        h, nc = mamba_apply(p["mamba"], h, cfg, sp.get("mamba"),
                            cache.get("ssm"), mode, policy=policy,
                            token_weights=token_weights)
        if nc is not None:
            new_cache["ssm"] = nc
        x = x + h
    if "cross" in p:
        h = rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        h, nc = attn_apply(p["cross"], h, cfg, "attn_bidir", sp.get("cross"),
                           cache.get("cross") if decode else None,
                           positions, mode,
                           kv_override=enc_out if enc_out is not None else x,
                           policy=policy, token_weights=token_weights,
                           aligned=aligned, role_base="cross")
        if mode == "prefill" and enc_out is not None:
            # stash static cross KV for decode (decode layouts)
            F = enc_out.shape[1]
            B = x.shape[0]
            KV, hd = cfg.num_kv_heads, cfg.head_dim
            ck = dense(enc_out, p["cross"]["wk"], policy=policy,
                       token_weights=None).reshape(B, F, KV, hd)
            cv = dense(enc_out, p["cross"]["wv"], policy=policy,
                       token_weights=None).reshape(B, F, KV, hd)
            new_cache["cross"] = {"k": ck.transpose(0, 2, 3, 1),
                                  "v": cv.transpose(0, 2, 1, 3)}
        x = x + h
    if ffn == "dense":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], h, cfg, sp.get("mlp"), mode,
                          policy=policy, token_weights=token_weights)
    elif ffn == "moe":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + moe_apply(p["moe"], h, cfg, sp.get("moe"), policy=policy)
    x = constrain(x, "batch", None, "embed_act")
    return x, (new_cache or None)


def _remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)   # "full": save nothing


def _rep_backends(policy, depth0: int, plen: int, reps: int):
    """Per-rep tuple of depth-resolved backends for one stacked group, or
    None when the policy has no per-block map (the uniform fast path)."""
    if policy is None or not policy.block_backends:
        return None
    return [tuple(policy.backend_at(depth=depth0 + r * plen + j)
                  for j in range(plen)) for r in range(reps)]


def _segments(policy, depth0: int, plen: int, reps: int):
    """``(r0, r1, jpols)`` per scan segment of one stacked group: the
    contiguous reps of equal backend signature and their per-pattern-
    position policies."""
    rb = _rep_backends(policy, depth0, plen, reps)
    if rb is None:
        return [(0, reps, (policy,) * plen)]
    segs, s = [], 0
    for r in range(1, reps + 1):
        if r == reps or rb[r] != rb[s]:
            jpols = tuple(policy.resolve_depth(depth0 + s * plen + j)
                          for j in range(plen))
            segs.append((s, r, jpols))
            s = r
    return segs


def _sparse_paths(sp, path=()):
    """Path of each sparse projection (an ``{"g", ...}`` leaf) in one
    layer's sp tree; its ``/``-joined form is the projection's role."""
    for k, v in sp.items():
        if "g" in v:
            yield path + (k,)
        else:
            yield from _sparse_paths(v, path + (k,))


def _leaf(tree, path):
    return functools.reduce(operator.getitem, path, tree)


def _with_leaf(tree, path, value):
    """Copy of nested dict ``tree`` with the leaf at ``path`` replaced."""
    head, *rest = path
    return {**tree, head: _with_leaf(tree[head], rest, value) if rest
            else value}


def _weight_feeds(gp, gsp, jpols):
    """How one scan segment feeds its sparse projections' weights:
    the param paths (``("l0", "mlp", "wo")``) whose stacked weight the
    ``pallas`` kernel reads in place, and how many sparse projections per
    layer take their weight as a slice through ``xs`` instead (expert
    stacks, and stacks the kernel could only read padded)."""
    in_place, sliced = [], 0
    if gsp is None:
        return in_place, sliced
    for j, pol in enumerate(jpols):
        for path in _sparse_paths(gsp.get(f"l{j}") or {}):
            if pol is None or pol.backend_at(role="/".join(path)) != "pallas":
                continue
            w = _leaf(gp[f"l{j}"], path)
            if w.ndim == 3 and reads_in_place(w.shape[1], w.shape[2],
                                              blk=pol.block):
                in_place.append((f"l{j}",) + path)
            else:
                sliced += 1
    return in_place, sliced


def sparse_weight_feeds(params, cfg: ModelConfig, sp=None, policy=None):
    """``{"in_place": n, "sliced": m}``: how many sparse projections of a
    decoder-stack forward under ``policy`` read their weight in place
    from the layer stack, and how many through a per-layer slice.  The
    decisions :func:`run_groups` takes, counted per layer; shapes alone
    decide them, so it runs on tracers (the engine calls it while its
    steps trace)."""
    counts = {"in_place": 0, "sliced": 0}
    if sp is None or policy is None:
        return counts
    depth = 0
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        plen = len(pattern)
        for r0, r1, jpols in _segments(policy, depth, plen, reps):
            in_place, sliced = _weight_feeds(params["groups"][gi], sp[gi],
                                             jpols)
            counts["in_place"] += len(in_place) * (r1 - r0)
            counts["sliced"] += sliced * (r1 - r0)
        depth += plen * reps
    return counts


def run_groups(groups, x, cfg: ModelConfig, patterns, *, mode="train",
               caches=None, positions=None, sp=None, enc_out=None,
               remat: str = "none", slot=None, policy=None,
               token_weights=None, aligned: bool = False, depth0: int = 0):
    """Scan each stacked layer group.  Returns (x, new_caches).

    Mixed per-block policies (``policy.block_backends``) split a group's
    rep scan into contiguous segments of equal backend signature — each
    segment is its own ``lax.scan`` over a slice of the stacked params /
    caches / sp, so the backend stays a static property of the trace while
    compile time grows only with the number of backend *switches*, not
    with depth.  Uniform policies take the single-scan fast path (HLO
    identical to the pre-policy code).
    """
    new_caches = []
    depth = depth0
    for gi, (gp, (pattern, reps)) in enumerate(zip(groups, patterns)):
        gc = caches[gi] if caches is not None else None
        gsp = sp[gi] if sp is not None else None
        plen = len(pattern)

        # NOTE (perf): through ``xs`` flows each layer's slice of the
        # caches, of the sp tree and of every weight a fused dot reads:
        # XLA fuses that slice into the dot.  Decode caches flow there
        # too, with update-only writes inside each per-layer slice:
        # carrying them through the scan carry, or unrolling the layer
        # loop over a stacked donated buffer, both force XLA to copy the
        # full stack per layer (10-600x memory-term regressions in the
        # decode dry-runs).  Not through ``xs``: the weight of a
        # projection the ``pallas`` kernel runs.  A custom call cannot
        # fuse a slice, so XLA would write the layer's whole dense
        # weight to a new buffer before each call, twice its bytes for
        # a kernel that reads the kept half (about 22 of the 130 ms of
        # a DeepSeek-67B-width decode step on one TPU v5e).  The segment
        # closes over that stack instead, and the kernel reads the
        # layer's kept tiles from it at the scan's layer index
        # (``LayerWeight``).

        seg_ys = []
        for (r0, r1, jpols) in _segments(policy, depth, plen, reps):
            in_place, _ = _weight_feeds(gp, gsp, jpols)
            rest = functools.reduce(
                lambda t, path: _with_leaf(t, path, None), in_place, gp)
            if (r0, r1) == (0, reps):
                xs = (rest, gc, gsp)
            else:
                xs = tuple(jax.tree_util.tree_map(
                    lambda a, lo=r0, hi=r1: a[lo:hi], t)
                    for t in (rest, gc, gsp))
            if in_place:
                xs += (jnp.arange(r0, r1, dtype=jnp.int32),)

            def body(xc, xs_in, pattern=pattern, jpols=jpols,
                     in_place=in_place, gp=gp):
                p_i, c_i, sp_i = xs_in[:3]
                for path in in_place:
                    p_i = _with_leaf(p_i, path, sparse_linear.LayerWeight(
                        _leaf(gp, path), xs_in[3]))
                ncs = []
                for j, kind in enumerate(pattern):
                    cj = c_i[j] if c_i is not None else None
                    spj = sp_i[f"l{j}"] if sp_i is not None else None
                    xc, nc = layer_apply(p_i[f"l{j}"], xc, cfg, kind, spj,
                                         cj, positions, mode, enc_out,
                                         slot=slot, policy=jpols[j],
                                         token_weights=token_weights,
                                         aligned=aligned)
                    ncs.append(nc)
                ys = tuple(ncs) if any(n is not None for n in ncs) else None
                return xc, ys

            wrapped = _remat_wrap(body, remat if mode == "train" else "none")
            x, ys = jax.lax.scan(wrapped, x, xs)
            seg_ys.append(ys)

        if len(seg_ys) == 1:
            new_caches.append(seg_ys[0])
        elif all(y is None for y in seg_ys):
            new_caches.append(None)
        else:
            new_caches.append(jax.tree_util.tree_map(
                lambda *ys: jnp.concatenate(ys, axis=0), *seg_ys))
        depth += plen * reps
    return x, new_caches


def embed_tokens(params, tokens, cfg: ModelConfig):
    e = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        e = e * jnp.asarray(cfg.d_model ** 0.5, e.dtype)
    return e


def lm_logits(params, x, cfg: ModelConfig):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return constrain(logits, "batch", None, "vocab")


def encode(params, frames, cfg: ModelConfig, sp=None, remat="none",
           policy=None):
    """Whisper encoder over precomputed conv-frontend frame embeddings.
    Per-block backend ranges index *decoder* depth, so the encoder runs
    the policy's default backend."""
    from repro.models.layers import sinusoidal_positions
    if policy is not None:
        policy = policy.resolve_depth(None)
    enc = params["encoder"]
    x = frames + sinusoidal_positions(frames.shape[1], cfg.d_model
                                      ).astype(frames.dtype)[None]
    patterns = [((("attn_bidir", "dense"),), cfg.encoder_layers)]
    x, _ = run_groups(enc["groups"], x, cfg, patterns, mode="train",
                      sp=sp, remat=remat, policy=policy, token_weights=None)
    return rmsnorm(x, enc["final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, *, tokens=None, frames=None,
            patch_embeds=None, mode="train", caches=None, positions=None,
            sp=None, sp_enc=None, remat="none", slot=None, policy=None,
            token_weights=None, aligned: bool = False):
    """Unified forward.

    train/prefill: tokens (B,S[-P]) [+ frames (B,F,D) | patch_embeds (B,P,D)]
    decode:        tokens (B,), positions (B,), caches required.
    chunk:         tokens (B,C) one request's prefill chunk, positions (B,)
                   chunk-start offset, slot () pool slot, caches = the full
                   slot pool (serving engine's chunked prefill).
    verify:        tokens (S,C) one C-token verify window per pool slot,
                   positions (S,) per-slot window start, caches = the full
                   slot pool (speculative decoding; batch dim == slot dim).

    policy: static SparsityPolicy (None runs dense).  token_weights:
    per-row weights for the shared top-k saliency (serving active-slot /
    real-token masks).
    aligned: static flag — all decode rows share one position, so cache
    writes collapse to a single dynamic_update_slice.

    Returns (logits, new_caches):
      train  -> logits (B,S,V), caches None
      prefill-> logits (B,V) last position, caches filled
      decode -> logits (B,V), caches updated
      chunk  -> logits (B,C,V) all chunk positions, pool caches updated
      verify -> logits (S,C,V) all window positions, pool caches updated
    """
    if policy is None:
        policy = sparse_linear.DENSE
    enc_out = None
    if cfg.family == "encdec" and frames is not None:
        enc_out = encode(params, frames, cfg, sp=sp_enc, remat=remat,
                         policy=policy)

    if mode in ("chunk", "verify"):
        x = embed_tokens(params, tokens, cfg)
        x, new_caches = run_groups(
            params["groups"], x, cfg, cfg.layer_groups(), mode=mode,
            caches=caches, positions=positions, sp=sp, slot=slot,
            policy=policy, token_weights=token_weights)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return lm_logits(params, x, cfg), new_caches

    if mode == "decode":
        x = embed_tokens(params, tokens[:, None], cfg)
        if cfg.family == "encdec" and cfg.rope_theta == 0.0:
            from repro.models.layers import sinusoidal_at
            x = x + sinusoidal_at(positions, cfg.d_model)[:, None].astype(x.dtype)
        x, new_caches = run_groups(
            params["groups"], x, cfg, cfg.layer_groups(), mode="decode",
            caches=caches, positions=positions, sp=sp, enc_out=enc_out,
            policy=policy, token_weights=token_weights, aligned=aligned)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return lm_logits(params, x, cfg)[:, 0], new_caches

    x = embed_tokens(params, tokens, cfg)
    if patch_embeds is not None:                      # VLM stub frontend
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    if cfg.family == "encdec":
        from repro.models.layers import sinusoidal_positions
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model
                                     ).astype(x.dtype)[None]
    x = constrain(x, "batch", None, "embed_act")
    x, new_caches = run_groups(
        params["groups"], x, cfg, cfg.layer_groups(), mode=mode,
        caches=None, positions=None, sp=sp, enc_out=enc_out, remat=remat,
        policy=policy, token_weights=token_weights)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if mode == "prefill":
        return lm_logits(params, x[:, -1:], cfg)[:, 0], new_caches
    return lm_logits(params, x, cfg), None
