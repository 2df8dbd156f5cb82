"""The plain reference: the served model's forward pass in float32
``jax.numpy``, teacher-forced over every token the program processed,
with weights remade from the seed layer by layer.  It imports nothing
of the program and takes nothing the program made.

What it computes is what the configuration file and the rung state: a
pre-norm decoder with grouped-query rotary attention, an FFN given by
the model type's own file under ``bench/models/``, an untied or tied
head.  On a sparse rung every sparsifiable projection keeps, per engine
step, the ``keep_frac`` of its 128-channel input blocks with the largest
summed weight-aware score |x| * ||W_row||^alpha over that step's rows,
and zeroes the others; the first ``prefill_dense_frac`` of each prompt
prefills dense.  Which rows shared a step is read from the harness's
own record of the run (which requests got a token in which step), not
from the program.

``precision="f32"`` multiplies at ``highest`` precision.  ``"fp8"`` is
the control: every projection's inputs and weights rounded to float8
e4m3 (scaled per row and per tensor), the nearest precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.counts import Shape

HIGHEST = jax.lax.Precision.HIGHEST
R = 256                          # query rows per attention block
ROWS = 2048                      # rows per block of the FFN and head
FP8_MAX = 448.0


@dataclasses.dataclass
class Rows:
    """Every token row the reference replays, requests contiguous, each
    request's rows at positions 0, 1, ...  ``step`` names the engine
    step whose sparse projections the row went through (-1: dense)."""
    tokens: np.ndarray
    positions: np.ndarray
    request: np.ndarray
    step: np.ndarray
    n_steps: int


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = FP8_MAX / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def matmul(x, w, precision: str):
    if precision == "fp8":
        return jnp.dot(_q8(x, -1), _q8(w, None), precision=HIGHEST)
    return jnp.dot(x, w, precision=HIGHEST)


def rmsnorm(x, offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset)


def rope(x, pos, theta):
    """x (N, heads, hd); half-split rotation (HF ``rotate_half``)."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def block_sums(x, w, alpha, block=128):
    """(N, nb): each row's summed |x| * ||W_row||^alpha per 128-channel
    input block of ``w``."""
    n = x.shape[1]
    nb = -(-n // block)
    g = jnp.sqrt(jnp.sum(w * w, axis=1))
    s = jnp.abs(x) * jnp.power(jnp.maximum(g, 1e-12), alpha)
    if nb * block != n:
        s = jnp.pad(s, ((0, 0), (0, nb * block - n)))
    return s.reshape(x.shape[0], nb, block).sum(-1)


def step_keep(bs, n, step, n_steps, keep_frac, block=128):
    """(n_steps + 1, n) 0/1 keep mask of the input channels per step: the
    top ``keep_frac`` of blocks by the block sums ``bs`` of the step's
    rows.  The last row (step -1: dense rows) keeps all."""
    nb = bs.shape[1]
    kb = max(1, min(nb, round(nb * keep_frac)))
    tot = jax.ops.segment_sum(bs, segments(step, n_steps),
                              num_segments=n_steps + 1)
    _, idx = jax.lax.top_k(tot, kb)
    keep = jnp.zeros((n_steps + 1, nb), jnp.float32)
    keep = keep.at[jnp.arange(n_steps + 1)[:, None], idx].set(1.0)
    keep = keep.at[n_steps].set(1.0)
    return jnp.repeat(keep, block, axis=1)[:, :n]


def segments(step, n_steps):
    return jnp.where(step >= 0, step, n_steps)


class Model:
    """The reference for one configuration, rung and seed."""

    def __init__(self, cfg: dict, rung: dict, seed: int,
                 precision: str = "f32"):
        self.cfg = cfg
        self.s = Shape.of(cfg)
        self.rung = rung
        self.sparse = rung.get("backend", "off") != "off"
        self.seed = W.seed_words(seed)
        self.precision = precision
        self.served = jnp.dtype(cfg["torch_dtype"])   # the weights' dtype
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.family = importlib.import_module(
            f"bench.models.{cfg['model_type']}")
        if self.sparse and self.family.SPARSE_FFN is False:
            raise NotImplementedError(
                f"no sparse reference for {cfg['model_type']}'s FFN")

    # -- weights, remade from the seed and rounded to the served dtype ---
    def _leaf(self, name, layer, shape, experts=0):
        if experts:
            w = jax.vmap(lambda e: W.draw(self.seed, name, layer, e, shape))(
                jnp.arange(experts, dtype=jnp.uint32))
        else:
            w = W.draw(self.seed, name, layer, 0, shape)
        return w.astype(self.served).astype(jnp.float32)

    def layer_weights(self, layer):
        s = self.s
        q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
        out = {"ln1": ((s.d,), 0), "attn/wq": ((s.d, q), 0),
               "attn/wk": ((s.d, kv), 0), "attn/wv": ((s.d, kv), 0),
               "attn/wo": ((q, s.d), 0), "ln2": ((s.d,), 0)}
        out.update(self.family.leaves(s))
        return {k: self._leaf(k, layer, shape, e)
                for k, (shape, e) in out.items()}

    # -- one layer over every row -----------------------------------------
    @property
    def alpha(self) -> float:
        return float(self.rung.get("alpha", 1.0))

    def keep(self, x, w, step, n_steps, sums=None):
        """Per-step channel keep mask of projection ``w`` over inputs
        ``x`` (or their precomputed :func:`block_sums`), or None on a
        dense rung."""
        if not self.sparse or step is None:
            return None
        if sums is None:
            sums = block_sums(x, w, self.alpha)
        return step_keep(sums, w.shape[0], step, n_steps,
                         float(self.rung["keep_frac"]))

    def proj(self, x, w, step, n_steps, keep=None):
        """x @ w, sparsified per step on a sparse rung.  ``keep`` is a
        mask from :meth:`keep` computed over all rows, for row blocks."""
        if keep is None:
            keep = self.keep(x, w, step, n_steps)
        if keep is not None:
            x = x * keep[segments(step, n_steps)]
        return matmul(x, w, self.precision)

    def row_blocks(self, fn, *arrays):
        """``fn`` over blocks of ROWS rows of ``arrays`` (first dims
        equal, a multiple of R), results concatenated."""
        N = arrays[0].shape[0]
        M = -(-N // ROWS) * ROWS
        padded = [jnp.pad(a, ((0, M - N),) + ((0, 0),) * (a.ndim - 1),
                          constant_values=(-1 if a.dtype == jnp.int32 else 0))
                  for a in arrays]
        out = jax.lax.map(lambda i: fn(*[
            jax.lax.dynamic_slice_in_dim(a, i * ROWS, ROWS) for a in padded]),
            jnp.arange(M // ROWS))
        return jax.tree_util.tree_map(
            lambda o: o.reshape((M,) + o.shape[2:])[:N], out)

    def attention(self, q, k, v, req, pos, window):
        """Causal attention within each request, query blocks of R rows
        against the ``window`` rows that end with the block."""
        s = self.s
        N = q.shape[0]
        G = s.heads // s.kv_heads
        front = window - R
        kp = jnp.pad(k, ((front, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((front, 0), (0, 0), (0, 0)))
        rq = jnp.pad(req, (front, 0), constant_values=-2)
        pp = jnp.pad(pos, (front, 0))
        scale = s.head_dim ** -0.5

        def blk(i):
            a = i * R
            qb = jax.lax.dynamic_slice_in_dim(q, a, R).reshape(
                R, s.kv_heads, G, s.head_dim)
            kb = jax.lax.dynamic_slice_in_dim(kp, a, window)
            vb = jax.lax.dynamic_slice_in_dim(vp, a, window)
            mask = ((jax.lax.dynamic_slice_in_dim(rq, a, window)[None, :]
                     == jax.lax.dynamic_slice_in_dim(req, a, R)[:, None])
                    & (jax.lax.dynamic_slice_in_dim(pp, a, window)[None, :]
                       <= jax.lax.dynamic_slice_in_dim(pos, a, R)[:, None]))
            sc = jnp.einsum("rkgd,wkd->kgrw", qb, kb,
                            precision=HIGHEST) * scale
            sc = jnp.where(mask[None, None], sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("kgrw,wkd->rkgd", p, vb, precision=HIGHEST)
            return o.reshape(R, s.heads * s.head_dim)

        out = jax.lax.map(blk, jnp.arange(N // R))
        return out.reshape(N, s.heads * s.head_dim)

    def layer(self, x, lw, tok_step, req, pos, n_steps, window):
        s = self.s
        N = x.shape[0]
        h = rmsnorm(x, lw["ln1"], self.eps)
        q = self.proj(h, lw["attn/wq"], tok_step, n_steps)
        k = self.proj(h, lw["attn/wk"], tok_step, n_steps)
        v = self.proj(h, lw["attn/wv"], tok_step, n_steps)
        q = rope(q.reshape(N, s.heads, s.head_dim), pos, self.theta)
        k = rope(k.reshape(N, s.kv_heads, s.head_dim), pos, self.theta)
        v = v.reshape(N, s.kv_heads, s.head_dim)
        o = self.attention(q, k, v, req, pos, window)
        x = x + self.proj(o, lw["attn/wo"], tok_step, n_steps)
        h = rmsnorm(x, lw["ln2"], self.eps)
        return x + self.family.ffn(self, lw, h, tok_step, n_steps)

    # -- the whole model ----------------------------------------------------
    def run(self, rows: Rows, compare: np.ndarray, candidates: np.ndarray,
            max_len: int):
        """Logit facts at the rows ``compare`` (indices into ``rows``):
        (best logit, argmax, logit of each of ``candidates[:, j]``)."""
        s = self.s
        n = len(rows.tokens)
        N = -(-n // R) * R
        pad = N - n

        def padded(a, value):
            return jnp.asarray(np.pad(a, (0, pad), constant_values=value))

        tok = padded(rows.tokens, 0)
        pos = padded(rows.positions, 0)
        req = padded(rows.request, -1)
        step = padded(rows.step, -1) if self.sparse else None
        window = -(-(max_len + R) // R) * R
        x = self._embed(tok)
        layer = jax.jit(functools.partial(self.layer, n_steps=rows.n_steps,
                                          window=window))
        lw_fn = jax.jit(self.layer_weights)
        for li in range(s.layers):
            lw = lw_fn(jnp.uint32(li))
            x = layer(x, lw, step, req, pos)
            del lw
        return self._head(x, jnp.asarray(compare), jnp.asarray(candidates))

    def _embed(self, tok):
        return jax.jit(lambda t: W.draw_rows(self.seed, "embed", t, self.s.d)
                       .astype(self.served).astype(jnp.float32))(tok)

    def _head(self, x, compare, cand):
        s = self.s

        @jax.jit
        def head(x, compare, cand):
            fn = self._leaf("final_norm", 0, (s.d,))
            if s.tied:
                wt = self._leaf("embed", 0, (s.vocab, s.d)).T
            else:
                wt = self._leaf("lm_head", 0, (s.d, s.vocab))
            m = compare.shape[0]
            M = -(-m // ROWS) * ROWS
            idx = jnp.pad(compare, (0, M - m))
            cd = jnp.pad(cand, ((0, M - m), (0, 0)))

            def blk(i):
                xi = jax.lax.dynamic_slice_in_dim(idx, i * ROWS, ROWS)
                ci = jax.lax.dynamic_slice_in_dim(cd, i * ROWS, ROWS)
                h = rmsnorm(x[xi], fn, self.eps)
                lg = matmul(h, wt, self.precision)
                return (lg.max(-1), jnp.argmax(lg, -1),
                        jnp.take_along_axis(lg, ci, axis=1))

            best, arg, at = jax.lax.map(blk, jnp.arange(M // ROWS))
            return (best.reshape(-1)[:m], arg.reshape(-1)[:m],
                    at.reshape(M, -1)[:m])

        out = head(x, compare, cand)
        return tuple(np.asarray(a) for a in out)


def build_rows(requests: Sequence, steps_of: Optional[Sequence] = None,
               n_steps: int = 0) -> Rows:
    """``requests``: token arrays (prompt then served tokens, as the
    program processed them); ``steps_of``: per request, the step id of
    each row (-1 dense), or None for a dense rung."""
    toks, poss, reqs, stp = [], [], [], []
    for i, t in enumerate(requests):
        t = np.asarray(t, np.int32)
        toks.append(t)
        poss.append(np.arange(len(t), dtype=np.int32))
        reqs.append(np.full(len(t), i, np.int32))
        stp.append(np.asarray(steps_of[i], np.int32) if steps_of is not None
                   else np.full(len(t), -1, np.int32))
    return Rows(np.concatenate(toks), np.concatenate(poss),
                np.concatenate(reqs), np.concatenate(stp), n_steps)


def gaps(model: Model, rows: Rows, compare: np.ndarray, served: np.ndarray,
         max_len: int, control: Optional[np.ndarray] = None):
    """Per compared row: how far the reference's logit of the served
    token lies below its best (and of ``control``'s token, if given)."""
    cand = served[:, None] if control is None \
        else np.stack([served, control], 1)
    best, _arg, at = model.run(rows, compare, cand.astype(np.int32), max_len)
    return best[:, None] - at


def control_tokens(model: Model, rows: Rows, compare: np.ndarray,
                   max_len: int) -> np.ndarray:
    """The tokens a model puts first at the compared rows."""
    dummy = np.zeros((len(compare), 1), np.int32)
    _best, arg, _at = model.run(rows, compare, dummy, max_len)
    return arg.astype(np.int32)


def free_device_memory() -> None:
    import gc
    gc.collect()
    jax.clear_caches()
