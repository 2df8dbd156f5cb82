"""Seeded weights, made by the benchmark and not by the program.

Every weight is a counter-based hash of (seed, leaf name, layer, expert,
element index), so one jitted call makes the whole tree on the device
in its served dtype, and the reference can remake any single layer in
float32 without touching what the program holds.

Values are uniform: the embedding at std 0.02, every matrix at std
1/sqrt(fan-in).  The input-channel rows of every
projection that the sparse rungs may skip come in 128-channel blocks of
four scales (1, 1/2, 1/4, 1/8), a quarter of the blocks each, placed by
the seed.  Real models have strongly salient channels; here the scales
make the shared block top-k choose with a 2x margin at a keep fraction
of 1/4, 1/2 or 3/4, so the served tokens can be checked against a float32
reference that chooses for itself (``PERF.md``, "How correct is
decided").  Norm weights are 1 + U(-0.1, 0.1), stored as the offset from
1 the way the program keeps them.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
BLOCK = 128
LEVELS = 4                      # block scales 2**-0 .. 2**-(LEVELS-1)
NORM_HALF_WIDTH = 0.1
# leaves whose input rows carry block scales (the sparsifiable roles)
PROJECTIONS = ("wq", "wk", "wv", "wo", "wi_gate", "wi_up")


def std(name: str, fan_in: int) -> float:
    """The embedding at 0.02; every matrix a token multiplies through at
    1/sqrt(fan-in), so each layer adds O(1) to the residual and a tied
    head's copy of the input token stays a small bias.  (At 0.02
    throughout, a tied model repeats its last token forever.)"""
    return EMBED_STD if name == "embed" else float(fan_in) ** -0.5


def seed_words(seed: int):
    """Two 32-bit words of a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def leaf_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0xFFFFFFFF


def _fmix(h):
    """murmur3's 32-bit finalizer, on uint32 arrays."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _key(seed, name: str, layer, expert):
    """uint32 key of one (leaf, layer, expert); ``seed`` is a pair of
    uint32 scalars, ``layer``/``expert`` uint32 scalars or arrays."""
    lo, hi = seed
    k = _fmix(jnp.uint32(lo) ^ _fmix(jnp.uint32(hi) + jnp.uint32(0x632BE5AB)))
    k = _fmix(k ^ jnp.uint32(leaf_id(name)))
    k = _fmix(k + jnp.asarray(layer, jnp.uint32) * jnp.uint32(0x9E3779B1))
    return _fmix(k + jnp.asarray(expert, jnp.uint32) * jnp.uint32(0x7FEB352D))


def _uniform(key, shape):
    """U(-1, 1) of ``shape`` from one uint32 key, element by element."""
    n = int(np.prod(shape))
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    return _hash_uniform(idx, key)


def _hash_uniform(idx, key):
    h = _fmix(_fmix(idx * jnp.uint32(0x9E3779B1) ^ key) + key)
    return 2.0 * ((h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)) - 1.0


def block_scales(key, n: int):
    """(n,) scale of each input row: blocks of 128 at 2**-level, a
    quarter of the blocks at each level, placed by ``key``."""
    if n < BLOCK:
        return jnp.ones((n,), jnp.float32)
    nb = -(-n // BLOCK)
    h = _fmix(jax.lax.iota(jnp.uint32, nb) * jnp.uint32(0x27D4EB2F) ^ key)
    rank = jnp.argsort(jnp.argsort(h))
    level = (rank * LEVELS) // nb
    scale = jnp.exp2(-level.astype(jnp.float32))
    return jnp.repeat(scale, BLOCK, total_repeat_length=nb * BLOCK)[:n]


def draw(seed, name: str, layer, expert, shape):
    """One leaf of one (layer, expert) in float32.  ``shape`` is the
    per-layer, per-expert shape: (n,) for a norm, (n, m) for a matrix
    whose rows are its inputs."""
    key = _key(seed, name, layer, expert)
    u = _uniform(key, shape)
    if len(shape) == 1:
        return NORM_HALF_WIDTH * u
    w = u * jnp.float32(std(name, shape[0]) * np.sqrt(3.0))
    if name.split("/")[-1] in PROJECTIONS:
        w = w * block_scales(key ^ jnp.uint32(0xA5A5A5A5), shape[0])[:, None]
    return w


def draw_rows(seed, name: str, rows, width: int):
    """Rows ``rows`` of a (n, width) leaf of layer 0 with no block
    scales (the embedding), equal to the same rows of :func:`draw`."""
    key = _key(seed, name, 0, 0)
    idx = (jnp.asarray(rows, jnp.uint32)[:, None] * jnp.uint32(width)
           + jax.lax.iota(jnp.uint32, width)[None, :])
    return _hash_uniform(idx, key) * jnp.float32(std(name, 0) * np.sqrt(3.0))


def draw_stacked(seed, name: str, shape, layers: int, experts: int = 0):
    """A leaf stacked over layers (and experts): (L, [E,] *shape), each
    slice equal to :func:`draw` of its (layer, expert)."""
    if experts:
        f = jax.vmap(lambda l: jax.vmap(
            lambda e: draw(seed, name, l, e, shape))(
                jnp.arange(experts, dtype=jnp.uint32)))
    else:
        f = jax.vmap(lambda l: draw(seed, name, l, 0, shape))
    return f(jnp.arange(layers, dtype=jnp.uint32))
