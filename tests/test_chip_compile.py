"""Ahead-of-time compiles of the serving main path for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a
``v5e:2x2`` topology description, which raises what the chip's compiler
would raise (block shapes off the (8, 128) tiling, VMEM overruns).
Interpret mode accepts all of those, so the kernel tests in
``test_kernels.py`` cannot.  Every kernel compiles with
``interpret=False`` and must lower to a Mosaic ``tpu_custom_call``.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library at a time, and each test
worker imports every test file.  The persistent compile cache is off
around these compiles: an entry written without a chip cannot be read
back here.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import serving_config
from repro.core.sp_schema import abstract_sp
from repro.kernels import ops
from repro.kernels import sparse_matmul as K
from repro.models import api
from repro.models import params as P
from repro.serving.engine import make_engine_steps
from repro.sparsity import SparsityPolicy

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    """Shape stand-ins of ``tree`` placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B", [16, 12])
@pytest.mark.parametrize("n,m", [(4096, 14336), (14336, 4096), (4096, 6144)])
def test_sparse_matmul_shared_compiles(one_chip, B, n, m):
    kb = n // K.DEFAULT_BLK // 2
    x, w, idx = _on((jax.ShapeDtypeStruct((B, n), jnp.bfloat16),
                     jax.ShapeDtypeStruct((n, m), jnp.bfloat16),
                     jax.ShapeDtypeStruct((kb,), jnp.int32)), one_chip)
    hlo = _hlo(lambda x, w, i: K.sparse_matmul_shared(x, w, i,
                                                      interpret=False),
               x, w, idx)
    assert KERNEL in hlo


def test_sparse_matmul_per_seq_compiles(one_chip):
    B, n, m = 16, 4096, 14336
    kb = n // K.DEFAULT_BLK // 2
    x, w, idx = _on((jax.ShapeDtypeStruct((B, n), jnp.bfloat16),
                     jax.ShapeDtypeStruct((n, m), jnp.bfloat16),
                     jax.ShapeDtypeStruct((B, kb), jnp.int32)), one_chip)
    hlo = _hlo(lambda x, w, i: K.sparse_matmul_per_seq(x, w, i,
                                                       interpret=False),
               x, w, idx)
    assert KERNEL in hlo


@pytest.mark.parametrize("n", [4096, 14336])
def test_score_mask_compiles(one_chip, n):
    B = 16
    x, g, rw = _on((jax.ShapeDtypeStruct((B, n), jnp.bfloat16),
                    jax.ShapeDtypeStruct((n,), jnp.float32),
                    jax.ShapeDtypeStruct((B,), jnp.float32)), one_chip)
    hlo = _hlo(lambda x, g, rw: K.score_mask(x, g, 1.0, 0.5,
                                             interpret=False,
                                             row_weights=rw), x, g, rw)
    assert KERNEL in hlo


def test_wisparse_project_compiles(one_chip):
    B, n, m = 16, 4096, 14336
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    x, w, sp = _on((jax.ShapeDtypeStruct((B, n), jnp.bfloat16),
                    jax.ShapeDtypeStruct((n, m), jnp.bfloat16),
                    {"g": jax.ShapeDtypeStruct((n,), jnp.float32),
                     "alpha": scalar, "tau": scalar, "keep_frac": scalar}),
                   one_chip)
    hlo = _hlo(lambda x, w, sp: ops.wisparse_project(
        x, w, sp, k_frac=0.5, interpret=False), x, w, sp)
    assert hlo.count(KERNEL) >= 2         # score_mask + sparse matmul


SLOTS, POOL_LEN, CHUNK = 16, 4096, 256


@pytest.fixture(scope="module")
def engine_args(one_chip):
    """The engine's step arguments at published Llama-3.1-8B widths, two
    layers deep, 16 slots x 4096 positions."""
    cfg = serving_config("llama31_8b", layers=2)
    params = _on(api.abstract_model(cfg)[0], one_chip)
    caches = _on(P.abstract_params(api.cache_schema(cfg, SLOTS, POOL_LEN),
                                   cfg.dtype), one_chip)
    sp = _on(abstract_sp(cfg)[0], one_chip)
    return cfg, params, caches, sp


def _policy(backend):
    return SparsityPolicy.uniform(backend, k_max_frac=0.5, interpret=False)


@pytest.mark.parametrize("backend", ["off", "pallas"])
def test_engine_decode_step_compiles(one_chip, engine_args, backend):
    cfg, params, caches, sp = engine_args
    tokens, positions, active = _on(
        (jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
         jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
         jax.ShapeDtypeStruct((SLOTS,), jnp.float32)), one_chip)
    dstep, _, _ = make_engine_steps(cfg)
    hlo = dstep.lower(params, tokens, positions, caches, sp, active,
                      policy=_policy(backend)).compile().as_text()
    assert (KERNEL in hlo) == (backend == "pallas")


def test_engine_chunk_step_compiles(one_chip, engine_args):
    cfg, params, caches, sp = engine_args
    tokens, offset, slot, weights = _on(
        (jax.ShapeDtypeStruct((1, CHUNK), jnp.int32),
         jax.ShapeDtypeStruct((1,), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32),
         jax.ShapeDtypeStruct((CHUNK,), jnp.float32)), one_chip)
    _, cstep, _ = make_engine_steps(cfg)
    hlo = cstep.lower(params, tokens, offset, slot, caches, sp, weights,
                      policy=_policy("pallas")).compile().as_text()
    assert KERNEL in hlo


_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* (\S+?)\(([^)]*)\)")


def _layer_copies(hlo, stacks, layers):
    """Fusions whose result is one layer's weight (shapes in ``layers``,
    e.g. ``bf16[8192,22016]``) and that read a stacked weight (shapes in
    ``stacks``): a layer's weight written out of its stack."""
    shapes, hits = {}, []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, op, args = m.groups()
        shapes[name] = shape
        if op == "fusion" and shape in layers and any(
                shapes.get(a) in stacks
                for a in re.findall(r"%([\w.\-]+)", args)):
            hits.append(name)
    return hits


@pytest.mark.parametrize("feed", ["in_place", "sliced"])
def test_pallas_decode_reads_weight_stacks_in_place(one_chip, monkeypatch,
                                                    feed):
    """The pallas decode step at DeepSeek-67B widths, two layers deep:
    the sparse kernel reads each layer's kept tiles from the scan's
    stacked weight, so no fusion writes one layer's gate, up or down
    weight out of its stack.  The same step fed through per-layer
    slices (the path a stack the kernel could only read padded takes)
    does write them — the check can fail."""
    from repro.models import model as M
    if feed == "sliced":
        monkeypatch.setattr(M, "reads_in_place", lambda *a, **kw: False)
    S, T = 32, 2048
    cfg = serving_config("deepseek_67b", layers=2)
    params = _on(api.abstract_model(cfg)[0], one_chip)
    caches = _on(P.abstract_params(api.cache_schema(cfg, S, T), cfg.dtype),
                 one_chip)
    sp = _on(abstract_sp(cfg)[0], one_chip)
    tokens, positions, active = _on(
        (jax.ShapeDtypeStruct((S,), jnp.int32),
         jax.ShapeDtypeStruct((S,), jnp.int32),
         jax.ShapeDtypeStruct((S,), jnp.float32)), one_chip)
    dstep, _, _ = make_engine_steps(cfg)
    hlo = dstep.lower(params, tokens, positions, caches, sp, active,
                      policy=_policy("pallas")).compile().as_text()
    assert K.SHARED_NAME in hlo
    ffn = ("8192,22016", "22016,8192")
    copies = _layer_copies(hlo, {f"bf16[2,{d}]" for d in ffn},
                           {f"bf16[{u}{d}]" for d in ffn for u in ("", "1,")})
    assert bool(copies) == (feed == "sliced"), copies
