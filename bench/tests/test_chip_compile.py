"""Each cell's step programs and weight generator, compiled at the
cell's own sizes for a described TPU v5e (no chip attached): what the
chip's compiler would refuse, for HBM or for a kernel's tiling, fails
here.  The topology is described inside a fixture, never at import."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import cell as cellmod

HBM = 15.75 * 2**30
CELLS = [w["name"] for w in cellmod.benchmark()["workloads"]]


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(t.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_steps_compile(one_chip, workload):
    from repro.core.sp_schema import abstract_sp
    from repro.models import api
    from repro.models import params as P
    from repro.serving.engine import make_engine_steps
    from repro.sparsity import SparsityPolicy

    c = cellmod.find(workload)
    pcfg = cellmod.program_config(c.cfg)
    sv = c.serving
    S, C = sv["max_slots"], sv["prefill_chunk"]
    pool = sv["max_len"] + C
    params = _on(api.abstract_model(pcfg)[0], one_chip)
    caches = _on(P.abstract_params(api.cache_schema(pcfg, S, pool),
                                   pcfg.dtype), one_chip)
    r = c.rung
    if r["backend"] == "off":
        sp, pol = None, SparsityPolicy.dense()
    else:
        sp = _on(abstract_sp(pcfg)[0], one_chip)
        pol = SparsityPolicy.uniform(r["backend"],
                                     k_max_frac=float(r["k_max_frac"]),
                                     interpret=False)
    sds = jax.ShapeDtypeStruct
    dstep, cstep, _ = make_engine_steps(pcfg)
    d = dstep.lower(params, *_on((sds((S,), jnp.int32), sds((S,), jnp.int32)),
                                 one_chip), caches, sp,
                    _on(sds((S,), jnp.float32), one_chip),
                    policy=pol.for_phase("decode")).compile()
    assert _bytes(d) < HBM
    assert ("tpu_custom_call" in d.as_text()) == (r["backend"] == "pallas")
    chunk_args = _on((sds((1, C), jnp.int32), sds((1,), jnp.int32),
                      sds((), jnp.int32)), one_chip)
    for phase in ("prefill_dense", "prefill_sparse"):
        ch = cstep.lower(params, *chunk_args, caches, sp,
                         _on(sds((C,), jnp.float32), one_chip),
                         policy=pol.for_phase(phase)).compile()
        assert _bytes(ch) < HBM


@pytest.mark.parametrize("config", sorted({w["config"] for w in
                                           cellmod.benchmark()["workloads"]}))
def test_weights_fit_in_one_jitted_call(one_chip, config):
    """The weight generator writes each leaf in its served dtype with no
    float32 copy alongside."""
    cfg = cellmod._load("configs", config + ".json")
    build, _ = cellmod.param_builder(cellmod.program_config(cfg), cfg, 1)
    ma = jax.jit(build, out_shardings=one_chip).lower().compile() \
        .memory_analysis()
    assert ma.temp_size_in_bytes < 0.05 * ma.output_size_in_bytes
    assert ma.output_size_in_bytes < HBM
