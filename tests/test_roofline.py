"""Roofline machinery: the trip-count-aware HLO analyzer vs XLA's own
cost_analysis, collective parsing, and model-FLOPs accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.launch import hlo_analysis as H
from repro.launch import roofline as R


def test_analyzer_matches_cost_analysis_unrolled():
    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 256, 256), jnp.float32)

    def g(x, ws):
        y = x
        for i in range(4):
            y = y @ ws[i]
        return y

    c = jax.jit(g).lower(x, ws).compile()
    a = H.analyze(c.as_text())
    expected = 2 * 64 * 256 * 256 * 4
    assert a["flops"] == expected
    # XLA agrees on scan-free modules (upto convert/noise ops)
    assert abs(a["flops"] - c.cost_analysis()["flops"]) / expected < 0.2


def test_analyzer_scales_scan_by_trip_count():
    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)

    def f(x, ws):
        def body(c, w):
            return c @ w, None
        return jax.lax.scan(body, x, ws)[0]

    c = jax.jit(f).lower(x, ws).compile()
    a = H.analyze(c.as_text())
    expected = 2 * 64 * 256 * 256 * 12
    assert a["flops"] == expected
    # ...which is what cost_analysis misses (counts the body once)
    assert c.cost_analysis()["flops"] < expected / 6


def test_collective_regex():
    line = ("%all-gather.3 = f32[8,192]{0,1} all-gather(%x), channel_id=1, "
            "replica_groups=[128,2]<=[16,8,2]T(1,0,2)")
    out = R.collective_bytes(line)
    assert out["all-gather"] == 8 * 192 * 4


def test_wire_bytes_allreduce_double():
    assert R.wire_bytes({"all-reduce": 100, "all-gather": 50,
                         "reduce-scatter": 0, "all-to-all": 0,
                         "collective-permute": 0}) == 250


def test_model_flops_moe_uses_active_params():
    dense = get_config("deepseek_67b")
    moe = get_config("granite_moe_1b_a400m")
    n_moe = R.active_matmul_params(moe)
    # granite-1b: active ~= attn + 8/32 of expert params
    total_expert = moe.num_layers * moe.num_experts * 3 * \
        moe.d_model * moe.expert_d_ff
    active_expert = total_expert * moe.num_experts_per_tok / moe.num_experts
    assert n_moe < total_expert            # sanity: activity discount applied
    attn = moe.num_layers * (2 * moe.d_model * moe.num_heads * moe.head_dim
                             + 2 * moe.d_model * moe.num_kv_heads * moe.head_dim)
    expect = attn + active_expert + moe.num_layers * moe.d_model * moe.num_experts \
        + moe.vocab_size * moe.d_model
    assert abs(n_moe - expect) / expect < 0.05
    # dense: ~67B plus head
    n_dense = R.active_matmul_params(dense)
    assert 6.0e10 < n_dense < 7.5e10


def test_roofline_terms_and_bottleneck():
    rl = R.Roofline("a", "s", "single", 256, hlo_flops=197e12,
                    hlo_bytes=819e9 * 2, coll_bytes=50e9 * 0.5,
                    model_flops_total=197e12 * 256 * 0.5,
                    device_kind="TPU v5 lite")
    assert abs(rl.compute_s - 1.0) < 1e-9
    assert abs(rl.memory_s - 2.0) < 1e-9
    assert abs(rl.collective_s - 0.5) < 1e-9
    assert rl.bottleneck == "memory"
    assert abs(rl.mfu - 0.25) < 1e-9


def test_roofline_refuses_unknown_device():
    """No peaks for the device: an error, never the v5e numbers."""
    rl = R.Roofline("a", "s", "single", 1, hlo_flops=1.0, hlo_bytes=1.0,
                    coll_bytes=0.0, model_flops_total=1.0, device_kind="cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        rl.compute_s


def test_executable_costs_reads_the_cost_dict():
    c = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    flops, byts = R.executable_costs(c)
    assert flops == pytest.approx(2 * 64 ** 3, rel=0.01)
    assert byts > 0


@pytest.mark.parametrize("shape,expected_factor", [
    ("train_4k", 6.0), ("prefill_32k", 2.0)])
def test_model_flops_mode_factor(shape, expected_factor):
    cfg = get_config("llama31_8b")
    s = SHAPES[shape]
    n = R.active_matmul_params(cfg)
    assert R.model_flops(cfg, s) == pytest.approx(
        expected_factor * n * s.global_batch * s.seq_len)
