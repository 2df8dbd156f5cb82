"""The yardstick's counts against hand arithmetic."""
import json
import os

import pytest

from bench import counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def shape(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return counts.Shape.of(json.load(f))


def test_deepseek_layer_params():
    s = shape("deepseek-llm-67b")
    attn = 2 * 8192 * 8192 + 2 * 8192 * 1024
    mlp = 3 * 8192 * 22016
    assert counts.layer_active_params(s) == attn + mlp == 692_060_160
    assert counts.active_params(s) == 6 * 692_060_160 + 8192 * 102400


def test_granite_scales_experts_over_the_published_forty():
    s = shape("granite-3.0-3b-a800m")
    assert s.experts == 40 and s.experts_per_tok == 8
    attn = 2 * 1536 * 1536 + 2 * 1536 * 512
    router = 1536 * 40
    experts = 8 * 3 * 1536 * 512          # 8 of 40, not of the padded 48
    assert counts.layer_active_params(s) == attn + router + experts
    # tied head still multiplies every token
    assert counts.active_params(s) == 16 * (attn + router + experts) \
        + 1536 * 49155


def test_step_flops():
    s = shape("deepseek-llm-67b")
    per = 2.0 * counts.active_params(s)
    attn = 4.0 * 100 * 64 * 128 * 6          # QK^T and PV over 100 positions
    assert counts.step_flops(s, 1, 100) == pytest.approx(per + attn)
    assert counts.step_flops(s, 3, 300) == pytest.approx(3 * per + 3 * attn)


def test_sparse_work_hand_count():
    s = shape("deepseek-llm-67b")
    w = counts.sparse_matmul_work(s, rows=32, keep_frac=0.5)
    # wq alone, one layer: 32 of 64 blocks of 128 rows kept
    k = 32 * 128
    wq_bytes = k * 8192 * 2 + 32 * k * 2 + 32 * 8192 * 2
    wq_flops = 2 * 32 * k * 8192
    total_b = total_f = 0
    for _r, n, m in counts.projections(s):
        kk = round(n // 128 * 0.5) * 128
        total_b += kk * m * 2 + 32 * kk * 2 + 32 * m * 2
        total_f += 2 * 32 * kk * m
    assert w.bytes == 6 * total_b and w.flops == 6 * total_f
    assert total_b > wq_bytes and total_f > wq_flops
    assert counts.sparse_matmul_work(shape("granite-3.0-3b-a800m"),
                                     32, 0.5) is None


def test_sparse_work_is_half_the_dense_weight_bytes():
    s = shape("deepseek-llm-67b")
    half = counts.sparse_matmul_work(s, 32, 0.5)
    full = counts.sparse_matmul_work(s, 32, 1.0)
    weights = 6 * sum(n * m for _r, n, m in counts.projections(s)) * 2
    assert full.bytes > weights > half.bytes > 0.49 * weights
    least, bound = half.least_s(counts.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert least == pytest.approx(half.bytes / 819e9)


def test_roofline_share_cannot_pass_100_for_the_least_time():
    """A kernel that took exactly the least time reads 100%, never more:
    the share divides the least time by the kernel's time."""
    s = shape("deepseek-llm-67b")
    w = counts.sparse_matmul_work(s, 32, 0.5)
    least, _ = w.least_s(counts.peaks("TPU v5 lite"))
    assert 100.0 * least / least == 100.0
    assert 100.0 * least / (1.25 * least) < 100.0


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        counts.peaks("TPU v99")
    p = counts.peaks("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bw) == (197e12, 819e9)
    assert "v5e" in p.source
