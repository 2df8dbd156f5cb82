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


def test_existing_shapes_sparse_work_unchanged():
    """The dense kernel's count stays what it was for every shape the
    benchmark had: DeepSeek's exact figures, None for the MoE shape."""
    s = shape("deepseek-llm-67b")
    w = counts.sparse_matmul_work(s, 32, 0.5)
    assert (w.flops, w.bytes) == (132_875_550_720.0, 4_193_157_120.0)
    for name in ("granite-3.0-3b-a800m", "granite-3.0-3b-a800m.pallas50"):
        assert counts.sparse_matmul_work(shape(name), 32, 0.5) is None
    assert counts.expert_sparse_matmul_work(s, 32, 0.5) is None


def test_expert_work_hand_count():
    """Decode at 48 rows, keep 0.5: gate and up keep 6 of 12 blocks of
    1536 inputs, down 2 of 4 of 512; 48 x 8 = 384 assignments."""
    s = shape("granite-3.0-3b-a800m.pallas50")
    w = counts.expert_sparse_matmul_work(s, rows=48, keep_frac=0.5)
    hit = 40 * (1 - (1 - 8 / 40) ** 48)
    assert counts.experts_hit(s, 48) == pytest.approx(hit, rel=1e-12)
    kept = 768 * 512 + 768 * 512 + 256 * 1536      # one expert's kept weights
    assert kept == 1_179_648
    per_layer_bytes = (hit * kept * 2 + 384 * (768 + 768 + 256) * 2
                       + 384 * (512 + 512 + 1536) * 2)
    assert w.bytes == pytest.approx(16 * per_layer_bytes, rel=1e-12)
    assert w.flops == 16 * 2 * 384 * kept
    # one row touches exactly its 8 experts
    assert counts.experts_hit(s, 1) == pytest.approx(8.0)


def test_expert_weight_bytes_at_half_keep_are_half_of_dense():
    """The weight part of the count (its bytes less those at zero bytes
    a weight) is the routed experts' dense weights at keep 1.0 and half
    of them at keep 0.5; pad experts (48 - 40) count for nothing."""
    s = shape("granite-3.0-3b-a800m.pallas50")

    def weight_bytes(keep):
        return (counts.expert_sparse_matmul_work(s, 48, keep).bytes
                - counts.expert_sparse_matmul_work(s, 48, keep,
                                                   w_bytes=0).bytes)

    dense = 16 * counts.experts_hit(s, 48) * 3 * 1536 * 512 * 2
    assert weight_bytes(1.0) == pytest.approx(dense, rel=1e-12)
    assert weight_bytes(0.5) == pytest.approx(dense / 2, rel=1e-12)
    assert dense < 16 * 40 * 3 * 1536 * 512 * 2


def test_expert_least_time_cannot_give_a_share_over_100():
    """Decode at these rows is bound by bytes, and the least time lies
    below even the time to read the kept blocks of every one of the 48
    padded experts once more for each of the kernel's 8-row tiles (which
    is what the program does), so a kernel cannot beat it."""
    s = shape("granite-3.0-3b-a800m.pallas50")
    p = counts.peaks("TPU v5 lite")
    for rows in (1, 8, 48):
        least, bound = counts.expert_sparse_matmul_work(s, rows, 0.5) \
            .least_s(p)
        assert bound == "bytes"
        floor = counts.experts_hit(s, rows) * 1_179_648 * 2 * 16 / p.hbm_bw
        assert floor < least
        assert least < 48 * 12 * 1_179_648 * 2 * 16 / p.hbm_bw
