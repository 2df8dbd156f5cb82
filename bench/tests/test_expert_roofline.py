"""``expert_sparse_mm_roofline``'s reader: which kernel calls it counts,
and the share it gives on a made-up trace of known times."""
import gzip
import json
import os
import re
import types

import pytest

from bench import client, counts, run
from bench import trace as TR

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")
DATA = os.path.join(os.path.dirname(__file__), "data", "decode_trace.json.gz")
METRIC = run.load_metric("expert_sparse_mm_roofline")

# op texts as the TPU trace names them (decode at 48 slots, capacity 2)
EXPERT_CALLS = [
    "%closed_call.9 = f32[96,512]{1,0:T(8,128)} custom-call(s32[6]{0} %a, "
    "s32[1]{0} %b, bf16[96,1536]{1,0} %c, bf16[1,1536,512]{2,1,0} %d)",
    "%closed_call.10 = f32[96,1536]{1,0:T(8,128)} custom-call(s32[2]{0} %a, "
    "s32[1]{0} %b, bf16[96,512]{1,0} %c, bf16[1,512,1536]{2,1,0} %d)",
]
OTHER_CALLS = [
    # attention, read in place from the stack of 16 layers
    "%closed_call.11 = f32[48,512]{1,0:T(8,128)} custom-call(s32[6]{0} %a, "
    "s32[1]{0} %b, bf16[48,1536]{1,0} %c, bf16[16,1536,512]{2,1,0} %d)",
    "%closed_call.12 = f32[48,1536]{1,0:T(8,128)} custom-call(s32[6]{0} %a, "
    "s32[1]{0} %b, bf16[48,1536]{1,0} %c, bf16[16,1536,1536]{2,1,0} %d)",
    # the experts' score/mask kernel, batched over experts
    "%wisparse_score_mask.3 = (bf16[48,96,1536]{2,1,0}, f32[48,1,1536]{2,1,0})"
    " custom-call(f32[2]{0} %a, bf16[48,96,1536]{2,1,0} %b)",
]


def shape(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return counts.Shape.of(json.load(f))


GRANITE = shape("granite-3.0-3b-a800m.pallas50")


def test_pattern_takes_expert_calls_only():
    rx = re.compile(METRIC.kernel_pattern(GRANITE))
    assert all(rx.search(t) for t in EXPERT_CALLS)
    assert not any(rx.search(t) for t in OTHER_CALLS)


def test_dense_kernel_pattern_is_unchanged():
    """``sparse_mm_roofline`` still reads what it read before experts
    had a reader: the same pattern, and no op of the recorded DeepSeek
    trace is an expert call."""
    assert run.load_metric("sparse_mm_roofline").KERNEL == \
        r"= f32\[\d+,\d+\]\{[^}]*\} custom-call\(s32\[\d+\]"
    with gzip.open(DATA, "rt") as f:
        tr = TR.Trace.from_json(json.load(f))
    rx = re.compile(METRIC.kernel_pattern(GRANITE))
    assert not any(rx.search(s.name) or rx.search(s.detail) for s in tr.ops)


def _run(kernel_s, rows=(48, 40), shape_=GRANITE, keep=0.5):
    """A run whose trace holds one decode step per entry of ``rows``,
    each with one expert call of ``kernel_s`` seconds in all."""
    ops, notes, steps = [], [], []
    t = 1_000.0
    for r in rows:
        dur = kernel_s * 1e9 / len(rows)
        notes.append(TR.Span("repro/decode", t, t + dur + 200))
        ops.append(TR.Span(EXPERT_CALLS[0], t + 100, t + 100 + dur))
        ops.append(TR.Span(OTHER_CALLS[0], t + 100, t + 150))
        steps.append(client.Step("decode", 0.0, 0.0, r, 0.0))
        t += dur + 1_000
    steps.append(client.Step("prefill", 0.0, 0.0, 256, 0.0))
    tr = TR.Trace(ops, notes + [TR.Span(TR.WINDOW, 0.0, t)], 1)
    return types.SimpleNamespace(
        trace=tr, trace_lo=0.0, trace_hi=t, traced_steps=list(range(len(steps))),
        client=types.SimpleNamespace(steps=steps), shape=shape_,
        cell=types.SimpleNamespace(rung={"keep_frac": keep}),
        device_kind="TPU v5 lite")


def _least(rows):
    w = [counts.expert_sparse_matmul_work(GRANITE, r, 0.5) for r in rows]
    return (w[0] + w[1]).least_s(counts.peaks("TPU v5 lite"))[0]


def test_share_of_a_kernel_at_the_least_time_is_100():
    least = _least((48, 40))
    assert METRIC.read(_run(least)) == pytest.approx(100.0, rel=1e-9)
    assert METRIC.read(_run(4 * least)) == pytest.approx(25.0, rel=1e-9)


def test_nothing_to_read_gives_none():
    least = _least((48, 40))
    dense = _run(least, keep=None)
    assert METRIC.read(dense) is None
    no_experts = _run(least, shape_=shape("deepseek-llm-67b.pallas50"))
    assert METRIC.read(no_experts) is None
    untraced = _run(least)
    untraced.trace = None
    assert METRIC.read(untraced) is None
    nocall = _run(least)
    nocall.trace.ops[:] = [s for s in nocall.trace.ops
                           if s.name != EXPERT_CALLS[0]]
    assert METRIC.read(nocall) is None
