"""Continuous-batching engine: parity with the legacy generate() loop,
ragged/mid-flight admission, slot reclamation, and decode jit-stability."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.core.sp_schema import default_sp_stacked
from repro.data import DataConfig, SyntheticLM
from repro.launch.serve import generate
from repro.models import api
from repro.serving import Engine, EngineConfig, SlotKVPool, Status
from repro.sparsity import SparsityPolicy


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    return params, cfg


def _prompts(cfg, n, seq, step=0):
    return np.asarray(SyntheticLM(
        DataConfig(cfg.vocab_size, seq, n)).batch(step))


def _engine(params, cfg, sp=None, **kw):
    defaults = dict(max_slots=4, max_len=32, prefill_chunk=8)
    defaults.update(kw)
    return Engine(params, cfg, EngineConfig(**defaults), sp)


# ---------------------------------------------------------------------------
# exact parity with the legacy static-batch loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,keep", [("off", 1.0),
                                          ("topk_shared", 0.5)])
def test_engine_matches_legacy_generate(model, backend, keep):
    """Equal-length prompts through the whole-prefill engine produce the
    exact tokens of the legacy generate() loop, dense and sparse."""
    params, cfg = model
    prompts = _prompts(cfg, 4, 16)
    sp = default_sp_stacked(params, cfg, keep_frac=keep) \
        if backend != "off" else None
    policy = SparsityPolicy.uniform(backend, k_max_frac=keep)
    legacy = np.asarray(generate(params, cfg, jnp.asarray(prompts), 8, sp,
                                 policy=policy))
    eng = _engine(params, cfg, sp, policy=policy,
                  prefill_strategy="whole", prefill_dense_frac=1.0)
    for b in range(4):
        eng.submit(prompts[b], 8)
    out = eng.run()
    for b in range(4):
        assert out[b] == list(legacy[b]), f"request {b} diverged"


def test_chunked_prefill_matches_whole(model):
    """Chunked prefill (in-place pool writes) agrees with the legacy
    whole-prompt prefill + insertion on the same requests."""
    params, cfg = model
    prompts = _prompts(cfg, 2, 24, step=3)
    outs = []
    for strategy in ("whole", "chunked"):
        eng = _engine(params, cfg, max_slots=2, max_len=32, prefill_chunk=8,
                      prefill_strategy=strategy)
        eng.submit(prompts[0], 6)
        eng.submit(prompts[1], 6)
        outs.append(eng.run())
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# continuous batching mechanics
# ---------------------------------------------------------------------------

def test_ragged_midflight_and_slot_reuse(model):
    """Ragged prompt lengths, more requests than slots, and a mid-flight
    submission: everything finishes, slots are reclaimed, and the decode
    step traces exactly once."""
    params, cfg = model
    prompts = _prompts(cfg, 4, 20, step=7)
    eng = _engine(params, cfg, max_slots=2, max_len=32, prefill_chunk=8,
                  prefill_strategy="chunked")
    lens = [9, 14, 20]
    for b, L in enumerate(lens):
        eng.submit(prompts[b][:L], 5)
    for _ in range(6):                       # start prefill/decode
        eng.step()
    late = eng.submit(prompts[3][:11], 5)    # mid-flight admission
    out = eng.run()
    assert set(out) == {0, 1, 2, 3}
    assert all(len(toks) == 5 for toks in out.values())
    assert all(rs.status == Status.FINISHED for rs in eng.states.values())
    assert late.tokens == out[3]
    assert eng.pool.num_free == 2            # all slots reclaimed
    assert eng.decode_traces == 1            # no retrace after warmup
    assert eng.stats.finished == 4
    assert eng.stats.decode_tokens == 20


def test_eos_stop_and_streaming(model):
    """EOS stops a request early; the streaming callback sees every token
    in order."""
    params, cfg = model
    prompts = _prompts(cfg, 1, 12, step=11)
    eng = _engine(params, cfg)
    eng.submit(prompts[0], 6)
    ref = eng.run()[0]
    assert len(ref) == 6

    seen = []
    eng2 = _engine(params, cfg)
    # pick an EOS whose first occurrence is unambiguous: greedy tokens on
    # a random-init model can repeat, and the engine (correctly) stops at
    # the *first* occurrence of the EOS id
    k = next((i for i in range(1, len(ref)) if ref[i] not in ref[:i]), None)
    if k is None:
        pytest.skip("every generated token repeats; no unambiguous EOS")
    rs = eng2.submit(prompts[0], 6, eos_id=ref[k],
                     on_token=lambda rid, t: seen.append((rid, t)))
    out = eng2.run()
    assert out[0] == ref[:k + 1]             # stopped at the EOS token
    assert rs.finish_reason.value == "eos"
    assert seen == [(0, t) for t in ref[:k + 1]]


def test_moe_and_ssm_archs_serve_sparse():
    """The engine serves MoE (expert projections opt out of slot-weighted
    saliency) and SSM archs (whole-prefill fallback) under a sparse
    backend with partially occupied slots."""
    for arch in ("granite_moe_1b_a400m", "mamba2_130m"):
        cfg = reduced(get_config(arch))
        params = api.init_model(cfg, 0)
        sp = default_sp_stacked(params, cfg, keep_frac=0.5)
        eng = Engine(params, cfg, EngineConfig(
            max_slots=3, max_len=24, prefill_chunk=8,
            policy=SparsityPolicy.uniform("topk_shared",
                                          k_max_frac=0.5)), sp)
        prompts = _prompts(cfg, 2, 10, step=17)
        eng.submit(prompts[0], 4)
        eng.submit(prompts[1][:7], 4)        # ragged + a free slot
        out = eng.run()
        assert all(len(t) == 4 for t in out.values()), arch
        assert eng.pool.num_free == 3


def test_pool_alloc_free_cycle(model):
    _, cfg = model
    pool = SlotKVPool(cfg, max_slots=3, max_len=16)
    slots = [pool.alloc() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2] and pool.num_free == 0
    with pytest.raises(RuntimeError):
        pool.alloc()
    pool.free(slots[1])
    assert pool.num_free == 1
    assert pool.alloc() == slots[1]


def test_engine_stats_and_phase_times(model):
    params, cfg = model
    prompts = _prompts(cfg, 2, 16, step=13)
    eng = _engine(params, cfg, max_slots=2)
    eng.submit(prompts[0], 4)
    eng.submit(prompts[1], 4)
    eng.run()
    s = eng.stats.summary()
    assert s["finished"] == 2
    assert s["decode_tokens"] == 8
    assert s["decode_tps"] > 0 and s["prefill_tps"] > 0
    assert eng.stats.prefill_tokens == 32
    for rs in eng.states.values():
        assert rs.first_token_time is not None
        assert rs.finish_time >= rs.first_token_time


# ---------------------------------------------------------------------------
# sparse projections read their weights in place from the layer stack
# ---------------------------------------------------------------------------

def _serve_warm(params, cfg, sp, policy, prompts, **kw):
    eng = _engine(params, cfg, sp, policy=policy, **kw)
    eng.warmup()
    for p in prompts:
        eng.submit(p, 6)
    return eng, eng.run()


def test_pallas_engine_reads_weights_in_place(model, monkeypatch):
    """On the pallas rung every projection's kernel reads its layer's
    tiles from the stacked weight (recorded while decode and the sparse
    chunk trace), serves the tokens of the same engine fed through
    per-layer slices, and never retraces; the feeds reach the metrics
    exposition as a labelled gauge."""
    from repro.models import model as M
    from repro.obs.metrics import parse_exposition, validate_exposition
    params, cfg = model
    sp = default_sp_stacked(params, cfg, keep_frac=0.5)
    policy = SparsityPolicy.uniform("pallas", k_max_frac=0.5)
    prompts = list(_prompts(cfg, 3, 12, step=5))
    n = 7 * cfg.num_layers              # q, k, v, o, gate, up, down
    eng, out = _serve_warm(params, cfg, sp, policy, prompts,
                           prefill_dense_frac=0.5)
    assert eng.sparse_weight_feeds == {
        "decode": {"in_place": n, "sliced": 0},
        "chunk": {"in_place": n, "sliced": 0}}
    assert eng.decode_retraces_after_warmup == 0
    assert eng.chunk_retraces_after_warmup == 0
    text = eng.metrics_exposition()
    validate_exposition(text)
    _, samples = parse_exposition(text)
    feeds = {(lb["program"], lb["feed"]): v for name, lb, v in samples
             if name == "repro_sparse_weight_feeds"}
    assert feeds == {("decode", "in_place"): n, ("decode", "sliced"): 0,
                     ("chunk", "in_place"): n, ("chunk", "sliced"): 0}

    # the same engine with every stack fed through the scan's xs slices
    monkeypatch.setattr(M, "reads_in_place", lambda *a, **kw: False)
    sliced, sliced_out = _serve_warm(params, cfg, sp, policy, prompts,
                                     prefill_dense_frac=0.5)
    assert sliced.sparse_weight_feeds == {
        "decode": {"in_place": 0, "sliced": n},
        "chunk": {"in_place": 0, "sliced": n}}
    assert sliced_out == out


def test_dense_engine_programs_unchanged(model, monkeypatch):
    """A dense-rung engine records no sparse weight feeds, and its decode
    and chunk programs lower to the text of the xs-slicing path."""
    from repro.models import model as M
    from repro.serving.engine import make_engine_steps
    params, cfg = model
    sp = default_sp_stacked(params, cfg, keep_frac=0.5)
    policy = SparsityPolicy.dense()
    eng, out = _serve_warm(params, cfg, sp, policy,
                           list(_prompts(cfg, 2, 12, step=9)))
    assert eng.sparse_weight_feeds == {}
    assert "repro_sparse_weight_feeds" not in eng.metrics_exposition()
    assert eng.decode_retraces_after_warmup == 0
    S, C = eng.ecfg.max_slots, eng.ecfg.prefill_chunk
    zeros = jnp.zeros((S,), jnp.int32)

    def program_text():
        dstep, cstep, _ = make_engine_steps(cfg)
        return (dstep.lower(params, zeros, zeros, eng.pool.caches, sp,
                            jnp.ones((S,), jnp.float32),
                            policy=policy).as_text(),
                cstep.lower(params, jnp.zeros((1, C), jnp.int32),
                            jnp.zeros((1,), jnp.int32), jnp.int32(0),
                            eng.pool.caches, sp,
                            jnp.ones((C,), jnp.float32),
                            policy=policy).as_text())

    now = program_text()
    monkeypatch.setattr(M, "_weight_feeds", lambda gp, gsp, jpols: ([], 0))
    assert program_text() == now
