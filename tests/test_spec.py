"""Speculative decoding: verify-step equivalence, KV rollback invariants,
engine-level token parity with verifier-only decode, retrace-free gamma
switching, pool slot-state guards and the acceptance controller."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.sp_schema import default_sp_stacked
from repro.data import DataConfig, SyntheticLM
from repro.models import api
from repro.serving import (SNAPSHOT_SCHEMA_VERSION, Engine, EngineConfig,
                           SlotKVPool, SpecConfig, SpecController)
from repro.sparsity import PolicyLadder, SparsityPolicy


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("llama31_8b"))
    params = api.init_model(cfg, 0)
    return params, cfg


@pytest.fixture(scope="module")
def ladder(model):
    params, cfg = model
    return PolicyLadder.uniform(params, cfg, (0.0, 0.5))


def _prompts(cfg, n, seq, step=0):
    return np.asarray(SyntheticLM(
        DataConfig(cfg.vocab_size, seq, n)).batch(step))


def _copy(tree):
    return jax.tree_util.tree_map(lambda x: jnp.array(x), tree)


def _prefill_slot(params, cfg, pool, slot, prompt):
    """Chunk-prefill one prompt into an allocated pool slot."""
    chunk = jax.jit(api.make_chunk_prefill_step(cfg),
                    static_argnames=("policy",))
    P = prompt.shape[0]
    _, pool.caches = chunk(
        params, jnp.asarray(prompt[None]), jnp.zeros((1,), jnp.int32),
        jnp.int32(slot), pool.caches, None, jnp.ones((P,), jnp.float32),
        policy=SparsityPolicy.dense())
    pool.lengths[slot] = P


# ---------------------------------------------------------------------------
# pool slot-state guards + length bookkeeping
# ---------------------------------------------------------------------------

def test_pool_guards(model):
    _, cfg = model
    pool = SlotKVPool(cfg, max_slots=2, max_len=8)
    slot = pool.alloc()
    pool.free(slot)
    with pytest.raises(ValueError, match=f"slot {slot}"):
        pool.free(slot)                          # double free
    with pytest.raises(ValueError, match="not allocated"):
        pool.insert(pool.caches, 0, slot, 4)     # insert into a free slot
    with pytest.raises(ValueError, match="not allocated"):
        pool.commit(slot, 1)
    with pytest.raises(ValueError, match="not allocated"):
        pool.rollback(slot, 0)
    slot = pool.alloc()
    with pytest.raises(ValueError, match="negative"):
        pool.commit(slot, -1)
    with pytest.raises(ValueError, match="exceeds"):
        pool.commit(slot, 9)                     # past the pool length
    pool.commit(slot, 5)
    with pytest.raises(ValueError, match="roll back"):
        pool.rollback(slot, 6)                   # more than committed
    pool.rollback(slot, 2)
    assert pool.lengths[slot] == 3
    with pytest.raises(ValueError, match="outside"):
        pool.free(99)


def test_commit_rollback_property(model):
    """rollback(n) o commit(m) bookkeeping: the pool's per-slot length
    always matches a pure-python model, and out-of-bounds ops raise
    without corrupting it."""
    hypothesis = pytest.importorskip("hypothesis")  # noqa: F841
    from hypothesis import given, settings, strategies as st
    _, cfg = model
    pool = SlotKVPool(cfg, max_slots=1, max_len=8)

    @given(st.lists(st.tuples(st.sampled_from(["commit", "rollback"]),
                              st.integers(0, 10)), max_size=8))
    @settings(deadline=None, max_examples=20)
    def run(ops):
        slot = pool.alloc()
        length = 0
        try:
            for op, n in ops:
                if op == "commit":
                    if length + n <= pool.max_len:
                        pool.commit(slot, n)
                        length += n
                    else:
                        with pytest.raises(ValueError):
                            pool.commit(slot, n)
                else:
                    if n <= length:
                        pool.rollback(slot, n)
                        length -= n
                    else:
                        with pytest.raises(ValueError):
                            pool.rollback(slot, n)
                assert pool.lengths[slot] == length
        finally:
            pool.free(slot)

    run()


# ---------------------------------------------------------------------------
# the core spec-decode invariants, at the pool/step level
# ---------------------------------------------------------------------------

def test_draft_rollback_redecode_bitwise(model):
    """Decoding T tokens plainly vs drafting T tokens (sparse drafter,
    garbage KV), rolling them back, then redecoding the same T tokens
    must produce bit-identical caches AND logits — rejected drafts leave
    no trace."""
    params, cfg = model
    T, P = 4, 10
    sp = default_sp_stacked(params, cfg, keep_frac=0.5)
    sparse = SparsityPolicy.uniform("topk_shared", k_max_frac=0.5)
    dense = SparsityPolicy.dense()
    dstep = jax.jit(api.make_slot_decode_step(cfg),
                    static_argnames=("policy",))

    pool = SlotKVPool(cfg, max_slots=2, max_len=24)
    slot = pool.alloc()
    prompt = _prompts(cfg, 1, P, step=5)[0]
    _prefill_slot(params, cfg, pool, slot, prompt)
    state0 = _copy(pool.caches)

    toks = _prompts(cfg, 1, T, step=9)[0]        # teacher-forced tokens
    active = jnp.asarray(np.eye(2, dtype=np.float32)[slot])

    def decode_T(caches):
        logits = []
        for i in range(T):
            tv = np.zeros((2,), np.int32)
            tv[slot] = toks[i]
            pos = np.full((2,), pool.max_len - 1, np.int32)
            pos[slot] = P + i
            lg, caches = dstep(params, jnp.asarray(tv), jnp.asarray(pos),
                               caches, None, active, policy=dense)
            logits.append(np.asarray(lg[slot]))
        return logits, caches

    # path A: plain decode
    logits_a, caches_a = decode_T(_copy(state0))

    # path B: draft T tokens sparsely, roll them back, redecode
    pool.caches = _copy(state0)
    for i in range(T):
        tv = np.zeros((2,), np.int32)
        tv[slot] = toks[i]
        pos = np.full((2,), pool.max_len - 1, np.int32)
        pos[slot] = P + i
        _, pool.caches = dstep(params, jnp.asarray(tv), jnp.asarray(pos),
                               pool.caches, sp, active, policy=sparse)
    pool.commit(slot, T)
    pool.rollback(slot, T)
    assert pool.lengths[slot] == P
    logits_b, caches_b = decode_T(pool.caches)

    for i in range(T):
        np.testing.assert_array_equal(logits_a[i], logits_b[i])
    for a, b in zip(jax.tree_util.tree_leaves(caches_a),
                    jax.tree_util.tree_leaves(caches_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_verify_step_matches_sequential_decode(model):
    """One batched (gamma+1)-token verify forward produces the same greedy
    tokens (and near-identical logits) as gamma+1 sequential decode steps
    over the same tokens — the equivalence the engine-level parity gate
    rests on."""
    params, cfg = model
    g1, P = 4, 8
    dense = SparsityPolicy.dense()
    dstep = jax.jit(api.make_slot_decode_step(cfg),
                    static_argnames=("policy",))
    vstep = jax.jit(api.make_verify_step(cfg), static_argnames=("policy",))

    pool = SlotKVPool(cfg, max_slots=3, max_len=20)
    prompts = _prompts(cfg, 2, P, step=2)
    slots = [pool.alloc(), pool.alloc()]         # slot 2 stays empty
    for s, pr in zip(slots, prompts):
        _prefill_slot(params, cfg, pool, s, pr)
    state0 = _copy(pool.caches)

    toks = _prompts(cfg, 3, g1, step=4).T        # (g1, 3) teacher-forced
    active = np.zeros((3,), np.float32)
    active[slots] = 1.0

    seq_logits = []
    caches = _copy(state0)
    for i in range(g1):
        pos = np.full((3,), pool.max_len - 1, np.int32)
        for s in slots:
            pos[s] = P + i
        lg, caches = dstep(params, jnp.asarray(toks[i].copy()),
                           jnp.asarray(pos), caches, None,
                           jnp.asarray(active), policy=dense)
        seq_logits.append(np.asarray(lg))

    vt = toks.T.copy()                           # (3, g1)
    pos = np.full((3,), pool.max_len - g1, np.int32)
    for s in slots:
        pos[s] = P
    wts = np.repeat(active[:, None], g1, axis=1)
    vlg, _ = vstep(params, jnp.asarray(vt), jnp.asarray(pos), state0,
                   None, jnp.asarray(wts), policy=dense)
    vlg = np.asarray(vlg)

    for s in slots:
        for i in range(g1):
            a, b = seq_logits[i][s], vlg[s, i]
            assert a.argmax() == b.argmax(), (s, i)
            np.testing.assert_allclose(a, b, atol=1e-4)


# ---------------------------------------------------------------------------
# engine-level parity + retrace discipline
# ---------------------------------------------------------------------------

def _ladder_engine(model, ladder, spec=None, **kw):
    params, cfg = model
    defaults = dict(max_slots=2, max_len=32, prefill_chunk=8, spec=spec)
    defaults.update(kw)
    eng = Engine(params, cfg, EngineConfig(**defaults), ladder=ladder)
    if spec is None:
        eng.warmup()
    return eng


def test_spec_engine_token_parity(model, ladder):
    """Ragged prompts, more requests than slots, a mid-flight submission:
    the spec engine's outputs are token-identical to verifier-only decode
    and no decode/verify executable retraces after warmup."""
    params, cfg = model
    prompts = _prompts(cfg, 4, 20, step=7)
    lens = [9, 14, 20, 11]

    def drive(spec):
        eng = _ladder_engine(model, ladder, spec=spec)
        for b in (0, 1, 2):
            eng.submit(prompts[b][:lens[b]], 6)
        for _ in range(6):
            eng.step()
        eng.submit(prompts[3][:lens[3]], 6)      # mid-flight admission
        return eng, eng.run()

    _, ref = drive(None)
    eng, out = drive(SpecConfig(gamma=2, drafter_rung=1))
    assert out == ref
    assert eng.decode_retraces_after_warmup == 0
    assert eng.verify_retraces_after_warmup == 0
    assert eng.pool.num_free == 2
    s = eng.stats
    assert s.spec_rounds > 0
    assert s.spec_committed_tokens == s.decode_tokens - 4  # first tokens
    #                                   come from prefill, not spec rounds
    assert s.spec_accepted_tokens <= s.spec_draft_tokens
    assert len(eng.states[3].token_rungs) == 6   # attributed to verifier


def test_spec_pallas_drafter_reads_weights_in_place(model):
    """A pallas drafter rung's decode program reads its sparse weights in
    place from the layer stacks; the dense verifier's program records no
    sparse feeds; neither retraces after warmup."""
    params, cfg = model
    ladder = PolicyLadder.uniform(params, cfg, (0.0, 0.5),
                                  backend="pallas")
    eng = _ladder_engine(model, ladder,
                         spec=SpecConfig(gamma=2, drafter_rung=1))
    for p in _prompts(cfg, 2, 12, step=3):
        eng.submit(p, 4)
    out = eng.run()
    assert all(len(t) == 4 for t in out.values())
    assert eng.stats.spec_rounds > 0
    n = 7 * cfg.num_layers              # q, k, v, o, gate, up, down
    in_place = {"in_place": n, "sliced": 0}
    assert eng.sparse_weight_feeds == {"decode": in_place,
                                       "chunk": in_place}
    assert eng.decode_retraces_after_warmup == 0
    assert eng.verify_retraces_after_warmup == 0


def test_spec_gamma_switch_retrace_free(model, ladder):
    """Adaptive-range warmup precompiles every gamma: switching the draft
    length mid-serve neither retraces nor changes the output tokens."""
    params, cfg = model
    prompts = _prompts(cfg, 2, 12, step=3)
    spec = SpecConfig(gamma=2, drafter_rung=1, adaptive=True,
                      gamma_min=1, gamma_max=3, dwell=10_000)
    eng = _ladder_engine(model, ladder, spec=spec)
    ref = _ladder_engine(model, ladder)

    outs, refs = [], []
    for b, g in ((0, 3), (1, 1)):
        eng.spec_decoder.set_gamma(g)
        rs = eng.submit(prompts[b], 6)
        eng.run()
        outs.append(rs.tokens)
        rr = ref.submit(prompts[b], 6)
        ref.run()
        refs.append(rr.tokens)
    assert outs == refs
    assert eng.decode_retraces_after_warmup == 0
    assert eng.verify_retraces_after_warmup == 0
    with pytest.raises(ValueError, match="gamma"):
        eng.spec_decoder.set_gamma(4)            # beyond the warmed range


def test_spec_eos_stops_like_verifier(model, ladder):
    """An EOS inside a committed draft window stops the request at the
    same token the verifier-only engine stops at."""
    params, cfg = model
    prompts = _prompts(cfg, 1, 12, step=11)
    ref_eng = _ladder_engine(model, ladder)
    ref_eng.submit(prompts[0], 8)
    ref = ref_eng.run()[0]
    k = next((i for i in range(2, len(ref)) if ref[i] not in ref[:i]), None)
    if k is None:
        pytest.skip("every generated token repeats; no unambiguous EOS")
    eng = _ladder_engine(model, ladder,
                         spec=SpecConfig(gamma=3, drafter_rung=1))
    rs = eng.submit(prompts[0], 8, eos_id=ref[k])
    out = eng.run()[0]
    assert out == ref[:k + 1]
    assert rs.finish_reason.value == "eos"
    assert eng.pool.num_free == 2


def test_spec_snapshot_schema(model, ladder):
    eng = _ladder_engine(model, ladder,
                         spec=SpecConfig(gamma=2, drafter_rung=1))
    snap = eng.snapshot()
    assert snap["schema_version"] == SNAPSHOT_SCHEMA_VERSION
    assert snap["spec_gamma"] == 2
    assert snap["spec_drafter_rung"] == 1
    assert "spec_accept_ewma" in snap and "spec_accept_rate" in snap
    plain = _ladder_engine(model, ladder).snapshot()
    assert plain["schema_version"] == SNAPSHOT_SCHEMA_VERSION
    assert "spec_gamma" not in plain


# ---------------------------------------------------------------------------
# configuration validation
# ---------------------------------------------------------------------------

def test_spec_config_validation(model, ladder):
    params, cfg = model
    with pytest.raises(ValueError, match="sparser"):
        SpecConfig(gamma=2, drafter_rung=0)      # drafter == verifier
    with pytest.raises(ValueError, match="gamma"):
        SpecConfig(gamma=0)
    with pytest.raises(ValueError, match="gamma_max"):
        SpecConfig(gamma=5, adaptive=True, gamma_max=4)
    with pytest.raises(ValueError, match="adaptive"):
        SpecConfig(adapt_drafter=True)
    with pytest.raises(ValueError, match="PolicyLadder"):
        Engine(params, cfg,
               EngineConfig(max_slots=2, max_len=32,
                            spec=SpecConfig(gamma=2, drafter_rung=1)))
    with pytest.raises(ValueError, match="outside"):
        _ladder_engine(model, ladder,
                       spec=SpecConfig(gamma=2, drafter_rung=5))
    with pytest.raises(ValueError, match="verifier rung"):
        _ladder_engine(model, ladder, initial_rung=1,
                       spec=SpecConfig(gamma=2, drafter_rung=1))
    # a sparse verifier would break the parity guarantee (shared top-k
    # saliency differs between multi-token verify and single-token decode)
    ladder3 = PolicyLadder.uniform(params, cfg, (0.0, 0.5, 0.75))
    with pytest.raises(ValueError, match="dense verifier"):
        _ladder_engine(model, ladder3, initial_rung=1,
                       spec=SpecConfig(gamma=2, drafter_rung=2,
                                       verifier_rung=1))
    # SSM archs cannot verify (no chunked write-in-place path)
    ssm_cfg = reduced(get_config("mamba2_130m"))
    ssm_params = api.init_model(ssm_cfg, 0)
    ssm_ladder = PolicyLadder.uniform(ssm_params, ssm_cfg, (0.0, 0.5))
    with pytest.raises(ValueError, match="plain-attention"):
        Engine(ssm_params, ssm_cfg,
               EngineConfig(max_slots=2, max_len=32,
                            spec=SpecConfig(gamma=2, drafter_rung=1)),
               ladder=ssm_ladder)


# ---------------------------------------------------------------------------
# acceptance controller
# ---------------------------------------------------------------------------

def test_spec_controller_gamma_dynamics():
    ctl = SpecController(2, 1, 4, drafter_rung=1, drafter_min=1,
                         drafter_max=1, dwell=3)
    # _since_switch starts at dwell: the first high-acceptance tick may act
    assert ctl.update(1.0) == (3, 1)             # high acceptance -> deeper
    assert ctl.accept_ewma is None               # EWMA reset on switch
    assert ctl.update(1.0) == (3, 1)             # dwell holds the next one
    for _ in range(20):
        g, d = ctl.update(1.0)
    assert g == 4                                # saturates at gamma_max
    for _ in range(20):
        g, d = ctl.update(0.0)
    assert g == 1                                # rejections -> gamma_min


def test_spec_controller_dwell_and_drafter():
    ctl = SpecController(1, 1, 1, drafter_rung=2, drafter_min=1,
                         drafter_max=3, adapt_drafter=True, dwell=4)
    assert ctl.update(1.0) == (1, 3)             # gamma maxed -> sparser
    for _ in range(3):
        assert ctl.update(0.0) == (1, 3)         # dwell holds it
    assert ctl.update(0.0) == (1, 2)             # low acceptance -> denser
    for _ in range(20):
        g, d = ctl.update(0.0)
    assert (g, d) == (1, 1)
    snap = ctl.snapshot()
    assert snap["spec_drafter_rung"] == 1
    assert snap["spec_switches"] == len(ctl.transitions)
    with pytest.raises(ValueError, match="gamma"):
        SpecController(3, 1, 2, drafter_rung=1, drafter_min=1,
                       drafter_max=1)
