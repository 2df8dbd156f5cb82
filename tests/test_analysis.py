"""repro.analysis: corpus precision, suppressions, baseline semantics,
the PR 9 regression tripwire, and negative coverage for the jaxpr
passes (a de-donated engine and a collapsed tile plan must be caught).

The corpus test is *exact*: the passes must flag every line marked
``# EXPECT: <rule-id>`` under ``tests/analysis_corpus`` and nothing
else — over-flagging is a failure just like under-flagging, because a
noisy linter gets baselined into oblivion.
"""
import dataclasses
import json
import os
import re
import shutil

import pytest

from repro.analysis import (Baseline, BaselineError, Finding,
                            is_suppressed, parse_suppressions,
                            run_ast_passes)
from repro.analysis.cli import main as cli_main

HERE = os.path.dirname(__file__)
REPO = os.path.abspath(os.path.join(HERE, os.pardir))
CORPUS = os.path.join(HERE, "analysis_corpus")

_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*([A-Za-z0-9-]+)")


def _expected_corpus_findings():
    expected = set()
    for dirpath, _, files in os.walk(os.path.join(CORPUS, "src")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, CORPUS)
            with open(path) as fh:
                for i, line in enumerate(fh, 1):
                    m = _EXPECT_RE.search(line)
                    if m:
                        expected.add((rel, i, m.group(1)))
    return expected


# ---------------------------------------------------------------------------
# AST passes: corpus precision
# ---------------------------------------------------------------------------

def test_corpus_flags_exactly_the_marked_lines():
    expected = _expected_corpus_findings()
    assert expected, "corpus has no EXPECT markers — did the files move?"
    got = {(f.path, f.line, f.rule)
           for f in run_ast_passes(CORPUS, roots=("src",))}
    missing = expected - got
    extra = got - expected
    assert not missing, f"rules failed to flag known-bad lines: {missing}"
    assert not extra, f"rules over-flagged unmarked lines: {extra}"


def test_corpus_covers_every_ast_rule():
    """Each AST rule must have at least one corpus trigger, or a rule
    regression ships silently."""
    from repro.analysis import ast_passes as _  # noqa: F401 (register)
    from repro.analysis.registry import ast_passes
    covered = {rule for _, _, rule in _expected_corpus_findings()}
    assert covered == set(ast_passes())


def test_inline_suppression_silences_one_rule_on_one_line():
    src = ("import time\n"
           "a = time.time()  # repro: ignore[no-raw-time]\n"
           "b = time.time()  # repro: ignore[some-other-rule]\n"
           "c = time.time()  # repro: ignore\n")
    sup = parse_suppressions(src)
    f = lambda line: Finding(rule="no-raw-time", path="x.py", line=line,
                             message="m")  # noqa: E731
    assert is_suppressed(f(2), sup)
    assert not is_suppressed(f(3), sup)        # names a different rule
    assert is_suppressed(f(4), sup)            # bare ignore = all rules
    assert not is_suppressed(f(1), sup)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_absorbs_exactly_its_findings(tmp_path):
    findings = run_ast_passes(CORPUS, roots=("src",))
    base = Baseline.from_findings(findings, justification="corpus test")
    assert base.filter(findings) == []          # everything grandfathered
    # a NEW finding (different snippet) still surfaces
    fresh = Finding(rule="no-raw-time", path="src/new.py", line=3,
                    message="m", snippet="t = time.time()")
    assert base.filter(findings + [fresh]) == [fresh]
    # per-fingerprint counts: a second identical offender is NOT covered
    dup = findings[0]
    assert base.filter(findings + [dup]) == [dup]
    path = tmp_path / "base.json"
    base.save(str(path))
    assert Baseline.load(str(path)).filter(findings) == []


def test_baseline_refuses_unjustified_entries(tmp_path):
    path = tmp_path / "base.json"
    path.write_text(json.dumps({
        "version": 1,
        "findings": [{"rule": "no-raw-time", "path": "a.py",
                      "snippet": "x", "justification": "  "}],
    }))
    with pytest.raises(BaselineError, match="justification"):
        Baseline.load(str(path))
    path.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(BaselineError, match="version"):
        Baseline.load(str(path))


def test_baseline_is_line_number_robust():
    """Moving a grandfathered line (edits above it) must not resurrect
    the finding: fingerprints use the stripped source line, not the
    line number."""
    f1 = Finding(rule="r", path="p.py", line=10, message="m",
                 snippet="x = hash(k)")
    base = Baseline.from_findings([f1], justification="j")
    moved = Finding(rule="r", path="p.py", line=42, message="m",
                    snippet="x = hash(k)")
    assert base.filter([moved]) == []


# ---------------------------------------------------------------------------
# the PR 9 tripwire: reverting the crc32 fix must re-flag params.py
# ---------------------------------------------------------------------------

def test_reverted_crc32_fix_is_redetected(tmp_path):
    with open(os.path.join(REPO, "src/repro/models/params.py")) as fh:
        src = fh.read()
    assert "zlib.crc32" in src, "params.py lost the PR 9 crc32 fix?"
    reverted = src.replace(
        "zlib.crc32(_path_str(path).encode())",
        "hash(_path_str(path))")
    assert reverted != src
    scratch = tmp_path / "src" / "repro" / "models"
    scratch.mkdir(parents=True)
    (scratch / "params.py").write_text(reverted)
    findings = run_ast_passes(str(tmp_path), roots=("src",),
                              rules=["no-builtin-hash-persistence"])
    assert findings, "the reverted PR 9 hash() bug was not re-detected"
    assert all(f.rule == "no-builtin-hash-persistence" for f in findings)


def test_tree_is_clean_under_ast_passes():
    """The acceptance bar: the real tree carries zero AST findings."""
    assert run_ast_passes(REPO) == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_clean_tree_exits_zero(capsys):
    assert cli_main(["--ast-only", "--root", REPO]) == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_cli_json_format_and_nonzero_on_findings(tmp_path, capsys):
    shutil.copytree(CORPUS, tmp_path / "c")
    (tmp_path / "c" / "pyproject.toml").write_text("")
    rc = cli_main(["--ast-only", "--root", str(tmp_path / "c"),
                   "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert len(doc["findings"]) == len(_expected_corpus_findings())
    assert {f["rule"] for f in doc["findings"]} >= {
        "no-builtin-hash-persistence", "no-raw-time"}


def test_cli_baseline_flow(tmp_path, capsys):
    shutil.copytree(CORPUS, tmp_path / "c")
    root = str(tmp_path / "c")
    (tmp_path / "c" / "pyproject.toml").write_text("")
    assert cli_main(["--ast-only", "--root", root, "--write-baseline",
                     str(tmp_path / "b.json")]) == 0
    capsys.readouterr()
    # TODO justifications must be rejected...
    assert cli_main(["--ast-only", "--root", root, "--baseline",
                     str(tmp_path / "b.json")]) == 2
    doc = json.loads((tmp_path / "b.json").read_text())
    for e in doc["findings"]:
        e["justification"] = "known-bad corpus, grandfathered on purpose"
    (tmp_path / "b.json").write_text(json.dumps(doc))
    capsys.readouterr()
    # ...and a justified baseline swallows every corpus finding
    assert cli_main(["--ast-only", "--root", root, "--baseline",
                     str(tmp_path / "b.json")]) == 0
    assert "baselined" in capsys.readouterr().out


def test_cli_rejects_unknown_rule(capsys):
    assert cli_main(["--ast-only", "--root", REPO,
                     "--rules", "not-a-rule"]) == 2


# ---------------------------------------------------------------------------
# jaxpr / executable passes
# ---------------------------------------------------------------------------

def test_global_passes_clean_on_tree():
    """Acceptance: donation took, statics hash, Pallas plans in bounds —
    zero findings over the full 3-rung warmup executable set."""
    from repro.analysis import run_global_passes
    assert run_global_passes(REPO) == []


def test_donation_pass_catches_dedonated_engine(monkeypatch):
    """Strip donate_argnums from the engine's step construction and the
    pass must flag every rung's decode/chunk executable."""
    import jax

    from repro.analysis.registry import global_passes
    from repro.models import api
    from repro.serving import engine as engine_mod

    def undonated(cfg, on_decode_trace=None, on_chunk_trace=None):
        slot_decode = api.make_slot_decode_step(cfg)
        chunk_step = api.make_chunk_prefill_step(cfg)
        prefill_step = api.make_prefill_step(cfg)

        def _decode(params, tokens, positions, caches, sp, active, *,
                    policy):
            return slot_decode(params, tokens, positions, caches, sp,
                               active, policy=policy)

        def _chunk(params, tokens, offset, slot, caches, sp, weights, *,
                   policy):
            return chunk_step(params, tokens, offset, slot, caches, sp,
                              weights, policy=policy)

        def _prefill(params, tokens, sp, *, policy):
            return prefill_step(params, {"tokens": tokens}, sp,
                                policy=policy)

        return (jax.jit(_decode, static_argnames=("policy",)),
                jax.jit(_chunk, static_argnames=("policy",)),
                jax.jit(_prefill, static_argnames=("policy",)))

    monkeypatch.setattr(engine_mod, "make_engine_steps", undonated)
    findings = global_passes()["jit-donation"].run(REPO)
    flagged = {f.snippet for f in findings}
    # 3 rungs x (decode + 2 chunk phases) lowered, plus the compiled
    # representative — every one must be caught
    assert len(findings) >= 9, findings
    assert any("decode[rung=0]" in s for s in flagged)
    assert any("chunk[rung=2" in s for s in flagged)


def test_pallas_pass_catches_collapsed_tiles(monkeypatch):
    """Re-introduce the pre-PR 5 behaviour (degrade to 1-wide tiles on
    awkward dims instead of padding) and the pass must flag it."""
    from repro.analysis.registry import global_passes
    from repro.kernels import sparse_matmul as K

    def collapsing_fit(size, want, align):
        want = min(want, size)
        t = want
        while size % t:
            t -= 1              # the old bug: walks all the way to 1
        return t

    monkeypatch.setattr(K, "_fit_tile", collapsing_fit)
    findings = global_passes()["pallas-blockspec"].run(REPO)
    assert any("_fit_tile" in f.snippet for f in findings), findings


def test_pallas_pass_catches_unaligned_blocks(monkeypatch):
    """Re-introduce score_mask's old block-score output — a (1, 1) block
    of an (nb, 1) array, which Mosaic refuses — and the pass must flag
    the tiling rule the TPU compiler enforces."""
    from repro.analysis.registry import global_passes
    from repro.kernels import sparse_matmul as K
    real_plan = K.score_mask_plan

    def old_plan(B, n, *, blk=128, x_bytes=4):
        plan = real_plan(B, n, blk=blk, x_bytes=x_bytes)
        nb = n // min(blk, n)
        xm, _ = plan.outputs
        bs = K.BlockPlan("bs", (1, 1), (nb, 1), lambda j, ab: (j, 0), 4)
        return dataclasses.replace(plan, outputs=(xm, bs))

    monkeypatch.setattr(K, "score_mask_plan", old_plan)
    findings = global_passes()["pallas-blockspec"].run(REPO)
    assert any(f.snippet == "score_mask/bs tiling" for f in findings), \
        findings


def test_pallas_pass_catches_layer_out_of_bounds(monkeypatch):
    """A W index map that reads past the layer stack (here: the layer
    after the one asked for) must be flagged: the pass evaluates every
    plan at the stack's last layer."""
    from repro.analysis.registry import global_passes
    from repro.kernels import sparse_matmul as K
    real_plan = K.shared_plan

    def off_by_one(*a, **kw):
        plan = real_plan(*a, **kw)
        x, w = plan.inputs
        w = dataclasses.replace(
            w, index_map=lambda b, j, i, idx, lyr: (lyr[0] + 1, idx[i], j))
        return dataclasses.replace(plan, inputs=(x, w))

    monkeypatch.setattr(K, "shared_plan", off_by_one)
    findings = global_passes()["pallas-blockspec"].run(REPO)
    assert any(f.snippet == "sparse_matmul_shared/w" for f in findings), \
        findings


@pytest.mark.parametrize("block,padded,ok", [
    ((1, 1), (32, 1), False),           # old score_mask block scores
    ((1, 128), (16, 4096), False),      # old per-seq row block, B > 1
    ((6, 256), (12, 4096), False),      # old batch-12 tile of 6
    ((8, 192), (8, 384), False),        # old lane tile of 192 for m=384
    ((1, 128), (1, 4096), True),        # unit dim spans the array
    ((None, 1, 128), (16, 1, 4096), True),
    ((None, 128, 256), (6, 8192, 22016), True),   # W read from a stack
    ((None, 128, 192), (6, 8192, 384), False),
    ((12, 64), (12, 64), True),         # whole array
    ((16, 128), (32, 4096), True),
    ((128,), (4096,), True),
    ((64,), (4096,), False),
])
def test_tiling_rule_cases(block, padded, ok):
    from repro.analysis.jaxpr_passes import tiling_violation
    from repro.kernels import sparse_matmul as K
    b = K.BlockPlan("op", block, padded, lambda *a: (0,) * len(block))
    assert (tiling_violation(b) is None) == ok


def test_static_args_pass_catches_unhashable_policy():
    from repro.analysis.registry import global_passes

    class Unhashable:
        __hash__ = None

    p = global_passes()["jit-static-args"]
    sites = [("src/repro/serving/engine.py", 1, object())]
    findings = p._check_policy(Unhashable(), sites)
    assert any("unhashable" in f.message for f in findings)

    class IdentityHashed:
        pass

    findings = p._check_policy(IdentityHashed(), sites)
    assert any("identity" in f.message or "frozen" in f.message
               for f in findings)
