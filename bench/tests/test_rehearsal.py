"""The harness end to end on the CPU, at a stand-in size
(``--rehearse``: two layers, tiny widths, float32, Pallas in interpret
mode), with the timed path intact, broken, and replaced by the
control."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import cell as cellmod
from bench import run

CELLS = [w["name"] for w in cellmod.benchmark()["workloads"]]


def _run(capsys, workload, *extra, alter=None, seed=3_000_000_011):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0", "--rehearse", *extra],
                  alter=alter)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, out


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(capsys, workload):
    rc, out = _run(capsys, workload)
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"] is True, res["compared"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                            "device"]
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert res["attempted"] > 0 and res["failed"] == 0


def test_refuses_without_a_chip(capsys):
    """Off a TPU, a real run exits non-zero and prints no result."""
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0 and "{" not in out


@pytest.mark.parametrize("workload", CELLS)
def test_altered_token_is_not_correct(capsys, workload):
    """A token altered where the engine produces it fails ``correct``."""
    def alter(eng):
        emit = eng._emit
        vocab = eng.cfg.vocab_size

        def bad(rs, token):
            if len(rs.tokens) == 2:
                token = (token + 1 + len(rs.request.prompt)) % vocab
            emit(rs, token)
        eng._emit = bad

    rc, out = _run(capsys, workload, alter=alter)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is False


@pytest.mark.parametrize("step", ["_dstep", "_cstep"])
@pytest.mark.parametrize("workload", CELLS)
def test_step_that_keeps_its_state_is_not_correct(capsys, workload, step):
    """A decode (or chunk) step that returns the KV pool it was given,
    unchanged, fails ``correct``."""
    def alter(eng):
        run_step = getattr(eng, step)
        cache_arg = 3 if step == "_dstep" else 4

        def stale(*args, **kw):
            # the step donates the pool it is given: keep a copy
            before = jax.tree_util.tree_map(jnp.copy, args[cache_arg])
            logits, _caches = run_step(*args, **kw)
            return logits, before
        setattr(eng, step, stale)

    rc, out = _run(capsys, workload, alter=alter)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_control_reads_above_the_program(capsys, workload):
    """The reference at fp8 in the program's place lies well above what
    the program reads, at this size as on the chip (``PERF.md``), and
    fails ``correct``."""
    rc, out = _run(capsys, workload, "--control")
    res = json.loads(out[-1])
    program = [float(line.split()[3].rstrip(";")) for line in out
               if line.startswith("program's widest gap")]
    assert rc == 0 and len(program) == 1
    assert res["compared"]["widest_gap"]["value"] > 3 * program[0]
    assert res["correct"] is False
