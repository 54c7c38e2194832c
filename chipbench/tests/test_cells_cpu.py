"""A CPU rehearsal of every cell's control flow at a tiny size, behind the
test-only entry (``run_cell(allow_cpu=True)``), and what `correct` has to
catch: the control (the reference rerun in bfloat16, put in the program's
place) and each fault a cell can have, planted under the timed path. The
real command finds no TPU here, exits non-zero and prints no metric.

A CPU run shows control flow and results; it gives no time.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import run

SEED = 2**31 + 11


def _go(name, faults=()):
    result, compared = run.run_cell(name, SEED, 1.0, False, allow_cpu=True,
                                    faults=faults)
    over = {k: v for k, (v, lim) in compared.items() if not v <= lim}
    return result, compared, over


@pytest.mark.parametrize("name,metric", [
    ("tiny-train", "train_seeds_per_s"), ("tiny-train-dp4", "train_seeds_per_s"),
    ("tiny-steady", "serve_p95_ms"), ("tiny-flood", "serve_req_per_s")])
def test_cell_runs_and_is_correct(tiny, name, metric):
    result, compared, over = _go(name)
    assert not over, over
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert result["metrics"][metric]["value"] > 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert compared["compiles_in_window"][0] == 0
    assert result["device"]["platform"] == "cpu"      # and so: no metric of a chip


@pytest.mark.parametrize("name,fault,caught_by", [
    ("tiny-train", "state_unchanged", "update_gap"),
    ("tiny-train", "half_batch", "grad_gap"),
    ("tiny-train-dp4", "half_batch", "loss_gap"),
    ("tiny-train-dp4", "no_exchange", "grad_gap"),
    ("tiny-flood", "answer_altered", "row_gap"),
    ("tiny-steady", "answer_altered", "row_gap")])
def test_fault_under_the_timed_path_is_not_correct(tiny, name, fault, caught_by):
    _, _, over = _go(name, faults=(fault,))
    assert caught_by in over, over


def test_state_unchanged_reads_one(tiny):
    _, compared, _ = _go("tiny-train", faults=("state_unchanged",))
    assert compared["update_gap"][0] == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["tiny-train", "tiny-train-dp4"])
def test_control_bfloat16_fails_training(tiny, name):
    import jax
    from chipbench import check, harness, spec, train_cell as tc
    cell = spec.Cell(name)
    jax.config.update("jax_default_matmul_precision", "highest")
    r = spec.plugin("entries", cell.entry).Run(cell, SEED, jax.devices())
    kept = r.first_steps()
    ref, facts = tc.follow(r, kept)
    sound = check.train_numbers(tc.program_numbers(r, kept), ref, facts)
    low, _ = tc.follow(r, kept, precision="bfloat16", verify=False)
    control = check.train_numbers(low, ref, facts)
    for k in ("loss_gap", "grad_gap"):
        assert sound[k] <= cell.limits[k] < control[k], (k, sound[k], control[k])
        assert control[k] > 30 * sound[k]
    for numbers, want in ((sound, True), (control, False)):
        numbers.pop("facts")
        correct, over = harness.judge(
            {k: (v, cell.limits[k]) for k, v in numbers.items()})
        assert correct is want, over


def test_control_bfloat16_fails_serving(tiny):
    import jax
    from chipbench import harness, serve_cell as sc, spec
    cell = spec.Cell("tiny-flood")
    jax.config.update("jax_default_matmul_precision", "highest")
    r = spec.plugin("entries", cell.entry).Run(cell, SEED, jax.devices())
    r.setup()
    win = r.window(0.5)
    r.stop()
    out = sc.compare(r, win, 4, control=True)
    assert out["row_gap"] <= cell.limits["row_gap"] < out["control_gap"]
    out.pop("facts")
    low = out.pop("control_gap")
    for numbers, want in ((out, True), (dict(out, row_gap=low), False)):
        correct, over = harness.judge(
            {k: (v, cell.limits[k]) for k, v in numbers.items()})
        assert correct is want, over


def test_the_command_needs_the_chip(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "papers100m-sage-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert "metrics" not in line
        with pytest.raises(ValueError):
            json.loads(line)
