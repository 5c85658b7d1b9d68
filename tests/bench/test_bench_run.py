"""The harness end to end on the CPU at smoke size, past its look for a
chip: a cell added from data files alone runs, reports its metrics and
comes out correct; with the timed path broken underneath it comes out not
correct; and the command itself refuses to report without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import jax

from bench import run
from bench.spec import load_cell

REPO = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 11


def _run(root, workload, **kw):
    cell = load_cell(workload, root)
    return cell, run.run_cell(cell, SEED, 2.0, False,
                              t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("workload", ["smoke-open", "smoke-long"])
def test_cell_from_files_alone_runs_and_is_correct(smoke_root, workload):
    cell, out = _run(smoke_root, workload)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert out["checks"]["max_logit_gap"]["value"] <= 0.05
    assert out["window"]["compiles_in_window"] == 0
    json.dumps(out)


def test_altered_token_is_not_correct(smoke_root, monkeypatch):
    from repro.serving.scheduler import Scheduler

    pick = Scheduler._pick_token

    def altered(self, req, row):
        tok = pick(self, req, row)
        return (tok + 1) % row.shape[-1] if len(req.generated) == 2 else tok

    monkeypatch.setattr(Scheduler, "_pick_token", altered)
    _, out = _run(smoke_root, "smoke-open")
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > 0.05


def test_step_returning_its_state_unchanged_is_not_correct(smoke_root,
                                                           monkeypatch):
    from repro.serving import engine

    make = engine.make_serve_step

    def stale(*a, **kw):
        step = make(*a, **kw)
        # the step writes into the dict it is given: hand it a copy, return
        # the cache as it came in
        return lambda params, cache, tokens: (
            step(params, jax.tree.map(lambda x: x, cache), tokens)[0], cache)

    monkeypatch.setattr(engine, "make_serve_step", stale)
    _, out = _run(smoke_root, "smoke-long")
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > 0.05


def test_control_int8_weights_is_not_correct(tmp_path):
    """The control: the reference with int8 weights, one precision below
    the bf16 the configuration states, put in the program's place on the
    requests a run served (``bench/control.py`` reads it on the chip at the
    cell's size).  At smoke size, against the reference with the cell's
    int8 attention, the sound program's served logits' error reads 1.3e-7
    to 4.1e-7 of the reference's mean square; the limit sits far above it
    and the control has to pass it."""
    from bench import check

    from bench_roots import write_root

    root = write_root(tmp_path, [("smoke-ctrl", "phi4-smoke", "tiny-open", {
        "check": {"tokens": 200, "attention": "int8",
                  "logit_mse_rel": 1e-5}})])
    seen = {}
    cell, sound = _run(root, "smoke-ctrl", keep=seen.update)
    ctrl = check.control(cell, seen["weights"], seen["records"], SEED)
    assert sound["correct"], sound["checks"]
    assert ctrl["tokens"] == sound["reference"]["tokens"] >= 100
    assert ctrl["logit_mse_rel"] > 1e-5
    assert ctrl["logit_mse_rel"] > \
        100 * sound["checks"]["logit_mse_rel"]["value"]


def _command(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi4-chat-int8",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_command_refuses_without_a_tpu():
    p = _command(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for d in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__", ".*"))
    p = _command(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
