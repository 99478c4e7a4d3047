"""Every cell walked end to end at a tiny size on the CPU, the refusals,
the control and the broken timed path at test size, and a cell, a
configuration and a per-layer metric added as files alone."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]


def run_cell(cell, *extra, devices=1, cwd=ROOT, seconds="1.5", seed="2147483700"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
           "--workload", cell, "--seed", seed, "--seconds", seconds, *extra]
    p = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True,
                       timeout=900)
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    last = None
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return p, last


@pytest.mark.parametrize("cell,chips", CELLS)
def test_no_tpu_no_result(cell, chips):
    p, last = run_cell(cell, "--trace", "0", devices=chips)
    assert p.returncode != 0
    assert last is None and "{" not in p.stdout
    assert "not a tpu" in p.stderr


@pytest.mark.parametrize("cell,chips", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_walks_the_cell(cell, chips, trace):
    p, last = run_cell(cell, "--trace", trace, "--rehearse", devices=chips)
    assert last is not None, p.stdout[-2000:] + p.stderr[-2000:]
    assert p.returncode != 0
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["rehearsal_checks_passed"] is True, p.stdout[-3000:]
    assert last["metrics"] == {} and last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == chips
    assert "check compiles_in_window: 0 limit 0 -> ok" in p.stdout
    if trace == "1":
        # on the CPU there is no device plane: the host-side readers find
        # their spans, the trace readers find nothing and stay out
        assert "rehearsal read per-layer metrics:" in p.stdout


def test_benchmark_alone_gives_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and benchmark/: the
    system under test is missing, so there is nothing to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p, last = run_cell(CELLS[0][0], "--trace", "0", "--rehearse",
                       cwd=str(tmp_path))
    assert p.returncode != 0 and last is None
    assert "{" not in p.stdout


def test_control_is_not_correct_at_test_size():
    """The serving cell's control (the engine serving weights that went
    through float8) reads a far wider gap than the sound run at the same
    tiny size."""
    p, sound = run_cell("mixtral-8x7b.serve-chat-over", "--trace", "0", "--rehearse",
                        seconds="3")
    q, control = run_cell("mixtral-8x7b.serve-chat-over", "--trace", "0", "--rehearse",
                          "--control", "1", seconds="3")
    assert sound["rehearsal_checks_passed"] is True, p.stdout[-2000:]
    assert control["rehearsal_checks_passed"] is False, q.stdout[-2000:]
    assert "served_token_logit_gap_mean" in q.stdout


def _in_process(cell, monkeypatch, **patches):
    """Drive a rehearsal of ``cell`` in this process (the look for a chip
    skipped), with the program patched underneath; -> the checks."""
    import time

    from benchmark import harness

    args = types.SimpleNamespace(
        workload=cell, seed=5, seconds=1.0, trace=0, rehearse=True, control=0)
    seen = {}
    real = harness.Run.check

    def check(self, what, value, limit, ok=None):
        out = real(self, what, value, limit, ok)
        seen[what.split("[")[0]] = out
        return out

    monkeypatch.setattr(harness.Run, "check", check)
    for target, fn in patches.items():
        mod, name = target.rsplit(":", 1)
        monkeypatch.setattr(__import__(mod, fromlist=[name]), name, fn)
    rc = harness.run(args, ROOT, time.perf_counter())
    assert rc == 1
    return seen


def test_served_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from fms_fsdp_tpu.serve import engine as program

    real = program.ServingEngine.step

    def step(self):
        out = real(self)
        for req in self._slots:
            if req is not None and req.generated:
                req.generated[-1] = (req.generated[-1] + 1) % 512
        return out

    monkeypatch.setattr(program.ServingEngine, "step", step)
    seen = _in_process("mixtral-8x7b.serve-chat-over", monkeypatch)
    assert seen["served_token_logit_gap_mean"] is False


def test_a_cell_a_configuration_and_a_metric_are_added_as_files_alone(tmp_path):
    """A temporary checkout: the program by symlink, ``benchmark/`` copied;
    then only new files and new manifest entries. No file that was there
    is edited, and the harness runs the new cell and reads the new metric.
    """
    for name in os.listdir(ROOT):
        if name in ("benchmark", "BENCHMARK.json") or name.startswith("."):
            continue
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.relpath(os.path.join(d, f), tmp_path): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(tmp_path / "benchmark") for f in fs}

    with open(os.path.join(BENCH, "configs", "mixtral-8x7b.1chip.json")) as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 2
    (tmp_path / "benchmark/configs/mixtral-8x7b.2layer.json").write_text(json.dumps(cfg))
    with open(os.path.join(
            BENCH, "workloads", "mixtral-8x7b.serve-chat-over.json")) as f:
        cell = json.load(f)
    cell["traffic"]["rate_per_s"] = 0.4
    cell["rehearse"]["traffic"]["rate_per_s"] = 6.0
    (tmp_path / "benchmark/workloads/mixtral-8x7b.serve-slow.json").write_text(
        json.dumps(cell))
    (tmp_path / "benchmark/layer_metrics/engine_steps.py").write_text(
        '"""Engine steps in the window (a count)."""\n\n\n'
        "def read(run):\n    return len(run.facts.get('steps_log') or []) or None\n")
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append(dict(
        m["configs"][0], name="mixtral-8x7b.2layer",
        file="benchmark/configs/mixtral-8x7b.2layer.json"))
    m["workloads"].append(dict(
        name="mixtral-8x7b.serve-slow", config="mixtral-8x7b.2layer",
        traffic="serve-slow", chips=1, why="a cell added by files alone"))
    next(e for e in m["end_to_end"]
         if e["name"] == "serve_tokens_per_s")["workloads"].append(
        "mixtral-8x7b.serve-slow")
    m["per_layer"].append(dict(
        name="engine_steps", unit="steps", better="higher",
        source="program_counter", layer="serving engine",
        moves="serve_tokens_per_s", workloads=["mixtral-8x7b.serve-slow"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    p, last = run_cell("mixtral-8x7b.serve-slow", "--trace", "1", "--rehearse",
                       cwd=str(tmp_path))
    assert last is not None, p.stdout[-2000:] + p.stderr[-2000:]
    assert last["rehearsal_checks_passed"] is True, p.stdout[-3000:]
    assert "rehearsal read per-layer metrics: ['engine_steps']" in p.stdout
    after = {
        os.path.relpath(os.path.join(d, f), tmp_path): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(tmp_path / "benchmark") for f in fs
        if "__pycache__" not in d}
    assert all(after[k] == v for k, v in before.items())
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/mixtral-8x7b.2layer.json",
        "benchmark/layer_metrics/engine_steps.py",
        "benchmark/workloads/mixtral-8x7b.serve-slow.json"]
