"""chip_smoke.py off the chip: it must fail, and its rehearsal must walk
the phases' control flow at a tiny size without ever passing for a chip
run."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

# what only a chip can make true; everything else must hold in rehearsal
CHIP_ONLY = {"flash_kernel_in_compiled_step", "obs_record_names_tpu"}


def _run(*argv, cwd=REPO, script=SCRIPT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: one here
    proc = subprocess.run(
        [sys.executable, script, *argv], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    return proc.returncode, lines


def test_off_chip_fails_at_the_device_check():
    rc, lines = _run()
    assert rc != 0
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    # before any phase: nothing ran on the CPU in the chip's name
    assert [ln["phase"] for ln in lines[:-1]] == ["device"]


def test_rehearsal_walks_the_phases_and_still_fails():
    rc, lines = _run("--rehearse", "--phase", "train", "--phase", "serve")
    assert rc != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert set(phases) == {"train", "serve"}
    for ln in phases.values():
        assert ln["rehearsal"] is True
        failed = {k for k, v in ln["checks"].items() if not v}
        assert failed <= CHIP_ONLY, (ln["phase"], failed)
    train = phases["train"]
    assert train["entry"] == "main_training_llama.main"
    assert len(train["losses"]) == 8 and train["losses"][-1] < train["losses"][0]
    serve = phases["serve"]
    assert serve["completed"] == serve["requests"]
    assert serve["attn_impl"] == "kernel"
    assert serve["compiles_in_measured_wave"] == 0


def test_alone_without_the_program_it_fails(tmp_path):
    lone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    rc, lines = _run("--rehearse", "--phase", "train",
                     cwd=str(tmp_path), script=str(lone))
    assert rc != 0
    assert lines and not any(ln["ok"] for ln in lines)
