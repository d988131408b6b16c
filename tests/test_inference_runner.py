"""End-to-end inference runner CLI (the reference's
``examples/inference/runner.py:232-260`` command surface): trace → infer →
check-accuracy as real subprocesses on the 8-device virtual CPU mesh —
the serving-side counterpart of the training-launcher tests."""

import os

from conftest import last_json_line, run_cli

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RUNNER = os.path.join(_REPO, "examples", "inference", "runner.py")


def test_trace_infer_check_accuracy_roundtrip(tmp_path):
    art = str(tmp_path / "traced")
    run_cli(_RUNNER, "trace", "--preset", "tiny", "--tp", "2", "--dtype", "float32",
            "--batch-size", "2", "--context-len", "32", "--max-total-len", "64",
            "--out", art, "--virtual-devices", "8")
    assert os.path.isdir(art)

    proc = run_cli(_RUNNER, "infer", "--model", art, "--max-new-tokens", "8",
                   "--virtual-devices", "8")
    gen = last_json_line(proc.stdout)["generated"]
    assert len(gen) == 2 and all(len(row) == 8 for row in gen)

    proc = run_cli(_RUNNER, "check-accuracy", "--preset", "tiny", "--tp", "2",
                   "--dtype", "float32",
                   "--batch-size", "2", "--context-len", "32",
                   "--max-total-len", "64", "--virtual-devices", "8")
    assert last_json_line(proc.stdout) == {"inference_success": 1}


def test_check_accuracy_gemma2_family():
    """Family dispatch through the serving CLI: Gemma-2 tiny (hybrid
    windows + softcaps) passes the cached-vs-teacher-forced check."""
    proc = run_cli(_RUNNER, "check-accuracy", "--family", "gemma2", "--dtype", "float32",
                   "--preset", "tiny", "--tp", "2", "--batch-size", "2",
                   "--context-len", "32", "--max-total-len", "64",
                   "--virtual-devices", "8")
    assert last_json_line(proc.stdout) == {"inference_success": 1}
