"""Parent-ladder logic of bench.py.

The child measurements are faked at the `_run_child` seam, so these pin the
DRIVER-facing control flow without a chip: first TPU success wins, no chip
means a non-zero exit and NO metric line (never a CPU number), a hung rung
ends the run, the explicit cpu rehearsal is routed and labelled as such, and
the parent stays off jax (the chip belongs to the one child it runs)."""

import json
import os
import subprocess
import sys
import types

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Proc(types.SimpleNamespace):
    pass


def _ok_json(value=1000.0, **extra):
    rec = {"metric": "llama_pretrain_tokens_per_sec_per_chip", "value": value,
           "unit": "tokens/s/chip (test)", "vs_baseline": 1.0,
           "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}
    rec.update(extra)
    return _Proc(returncode=0, stdout=json.dumps(rec) + "\n", stderr="")


def _failed(msg="RuntimeError: requested tpu but jax.devices() -> cpu"):
    return _Proc(returncode=1, stdout="", stderr=f"bench attempt failed: {msg}")


def _run(monkeypatch, capsys, behavior, platform="tpu"):
    """behavior(args, timeout, env) -> _Proc | None; returns (rc, the JSON
    lines printed to stdout)."""
    monkeypatch.setattr(bench, "_run_child",
                        lambda extra, t, env=None: behavior(extra, t, env))
    rc = bench.parent_main(platform)
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
    return rc, out


def test_first_tpu_success_wins(monkeypatch, capsys):
    calls = []

    def behavior(extra, t, env):
        calls.append(extra)
        # the two biggest rungs do not fit; the third does
        return _failed("RESOURCE_EXHAUSTED") if len(calls) < 3 else _ok_json(111.0)

    rc, out = _run(monkeypatch, capsys, behavior)
    assert rc == 0 and len(out) == 1 and out[0]["value"] == 111.0
    assert len(calls) == 3  # nothing runs after the first success
    assert all("--platform=tpu" in c for c in calls)


def test_no_chip_exits_nonzero_without_metric_line(monkeypatch, capsys):
    """Every rung fails the way a chip-less machine fails it: the run must
    end non-zero with NOTHING on stdout — no value-0.0 line, no CPU rung."""
    calls = []

    def behavior(extra, t, env):
        calls.append(extra)
        return _failed()

    rc, out = _run(monkeypatch, capsys, behavior)
    assert rc != 0 and out == []
    assert len(calls) == len(bench.LADDER)
    assert not any("--platform=cpu" in c for c in calls)


def test_hung_rung_ends_the_run(monkeypatch, capsys):
    calls = []

    def behavior(extra, t, env):
        calls.append((extra, t))
        return None  # the child was killed at its time limit

    rc, out = _run(monkeypatch, capsys, behavior)
    assert rc != 0 and out == []
    # one attempt, at the full budget: the next rung would hang behind it
    assert len(calls) == 1 and calls[0][1] == bench.ATTEMPT_TIMEOUT_S


def test_explicit_cpu_rehearsal_is_routed_and_labelled(monkeypatch, capsys):
    seen = []

    def behavior(extra, t, env):
        seen.append((extra, env.get("JAX_PLATFORMS")))
        return _ok_json(9.0, metric="cpu_rehearsal_tokens_per_sec",
                        platform="cpu", device_kind="cpu")

    rc, out = _run(monkeypatch, capsys, behavior, platform="cpu")
    assert rc == 0 and len(out) == 1
    assert out[0]["platform"] == "cpu"
    assert out[0]["metric"] != "llama_pretrain_tokens_per_sec_per_chip"
    # exactly one child, told to run on the cpu and held to it by the env
    assert len(seen) == 1
    assert "--platform=cpu" in seen[0][0] and seen[0][1] == "cpu"


def test_parent_never_imports_jax():
    """The chip belongs to one process: the parent must leave it to the
    child, so importing bench and running the ladder may not pull jax in."""
    code = (
        "import sys; sys.path.insert(0, %r); import bench\n"
        "bench._run_child = lambda *a, **k: None\n"
        "rc = bench.parent_main()\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "sys.exit(0 if rc != 0 else 1)\n" % REPO
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""  # no metric line on total failure
