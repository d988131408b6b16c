"""chip_smoke.py's contract where no chip is: it fails without an
accelerator, and its control flow runs end to end at the rehearsal size —
plus the one compile-cache helper every entry point calls."""

import json
import os
import subprocess
import sys

import jax
import pytest

from neuronx_distributed_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, timeout=800, **env):
    return subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        timeout=timeout, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_chip_smoke_fails_without_an_accelerator():
    """JAX finds only the CPU: non-zero exit, a traceback, and no result —
    ``"ok": true`` must not appear anywhere in what it printed."""
    proc = _run_smoke()
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout and '"ok": true' not in proc.stderr
    assert "no accelerator" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith('{"ok"')]


def test_chip_smoke_rehearsal_runs_every_phase():
    """The same control flow at a tiny size on the CPU (kernels interpreted):
    kernels vs references, fit() steps, staggered paged serving, the logits
    probes.  Its last line is a rehearsal marker, never an ok result."""
    proc = _run_smoke("--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    for phase in ("[device]", "[kernels]", "[train]", "[serve]"):
        assert phase in out, f"phase {phase} did not run"
    assert "0 compiles after warm-up" in out
    assert "cache vs full forward, decode step" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert '"ok": true' not in out


# -- the compile-cache helper --------------------------------------------------


@pytest.fixture
def recorded_config(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_cache_env_var_wins_and_no_directory_is_set_in_code(
        tmp_path, monkeypatch, recorded_config):
    want = str(tmp_path / "from_env")
    monkeypatch.setenv(compile_cache.ENV_VAR, want)
    assert compile_cache.configure_compile_cache() == want
    assert os.path.isdir(want)
    # jax reads the variable itself; the helper sets no directory on top
    assert compile_cache.CACHE_DIR_OPTION not in recorded_config
    assert recorded_config["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_cache_default_is_the_fixed_path_in_the_checkout(
        monkeypatch, recorded_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.configure_compile_cache()
    assert got == os.path.join(REPO, ".jax_cache") == os.path.normpath(got)
    assert recorded_config[compile_cache.CACHE_DIR_OPTION] == got
    # the same answer every time: no pid, time or temp name in it
    assert compile_cache.configure_compile_cache() == got


def test_cache_that_cannot_be_created_is_an_error(tmp_path, monkeypatch,
                                                   recorded_config):
    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory")
    monkeypatch.setenv(compile_cache.ENV_VAR, str(blocker / "cache"))
    with pytest.raises(OSError):
        compile_cache.configure_compile_cache()
