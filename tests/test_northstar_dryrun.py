"""Pin the north-star topology: Llama-2-7B pretrain on a v5e-32.

Runs ``__graft_entry__.dryrun_northstar(32)`` as a subprocess: a 32-device
virtual CPU mesh instantiated as tp=8 x dp=4 with sequence parallelism,
ZeRO-1, GQA kv-replication, flash attention, one real train step and a
checkpoint save/restore cycle — the exact v5e-32 production layout from the
reference's 70B launch discipline
(``examples/training/llama2/tp_pp_llama2_hf_pretrain/run_llama_70b_tp_pp.sh:48-100``),
on tiny shapes.  A subprocess because the 32-device backend reset must not
leak into the session-wide 8-device test mesh.
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_northstar_topology_32_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "__graft_entry__.py"), "32", "northstar"],
        capture_output=True, text=True, timeout=590, env=env,
    )
    assert proc.returncode == 0, (
        f"northstar dryrun failed rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-3000:]}"
    )
    assert "dryrun northstar ok: 32 devices tp=8 dp=4 kvr=2" in proc.stdout
