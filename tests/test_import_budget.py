"""No run pays for a checkpoint library it does not call.

``orbax.checkpoint`` is 12-28 s of import on a chip machine's host (its
logging brings ``google.cloud.logging`` along, and ``tensorstore`` comes with
it), and is loaded by ``utils.checkpoint_library.checkpoint_library`` when a
run reads, writes or exports a checkpoint:

- importing the package or a subpackage loads none of it (a child process
  each: this one has long since loaded it for some other test);
- ``newest_tag`` reads the filesystem alone;
- ``save_checkpoint`` then ``load_checkpoint`` DO load it, and round-trip.

How to look at an import graph: ``python -X importtime -c "import
neuronx_distributed_tpu.serving" 2>&1 | sort -t'|' -k2 -n | tail``.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEAVY = ("orbax", "tensorstore", "google.cloud.logging")

_REPORT = f"""
import json, sys
print("LOADED", json.dumps(sorted(
    k for k in sys.modules if k.startswith({HEAVY!r}))))
"""


def _child(code):
    """Run ``code`` then :data:`_REPORT` in a fresh interpreter on the CPU;
    the heavy modules it ended with."""
    proc = subprocess.run(
        [sys.executable, "-c", code + _REPORT], capture_output=True,
        text=True, cwd=REPO, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("LOADED ")]
    return json.loads(line[len("LOADED "):])


@pytest.mark.parametrize("module", [
    "neuronx_distributed_tpu",
    "neuronx_distributed_tpu.trainer",
    "neuronx_distributed_tpu.trace",
    "neuronx_distributed_tpu.serving",
    "neuronx_distributed_tpu.models",
    "neuronx_distributed_tpu.weights",
    "neuronx_distributed_tpu.tenancy",
    "neuronx_distributed_tpu.resilience",
])
def test_an_import_loads_no_checkpoint_library(module):
    assert _child(f"import {module}\n") == []


def test_newest_tag_reads_the_filesystem_alone(tmp_path):
    assert _child(
        "from neuronx_distributed_tpu.trainer import newest_tag\n"
        "from neuronx_distributed_tpu.trainer.checkpoint import "
        "wait_for_checkpoint\n"
        f"assert newest_tag({str(tmp_path)!r}) is None\n"
        "wait_for_checkpoint()\n") == []


def test_a_save_and_a_load_bring_the_library_and_round_trip(tmp_path):
    from neuronx_distributed_tpu.trainer import (
        load_checkpoint,
        newest_tag,
        save_checkpoint,
    )
    from neuronx_distributed_tpu.utils.checkpoint_library import (
        checkpoint_library,
    )

    tree = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"scale": jnp.full((4,), 0.5, jnp.bfloat16)}}
    save_checkpoint(str(tmp_path), "step_1", tree,
                    user_content={"step": 1})
    assert sys.modules.get("orbax.checkpoint") is checkpoint_library()
    assert newest_tag(str(tmp_path)) == "step_1"
    model, opt, _, user = load_checkpoint(str(tmp_path), model_template=tree)
    assert opt is None and user == {"step": 1}
    for got, want in zip(jax.tree.leaves(model), jax.tree.leaves(tree)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
