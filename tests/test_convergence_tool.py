"""Convergence-evidence harness (VERDICT r4 next-step #5).

The committed CPU-golden trajectory (``docs/convergence/golden_parity/``,
written by ``tools/convergence_run.py golden``) is the comparison target of
the chip parity job (``tools/convergence_run.py parity``, run through the
builder's chip tool).  These tests pin the harness parts
that need no hardware: the golden exists, descends, self-compares clean,
and the comparator actually rejects a diverged curve.
"""

import json
import os

from neuronx_distributed_tpu.testing.convergence import (
    compare_scalar_logs,
    smoothed,
)
from neuronx_distributed_tpu.trainer.scalar_log import read_scalars

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(_REPO, "docs", "convergence", "golden_parity")


def _golden_losses():
    recs = sorted(read_scalars(GOLDEN, "loss"), key=lambda r: r["step"])
    return [r["value"] for r in recs]


def test_golden_trajectory_committed_and_descending():
    assert os.path.isdir(GOLDEN), (
        "CPU-golden missing — regenerate with `python tools/convergence_run.py golden`"
    )
    losses = _golden_losses()
    assert len(losses) == 160
    sm = smoothed(losses)
    # the Markov task is learnable: the curve must clearly descend from the
    # uniform floor (log 512 ~= 6.24) toward the chain entropy (log 16 ~= 2.77)
    assert sm[-1] < 0.8 * sm[20]
    v = compare_scalar_logs(GOLDEN, GOLDEN, tag="loss", warmup_steps=20)
    assert v.ok and v.max_deviation_pct == 0.0


def test_comparator_rejects_diverged_curve(tmp_path):
    losses = _golden_losses()
    cand = str(tmp_path / "cand")
    os.makedirs(cand)
    with open(os.path.join(cand, "scalars.jsonl"), "w") as f:
        for i, v in enumerate(losses):
            bad = v * (1.08 if i > 60 else 1.0)  # 8% late divergence
            f.write(json.dumps({"step": i, "tag": "loss", "value": bad}) + "\n")
    v = compare_scalar_logs(cand, GOLDEN, tag="loss", warmup_steps=20,
                            tolerance_pct=1.0)
    assert not v.ok and v.max_deviation_pct > 5.0 and v.worst_step > 60
