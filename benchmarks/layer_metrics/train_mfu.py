"""train_mfu — tokens/s/chip at the median step time x model FLOPs per token
(benchmarks/harness/flops.py: causal, windowed, no recomputation) over the
chip's published bf16 peak.

BENCHMARK.json holds this metric's entries (``train_mfu`` or ``train_mfu.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "host_clock"

from benchmarks.harness import stats


def read(r):
    # from the MEDIAN step time, not the window's rate: this is read in the
    # traced run, whose window also holds the profiler's start and stop
    step_ms = stats.median(r.samples.get("train_step_ms", []))
    if r.peak is None or not step_ms:
        return None
    rate = r.notes["tokens_per_step"] / (step_ms * 1e-3) / r.chips
    return 100.0 * rate * r.notes["flops_per_token"] / r.peak["bf16_flops_per_s"]
