"""startup_ready_s — seconds from the PROCESS's start (the kernel's stamp of
it, not the interpreter's first line) to the moment the program called itself
ready: a serve cell's ServingEngine.declare_warmup_done, a train cell's
first step's loss on the host.  The gauge startup/ready_s of the
program's start-up account (neuronx_distributed_tpu.obs.startup), on the
account's own monotonic clock.  setup_s less this is the mix's lead-in
(serve) or skip_steps - 1 steps (train).  Before the number the reader
prints the one [startup] line: the seconds by phase (self times; they add
up to the number), JAX's compile path by stage inside them, the persistent
cache's requests, hits and misses, and the programs with the most seconds on
the compile path.  None where the program keeps no such account.

BENCHMARK.json holds this metric's entry with its ``moves`` and ``workloads``; the
three constants below must agree with it (``benchmarks/tests/test_manifest.py``).
"""

from benchmarks.harness import startup_account

LAYER = "entry"
UNIT = "s"
SOURCE = "program_counter"


def read(r):
    snap = startup_account.snapshot()
    if snap is None:
        return None
    phases = startup_account.by(snap, startup_account.PHASE_MS)
    stages = startup_account.by(snap, startup_account.STAGE_MS)
    print(f"[startup] ready ({snap['label']}) {snap[startup_account.READY]:.3f}"
          " s after the process started; by phase (self time) "
          + ", ".join(f"{p} {s:.3f}" for p, s in phases.items() if s)
          + "; compile path " + ", ".join(
              f"{k} {s:.3f}" for k, s in stages.items())
          + f" (saved {snap['startup/compile_saved_ms_total'] / 1e3:.1f}); "
          f"requests {snap['startup/compile_requests_total']:.0f}, hits "
          f"{snap['startup/cache_hits_total']:.0f}, misses "
          f"{snap['startup/cache_misses_total']:.0f}; programs "
          + ", ".join(f"{name} {s:.2f}" for name, s in snap["programs"]),
          flush=True)
    return snap[startup_account.READY]
