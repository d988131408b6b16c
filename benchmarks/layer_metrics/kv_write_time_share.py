"""kv_write_time_share — self time of the page pool's writes (scope ``kv_write``
of the attention module: the step's new K/V rows scattered into their pages,
and the copy of the whole pool that the compiler hangs on the scatter), of the
validity updates (``kv_valid``) and of the copies of the pool on its way INTO a
program (group ``pool_copy``: operations named for the argument ``caches[..]``)
over the device's busy time: what the cache's upkeep costs beside the kernel
that reads it.

BENCHMARK.json holds this metric's entries (``kv_write_time_share`` or ``kv_write_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kv cache"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    return None if sc is None else sc.share("kv_write", "pool_copy")
