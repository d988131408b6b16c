"""mla_proj_time_share — self time of the latent layers' PROJECTIONS over the device's busy time:
every operation whose name stack passes through one of the program's scopes
``mla_q`` (the query bottleneck, its norm and up-projection), ``mla_kv_down``
(the latent and the shared RoPE key), ``mla_kv_up`` (cached latents expanded
to keys and values outside a kernel), ``mla_absorb`` (the key up-projection
folded into a decode's queries, the value's applied to its result) or the
mixer's output projection ``o_proj`` — not the attention kernels
(``mla_attn_time_share``) and not the pool write.  ``None`` where no ``mla_*``
scope ran.

BENCHMARK.json holds this metric's entries (``mla_proj_time_share`` or ``mla_proj_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("mla_q", "mla_kv_down", "mla_kv_up", "mla_absorb")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = mla = 0.0
    for d in sc.devices:
        for op in d.ops:
            parts = set(trace_scopes.components(op.tf_op))
            if parts & set(SCOPES):
                mla += op.own
            elif not ({"attn", "o_proj"} <= parts):
                continue
            own += op.own
    # the output projection counts only beside mla scopes: every attention
    # layer has an ``attn/o_proj``
    return 100.0 * own / len(sc.devices) / sc.busy_s if mla else None
