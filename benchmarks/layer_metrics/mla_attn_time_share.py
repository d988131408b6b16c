"""mla_attn_time_share — time of the latent-attention kernel calls over the device's busy time:
the Mosaic calls named ``latent_attention_decode`` (absorbed, one row a
head) and ``latent_attention_chunk`` in their name stack — the attention
core over the latent pages, NOT the projections around it
(``mla_proj_time_share``).  ``None`` where no such call ran (a model without
latent layers, a program older than the kernels).

BENCHMARK.json holds this metric's entries (``mla_attn_time_share`` or ``mla_attn_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("latent_attention_decode", "latent_attention_chunk")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if set(trace_scopes.components(op.tf_op)) & set(SCOPES))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
