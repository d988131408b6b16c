"""gdn_tokens_step_share — the counter ``serving/gdn_tokens_total/step``
(tokens through the gated-delta layers by a decode: one a live row a step)
over its sum with ``serving/gdn_tokens_total/chunk`` (a prefill chunk's own
tokens): how much of the delta rule's traffic is decode, which steps a whole
state row a token, and how much is prefill, which passes it once a chunk.
Counted from the engine's start (the lead-in included), as the program
counts.  ``None`` for a program without the counters or a run without such
tokens.

BENCHMARK.json holds this metric's entries (``gdn_tokens_step_share`` or ``gdn_tokens_step_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    step = r.counters.get("serving/gdn_tokens_total/step")
    chunk = r.counters.get("serving/gdn_tokens_total/chunk")
    if step is None or chunk is None or not step + chunk:
        return None
    return 100.0 * step / (step + chunk)
