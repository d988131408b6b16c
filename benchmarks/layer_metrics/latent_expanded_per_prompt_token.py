"""latent_expanded_per_prompt_token — latent rows up-projected to keys and values over the latent rows prefill
wrote, a layer: the program's counters ``serving/latent_tokens_expanded_total``
(the visible latents of every chunk attended expanded; a decode, absorbed,
expands none) over ``kvcache/latent_rows_written_total/prefill_chunk_pages``.
1.0 where a latent is expanded once in its life, about ``context / (2 x
chunk)`` where every chunk expands what it sees (9-32 at 8k-32k prompts in
chunks of 512), 0 where prefill is absorbed too.  ``None`` where the program
counts neither (no latent layers).

BENCHMARK.json holds this metric's entries (``latent_expanded_per_prompt_token`` or ``latent_expanded_per_prompt_token.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "ratio"
SOURCE = "program_counter"


def read(r):
    wrote = r.counters.get(
        "kvcache/latent_rows_written_total/prefill_chunk_pages")
    if not wrote:
        return None
    return r.counters.get("serving/latent_tokens_expanded_total", 0.0) / wrote
