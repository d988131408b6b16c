"""head_rows_per_prefill_chunk — rows of hidden states the head was applied to in the chunk-prefill programs
over the chunk programs run: the program's counters
``serving/head_rows_total/prefill_chunk_pages`` (host arithmetic from the
shapes: one row in a prompt's last chunk, none in the others) over
``serving/prefill_chunks_total``.  It reads ``1 / chunks a prompt`` — 1.0
where every prompt is one chunk, ~0.03 at 13k-32k tokens in chunks of 512 —
and the chunk's whole width where the head runs before the row is chosen.
``None`` where the program does not count the head's rows, or ran no chunk.

BENCHMARK.json holds this metric's entries (``head_rows_per_prefill_chunk`` or ``head_rows_per_prefill_chunk.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "compiled programs"
UNIT = "rows"
SOURCE = "program_counter"


def read(r):
    rows = r.counters.get("serving/head_rows_total/prefill_chunk_pages")
    chunks = r.counters.get("serving/prefill_chunks_total")
    return None if rows is None or not chunks else rows / chunks
