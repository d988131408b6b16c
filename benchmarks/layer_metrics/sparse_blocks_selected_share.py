"""sparse_blocks_selected_share — of the blocks visible to the queries of the block-sparse
layers, the share they attend: ``serving/sparse_blocks_selected_total`` over
``serving/sparse_blocks_visible_total``, the program's counters over the whole
run (warm-up, lead-in and window; counted from the host's offsets, a kv head
a layer).  100% is dense attention; 64 blocks of a 12.8k-token context's 200
is 32%.  What the selection saves the attention kernels in keys read.
``None`` where the program counts no selection.

BENCHMARK.json holds this metric's entries (``sparse_blocks_selected_share`` or ``sparse_blocks_selected_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    visible = r.counters.get("serving/sparse_blocks_visible_total")
    chosen = r.counters.get("serving/sparse_blocks_selected_total")
    if not visible or chosen is None:
        return None
    return 100.0 * chosen / visible
