"""kv_page_bytes_per_token — the gauge ``kvcache/page_bytes_per_token``: the
bytes ONE token's K/V cells take of the device across the layers that keep
pages, as the device lays the pool's arrays out (minor dimension on 128
lanes).  ``2 x layers x kv heads x head_dim x itemsize`` where no lane of a
page is padding (8,192 for Granite-4.0-H-Micro's 4 attention layers of 8 kv
heads of 64 in bfloat16); twice that where a 64-wide head lies alone in its
lane row — which a share of the pool's bytes would not show.  ``None`` for a
program without the gauge.

BENCHMARK.json holds this metric's entries (``kv_page_bytes_per_token`` or ``kv_page_bytes_per_token.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kv cache"
UNIT = "bytes"
SOURCE = "program_counter"


def read(r):
    v = r.counters.get("kvcache/page_bytes_per_token")
    return float(v) if v else None
