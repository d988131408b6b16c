"""The DeepSeek-V2 cell's two serve programs compile for the chip at REAL
size — asked of the chip's compiler, without the chip (as
``tests/test_tpu_aot_compile.py`` asks for the other configurations; a file
of its own, so that the two compiles run beside those and not after them).

``benchmarks/tools/xing4_aot.py`` builds both programs of any configuration
whose layers keep pages of latents: here 1 + 5 layers at the PUBLISHED widths
— 128 heads of 128 + 64 over a latent of 512, queries through 1536, a dense
layer of 12,288 and one routing group of 20 experts of 1536 held of 160, a
shared expert of 3072, the whole 102,400-row head — 32 slots of 17,408 tokens
in 8,705 pages, a 512-row chunk.  Nothing runs: counts, bytes and names, never
a time."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import pytest

GIB = 2.0 ** 30


@pytest.fixture(scope="module")
def programs():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from benchmarks.harness import manifest
    from benchmarks.tools import xing4_aot

    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # a compile for a described device cannot be read back from the
    # persistent cache without a chip: off around these
    prev = jax.config.jax_enable_compilation_cache
    try:
        cell = manifest.Cell("deepseek-v2.serve-repo-context")
        compiled, weights, pool, shapes, _ = \
            xing4_aot.compile_serve_programs(cell)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    return dict(compiled), weights, pool, shapes


@pytest.mark.parametrize("program", ["paged decode", "paged chunk prefill"])
def test_deepseek_v2_serve_programs_fit_a_v5e_and_copy_no_pool(programs,
                                                               program):
    """The latent walk at 128 heads (absorbed for a decode: 128 rows a slot;
    expanded for a chunk: 32 head blocks a slot), the pool writer and the
    held experts' grouped matmuls are Mosaic calls — 6 + 6 + 15; the
    group-limited choice is XLA's, in the program; the pages of latents are
    donated and aliased; nothing shaped like the pool or like a slot's
    expanded keys is copied; all of it under the 13.6 GiB that decided the
    depth."""
    compiled, weights, pool, shapes = programs
    text = compiled[program].as_text()
    kernel = ("latent_attention_decode" if program == "paged decode"
              else "latent_attention_chunk")
    assert text.count(f"%{kernel}") >= 6 and "%paged_attention" not in text
    assert text.count("%kv_pool_write") >= 6 and "%gmm" in text
    assert "moe_group_select" in compiled[program].as_text()
    assert shapes[0].shape == (8705, 64, 640)      # 1,280 bytes a token
    for s in shapes:
        shape = f"bf16[{','.join(map(str, s.shape))}]"
        copied = [ln.strip()[:120] for ln in text.splitlines()
                  if re.search(rf"= {re.escape(shape)}\S* (copy|transpose)\(",
                               ln) and "fused_computation" not in ln]
        assert not copied, f"{shape} is copied: {copied}"
    m = compiled[program].memory_analysis()
    assert m.alias_size_in_bytes >= pool
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert weights + pool < total < 13.6 * GIB
    assert 8.8 * GIB < weights < 8.83 * GIB and 3.97 * GIB < pool < 3.99 * GIB
