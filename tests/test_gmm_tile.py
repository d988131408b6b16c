"""The grouped matmul's tile is chosen from the operand's shape
(``parallel.moe.gmm_tile``): a k-tile that divides the contraction wherever
a multiple of 128 does, so that megablox never masks a last k-tile in float32
at the widths this repository serves; OLMoE's tile stays what PR 25 measured.
Counts and equalities on the CPU — the times are ``tools/gmm_tile_probe.py``'s,
on the chip."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.parallel.moe import (
    GMM_TILE_BYTES,
    GMM_TILING,
    gmm_tile,
    grouped_matmul,
    take_gmm_lowered,
)

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")


def _routed():
    """``(configuration, matmul, rows, K, N)`` for every routed configuration
    of the benchmark at its PUBLISHED widths: the up (and gate) and the down
    matmul, a decode's assignment rows and a 512-row chunk's."""
    cases = []
    for path in sorted(glob.glob(os.path.join(CONFIGS, "*.json"))):
        cfg = json.load(open(path))
        kw = cfg["program"]["kwargs"]
        if kw.get("moe_dispatch") != "dropless" or "serving" not in cfg:
            continue        # (a TRAINED routed configuration has no slots)
        hidden = kw["hidden_size"]
        width = kw.get("moe_intermediate_size", kw["intermediate_size"])
        name = os.path.basename(path).split(".serve")[0]
        for rows in (cfg["serving"]["slots"],
                     cfg["serving"]["prefill_chunk_tokens"]):
            m = rows * kw["moe_top_k"]
            cases += [pytest.param(m, hidden, width, id=f"{name}-up-{m}"),
                      pytest.param(m, width, hidden, id=f"{name}-down-{m}")]
    return cases


def test_the_benchmark_has_six_routed_configurations():
    # OLMoE, Nemotron, Xing4.0, DeepSeek-V2, since PR 48 SmallThinker and,
    # since PR 59, Qwen3-Next
    assert len(_routed()) == 6 * 2 * 2


@pytest.mark.parametrize("m", [128, 4096], ids=["decode", "chunk"])
@pytest.mark.parametrize("k, n, tile", [
    (2048, 1024, (128, 2048, 1024)),       # gate and up [E, 2048, 1024]
    (1024, 2048, (128, 1024, 1024)),       # down [E, 1024, 2048]
], ids=["gate-up", "down"])
def test_olmoe_keeps_the_tile_it_was_measured_with(m, k, n, tile):
    """What ``(tm, min(tk, k), min(tn, n))`` of ``(128, 2048, 1024)`` gave
    until PR 41: OLMoE compiles the program it compiled."""
    assert GMM_TILING == (128, 2048, 1024)
    assert gmm_tile(m, k, n, 2) == tile


@pytest.mark.parametrize("m, k, n", _routed())
def test_the_k_tile_divides_every_served_contraction(m, k, n):
    tm, tk, tn = gmm_tile(m, k, n, 2)
    assert tm == 128
    assert k % tk == 0                      # nothing for mask_k_rem to mask
    # what megablox's gmm and tgmm both take: lanes of 128 or the whole axis
    assert tk % 128 == 0 or tk == k
    assert tn % 128 == 0 or tn == n
    # the double-buffered row tile and weight tile, bfloat16
    assert tn <= n and 2 * 2 * tk * (tm + tn) <= GMM_TILE_BYTES


@pytest.mark.parametrize("k, n, tile", [
    (2688, 1856, (128, 2688, 1024)),        # Nemotron up [E, 1856, 2688]
    (1856, 2688, (128, 1856, 896)),         # ... down: three lane tiles, whole
    (3584, 1024, (128, 1792, 1024)),        # Xing4.0 gate and up: two steps
    (1024, 3584, (128, 1024, 896)),         # ... down
    (5120, 1536, (128, 5120, 512)),         # DeepSeek-V2 gate and up
    (1536, 5120, (128, 1536, 1024)),        # ... down
], ids=["nemotron-up", "nemotron-down", "xing-up", "xing-down",
        "deepseek-v2-up", "deepseek-v2-down"])
def test_the_tiles_the_probe_measured(k, n, tile):
    """The fewest grid steps an expert, then the widest lane tile
    (``tools/gmm_tile_probe.py``; PERF.md §6, PR 41): what the chip runs at
    the published widths."""
    assert gmm_tile(384, k, n, 2) == tile


@pytest.mark.parametrize("k, n, itemsize, tile", [
    (7168, 2048, 2, (128, 1792, 1024)),     # too long to hold whole: divided
    (5200, 1000, 2, (128, 2048, 1000)),     # no multiple of 128 divides it:
    (6000, 1024, 2, (128, 2048, 1024)),     # ... the old tile, a masked k-tile
    (2100, 1000, 2, (128, 2100, 1000)),     # K whole needs no divisor
    (2688, 1856, 4, (128, 896, 1024)),      # float32: half the elements
], ids=["k-7168", "k-5200", "k-6000", "k-2100-whole", "float32"])
def test_a_contraction_too_long_is_divided_or_falls_back(k, n, itemsize, tile):
    assert gmm_tile(256, k, n, itemsize) == tile
    assert GMM_TILE_BYTES == 25 * 2 ** 19


def _loop(x, w, sizes, transpose_rhs):
    out, start = np.zeros((x.shape[0], w.shape[1 if transpose_rhs else 2]),
                          np.float32), 0
    for e, cnt in enumerate(sizes):
        we = w[e].T if transpose_rhs else w[e]
        out[start:start + cnt] = x[start:start + cnt] @ we
        start += cnt
    return out


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["k-n", "n-k"])
@pytest.mark.parametrize("k, n", [(384, 192), (2688, 1856)],
                         ids=["small", "nemotron-up"])
def test_megablox_under_the_chosen_tile_is_the_loop(k, n, transpose_rhs):
    """The interpreted kernel under the rule's tile against a loop over the
    experts: ragged sizes with an empty group, rows in no group at the end;
    at Nemotron's up-projection widths the float32 tile is three k-steps by
    two lane tiles, the last lane tile partly outside the matrix."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rs = np.random.RandomState(k)
    sizes = np.asarray([70, 0, 41], np.int32)
    x = rs.randn(128, k).astype(np.float32)
    w = (rs.randn(3, n, k) if transpose_rhs else rs.randn(3, k, n)
         ).astype(np.float32) / np.sqrt(k)
    tile = gmm_tile(128, k, n, 4)
    assert k % tile[1] == 0
    got = np.asarray(gmm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(sizes),
                         preferred_element_type=jnp.float32, tiling=tile,
                         transpose_rhs=transpose_rhs, interpret=True))
    live = int(sizes.sum())
    np.testing.assert_allclose(got[:live], _loop(x, w, sizes, transpose_rhs
                                                 )[:live],
                               rtol=1e-4, atol=1e-4)


def test_the_tally_counts_a_lowered_call_once_by_its_k_tile():
    take_gmm_lowered()
    sizes = jnp.asarray([3, 0, 5], jnp.int32)

    def run(k, n):
        x, w = jnp.ones((8, k), jnp.float32), jnp.ones((3, k, n), jnp.float32)
        f = jax.jit(lambda x, w: grouped_matmul(x, w, sizes, jnp.float32))
        f(x, w)
        return f(x, w)                       # cached: traced once

    run(256, 128)
    assert take_gmm_lowered() == {"whole_k": 1, "masked_k": 0}
    run(5200, 1024)          # too long whole, and no multiple of 128 divides
    assert take_gmm_lowered() == {"whole_k": 0, "masked_k": 1}
    assert take_gmm_lowered() == {"whole_k": 0, "masked_k": 0}
