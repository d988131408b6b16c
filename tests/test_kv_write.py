"""The page pool's writer (``ops.kv_pool_write``) against the split-index row
scatter it replaced, kept HERE as its reference: after any write the pools
are the same bits, dropped rows included — both forms, the XLA one and the
Pallas call through the interpreter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.ops.kv_pool_write import (
    touched_pages,
    write_pool_rows,
)

PAGE, D, PP = 16, 128, 8
T = PP * PAGE
B = 6


def scatter_reference(pool, new, phys, in_off):
    """The models' writer up to PR 27: cell ``(phys, :, in_off)`` of the
    head-major pool, a row whose ``phys`` is out of range dropped."""
    return pool.at[phys, :, in_off].set(new.astype(pool.dtype), mode="drop")


def cells(offset, table, kv_valid, Sn, num_pages):
    """``(phys, in_off)`` of a write as ``models/llama.py`` derives them
    from the slots' offsets, block tables and key validity."""
    idx = offset[:, None] + np.arange(Sn)[None, :]
    page_idx = np.clip(idx // PAGE, 0, PP - 1)
    phys = np.take_along_axis(table, page_idx, axis=1)
    phys = np.where(idx < T, phys, num_pages)
    live = np.take_along_axis(kv_valid, np.clip(idx, 0, T - 1), axis=1) > 0
    return np.where(live, phys, num_pages), idx % PAGE


def bits(x):
    return np.asarray(x).view(np.uint16)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("nkv", [4, 8, 16])
@pytest.mark.parametrize("first", [0, 5], ids=["aligned", "midpage"])
@pytest.mark.parametrize("Sn", [1, 3, 17, 64])
def test_pool_write_leaves_the_scatters_bits(Sn, first, nkv, kernel):
    """Six slots in one write: two plain ones on pages of their own, a
    parked one (offset T), one whose rows run past the table's end, one
    whose first rows are left pads (validity 0), and one whose table names,
    off the cells it writes, a page out of range and a page of NaNs."""
    rs = np.random.RandomState(Sn * 100 + first * 10 + nkv)
    num_pages = B * PP + 2
    nan_page = num_pages - 1
    pool = rs.randn(num_pages, nkv, PAGE, D).astype(np.float32)
    pool[nan_page] = np.nan
    pool = jnp.asarray(pool, jnp.bfloat16)
    new = jnp.asarray(rs.randn(B, Sn, nkv, D), jnp.bfloat16)
    table = (1 + rs.permutation(num_pages - 2)[:B * PP]).reshape(B, PP)
    offset = np.array([first, PAGE + first, T, T - 2 - first,
                       2 * PAGE + first, 3 * PAGE + first])
    kv_valid = np.ones((B, T), np.int32)
    kv_valid[4, :offset[4] + 2] = 0           # the write's first two rows
    table[5, :3] = (num_pages + 7, nan_page, nan_page)
    phys, in_off = cells(offset, table, kv_valid, Sn, num_pages)
    assert (phys[2] == num_pages).all() and (phys[3, 2 + first:] == num_pages).all()
    assert (phys[4, :2] == num_pages).all() and (phys[0] < num_pages).all()

    want = scatter_reference(pool, new, jnp.asarray(phys), jnp.asarray(in_off))
    got = jax.jit(lambda *a: write_pool_rows(*a, kernel=kernel))(
        pool, new, jnp.asarray(phys), jnp.asarray(in_off))
    assert np.array_equal(bits(got), bits(want))
    assert np.isnan(np.asarray(got[nan_page], np.float32)).all()
    # and something was written: the plain slots' rows are in the pool
    assert np.array_equal(bits(got[table[0, 0], :, first]), bits(new[0, 0]))


def test_touched_pages_groups_rows_by_page():
    """17 rows from cell 5 of a chain touch two pages (11 cells of the
    first, 6 of the second, two of them dropped); a parked slot's pages get
    the id ``NP``: no cell of them is written."""
    num_pages = 9
    phys = np.full((2, 17), num_pages)
    in_off = np.stack([5 + np.arange(17), np.arange(17)]) % PAGE
    phys[0, :11] = 4
    phys[0, 11:15] = 7
    new = jnp.zeros((2, 17, 2, D), jnp.bfloat16)
    pj, hot, ins = touched_pages(new, jnp.asarray(phys), jnp.asarray(in_off),
                                 num_pages, PAGE)
    assert pj.tolist() == [4, 7, num_pages, num_pages]
    assert np.asarray(hot).sum(axis=1).tolist() == [11, 4, 0, 0]
    assert np.asarray(hot)[0, :5].sum() == 0 and ins.shape == (4, 2, PAGE, D)


def test_engine_counts_rows_and_pages_from_the_host_offsets():
    """``serving/kv_rows_written_total`` / ``kv_pages_touched_total``: a
    prompt of 6 behind 2 pads commits 6 rows on 2 pages of 4 in its chunk;
    every decode dispatch one row on one page a live slot; a verify round
    of 3 rows from cell 3 of a page straddles two."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none")
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((2, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=2, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    engine = ServingEngine(pool, page_size=4)
    dispatched = []
    dispatch = engine._dispatch_decode

    def spy(active, offs, ahead):
        dispatched.append([int(offs[s]) for s, _ in active])
        return dispatch(active, offs, ahead)

    engine._dispatch_decode = spy
    engine.submit(Request(request_id=0, prompt_ids=[3, 4, 5, 6, 7, 8],
                          max_new_tokens=5))
    engine.run_until_complete(max_steps=50)
    snap = engine.registry.snapshot()
    rows = sum(len(offs) for offs in dispatched)
    assert rows >= 4 and dispatched[0] == [8]
    assert snap["serving/kv_rows_written_total"] == 6 + rows
    assert snap["serving/kv_pages_touched_total"] == 2 + rows

    # a verify round's rows, straight from offsets: slot 0 writes cells
    # 7..9 (two pages), slot 1 cells 14..15 of 16 (its third row is past T)
    engine._spec_k = 2
    engine._offsets[:] = (7, 14)
    engine._count_decode_write([(0, None), (1, None)], engine._offsets)
    snap = engine.registry.snapshot()
    assert snap["serving/kv_rows_written_total"] == 6 + rows + 5
    assert snap["serving/kv_pages_touched_total"] == 2 + rows + 3
    engine._spec_k = 0
    engine.close()
