"""The attention kernels compile for the chip — asked of the chip's compiler,
without the chip.

libtpu compiles for a TPU that is DESCRIBED, not attached
(``jax.experimental.topologies``), so each case here is an AOT compile for a
``v5e:2x2`` host at Mistral-7B / Llama-3 attention geometry (32 q / 8 kv
heads, head_dim 128, bf16), through the production entry points with the
``interpret`` argument left at its default.  Interpret-mode parity (the rest
of the suite) cannot see what these see: a block shape Mosaic refuses, a
kernel that cannot be partitioned, a program that lowers for a TPU and comes
out interpreted.  Every case asserts the Mosaic ``tpu_custom_call`` is in the
compiled program.  Nothing runs, so nothing here says anything about results
or times; ``chip_smoke.py`` checks the same kernels against their references
on the chip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from neuronx_distributed_tpu.ops.paged_attention import paged_attention
from neuronx_distributed_tpu.ops.ring_attention import ring_attention
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel

NQ, NKV, D = 32, 8, 128
WINDOW = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one warns and compiles
    again), so the cache is off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _mesh(topo, tp=1):
    return initialize_model_parallel(
        tensor_parallel_size=tp, devices=topo.devices[:tp])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# -- flash attention, through ring_attention (what the models call) ---------


FLASH_CASES = {
    # name: (S, window, segmented, backward, the Mosaic kernels expected)
    "fwd": (2048, None, False, False, {"flash_fwd"}),
    "bwd": (2048, None, False, True, {"flash_fwd", "flash_dq_dkv"}),
    "window4096_s8192_bwd": (8192, WINDOW, False, True,
                             {"flash_fwd", "flash_dq_dkv"}),
    "segmented_bwd": (2048, None, True, True, {"flash_fwd", "flash_dq_dkv"}),
    # 8 MiB of float32 dq rows a head: the most the one backward call keeps
    "s16384_bwd": (16384, None, False, True, {"flash_fwd", "flash_dq_dkv"}),
    # past it (a ring's shard): the two kernels
    "s32768_bwd": (32768, None, False, True,
                   {"flash_fwd", "flash_dq", "flash_dkv"}),
}


def _flash_kernels(text):
    """The flash kernels' names among a compiled program's Mosaic calls."""
    import re

    return set(re.findall(r"%(flash_[a-z_]+?)[.\d]* = [^\n]*tpu_custom_call",
                          text))


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_kernel_compiles_for_v5e(topo, case):
    S, window, segmented, backward, kernels = FLASH_CASES[case]
    mesh = _mesh(topo)
    sh = NamedSharding(mesh, P())
    q = jax.ShapeDtypeStruct((1, S, NQ, D), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((1, S, NKV, D), jnp.bfloat16, sharding=sh)
    seg = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=sh)

    def attn(q, k, v, seg):
        return ring_attention(q, k, v, causal=True, window=window,
                              segment_ids=seg if segmented else None)

    def loss(q, k, v, seg):
        return jnp.sum(attn(q, k, v, seg).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else attn
    text = _compiled_text(fn, q, kv, kv, seg)
    assert _flash_kernels(text) == kernels, (
        f"{case}: expected the Mosaic kernels {sorted(kernels)} in the "
        f"program compiled for the TPU, found {text.count('tpu_custom_call')} "
        f"custom call(s): {sorted(_flash_kernels(text))}")


# -- paged attention ---------------------------------------------------------


PAGED_CASES = {
    # name: (S, page, pages_per_slot, int8 pages, window, tp)
    "decode": (1, 16, 512, False, None, 1),
    "decode_windowed": (1, 16, 512, False, WINDOW, 1),
    "verify_s5": (5, 16, 512, False, WINDOW, 1),
    "chunk_s64": (64, 16, 512, False, WINDOW, 1),
    "int8_decode": (1, 16, 512, True, WINDOW, 1),
    "int8_chunk_s64": (64, 16, 512, True, WINDOW, 1),
    # the serving tools' default page size
    "page8_decode": (1, 8, 1024, False, WINDOW, 1),
    "tp4_shard_map_decode": (1, 16, 512, False, WINDOW, 4),
    "tp4_shard_map_int8_chunk_s64": (64, 16, 512, True, WINDOW, 4),
}


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_kernel_compiles_for_v5e(topo, case):
    S, page, pp, quant, window, tp = PAGED_CASES[case]
    B, num_pages = 8, 2048
    mesh = _mesh(topo, tp)

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    heads = "tp" if tp > 1 else None
    q = sds((B, S, NQ, D), jnp.bfloat16, None, None, heads, None)
    pages = sds((num_pages, NKV, page, D),
                jnp.int8 if quant else jnp.bfloat16, None, heads, None, None)
    pool = (pages, pages)
    if quant:
        pool += (sds((num_pages,), jnp.float32),) * 4
    table = sds((B, pp), jnp.int32)
    vec = sds((B,), jnp.int32)

    text = _compiled_text(
        lambda q, pool, bt, off, start: paged_attention(
            q, pool, bt, off, start, window=window),
        q, pool, table, vec, vec)
    assert "tpu_custom_call" in text, (
        f"{case}: no Mosaic kernel in the program compiled for the TPU")
    if tp > 1:
        # heads shard over tp with the pool: the shard_map'd kernel needs
        # no collective (the output projection reduces afterwards)
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert op not in text, f"{case}: unexpected {op}"


# -- the serving sampler -----------------------------------------------------


def test_sampler_keeps_its_sort_behind_a_conditional_for_v5e(topo):
    """The chip's compiler keeps ``_sample_rows``'s batch-level choice a
    ``conditional`` of three branches (it neither flattens it into a select
    of both sides nor hoists the sorts out), so an all-greedy batch runs the
    argmax branch alone.  At the chat cell's shape: 32 slots, Qwen2's
    vocabulary (one case: the sort alone takes the compiler half a minute)."""
    import re

    from jax.sharding import SingleDeviceSharding

    from neuronx_distributed_tpu.serving.engine import (
        SAMPLER_PATHS,
        _sample_rows,
    )

    B, V = 32, 152064
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _sample_rows.lower(
        sds((B, V), jnp.float32), sds((B, 2), jnp.uint32),
        sds((B,), jnp.int32), sds((B,), jnp.float32), sds((B,), jnp.int32),
        sds((B,), jnp.float32)).compile().as_text()
    [cond] = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}",
                        text)
    branches = [b.strip().lstrip("%") for b in cond.split(",")]
    assert len(branches) == len(SAMPLER_PATHS)
    # every sort sits in a computation reachable only from the last branch:
    # the entry computation and the first two branches name none
    bodies = dict(re.findall(
        r"^(?:ENTRY )?%(\S+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M))
    assert " sort(" not in bodies[branches[0]]
    assert " sort(" not in bodies[branches[1]]
    assert " sort(" in bodies[branches[2]]
    entry = re.search(r"^ENTRY %\S+ \([^\n]*\{\n(.*?)^\}", text,
                      re.S | re.M).group(1)
    assert " sort(" not in entry and " conditional(" in entry


# -- the three serving cells' paged geometries; OLMoE's routed expert block ---


# cell: (slots, kv heads, group, pages a slot, pool pages, window); page 16
CELL_PAGED = {
    "qwen2-7b.serve-chat": (32, 4, 7, 128, 4353, None),
    "mistral-7b.serve-docs": (8, 8, 4, 512, 4161, WINDOW),
    # 16 query heads = 16 kv heads: ONE query row a kv head in a decode
    "olmoe-1b-7b.serve-backlog": (16, 16, 1, 64, 1153, None),
}


@pytest.mark.parametrize("S", [1, 512], ids=["decode", "chunk_s512"])
@pytest.mark.parametrize("cell", sorted(CELL_PAGED))
def test_paged_kernel_compiles_at_the_cells_geometries(topo, cell, S):
    """Each serving cell's decode call and its 512-row prefill chunk, at the
    cell's pool: one Mosaic call, which takes the K and the V pool ONCE each
    — whole, in HBM, for the walk's own copies — not a page of them per
    operand."""
    import re

    B, nkv, group, pp, num_pages, window = CELL_PAGED[cell]
    mesh = _mesh(topo)
    B = B if S == 1 else 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    pages = sds((num_pages, nkv, 16, D), jnp.bfloat16)
    text = _compiled_text(
        lambda q, pool, bt, off, start: paged_attention(
            q, pool, bt, off, start, window=window),
        sds((B, S, nkv * group, D), jnp.bfloat16), (pages, pages),
        sds((B, pp), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.int32))
    [call] = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    name = "paged_attention_decode" if S == 1 else "paged_attention_chunk"
    assert f"%{name}" in call
    [operands] = re.findall(r"operand_layout_constraints=\{(.*?)\}\}, ", call)
    assert operands.count(f"bf16[{num_pages},{nkv},16,{D}]") == 2


WRITE_CASES = [(cell, S, 1) for cell in sorted(CELL_PAGED) for S in (1, 512)]
WRITE_CASES += [("mistral-7b.serve-docs", 1, 4), ("mistral-7b.serve-docs", 512, 4)]


@pytest.mark.parametrize(
    "cell,S,tp", WRITE_CASES,
    ids=[f"{c}-{'decode' if S == 1 else 'chunk_s512'}-tp{tp}"
         for c, S, tp in WRITE_CASES])
def test_pool_write_then_attend_copies_no_pool_for_v5e(topo, cell, S, tp):
    """A serve program's K / V write and its paged call, both pools donated:
    the write is in place.  No ``copy`` or ``transpose`` gives a pool-shaped
    result, the program's temporaries stay far under a pool (the row scatter
    that split page and cell round the head axis had the compiler relay
    both pools out and back: 130 MiB of temporaries at the docs geometry,
    PR 28), both pools are aliased to their outputs, and under tp = 4 no
    collective moves one."""
    import re

    from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows

    B, nkv, group, pp, num_pages, window = CELL_PAGED[cell]
    mesh = _mesh(topo, tp)
    B = B if S == 1 else 1
    heads = "tp" if tp > 1 else None

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    def step(q, k, v, pool, bt, off, start):
        # models/llama.py's index arithmetic, without the validity mask
        idx = off[:, None] + jnp.arange(S)[None, :]
        phys = jnp.take_along_axis(bt, jnp.clip(idx // 16, 0, pp - 1), axis=1)
        phys = jnp.where(idx < pp * 16, phys, num_pages)
        with jax.named_scope("kv_write"):
            pool = tuple(write_pool_rows(c, x, phys, idx % 16, kernel=True)
                         for c, x in zip(pool, (k, v)))
        return paged_attention(q, pool, bt, off, start, window=window), pool

    pages = sds((num_pages, nkv, 16, D), jnp.bfloat16, None, heads, None, None)
    new = sds((B, S, nkv, D), jnp.bfloat16, None, None, heads, None)
    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        sds((B, S, nkv * group, D), jnp.bfloat16, None, None, heads, None),
        new, new, (pages, pages), sds((B, pp), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("%kv_pool_write") >= 2 and "%paged_attention" in text
    pool_shape = re.escape(f"bf16[{num_pages},{nkv // tp},16,{D}]")
    relaid = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(rf"= {pool_shape}\S* (copy|transpose)\(", ln)]
    assert not relaid, f"a pool is copied: {relaid}"
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 8 * 2 ** 20
    pool_bytes = num_pages * (nkv // tp) * 16 * D * 2
    assert memory.alias_size_in_bytes == 2 * pool_bytes
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert op not in text, f"unexpected {op}"


def test_routed_expert_block_compiles_for_v5e_without_relaying_out_its_weights(topo):
    """The dropless expert block at OLMoE-1B-7B's widths (64 experts x 1024,
    8 a token, hidden 2048) on a decode's 16 rows: three megablox calls
    (gate, up, down) and no temporary anywhere near an expert matrix —
    stored FUSED as ``[E, H, 2, I]`` the same block copied 512 MiB out a
    call on the v5e (PR 25)."""
    from neuronx_distributed_tpu.parallel.moe import ExpertParallelMLP

    mesh = _mesh(topo)
    moe = ExpertParallelMLP(
        num_experts=64, intermediate_size=1024, top_k=8, dispatch="dropless",
        norm_topk_prob=False, fused_gate_up=False, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((16, 1, 2048), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    from flax import linen as nn

    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=NamedSharding(mesh, P())),
        nn.unbox(jax.eval_shape(moe.init, jax.random.PRNGKey(0), x)))
    compiled = jax.jit(lambda p, x: moe.apply(p, x)[0]).lower(
        params, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


@pytest.mark.parametrize("rows", ["walked", "whole"])
def test_held_gated_experts_backward_compiles_for_v5e_at_lfm2_widths(
        topo, rows, monkeypatch):
    """The TRAINED dropless block at LFM2-8B-A1B's widths (8 of 32 experts x
    1792 held, 4 a token, hidden 2048) over 16,384 tokens, loss and
    gradients.  ``walked``: what the program is since PR 45 — the grouped
    matmuls once each over all 65,536 sorted rows, as before, and everything
    between them in two spans, ``held_rows_slab``'s 18,432 and the other
    47,104, the second under a ``cond``: eleven megablox calls (gate, up,
    down forward; in the written-out backward gate and up again, ``dy
    wo^T``, two flipped ``gmm`` for the rows' gradient and three ``tgmm``,
    each weight's over all its rows), under the tiles the whole block's take.
    ``whole``: the block as a layer that holds every expert runs it: nine
    calls — gate, up and down, each forward, flipped and ``tgmm`` — under
    tiles picked from the backward's own operands (under the forward's tile,
    what upstream's ``custom_vjp`` hands on, the chip's compiler refused
    both backward kernels: AOT, PR 41), and its transposes are gathers."""
    from flax import linen as nn

    from neuronx_distributed_tpu.parallel import moe as moe_lib

    if rows == "whole":
        monkeypatch.setattr(moe_lib, "held_rows_slab", lambda *a: 0)
    else:
        assert moe_lib.held_rows_slab(16384 * 4, 8, 32) == 18432
    mesh = _mesh(topo)
    moe = moe_lib.ExpertParallelMLP(
        num_experts=8, num_experts_global=32, first_expert=0,
        intermediate_size=1792, top_k=4, dispatch="dropless",
        fused_gate_up=False, router_scores="sigmoid", router_bias=True,
        norm_topk_prob=True, dtype=jnp.bfloat16,
        param_dtype=jnp.float32)
    rep = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16, sharding=rep)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        nn.unbox(jax.eval_shape(moe.init, jax.random.PRNGKey(0), x)))

    def loss(p, x):
        y = moe.apply(p, x)[0].astype(jnp.float32)
        return jnp.sum(y * jnp.cos(y))      # a loss that needs the forward

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    import re

    assert text.count("tpu_custom_call") == (11 if rows == "walked" else 9)
    # rows move by gathers, in the walk as in the transposes of the whole
    # block's dispatch and combine: no scatter of ROWS (the scalar ones —
    # the inverse permutation, the gates' place among the 32 scores,
    # megablox's group tables — stay)
    assert not re.findall(r"\[\d+,\d+[^\]]*\]\S* scatter\(", text)


def test_flash_kernels_compile_for_v5e_at_head_dim_64(topo):
    """LFM2's attention geometry — 32 q / 8 kv heads of 64, full causal, 8192
    rows: forward and the one backward call (every other case here is at
    128)."""
    mesh = _mesh(topo)
    sh = NamedSharding(mesh, P())
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16, sharding=sh)

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert _flash_kernels(text) == {"flash_fwd", "flash_dq_dkv"}


@pytest.mark.parametrize("program", ["decode", "chunk_s512"])
def test_hybrid_paged_programs_compile_for_v5e_and_copy_no_state(topo, program):
    """MiniCPM-SALA's paged programs at its PUBLISHED widths and the cell's
    serving geometry (8 slots of 20,992 tokens, pages of one 64-token block,
    a 512-row chunk), cut to one period of two layers (a block-sparse
    softmax layer, a lightning layer): the chosen-table decode walk, the
    masked chunk walk, the top-k selection and the pool writer are Mosaic
    calls and nothing sorts; the pool, the
    compressed keys and the float32 state rows are donated, aliased to their
    outputs and never copied."""
    import functools
    import re

    from neuronx_distributed_tpu.kvcache.pool import LayerStates
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )
    from flax import linen as nn

    mesh = _mesh(topo)
    B, T, page, W, num_pages = 8, 20992, 64, 512, 2689
    cfg = LlamaConfig(
        vocab_size=73448, hidden_size=4096, intermediate_size=16384,
        num_layers=2, num_heads=32, num_kv_heads=2, head_dim=128,
        max_seq_len=T, rms_eps=1e-6,
        mixer_types=("minicpm4", "lightning-attn"), embed_scale=12.0,
        residual_scale=1.4 / 32 ** 0.5, logit_scale=1.0 / 16,
        lightning_heads=32, lightning_head_dim=128, sequence_parallel=False,
        remat="none", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    module = LlamaForCausalLM(cfg)
    rep = NamedSharding(mesh, P())
    boxed = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                           jnp.zeros((1, page), jnp.int32))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        nn.unbox(boxed))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=20480, max_total_len=T,
                        kv_cache_dtype=jnp.bfloat16))
    layers = LayerStates.for_config(cfg, page, state_rows=B)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    pages = sds((num_pages, 2, page, 128), jnp.bfloat16)
    comp = sds((num_pages, layers.comp_slots, 2, 128), jnp.bfloat16)
    state = sds((B, 32, 128, 128), jnp.float32)
    caches = ((pages, pages, comp), (state,))
    decode = program == "decode"
    rows, S = (B, 1) if decode else (1, W)
    fn = jax.jit(functools.partial(
        model._paged_step_fn, paged_kernel=True, update_valid=decode,
        last_only=True), donate_argnums=(4,))
    compiled = fn.lower(
        params, sds((rows, S)), sds((rows,)), sds((rows, T // page)), caches,
        sds((rows, T)), state_rows=sds((rows,)),
        **({} if decode else {"last_row": sds(())})).compile()
    text = compiled.as_text()
    kernel = "sparse_attention_decode" if decode else "sparse_attention_chunk"
    assert f"%{kernel}" in text and text.count("%kv_pool_write") >= 2
    # the top-64 of 328 pages is a threshold search in one Mosaic call, not
    # the full sort the chip made of ``lax.top_k`` (PR 40)
    assert "%sparse_topk_select" in text
    assert not re.search(r" sort\(|TopK", text)
    # (a decode's per-slot state UPDATE has the state array's own shape, 8
    # slots being 8 rows: for it only a ``copy`` is a copy)
    for shape, ops in ((f"bf16[{num_pages},2,{page},128]", "copy|transpose"),
                       (f"bf16[{num_pages},{layers.comp_slots},2,128]",
                        "copy|transpose"),
                       (f"f32[{B},32,128,128]", "copy")):
        copied = [ln.strip()[:120] for ln in text.splitlines()
                  if re.search(rf"= {re.escape(shape)}\S* ({ops})\(", ln)]
        assert not copied, f"{shape} is copied: {copied}"
    memory = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    assert memory.alias_size_in_bytes >= held


@pytest.mark.parametrize("rows,groups", [(32, 1), (64, 8)],
                         ids=["granite_32_rows", "nemotron_64_rows"])
def test_the_scan_step_kernel_compiles_for_v5e_on_the_state_where_it_lies(
        topo, rows, groups):
    """``ops.ssm_scan.ssm_step`` at the cells' shapes — 32 rows of ``[64,
    64, 128]`` float32 in ONE group (Granite), 64 in 8 (Nemotron), a whole
    2 MiB row a program: the Mosaic call ``ssm_step`` with the state array
    donated, aliased to its output and never copied, and nothing as large as
    a state row beside it."""
    import re

    from neuronx_distributed_tpu.ops import ssm_scan as ssm

    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    NH, HP, N = 64, 64, 128
    assert ssm._step_heads(NH, HP, N) == NH
    compiled = jax.jit(ssm.ssm_step, donate_argnums=(0,)).lower(
        sds((rows, NH, HP, N)), sds((rows, NH, HP), jnp.bfloat16),
        sds((rows, groups, N), jnp.bfloat16),
        sds((rows, groups, N), jnp.bfloat16), sds((rows, NH)), sds((NH,)),
        sds((NH,)), sds((rows,), jnp.bool_), sds((rows,), jnp.bool_),
        sds((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert re.search(r"%ssm_step[.\d]* = [^\n]*tpu_custom_call", text)
    copied = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(rf"= f32\[{rows},64,64,128\]\S* (copy|transpose)\(",
                           ln)]
    assert not copied, f"the state array is copied: {copied}"
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == rows * NH * HP * N * 4
    assert m.temp_size_in_bytes < NH * HP * N * 4


@pytest.fixture(scope="module")
def nemotron_programs(topo):
    """Both serve programs of the benchmark's Nemotron-3-Nano configuration
    (``benchmarks/tools/nemotron_aot.py``: every layer, published widths, 64
    slots), compiled once for the two cases below."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from benchmarks.harness import manifest
    from benchmarks.tools import nemotron_aot

    prev = jax.config.jax_enable_compilation_cache
    try:
        cell = manifest.Cell("nemotron-3-nano.serve-agents")
        programs, weights, pool, shapes, _ = \
            nemotron_aot.compile_serve_programs(cell)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    return dict(programs), weights, pool, shapes


@pytest.mark.parametrize("program", ["paged decode", "paged chunk prefill"])
def test_nemotron_serve_programs_fit_a_v5e_and_copy_no_state(
        nemotron_programs, program):
    """14 one-sublayer layers at the PUBLISHED widths (Mamba-2 64 heads x 64
    with a state of 128, 32 q / 2 kv attention, 64 held of 128 relu2 experts
    and the shared one), 64 slots of 2,304 tokens, a 512-row chunk: the
    grouped matmuls, the paged walk (a chunk's 8,192 query rows a kv head in
    parts VMEM holds) and the pool writer are Mosaic calls; the expert
    weights enter the kernel as they lie; the pages, the float32 scan states
    and the convolution taps are donated and aliased; all of it under 14 GiB."""
    import re

    programs, weights, pool, shapes = nemotron_programs
    compiled = programs[program]
    text = compiled.as_text()
    kernel = ("paged_attention_decode" if program == "paged decode"
              else "paged_attention_chunk")
    assert f"%{kernel}" in text and "%gmm" in text
    assert text.count("%kv_pool_write") >= 2
    # no copy of an expert stack ([64, 1856, 2688] up and down), of the page
    # pool or of the scan states
    for shape in ["bf16[64,1856,2688]"] + [
            f"{'f32' if s.dtype == jnp.float32 else 'bf16'}"
            f"[{','.join(map(str, s.shape))}]" for s in shapes[:2]]:
        copied = [ln.strip()[:120] for ln in text.splitlines()
                  if re.search(rf"= {re.escape(shape)}\S* (copy|transpose)\(",
                               ln) and "fused_computation" not in ln]
        assert not copied, f"{shape} is copied: {copied}"
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert weights + pool < total < 14 * 2 ** 30


# Granite-4.0-H-Micro's attention layers at the cell's pool: 32 slots, 8 kv
# heads of 64 (group 4), pages of 64, 528 a slot, 8,193 in the pool
GRANITE = dict(slots=32, nkv=8, group=4, d=64, page=64, pp=528, pages=8193)


@pytest.mark.parametrize("S", [1, 512], ids=["decode", "chunk_s512"])
def test_heads_of_64_are_written_and_walked_in_place_at_their_true_bytes(
        topo, S):
    """Heads of 64 for a v5e: the pool keeps two to a 128-lane row
    (``kvcache.pool.page_layout``), so the writer and the walk are the
    ``D`` 128 Mosaic calls under their own names, each takes the K and the V
    pool once, whole, and the two pools are donated, aliased and EXACTLY
    ``tokens x kv heads x 64 x 2`` bytes each — where ``[pages, 8, 64, 64]``
    does not lower at all (a slice of 64 lanes of a 128-lane tiling)."""
    import re

    from neuronx_distributed_tpu.kvcache.pool import page_layout
    from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows

    g = GRANITE
    nkv, d, page, pp, num_pages = g["nkv"], g["d"], g["page"], g["pp"], g["pages"]
    mesh = _mesh(topo)
    B = g["slots"] if S == 1 else 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    def step(q, k, v, pool, bt, off, start):
        idx = off[:, None] + jnp.arange(S)[None, :]
        phys = jnp.take_along_axis(bt, jnp.clip(idx // page, 0, pp - 1), axis=1)
        phys = jnp.where(idx < pp * page, phys, num_pages)
        with jax.named_scope("kv_write"):
            pool = tuple(write_pool_rows(c, x, phys, idx % page, kernel=True)
                         for c, x in zip(pool, (k, v)))
        return paged_attention(q, pool, bt, off, start,
                               sm_scale=0.015625), pool

    heads, width = page_layout(nkv, d)
    assert (heads, width) == (4, 128)
    pages = sds((num_pages, heads, page, width), jnp.bfloat16)
    new = sds((B, S, nkv, d), jnp.bfloat16)
    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        sds((B, S, nkv * g["group"], d), jnp.bfloat16), new, new,
        (pages, pages), sds((B, pp), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32)).compile()
    text = compiled.as_text()
    name = "paged_attention_decode" if S == 1 else "paged_attention_chunk"
    assert text.count("%kv_pool_write") >= 2 and f"%{name}" in text
    pool_shape = f"bf16[{num_pages},{heads},{page},{width}]"
    walks = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and f"%{name}" in ln]
    # a 512-row chunk of a pair's 8 query heads is walked in two parts
    assert len(walks) == (1 if S == 1 else 2)
    for call in walks:
        [operands] = re.findall(r"operand_layout_constraints=\{(.*?)\}\}, ",
                                call)
        assert operands.count(pool_shape) == 2
    relaid = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(rf"= {re.escape(pool_shape)}\S* (copy|transpose)\(",
                           ln)]
    assert not relaid, f"a pool is copied: {relaid}"
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 24 * 2 ** 20
    tokens = num_pages * page
    assert memory.alias_size_in_bytes == 2 * tokens * nkv * d * 2


@pytest.fixture(scope="module")
def granite_programs(topo):
    """Both serve programs of the benchmark's Granite-4.0-H-Micro
    configuration (``benchmarks/tools/granite_aot.py``: all 40 layers, every
    published width, the whole tied table, 32 slots), compiled once for the
    two cases below."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from benchmarks.harness import manifest
    from benchmarks.tools import granite_aot

    prev = jax.config.jax_enable_compilation_cache
    try:
        cell = manifest.Cell("granite-4.0-h-micro.serve-sessions")
        programs, weights, pool, shapes, _ = \
            granite_aot.compile_serve_programs(cell)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    return dict(programs), weights, pool, shapes, cell.config["serving"]


@pytest.mark.parametrize("program", ["paged decode", "paged chunk prefill"])
def test_granite_serve_programs_fit_a_v5e_whole_and_copy_no_state(
        granite_programs, program):
    """The WHOLE model at the published widths — 36 Mamba-2 layers (64 heads
    x 64, ONE group, state 128, blocks of 256 rows) beside 4 attention
    layers of 32 q / 8 kv heads of 64, a SwiGLU of 8,192 in each, the tied
    100,352-row table — 32 slots of 33,792 tokens, a 512-row chunk: the
    paged walk and the pool writer are the named Mosaic calls; the K/V pages
    are laid out at exactly ``tokens x 8,192`` bytes; the pages, the float32
    scan states ``[32, 64, 64, 128]`` and the taps are donated and aliased,
    and no copy of any of them is in the text; all of it under 13.5 GiB of
    the chip's 15.75."""
    import re

    from benchmarks.tools import granite_aot

    programs, weights, pool, shapes, s = granite_programs
    compiled = programs[program]
    text = compiled.as_text()
    kernel = ("paged_attention_decode" if program == "paged decode"
              else "paged_attention_chunk")
    assert f"%{kernel}" in text
    assert text.count("%kv_pool_write") >= 2 * 4
    page, state, taps = shapes
    assert page.shape == (s["num_pages"], 4, s["page_size"], 128)
    assert state.shape == (32, 64, 64, 128) and state.dtype == jnp.float32
    laid, layout = granite_aot.laid_out_bytes(text, page)
    tokens = s["num_pages"] * s["page_size"]
    assert 2 * 4 * laid == tokens * 8192, layout
    for sds in shapes:
        shape = (f"{'f32' if sds.dtype == jnp.float32 else 'bf16'}"
                 f"[{','.join(map(str, sds.shape))}]")
        copied = [ln.strip()[:120] for ln in text.splitlines()
                  if re.search(rf"= {re.escape(shape)}\S* (copy|transpose)\(",
                               ln) and "fused_computation" not in ln]
        assert not copied, f"{shape} is copied: {copied}"
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool
    assert pool == tokens * 8192 + 32 * 36 * (2097152 + 26112)
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert weights + pool < total < 13.5 * 2 ** 30
    # what a step keeps beside the resident bytes: a decode next to nothing,
    # a chunk its block arrays and activations
    assert m.temp_size_in_bytes < (0.15 if program == "paged decode"
                                   else 0.5) * 2 ** 30


# (cell's fixture, rows x heads x width of one BLOCK of the scan's float32
# ``y``, the Mamba-2 layers — each ONE Mosaic call ``ssm_step`` in the decode
# text since PR 58 — and the state array a layer)
SCAN_WALKS = {
    "granite": ("granite_programs", 256 * 64 * 64, 36, "f32[32,64,64,128]"),
    "nemotron": ("nemotron_programs", 128 * 64 * 64, 6, "f32[64,64,64,128]")}


@pytest.mark.parametrize("cell", sorted(SCAN_WALKS))
def test_a_chunks_scan_blocks_reach_their_rows_without_a_loops_output(
        cell, request):
    """The chunk program walks its 2 (Granite) or 4 (Nemotron) blocks
    written out (``ops/ssm_scan.py::UNROLLED_BLOCKS``): under the scope
    ``ssm_scan_chunk`` the text holds no ``while``, no dynamic update of a
    slice (``lax.scan``'s stacked ``ys``, which the v5e stored a sublane a
    tile: 268 of a layer's 385 us, PERF.md PR 56) and no float32 copy or
    reshape as large as a block's rows; the decode program holds what it
    held under ``ssm_step``."""
    import math
    import re

    fixture, block, layers, state = SCAN_WALKS[cell]
    programs = request.getfixturevalue(fixture)[0]
    chunk = [ln for ln in programs["paged chunk prefill"].as_text()
             .splitlines() if re.search(r'op_name="[^"]*/ssm_scan_chunk/', ln)]
    assert len(chunk) > 100
    moved = []
    for ln in chunk:
        op = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(", ln)
        if op is None:      # a tuple's result
            assert not re.search(r" while\(", ln), ln[:200]
            continue
        dtype, dims, opcode = op.groups()
        assert opcode not in ("while", "dynamic-update-slice"), ln[:200]
        if opcode in ("copy", "reshape") and dtype == "f32" and math.prod(
                int(d) for d in dims.split(",") if d) >= block:
            moved.append(ln.strip()[:200])
    assert not moved, moved
    decode = programs["paged decode"].as_text()
    assert "/ssm_scan_chunk/" not in decode
    step = [ln for ln in decode.splitlines()
            if re.search(r'op_name="[^"]*/ssm_step/', ln)]
    calls = [ln for ln in step if re.search(r"%ssm_step[.\d]* = ", ln)]
    assert len(calls) == layers and all("custom-call(" in ln for ln in calls)
    made = [ln.strip()[:160] for ln in step
            if re.match(rf"\s*%\S+ = {re.escape(state)}", ln)
            and "get-tuple-element(%ssm_step" not in ln]
    assert not made, made
    assert sum(" reduce-window(" in ln for ln in step) == 1


@pytest.fixture(scope="module")
def xing4_programs(topo):
    """Both serve programs of the benchmark's Xing4.0 configuration
    (``benchmarks/tools/xing4_aot.py``: every layer, published widths, 8
    slots of 33,280 tokens), compiled once for the two cases below."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from benchmarks.harness import manifest
    from benchmarks.tools import xing4_aot

    prev = jax.config.jax_enable_compilation_cache
    try:
        cell = manifest.Cell("xing4.0-29b-a4b.serve-longdocs")
        programs, weights, pool, shapes, _ = \
            xing4_aot.compile_serve_programs(cell)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    return dict(programs), weights, pool, shapes


@pytest.mark.parametrize("program", ["paged decode", "paged chunk prefill"])
def test_xing4_serve_programs_fit_a_v5e_and_copy_no_pool(
        xing4_programs, program):
    """7 layers at the PUBLISHED widths (latent attention 32 heads of 128 +
    64 over a latent of 512, four residual streams, a dense layer of 9216
    and 64 experts of 1024 with a shared one), 8 slots of 33,280 tokens, a
    512-row chunk: the latent walk (absorbed for a decode, expanded for a
    chunk), the pool writer and the grouped matmuls are Mosaic calls (the
    Sinkhorn sweeps are XLA's); the pages of latents are donated and aliased; nothing
    shaped like the pool or like a slot's expanded keys is copied; all of it
    under 14.5 GiB."""
    import re

    programs, weights, pool, shapes = xing4_programs
    compiled = programs[program]
    text = compiled.as_text()
    kernel = ("latent_attention_decode" if program == "paged decode"
              else "latent_attention_chunk")
    assert text.count(f"%{kernel}") >= 7 and "%gmm" in text
    assert "%paged_attention" not in text
    assert text.count("%kv_pool_write") >= 7
    assert "%hc_sinkhorn" not in text
    assert shapes[0].shape == (4161, 64, 640)      # 1,280 bytes a token
    for s in shapes:
        shape = f"bf16[{','.join(map(str, s.shape))}]"
        copied = [ln.strip()[:120] for ln in text.splitlines()
                  if re.search(rf"= {re.escape(shape)}\S* (copy|transpose)\(",
                               ln) and "fused_computation" not in ln]
        assert not copied, f"{shape} is copied: {copied}"
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert weights + pool < total < 14.5 * 2 ** 30
    assert weights > 10.3 * 2 ** 30 and pool > 2.2 * 2 ** 30


# -- a sequence-parallel gather under its neighbour's matmul (tp = 4) ---------


def _matmuls_inside_async_collectives(text):
    """For every asynchronous collective of a compiled program's ENTRY
    computation (``async-collective-start``, ``collective-permute-start``,
    ``all-gather-start``), in its scheduled order: how many matmul fusions
    stand between the start and its done."""
    import re

    comps = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)",
                     text)
    called = {c.split(" ")[0]: c for c in comps if not c.startswith("ENTRY")}
    entry = [c for c in comps if c.startswith("ENTRY")][0].splitlines()
    order = [(m.group(1), ln) for ln in entry
             if (m := re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", ln))]
    at = {name: i for i, (name, _) in enumerate(order)}

    def is_matmul(ln):
        call = re.search(r"calls=(%[\w.\-]+)", ln)
        return (" fusion(" in ln and call is not None
                and "convolution(" in called.get(call.group(1), ""))
    found = {}
    for name, _ in order:
        m = re.match(r"%(async-collective|collective-permute|all-gather)"
                     r"-start(\.\d+)?$", name)
        done = m and f"%{m.group(1)}-done{m.group(2) or ''}"
        if done in at:
            found[name] = sum(is_matmul(ln)
                              for _, ln in order[at[name] + 1: at[done]])
    return found


def _abstract_layer(layer, mesh, x):
    from flax import linen as nn

    boxed = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        nn.unbox(boxed), nn.get_partition_spec(boxed))


@pytest.mark.parametrize("form", ["cut", "whole"])
def test_qkv_gather_rides_under_a_matmul_for_v5e(topo, monkeypatch, form):
    """Mistral-7B's q/k/v projection on tp = 4 with sequence parallel at the
    tp4 cell's shapes (batch 2 x 8192): left whole, the compiled forward
    holds no asynchronous collective at all — the gather stands alone before
    its matmuls; cut in pieces (``parallel/collective_matmul.py``), a piece's
    gather is started before its neighbour's matmuls and waited for after."""
    from neuronx_distributed_tpu.parallel import collective_matmul as cm
    from neuronx_distributed_tpu.parallel.mesh import SEQUENCE_AXES
    from neuronx_distributed_tpu.parallel.qkv import GQAQKVColumnParallelLinear

    if form == "whole":
        monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", 1 << 30)
    mesh = _mesh(topo, tp=4)
    layer = GQAQKVColumnParallelLinear(
        num_heads=NQ, num_kv_heads=NKV, head_dim=D, sequence_parallel=True)
    x = jax.ShapeDtypeStruct(
        (2, 8192, 4096), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, SEQUENCE_AXES, None)))
    params = _abstract_layer(layer, mesh, x)
    text = _compiled_text(layer.apply, params, x)
    over = _matmuls_inside_async_collectives(text)
    if form == "cut":
        assert any(n > 0 for n in over.values()), over
    else:
        assert not over, over


@pytest.mark.parametrize("program", ["decode", "chunk_s512"])
def test_retention_programs_compile_for_v5e_and_step_the_state_in_place(
        topo, program):
    """Brumby-14B's paged programs at its PUBLISHED widths and the cell's
    serving geometry (16 slots of 17,408 tokens, a 512-row chunk), cut to
    one layer: the decode steps 16 state rows of 36 MiB by row id in the
    Mosaic call ``retention_step`` (which takes the token's own q, k, v:
    the decode program holds no ``[.., 9216]`` array but the state), the
    chunk continues one in ``retention_chunk``; the state arrays are
    donated, aliased to their outputs and never copied — a second copy of
    the cell's 4.5 GiB of state would not fit the chip."""
    import functools
    import re

    from neuronx_distributed_tpu.kvcache.pool import LayerStates
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )
    from flax import linen as nn

    mesh = _mesh(topo)
    B, T, page, W = 16, 17408, 64, 512
    cfg = LlamaConfig(
        vocab_size=151936, hidden_size=5120, intermediate_size=17408,
        num_layers=1, num_heads=40, num_kv_heads=8, head_dim=128,
        max_seq_len=T, rope_theta=1e6, rms_eps=1e-6,
        mixer_types=("power-retention",), sequence_parallel=False,
        remat="none", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    module = LlamaForCausalLM(cfg)
    rep = NamedSharding(mesh, P())
    boxed = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                           jnp.zeros((1, page), jnp.int32))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        nn.unbox(boxed))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=16384, max_total_len=T,
                        kv_cache_dtype=jnp.bfloat16))
    layers = LayerStates.for_config(cfg, page, state_rows=B)
    assert layers.paged == 0 and layers.state_shape == (8, 128, 9216)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    caches = (tuple(sds((B,) + shape, jnp.dtype(dt))
                    for shape, dt in layers.state_arrays),)
    decode = program == "decode"
    rows, S = (B, 1) if decode else (1, W)
    fn = jax.jit(functools.partial(
        model._paged_step_fn, paged_kernel=True, update_valid=decode,
        last_only=True), donate_argnums=(4,))
    compiled = fn.lower(
        params, sds((rows, S)), sds((rows,)), sds((rows, T // page)), caches,
        sds((rows, T)), state_rows=sds((rows,)),
        **({} if decode else {"last_row": sds(())})).compile()
    text = compiled.as_text()
    assert ("%retention_step" if decode else "%retention_chunk") in text
    copied = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(r"= f32\[16,8,128,9216\]\S* (copy|transpose)\(",
                           ln)]
    assert not copied, f"the state array is copied: {copied}"
    if decode:
        # the token's phi(q) and phi(k) are formed inside the call, a column
        # at a time in VMEM: in no HBM buffer (PR 54)
        formed = [ln.strip()[:120] for ln in text.splitlines()
                  if re.search(r"f32\[16,8,(5,)?9216\]", ln)]
        assert not formed, f"phi is a value of the decode program: {formed}"
    memory = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    assert memory.alias_size_in_bytes >= held
    # ... and beside the one copy the program keeps well under a state row
    assert memory.temp_size_in_bytes < 8 * 128 * 9216 * 4 * 8


# -- Qwen3-Next: the delta rule's two calls, and heads of 256 in the pool -----


@pytest.mark.parametrize("call", ["gdn_chunk", "gdn_step"])
def test_the_delta_rule_compiles_for_v5e_on_the_state_where_it_lies(
        topo, call):
    """``ops.gated_delta`` at Qwen3-Next's shapes — 8 rows of ``[32, 128,
    128]`` float32, a 512-row chunk continuing ONE row in eight blocks
    written out as XLA, a decode over all eight in the Mosaic call
    ``gdn_step``: the state array donated, aliased to its output and never
    copied (a walk without its barrier copied the array twice a chunk:
    ``ops/ssm_scan.py``)."""
    import re

    from neuronx_distributed_tpu.ops import gated_delta as gd

    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    R, NH, Dh = 8, 32, 128
    if call == "gdn_chunk":
        fn = lambda st, q, k, v, g, b, rows: gd.gdn_chunk(  # noqa: E731
            q, k, v, g, b, None, jnp.zeros((1,), bool), st, rows)
        args = (sds((R, NH, Dh, Dh)), sds((1, 512, NH, Dh)),
                sds((1, 512, NH, Dh)), sds((1, 512, NH, Dh), jnp.bfloat16),
                sds((1, 512, NH)), sds((1, 512, NH)), sds((1,), jnp.int32))
    else:
        fn = lambda st, q, k, v, g, b, live: gd.gdn_step(  # noqa: E731
            st, q, k, v, g, b, live, None, None, kernel=True)
        args = (sds((R, NH, Dh, Dh)), sds((R, NH, Dh)), sds((R, NH, Dh)),
                sds((R, NH, Dh)), sds((R, NH)), sds((R, NH)),
                sds((R,), jnp.bool_))
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    mosaic = re.search(r"%gdn_\w+?[.\d]* = [^\n]*tpu_custom_call", text)
    assert bool(mosaic) == (call == "gdn_step")
    copied = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(rf"= f32\[{R},32,128,128\]\S* (copy|transpose)\(",
                           ln)]
    assert not copied, f"the state array is copied: {copied}"
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= R * NH * Dh * Dh * 4


@pytest.mark.parametrize("S", [1, 512], ids=["decode", "chunk_s512"])
def test_heads_of_256_are_written_and_walked_for_v5e(topo, S):
    """Qwen3-Next's attention geometry — 16 query heads over 2 kv heads of
    256 (a group of eight, a page row of two lane tiles), pages of 64, 520 a
    slot: the pool write and the walk lower and fit VMEM; a decode is one
    walk, a 512-row chunk (4,096 query rows a kv head) two."""
    from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows

    mesh = _mesh(topo)
    B = 8 if S == 1 else 1
    NQ_, NKV_, D_, page, num_pages, pp = 16, 2, 256, 64, 4161, 520

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    pages = sds((num_pages, NKV_, page, D_), jnp.bfloat16)

    def write_then_attend(q, new, pool, bt, off, start, phys, cell):
        pool = tuple(write_pool_rows(p, new, phys, cell, kernel=True)
                     for p in pool)
        return paged_attention(q, pool, bt, off, start), pool

    compiled = jax.jit(write_then_attend, donate_argnums=(2,)).lower(
        sds((B, S, NQ_, D_), jnp.bfloat16), sds((B, S, NKV_, D_), jnp.bfloat16),
        (pages, pages), sds((B, pp), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32), sds((B, S), jnp.int32),
        sds((B, S), jnp.int32)).compile()
    text = compiled.as_text()
    import re

    name = "paged_attention_decode" if S == 1 else "paged_attention_chunk"
    made = lambda call: len(re.findall(  # noqa: E731
        rf"%{call}[.\d]* = [^\n]*tpu_custom_call", text))
    assert made(name) == (1 if S == 1 else 2)
    assert made("kv_pool_write") == 2
    held = 2 * num_pages * NKV_ * page * D_ * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= held
