#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no subprocesses (a chip belongs to one process), no ``try``
around a phase: the first thing that is wrong raises, the traceback is the
report, the exit code is non-zero and no result line is printed.  With no
arguments it needs ONE TPU chip and runs, at Mistral-7B-v0.1 widths (hidden
4096, FFN 14336, 32 q / 8 kv heads, head_dim 128, vocab 32000, sliding
window 4096 — only depth is cut, and the cut is printed):

- **device**  — the platform must be ``tpu`` and its ``device_kind`` must be
  in the one peak table (``utils.profiling.DEVICE_SPECS``);
- **kernels** — the flash kernel (forward + backward, through
  ``ring_attention``; causal, and window 4096 at sequence 8192) against
  ``mha_reference``, and ``paged_attention`` (decode, verify, chunk; fp and
  int8 pages; window on; then the three serving cells' head layouts — 7, 4
  and 1 query heads a kv head — with two thirds of the slots parked and the
  rest left-padded, the step's K / V rows first written into the pools by
  ``ops.kv_pool_write``'s kernel and the fetched pools held to a numpy
  write, bit for bit) against ``paged_attention_reference`` — compiled,
  with the Mosaic call asserted in each program;
- **train**   — ``initialize_model_parallel`` → ``training_config`` →
  ``initialize_parallel_model`` → ``initialize_parallel_optimizer`` →
  ``make_train_step``, driven by ``trainer.fit()`` with flash attention,
  remat and the chunked loss head at sequence 8192;
- **serve**   — ``ParallelInferenceModel`` + paged ``ServingEngine`` with the
  kernel left at ``"auto"`` and chunked prefill on, answering staggered
  requests of 512–6000 prompt tokens; logits of the kernel path against the
  gather path, and of prefill-then-decode through the cache against a full
  forward of the same sequence;
- **hybrid**  — a thin model with a layer LIST (``mixer_types``: block-sparse
  softmax layers and lightning linear-attention layers, at MiniCPM-SALA's
  head geometry) through the same engine: requests finish, the state rows
  and the pages come back, no prefix is shared; logits of the kernel path
  (the chosen-table decode walk, the masked chunk walk) against the gather
  path;
- **ssm**     — a thin model of ONE-sublayer layers (``mixer_types`` /
  ``ffn_types``: Mamba-2 scans, attention without RoPE, sigmoid-routed relu2
  experts with a shared one and HALF of the experts held, at
  Nemotron-3-Nano's head and state geometry) through the same engine:
  requests finish, a repeated prompt repeats its tokens, the state rows come
  back; logits of the kernel path (a 16-head group's chunk walked in parts)
  against the gather path.

``--four-chips`` runs ONLY the path that exists across chips and what it is
compared with: a tp=4 mesh (sequence parallel on) over four real devices —
train steps, the pool write at the cells' head layouts with the kv heads
over tp (bit for bit against numpy) and paged requests through the
``shard_map``'d kernels — then a
dp=2 x tp=2 ZeRO-1 step, then the same seeded model on a one-device mesh in
the same process, whose step-0 loss and first-decode logits must agree.

``--rehearse`` runs the same control flow at a tiny size wherever it is (the
CPU, kernels interpreted): it finds wrong paths, arguments and shapes at no
chip time.  Its last line says ``"ok": false`` — a rehearsal is not a result.

The last stdout line of a real run, and nothing else on it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything else worth reading is on earlier lines.  No rate, MFU or latency
printed here is a metric: host-clock times of a handful of steps, as
information for whoever reads the log.
"""

import argparse
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# -- sizes --------------------------------------------------------------------

WINDOW = 4096

# Mistral-7B-v0.1 at its published widths (LlamaConfig.mistral_7b).  Depth:
# training keeps fp32 master params + fp32 Adam moments (12 B/param resident,
# fp32 grads transient): 218M params a layer + 262M for embedding and head.
# The chip's compiler (AOT, described v5e) puts the 2-layer step at batch 2,
# sequence 8192 at 14.2 GiB and the 3-layer step at batch 1 at 15.2 GiB of
# 16 — the latter leaves no room for anything else the process holds, so the
# smoke takes 2 layers x batch 2.  Serving holds bf16 weights: 16 layers =
# 7.5 GiB, about half the chip, and a page pool of 4.1 GiB (8 slots x 8192
# tokens and a little spare, 1 MiB a page over 16 layers) most of the rest
# — the gather-path comparison and the full forward need the remainder.
REAL = dict(
    preset="mistral_7b",
    heads=(32, 8), head_dim=128, window=WINDOW,
    flash_seq=2048, flash_window_seq=8192, flash_ref_heads=(8, 2),
    flash_tail=512,
    paged=dict(batch=8, page=16, pages_per_slot=512, num_pages=2048),
    # (q heads, kv heads, slots, pages a slot, window) of the three serving
    # cells' decode: Qwen2-7B chat, Mistral-7B docs, OLMoE-1B-7B backlog
    paged_cells=((28, 4, 32, 128, None), (32, 8, 8, 512, WINDOW),
                 (16, 16, 16, 64, None)),
    train=dict(layers=2, batch=2, seq=8192, steps=6, loss_chunk=512),
    serve=dict(layers=16, slots=8, context=6144, total=8192, page=16,
               chunk=512, new=64,
               prompts=(512, 1297, 2080, 2901, 3688, 4500, 5333, 6000),
               probe=(700, 4500, 6000), probe_decodes=4),
    four=dict(layers=2, batch=2, seq=8192, steps=3, loss_chunk=512,
              slots=4, context=2048, total=4096, page=16, chunk=512, new=16,
              prompts=(600, 1297, 2000), probe=(1297,), probe_decodes=2),
    # MiniCPM-SALA's mixers at their published head geometry, thin elsewhere;
    # dense_len low enough that 2300 and 3900 tokens take the sparse rule
    # Nemotron-3-Nano's mixers at their published geometry (Mamba-2 64 heads
    # x 64 in 8 groups, state 128; 32 q / 2 kv x 128), thin elsewhere
    ssm=dict(heads=(32, 2), head_dim=128, hidden=512, vocab=1024,
             ssm=dict(ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
                      ssm_state_size=128),
             experts=(16, 8, 4), expert_width=256, shared_width=512,
             slots=8, context=1536, total=2304, page=16, chunk=512, new=8,
             prompts=(300, 1100, 1530), probe_decodes=2),
    hybrid=dict(heads=(32, 2), head_dim=128, hidden=512, mlp=1024, vocab=1024,
                slots=4, context=4096, total=4608, page=64, chunk=512, new=8,
                prompts=(700, 2300, 3900), probe_decodes=2,
                sparse=dict(sparse_block_size=64, sparse_kernel_size=32,
                            sparse_kernel_stride=16, sparse_window_size=512,
                            sparse_topk=24, sparse_dense_len=2048)),
)

# the same control flow at sizes the CPU and the Pallas interpreter can carry
TINY = dict(
    preset="tiny", rehearsal=True,
    heads=(8, 4), head_dim=16, window=96,
    flash_seq=128, flash_window_seq=256, flash_ref_heads=(4, 2),
    flash_tail=64,
    paged=dict(batch=4, page=8, pages_per_slot=16, num_pages=40),
    paged_cells=((14, 2, 6, 16, None), (8, 2, 3, 16, 96), (4, 4, 6, 8, None)),
    train=dict(layers=2, batch=2, seq=128, steps=6, loss_chunk=64),
    serve=dict(layers=2, slots=4, context=64, total=96, page=8,
               chunk=16, new=4,
               prompts=(9, 20, 33, 41, 50, 64),
               probe=(12, 40, 64), probe_decodes=2),
    four=dict(layers=2, batch=2, seq=128, steps=3, loss_chunk=64,
              slots=4, context=64, total=96, page=8, chunk=16, new=4,
              prompts=(9, 33, 50), probe=(33,), probe_decodes=2),
    ssm=dict(heads=(4, 2), head_dim=16, hidden=64, vocab=256,
             ssm=dict(ssm_heads=8, ssm_head_dim=8, ssm_groups=2,
                      ssm_state_size=16, ssm_chunk_rows=4),
             experts=(8, 4, 3), expert_width=48, shared_width=96,
             slots=3, context=48, total=64, page=4, chunk=8, new=3,
             prompts=(7, 14, 45), probe_decodes=2),
    hybrid=dict(heads=(4, 2), head_dim=16, hidden=64, mlp=96, vocab=256,
                slots=3, context=48, total=64, page=4, chunk=8, new=3,
                prompts=(7, 14, 45), probe_decodes=2,
                sparse=dict(sparse_block_size=4, sparse_kernel_size=2,
                            sparse_kernel_stride=1, sparse_window_size=6,
                            sparse_topk=4, sparse_dense_len=16)),
)

# Tolerances.  Every comparison is max|a - b| <= tol * max|b| (an error
# relative to the reference's largest value: attention outputs and logits of
# random weights have many near-zero entries, where an element-wise relative
# error means nothing).
#
# KERNEL_TOL: bf16 operands into the MXU with fp32 accumulation against an
# fp32 reference at "highest" matmul precision.  bf16 keeps 8 significant
# bits (2^-8 = 0.4%); the probabilities are rounded to bf16 before the PV
# product and the output once more.  Measured on the v5e (PR 21): 0.2-0.6%
# for the flash kernel and its gradients, 0.4-0.8% for the paged kernel
# (int8 pages the most).  2% is the bound: computing in anything coarser
# than bf16, or dropping one block of the band, moves the error to tens of
# percent.
KERNEL_TOL = 2e-2
# LOGITS_TOL: two bf16 programs of the same model (kernel vs gather path;
# cache vs full forward; tp=4 vs one device) — same math, other summation
# order, through up to 16 layers of bf16 residual stream, logits stored in
# bf16.  Measured on the v5e: 0.6% everywhere, which is ONE bf16 unit in
# the last place of the largest logit (2^-5 at |x| ~ 5.2).  The bound is
# three such units.  (The rehearsal's tiny model has smaller logits, ~3,
# where a unit is 0.5% and the few-unit disagreements reach 1.4%: it checks
# control flow, and takes 5%.)
LOGITS_TOL = 2e-2
REHEARSAL_LOGITS_TOL = 5e-2
# LOSS_MESH_TOL: the same seeded model on a tp=4 or dp2 x tp2 mesh and on
# one device.  The loss is a mean over the batch's tokens of fp32 cross
# entropies (~10.9 at step 0), so the meshes may differ only by bf16 matmul
# summation order.
LOSS_MESH_TOL = 5e-3


def log(msg):
    print(msg, flush=True)


def check_close(name, a, b, tol):
    """max|a - b| <= tol * max|b|, in fp32 on the host."""
    import numpy as np

    a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if a32.shape != b32.shape:
        raise AssertionError(f"{name}: shape {a32.shape} vs {b32.shape}")
    if not (np.isfinite(a32).all() and np.isfinite(b32).all()):
        raise AssertionError(f"{name}: non-finite values")
    err = float(np.max(np.abs(a32 - b32)) / (np.max(np.abs(b32)) + 1e-12))
    log(f"  {name}: rel err {err:.4f} (tol {tol}, ref max "
        f"{float(np.max(np.abs(b32))):.3f})")
    if err > tol:
        raise AssertionError(f"{name}: rel err {err:.4f} > {tol}")


def compiled_with_kernel(fn, *args, on_tpu):
    """Compile ``fn`` for ``args`` and — on the chip — insist the Mosaic
    kernel is in the program: an interpreted or substituted kernel must not
    pass under the kernel's name."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if on_tpu and "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("no Mosaic tpu_custom_call in the compiled "
                             "program: the kernel was interpreted or replaced")
    return compiled


def paged_cell_cases(size, seed):
    """The serving cells' head layouts as the engine presents them, for a
    decode (S = 1) and a verify chunk (S = 5): two slots in three parked,
    the live ones behind a left pad, each holding pages of its own — every
    other page of the pool is NaN, so a walk or a write that strays off a
    slot's band cannot pass.  Yields ``(layout, S, win, q, new (k, v), clean
    pools, pools, table, offs, starts)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    D, page = size["head_dim"], size["paged"]["page"]
    rs = np.random.RandomState(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 5)
    for nq_c, nkv_c, B, PP, win in size["paged_cells"]:
        T = PP * page
        live = np.arange(B) % 3 == 0
        for S in (1, 5):
            offs = np.where(live, rs.randint(T // 2, T - S, size=B), T)
            starts = np.where(live, offs - rs.randint(1, T // 2, size=B), 0)
            table = rs.permutation(np.arange(1, B * PP + 1)).reshape(B, PP)
            held = np.zeros((B, PP), bool)
            for b in np.flatnonzero(live):
                lo = starts[b] if win is None else max(starts[b],
                                                       offs[b] - win + 1)
                held[b, lo // page:(offs[b] + S - 1) // page + 1] = True
            kk = jax.random.split(jax.random.fold_in(key, nq_c * 8 + S), 5)
            clean = tuple(
                jax.random.normal(k_, (B * PP + 1, nkv_c, page, D),
                                  jnp.bfloat16) for k_ in kk[:2])
            dead = np.setdiff1d(np.arange(B * PP + 1), table[held])
            pool = tuple(c.at[dead].set(jnp.nan) for c in clean)
            q = jax.random.normal(kk[2], (B, S, nq_c, D), jnp.bfloat16)
            new = tuple(jax.random.normal(k_, (B, S, nkv_c, D), jnp.bfloat16)
                        for k_ in kk[3:])
            layout = (f"{nq_c}q/{nkv_c}kv: B{B} ({int(live.sum())} live) "
                      f"page {page} T {T} window {win}")
            yield (layout, S, win, q, new, clean, pool,
                   np.where(held, table, 0), offs, starts)


def write_rows_checked(pool, new, table, offs, starts, on_tpu, place=None):
    """Commit a step's ``new`` (k, v) rows ``[B, S, NKV, D]`` to the pool
    pair through ``ops.kv_pool_write``'s kernel, addressed as
    ``models/llama.py`` addresses them (row ``s`` of slot ``b`` is cell
    ``offs[b] + s`` of its chain; a parked slot and a cell before its
    ``start`` write nothing), fetch the pools and hold them to a numpy
    write, bit for bit.  Returns the written pools."""
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows

    num_pages, _, page, _ = pool[0].shape
    S = new[0].shape[1]
    idx = offs[:, None] + np.arange(S)[None, :]
    keep = (idx < table.shape[1] * page) & (idx >= starts[:, None])
    phys = np.where(keep, np.take_along_axis(
        table, np.clip(idx // page, 0, table.shape[1] - 1), axis=1), num_pages)
    cells = (jnp.asarray(phys, jnp.int32), jnp.asarray(idx % page, jnp.int32))
    if place is not None:
        pool, new = place(pool, new)
    write = compiled_with_kernel(
        lambda pool, new, phys, in_off: tuple(
            write_pool_rows(c, x, phys, in_off, kernel=True)
            for c, x in zip(pool, new)),
        pool, new, *cells, on_tpu=on_tpu)
    got = write(pool, new, *cells)
    for name, g, c, x in zip("kv", got, pool, new):
        want, rows = np.array(c), np.asarray(x)
        for b, s in zip(*np.nonzero(keep)):
            want[phys[b, s], :, idx[b, s] % page] = rows[b, s]
        if not np.array_equal(np.asarray(g).view(np.uint16),
                              want.view(np.uint16)):
            raise AssertionError(
                f"pool write S={S}: the {name} pool differs from the numpy "
                "write")
    return got


def model_config(size, **overrides):
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    if size["preset"] == "mistral_7b":
        return LlamaConfig.mistral_7b(**overrides)
    nq, nkv = size["heads"]
    return LlamaConfig.tiny(num_heads=nq, num_kv_heads=nkv,
                            head_dim=size["head_dim"],
                            sliding_window=size["window"], **overrides)


# -- phase: device ------------------------------------------------------------


def phase_device(args, cache_dir):
    import importlib.metadata as md

    import jax

    from neuronx_distributed_tpu.data.loader import loader_backend
    from neuronx_distributed_tpu.utils.profiling import device_spec

    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    log(f"[device] {len(devices)} x {dev.device_kind} (platform "
        f"{dev.platform}); jax {jax.__version__}, jaxlib "
        f"{md.version('jaxlib')}, libtpu {md.version('libtpu')}")
    log(f"[device] compile cache: {cache_dir}; data loader: "
        f"{loader_backend()}")
    if len(devices) < need:
        raise RuntimeError(f"need {need} device(s), jax.devices() has "
                           f"{len(devices)}")
    if args.rehearse:
        log("[device] REHEARSAL: tiny sizes, any platform, no result")
        return devices
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no accelerator: jax.devices()[0].platform is {dev.platform!r}; "
            "chip_smoke.py runs on a TPU (use --rehearse for a CPU rehearsal)")
    spec = device_spec(dev)  # raises on a kind the peak table does not hold
    log(f"[device] published peaks ({spec.kind}): {spec.peak_flops / 1e12:.0f} "
        f"TFLOP/s bf16, {spec.hbm_bytes_per_s / 1e9:.0f} GB/s HBM")
    return devices


# -- phase: kernels -----------------------------------------------------------


def phase_kernels(size, seed, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kvcache.quant import quantize_page
    from neuronx_distributed_tpu.ops.flash_attention import mha_reference
    from neuronx_distributed_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_reference,
    )
    from neuronx_distributed_tpu.ops.ring_attention import ring_attention

    D, window = size["head_dim"], size["window"]

    def qkv(key, batch, seq, nq, nkv):
        ks = jax.random.split(key, 4)
        shape = lambda h: (batch, seq, h, D)  # noqa: E731 — model layout
        return (jax.random.normal(ks[0], shape(nq), jnp.bfloat16),
                jax.random.normal(ks[1], shape(nkv), jnp.bfloat16),
                jax.random.normal(ks[2], shape(nkv), jnp.bfloat16),
                jax.random.normal(ks[3], shape(nq), jnp.bfloat16))

    def reference(q, k, v, win):
        # fp32 at "highest": on a TPU an fp32 matmul otherwise runs in
        # lower precision, and this is the side that has to be right
        with jax.default_matmul_precision("highest"):
            f32 = lambda x: x.transpose(0, 2, 1, 3).astype(jnp.float32)  # noqa: E731
            return mha_reference(f32(q), f32(k), f32(v), causal=True,
                                 window=win).transpose(0, 2, 1, 3)

    def flash_fwd_bwd(name, key, batch, seq, nq, nkv, win):
        q, k, v, do = qkv(key, batch, seq, nq, nkv)

        # the loss is a probe for the backward pass only: a sum of signed
        # terms is too ill-conditioned to compare, the output is compared.
        # ``do`` is an ARGUMENT: closed over, it would be baked into the
        # program as a constant — tens of MB per compile-cache entry, enough
        # to thrash a size-capped cache into zero hits
        def loss(q, k, v, do):
            o = ring_attention(q, k, v, causal=True, window=win)
            return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

        def loss_ref(q, k, v, do):
            o = reference(q, k, v, win)
            return jnp.sum(o * do.astype(jnp.float32)), o

        grad = compiled_with_kernel(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True),
            q, k, v, do, on_tpu=on_tpu)
        (_, o), g = grad(q, k, v, do)
        (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
            loss_ref, argnums=(0, 1, 2), has_aux=True))(q, k, v, do)
        log(f" flash fwd+bwd {name}: B{batch} S{seq} {nq}q/{nkv}kv D{D} "
            f"window {win}")
        for nm, a, b in zip(("out", "dq", "dk", "dv"), (o, *g), (o_ref, *g_ref)):
            check_close(nm, a, b, KERNEL_TOL)

    nq, nkv = size["heads"]
    rq, rkv = size["flash_ref_heads"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    flash_fwd_bwd("causal", keys[0], 2, size["flash_seq"], nq, nkv, None)
    # the dense oracle of the long sequence holds [H, S, S] fp32 scores (and
    # their cotangents): at S 8192 that fits for a quarter of the heads, at
    # the same per-head geometry and the same 4:1 grouping ...
    flash_fwd_bwd("window, S > window", keys[1], 1, size["flash_window_seq"],
                  rq, rkv, window)
    # ... and at ALL heads for the last rows of the sequence, forward only:
    # the rows whose band starts past position 0, i.e. the grid-skip path
    S, tail = size["flash_window_seq"], size["flash_tail"]
    q, k, v, _ = qkv(keys[2], 1, S, nq, nkv)
    fwd = compiled_with_kernel(
        lambda q, k, v: ring_attention(q, k, v, causal=True, window=window),
        q, k, v, on_tpu=on_tpu)
    log(f" flash fwd window, all heads: S{S} {nq}q/{nkv}kv, last {tail} rows")
    check_close("out", fwd(q, k, v)[:, -tail:],
                jax.jit(lambda q, k, v: reference(q[:, -tail:], k, v, window))(
                    q, k, v), KERNEL_TOL)

    # paged attention: one pool, slots at offsets on both sides of the
    # window, left-padded starts, one parked slot
    p = size["paged"]
    B, page, PP, NP_ = p["batch"], p["page"], p["pages_per_slot"], p["num_pages"]
    T = PP * page
    rs = np.random.RandomState(seed)
    pool_fp = tuple(
        jax.random.normal(kk, (NP_, nkv, page, D), jnp.bfloat16)
        for kk in jax.random.split(keys[3], 2))
    qk, ks_, kz = quantize_page(pool_fp[0])
    qv, vs_, vz = quantize_page(pool_fp[1])
    pools = {"fp": pool_fp, "int8": (qk, qv, ks_, kz, vs_, vz)}
    table = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    for S in (1, 5, 64 if on_tpu else 16):
        hi = T - S
        offs = np.linspace(page + 3, hi, B).astype(np.int32)
        offs[1] = min(window - 1, hi)      # the band's first full row
        offs[2] = min(window, hi)          # ... and the first clipped one
        offs[-1] = T                       # parked: rows come back zeros
        starts = np.zeros((B,), np.int32)
        starts[0] = page // 2              # left-padded prompts
        starts[3] = min(offs[3] // 2, window // 2)
        off, start = jnp.asarray(offs), jnp.asarray(starts)
        q = jax.random.normal(jax.random.fold_in(keys[4], S),
                              (B, S, nq, D), jnp.bfloat16)
        for layout, pool in pools.items():
            kern = compiled_with_kernel(
                lambda q, pool, bt, off, start: paged_attention(
                    q, pool, bt, off, start, window=window),
                q, pool, table, off, start, on_tpu=on_tpu)
            out = kern(q, pool, table, off, start)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(
                    lambda q, pool, bt, off, start: paged_attention_reference(
                        q, pool, bt, off, start, window=window)
                )(q, pool, table, off, start)
            log(f" paged S={S} {layout}: B{B} {nq}q/{nkv}kv D{D} page {page} "
                f"T {T} window {window}")
            check_close("out", out, ref, KERNEL_TOL)
            if np.any(np.asarray(out[-1], np.float32) != 0.0):
                raise AssertionError("parked slot rows are not exact zeros")

    # the serving cells' head layouts: the step's rows are written into the
    # pool (and held to a numpy write) before the kernel reads them
    for layout, S, win, q, new, clean, pool, table, offs, starts in \
            paged_cell_cases(size, seed):
        pool = write_rows_checked(pool, new, table, offs, starts, on_tpu)
        clean = write_rows_checked(clean, new, table, offs, starts, on_tpu)
        args = (jnp.asarray(table, jnp.int32), jnp.asarray(offs, jnp.int32),
                jnp.asarray(starts, jnp.int32))
        kern = compiled_with_kernel(
            lambda q, pool, bt, off, start, win=win: paged_attention(
                q, pool, bt, off, start, window=win),
            q, pool, *args, on_tpu=on_tpu)
        out = kern(q, pool, *args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(
                lambda q, pool, bt, off, start, win=win:
                paged_attention_reference(q, pool, bt, off, start,
                                          window=win))(q, clean, *args)
        log(f" paged S={S} cell layout {layout}: pools written bit for bit")
        check_close("out", out, ref, KERNEL_TOL)
        if np.any(np.asarray(out, np.float32)[offs >= table.shape[1] * page]
                  != 0.0):
            raise AssertionError("parked slot rows are not exact zeros")


# -- phase: train -------------------------------------------------------------


def run_training(size_train, size, seed, mesh_kw, devices, on_tpu, steps,
                 zero1=True, sequence_parallel=False, expect_collectives=()):
    """initialize_model_parallel → ... → fit() for ``steps`` steps on ONE
    seeded batch; returns the per-step losses.  Leaves the mesh installed."""
    import jax
    import jax.numpy as jnp

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.obs import Observability
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        fit,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )

    t = size_train
    nxd.initialize_model_parallel(devices=devices, **mesh_kw)
    config = nxd.training_config(
        learning_rate=3e-4, zero_one_enabled=zero1,
        compute_dtype="bfloat16", param_dtype="float32", seed=seed, **mesh_kw)
    cfg = model_config(
        size, num_layers=t["layers"], max_seq_len=t["seq"],
        attention_impl="flash", remat="selective",
        sequence_parallel=sequence_parallel)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, t["seq"]), jnp.int32),), seed=seed)
    opt = initialize_parallel_optimizer(config, model)
    log(f" model: {cfg.num_layers} layer(s) of hidden {cfg.hidden_size} / ffn "
        f"{cfg.intermediate_size} / {cfg.num_heads}q {cfg.num_kv_heads}kv x "
        f"{cfg.head_dim_} / vocab {cfg.vocab_size} / window "
        f"{cfg.sliding_window}: {model.num_parameters() / 1e6:.0f}M params, "
        f"mesh {dict(model.mesh.shape)}")
    stats = [d.memory_stats() for d in model.mesh.devices.flat]
    if all(s and "bytes_in_use" in s for s in stats):
        used = [s["bytes_in_use"] for s in stats]
        log(" bytes_in_use per device after placement: "
            + ", ".join(f"{u / 2**30:.2f} GiB" for u in used))
        if max(used) > 2 * (sum(used) / len(used)):
            raise AssertionError(
                "one device holds more than twice the mean: the model was "
                f"not born sharded ({used})")
    loss_fn = make_causal_lm_loss_sum(chunk_size=t["loss_chunk"])
    bspec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1),
                             (t["batch"], t["seq"]), 0, cfg.vocab_size)
    batch = {"ids": ids, "labels": jnp.roll(ids, -1, axis=1)}

    # the program fit() is about to run, asked for its text: the Mosaic call
    # must be in it (an interpreted flash kernel cannot pass), and so must
    # the collectives the mesh implies
    step = make_train_step(config, model, opt, loss_fn, batch_spec=bspec)
    text = step.lower(model.params, opt.state, batch, None).compile().as_text()
    if on_tpu and "tpu_custom_call" not in text:
        raise AssertionError("train step compiled without the Mosaic kernel")
    for op in expect_collectives:
        # what the TPU compiler emits; the CPU backend of a rehearsal spells
        # a reduce-scatter as all-reduce + slice
        if on_tpu and op not in text:
            raise AssertionError(f"train step on mesh {mesh_kw} has no {op}")
    if expect_collectives:
        log(" collectives in the compiled step: " + ", ".join(
            f"{op} x{text.count(op)}" for op in expect_collectives))
    del step, text

    out_dir = os.path.join("chiprun_out", "chip_smoke",
                           "train_" + "_".join(f"{k[:2]}{v}" for k, v in
                                               sorted(mesh_kw.items())))
    obs = Observability(out_dir, ledgers=True)
    losses, stamps = [], []

    def on_step(step_i, m):
        losses.append(float(m["loss"]))
        stamps.append(time.perf_counter())

    fit(config, model, opt, lambda step_i: batch, steps=steps,
        loss_fn=loss_fn, batch_spec=bspec, obs=obs, on_step=on_step,
        log_every=1)
    obs.close()
    led = obs.compile_ledger
    jit_rows = [r for r in led.rows if r["event"] == "compile"
                and r["family"] == "train_step" and r["kind"] == "jit"]
    if len(jit_rows) != 1 or led.storms:
        raise AssertionError(
            f"expected exactly one compile of the train step, ledger has "
            f"{len(jit_rows)} jit row(s) and {led.storms} storm(s)")
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"losses not finite / incomplete: {losses}")
    if steps > 1 and not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    warm = [b - a for a, b in zip(stamps[1:], stamps[2:])]
    peak = (model.mesh.devices.flat[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    log(f" losses: {', '.join(f'{x:.4f}' for x in losses)}")
    log(f" info: {t['batch'] * t['seq']} tokens/step; step time after "
        f"warm-up {min(warm) if warm else float('nan'):.3f} s (host clock, "
        f"not a metric); peak_bytes_in_use "
        + (f"{peak / 2**30:.2f} GiB" if peak else "not reported"))
    return losses


def phase_train(size, seed, devices, on_tpu):
    from neuronx_distributed_tpu.parallel.mesh import destroy_model_parallel

    t = size["train"]
    log(f"[train] depth cut to {t['layers']} layers; batch {t['batch']}, "
        f"sequence {t['seq']}, {t['steps']} steps; flash attention, "
        f"selective remat, chunked loss head ({t['loss_chunk']})")
    run_training(t, size, seed, dict(tensor_parallel_size=1), devices[:1],
                 on_tpu, t["steps"])
    destroy_model_parallel()
    gc.collect()  # drop the phase's device arrays


# -- phase: serve -------------------------------------------------------------


def build_server(s, size, seed, ledger=None):
    """bf16 weights born sharded over the live mesh + the compiled serving
    wrapper, at serving shapes ``s``."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM
    from neuronx_distributed_tpu.parallel.layers import init_sharded_params
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    cfg = model_config(
        size, num_layers=s["layers"], max_seq_len=s["total"],
        sequence_parallel=False, remat="none",
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    module = LlamaForCausalLM(cfg)
    params, _ = init_sharded_params(
        module, jax.random.PRNGKey(seed),
        jnp.zeros((1, s["page"]), jnp.int32))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=s["slots"], context_len=s["context"],
                        max_total_len=s["total"],
                        kv_cache_dtype=jnp.bfloat16),
        compile_ledger=ledger)
    return cfg, module, params, model


def serve_requests(s, cfg, model, ledger, seed, on_tpu):
    """Staggered requests through a paged ServingEngine at its defaults
    (paged_kernel "auto", chunked prefill on)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.serving import Request, ServingEngine

    pages_per_slot = s["total"] // s["page"]
    num_pages = s["slots"] * pages_per_slot + pages_per_slot // 8 + 1
    engine = ServingEngine(
        model, page_size=s["page"], num_pages=num_pages,
        prefill_chunk_tokens=s["chunk"], compile_ledger=ledger)
    if on_tpu and not engine._paged_kernel:
        raise AssertionError(
            "paged_kernel='auto' resolved to the gather path on a TPU")
    rs = np.random.RandomState(seed)

    def request(rid, length):
        return Request(request_id=rid, max_new_tokens=s["new"],
                       prompt_ids=rs.randint(1, cfg.vocab_size,
                                             size=length).tolist())

    # warm-up: one request whose prompt takes a whole and a ragged chunk,
    # decoded to its end — every program the engine runs is compiled by it
    engine.submit(request(10_000, s["chunk"] + s["chunk"] // 3))
    done = engine.run_until_complete(max_steps=10 * s["new"] + 100)
    if [o.state for o in done] != ["finished"]:
        raise AssertionError(f"warm-up request did not finish: {done}")
    engine.declare_warmup_done()
    mark = ledger.mark()

    outputs, step, nxt = {}, 0, 0
    prompts = s["prompts"]
    max_steps = 4 * (3 * len(prompts) + s["new"] + 100
                     + sum(-(-L // s["chunk"]) for L in prompts))
    t0 = time.perf_counter()
    while nxt < len(prompts) or engine.has_work:
        if nxt < len(prompts) and step % 3 == 0:
            # arrivals every third step: prefill chunks of the newcomers
            # share steps with the decodes of those already admitted
            engine.submit(request(nxt, prompts[nxt]))
            nxt += 1
        for o in engine.step():
            outputs[o.request_id] = o
        step += 1
        if step > max_steps:
            raise RuntimeError(f"engine did not drain in {step} steps")
    wall = time.perf_counter() - t0
    for rid, length in enumerate(prompts):
        o = outputs[rid]
        if o.state != "finished" or len(o.token_ids) != s["new"]:
            raise AssertionError(
                f"request {rid} (prompt {length}): state {o.state}, "
                f"{len(o.token_ids)} of {s['new']} tokens")
    engine._kv.assert_invariants()
    if ledger.compiles_since(mark):
        raise AssertionError(
            f"{ledger.compiles_since(mark)} compile(s) after warm-up: "
            + str([(r["family"], r["key"]) for r in ledger.rows[mark:]
                   if r["event"] == "compile"]))
    snap = engine.registry.snapshot()
    if on_tpu and snap.get("kvcache/gather_bytes_total", 0):
        raise AssertionError("the kernel engine gathered pages")
    log(f" {len(prompts)} requests, prompts {list(prompts)} (+{s['new']} "
        f"new each) finished in {step} engine steps, "
        f"{int(snap['serving/prefill_chunks_total'])} prefill chunks; "
        f"0 compiles after warm-up; invariants clean; info: {wall:.1f} s "
        f"wall (host clock, not a metric)")

    # the paged decode program the engine ran, asked for its text
    programs = model._serving_cache._d
    keys = [k for k in programs
            if isinstance(k, tuple) and k[0] == "decode_pages"]
    if len(keys) != 1:
        raise AssertionError(f"expected one decode_pages program, have {keys}")
    B, T = s["slots"], s["total"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = programs[keys[0]].lower(
        model.params, i32(B, 1), i32(B), i32(B, pages_per_slot),
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding), engine.caches),
        i32(B, T)).compile().as_text()
    if on_tpu and "tpu_custom_call" not in text:
        raise AssertionError(
            f"paged decode program {keys[0]} has no Mosaic kernel")
    log(f" paged decode program {keys[0]}: "
        + (f"{text.count('tpu_custom_call')} Mosaic call(s)" if on_tpu
           else "not on a TPU, kernel not expected (rehearsal)"))
    engine.close()


class CacheProbe:
    """Prefill-then-decode of a few hand-laid-out slots through the model's
    paged phase programs (the ones the engine dispatches), teacher-forced on
    seeded tokens so both paths see identical inputs, returning logits."""

    def __init__(self, s, cfg, model, seed):
        import numpy as np

        self.s, self.cfg, self.model = s, cfg, model
        self.page, self.C, self.T = s["page"], s["context"], s["total"]
        self.B = s["slots"]
        self.PP = self.T // self.page
        rs = np.random.RandomState(seed + 7)
        self.seqs = [rs.randint(1, cfg.vocab_size,
                                size=L + s["probe_decodes"]).astype(np.int32)
                     for L in s["probe"]]
        # left-padded rows, as the engine lays them out: the prompt sits in
        # cache positions [C - L, C), decode tokens from C on; padding pages
        # ride the NULL page 0
        self.tables = np.zeros((self.B, self.PP), np.int32)
        self.valid = np.zeros((self.B, self.T), np.int32)
        nxt = 1
        for b, L in enumerate(s["probe"]):
            first = (self.C - L) // self.page
            last = (self.C + s["probe_decodes"] - 1) // self.page
            for lp in range(first, last + 1):
                self.tables[b, lp] = nxt
                nxt += 1
            self.valid[b, self.C - L:self.C] = 1
        self.num_pages = nxt

    def run(self, paged_kernel):
        import jax.numpy as jnp
        import numpy as np

        s, m = self.s, self.model
        caches = m.make_page_pool(self.num_pages, self.page).caches
        W = s["chunk"]
        prefill_logits = []
        for b, L in enumerate(s["probe"]):
            row = np.zeros((self.C,), np.int32)
            row[self.C - L:] = self.seqs[b][:L]
            off = (self.C - L) // self.page * self.page
            logits = None
            while off < self.C:
                width = min(W, self.C - off)
                ids = np.zeros((1, W), np.int32)
                ids[0, :width] = row[off:off + width]
                logits, caches = m.prefill_chunk_pages(
                    jnp.asarray(ids), off, self.tables[b][None, :], caches,
                    self.valid[b][None, :], paged_kernel=paged_kernel,
                    last_row=width - 1)
                off += width
            prefill_logits.append(np.asarray(logits[0], np.float32))
        n = len(s["probe"])
        valid = jnp.asarray(self.valid)
        decode_logits = []
        for j in range(s["probe_decodes"]):
            tok = np.zeros((self.B, 1), np.int32)
            offs = np.full((self.B,), self.T, np.int32)  # parked
            for b, L in enumerate(s["probe"]):
                tok[b, 0] = self.seqs[b][L + j]
                offs[b] = self.C + j
            logits, caches, valid = m.decode_pages(
                jnp.asarray(tok), offs, self.tables, caches, valid,
                paged_kernel=paged_kernel)
            decode_logits.append(np.asarray(logits[:n], np.float32))
        del caches
        return prefill_logits, decode_logits


def logits_checks(s, cfg, module, params, model, seed, rehearsal):
    import jax
    import jax.numpy as jnp
    import numpy as np

    logits_tol = REHEARSAL_LOGITS_TOL if rehearsal else LOGITS_TOL
    probe = CacheProbe(s, cfg, model, seed)
    log(f" logits probe: prompts {list(s['probe'])} + "
        f"{s['probe_decodes']} decodes, chunks of {s['chunk']}, "
        f"{probe.num_pages} pages")
    pre_k, dec_k = probe.run(paged_kernel=True)
    gc.collect()  # drop the phase's device arrays
    pre_g, dec_g = probe.run(paged_kernel=False)
    gc.collect()  # drop the phase's device arrays
    for b, L in enumerate(s["probe"]):
        check_close(f"kernel vs gather, prefill logits (prompt {L})",
                    pre_k[b], pre_g[b], logits_tol)
    for j, (a, b_) in enumerate(zip(dec_k, dec_g)):
        check_close(f"kernel vs gather, decode step {j} logits", a, b_,
                    logits_tol)
    # the cache against no cache: one request's prefill-then-decode logits
    # against a full forward of the same sequence (dense attention over the
    # unpadded tokens — no pages, no cache, no kernel)
    b = len(s["probe"]) // 2
    L = s["probe"][b]
    full = jax.jit(module.apply)(params, jnp.asarray(probe.seqs[b][None, :]))
    full = np.asarray(full[0], np.float32)
    check_close(f"cache vs full forward, prefill logits (prompt {L})",
                pre_k[b], full[L - 1], logits_tol)
    for j in range(s["probe_decodes"]):
        check_close(f"cache vs full forward, decode step {j} logits",
                    dec_k[j][b], full[L + j], logits_tol)
    return pre_k, dec_k


def phase_serve(size, seed, devices, on_tpu):
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger
    from neuronx_distributed_tpu.parallel.mesh import destroy_model_parallel

    s = size["serve"]
    log(f"[serve] depth cut to {s['layers']} layers (bf16 weights); "
        f"{s['slots']} slots, context {s['context']}, max_total_len "
        f"{s['total']}, page {s['page']}, prefill chunks of {s['chunk']}")
    nxd.initialize_model_parallel(tensor_parallel_size=1, devices=devices[:1])
    ledger = CompileLedger()
    cfg, module, params, model = build_server(s, size, seed, ledger)
    serve_requests(s, cfg, model, ledger, seed, on_tpu)
    model.compile_ledger = None  # the probe's own programs are not storms
    gc.collect()  # drop the phase's device arrays
    logits_checks(s, cfg, module, params, model, seed,
                  size.get("rehearsal", False))
    destroy_model_parallel()


# -- phase: hybrid serve ------------------------------------------------------


def phase_serve_hybrid(size, seed, devices, on_tpu, kind="hybrid"):
    """A layer list (block-sparse softmax + lightning linear attention)
    through the paged engine, then kernel path against gather path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from neuronx_distributed_tpu.parallel.layers import init_sharded_params
    from neuronx_distributed_tpu.parallel.mesh import destroy_model_parallel
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    h = size[kind]
    nxd.initialize_model_parallel(tensor_parallel_size=1, devices=devices[:1])
    dtype = jnp.float32 if size.get("rehearsal") else jnp.bfloat16
    shared = dict(
        vocab_size=h["vocab"], hidden_size=h["hidden"],
        num_heads=h["heads"][0], num_kv_heads=h["heads"][1],
        head_dim=h["head_dim"], max_seq_len=h["total"],
        sequence_parallel=False, remat="none", dtype=dtype, param_dtype=dtype)
    if kind == "ssm":
        pattern = "MEM*E"
        mixers = tuple({"M": "mamba2", "E": "none", "*": "attention"}[c]
                       for c in pattern)
        routed, held, top = h["experts"]
        cfg = LlamaConfig(
            **shared, intermediate_size=h["expert_width"],
            num_layers=len(pattern), rms_eps=1e-5, attn_rope=False,
            mixer_types=mixers, ffn_types=tuple(
                {"M": "none", "E": "moe", "*": "none"}[c] for c in pattern),
            num_experts=routed, moe_top_k=top, moe_dispatch="dropless",
            moe_router_scores="sigmoid", moe_router_bias=True,
            moe_route_scale=2.5, mlp_activation="relu2",
            moe_shared_intermediate_size=h["shared_width"],
            moe_experts_held=(0, held), **h["ssm"])
    else:
        mixers = ("minicpm4", "lightning-attn", "lightning-attn", "minicpm4")
        cfg = LlamaConfig(
            **shared, intermediate_size=h["mlp"], num_layers=len(mixers),
            rms_eps=1e-6, mixer_types=mixers, embed_scale=12.0,
            residual_scale=1.4 / 32 ** 0.5, logit_scale=1.0 / 16,
            lightning_heads=h["heads"][0], lightning_head_dim=h["head_dim"],
            **h["sparse"])
    log(f"[{kind}] layers {mixers}, {h['heads'][0]} q / {h['heads'][1]} kv x "
        f"{h['head_dim']}; {h['slots']} slots, context {h['context']}, page "
        f"{h['page']}, chunks of {h['chunk']}")
    module = LlamaForCausalLM(cfg)
    params, _ = init_sharded_params(
        module, jax.random.PRNGKey(seed), jnp.zeros((1, h["page"]), jnp.int32))
    B, C, T, page, W, nd = (h["slots"], h["context"], h["total"], h["page"],
                            h["chunk"], h["probe_decodes"])
    icfg = InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                           kv_cache_dtype=dtype)
    rs = np.random.RandomState(seed + 5)
    lens = h["prompts"]
    seqs = [rs.randint(1, h["vocab"], size=L + nd).astype(np.int32)
            for L in lens]

    model = ParallelInferenceModel(module, params, icfg)
    engine = ServingEngine(model, page_size=page, prefill_chunk_tokens=W)
    if on_tpu and not engine._paged_kernel:
        raise AssertionError("paged_kernel='auto' took the gather path on a TPU")
    if engine._kv.index is not None:
        raise AssertionError("prefix sharing is on for a model with state rows")
    for rid, seq in enumerate(seqs + seqs[:1]):   # the first prompt twice
        engine.submit(Request(request_id=rid, prompt_ids=seq[:-nd].tolist(),
                              max_new_tokens=h["new"]))
    outs = {o.request_id: o for o in engine.run_until_complete(max_steps=4000)}
    engine._kv.assert_invariants()
    snap = engine.registry.snapshot()
    if sorted(outs) != list(range(len(lens) + 1)) or any(
            o.state != "finished" or len(o.token_ids) != h["new"]
            for o in outs.values()):
        raise AssertionError(f"{kind} requests did not all finish: {outs}")
    if tuple(outs[0].token_ids) != tuple(outs[len(lens)].token_ids):
        raise AssertionError("a repeated prompt gave other tokens")
    if snap["kvcache/prefix_hits_total"] or snap["kvcache/state_rows_in_use"] \
            or engine._kv.alloc.in_use:
        raise AssertionError(f"state not returned: {snap}")
    log(f"  {len(outs)} requests finished; " + (
        f"state rows stepped / skipped "
        f"{snap['serving/ssm_state_rows_stepped_total']:.0f} / "
        f"{snap['serving/ssm_state_rows_skipped_total']:.0f}, assignments "
        f"held / made {snap['moe/assignments_held_total']:.0f} / "
        f"{snap['moe/assignments_total']:.0f}" if kind == "ssm" else
        f"blocks chosen / visible "
        f"{snap['serving/sparse_blocks_selected_total']:.0f} / "
        f"{snap['serving/sparse_blocks_visible_total']:.0f}, dense queries "
        f"{snap['serving/sparse_dense_queries_total']:.0f}"))
    engine.close()

    PP = T // page
    tables = np.zeros((B, PP), np.int32)
    valid = np.zeros((B, T), np.int32)
    nxt = 1
    for b, L in enumerate(lens):
        for lp in range((C - L) // page, (C + nd - 1) // page + 1):
            tables[b, lp] = nxt
            nxt += 1
        valid[b, C - L:C] = 1

    def probe(kernel):
        m = ParallelInferenceModel(module, params, icfg, paged_kernel=kernel)
        caches = m.make_page_pool(nxt + 1, page).caches
        got = {}
        for b, L in enumerate(lens):
            row = np.zeros((C,), np.int32)
            row[C - L:] = seqs[b][:L]
            off = (C - L) // page * page
            while off < C:
                width = min(W, C - off)
                ids = np.zeros((1, W), np.int32)
                ids[0, :width] = row[off:off + width]
                logits, caches = m.prefill_chunk_pages(
                    jnp.asarray(ids), off, tables[b][None, :], caches,
                    valid[b][None, :], last_row=width - 1, state_row=b)
                off += width
            got[(b, 0)] = np.asarray(logits[0], np.float32)
        dvalid = jnp.asarray(valid)
        for j in range(nd):
            tok = np.zeros((B, 1), np.int32)
            offs = np.full((B,), T, np.int32)
            for b, L in enumerate(lens):
                tok[b, 0], offs[b] = seqs[b][L + j], C + j
            logits, caches, dvalid = m.decode_pages(
                jnp.asarray(tok), offs, tables, caches, dvalid)
            for b in range(len(lens)):
                got[(b, j + 1)] = np.asarray(logits[b], np.float32)
        return got

    kern, gath = probe(True), probe(False)
    tol = REHEARSAL_LOGITS_TOL if size.get("rehearsal") else LOGITS_TOL
    for b, L in enumerate(lens):
        for j in range(nd + 1):
            check_close(f"{kind} prompt {L}, "
                        + ("prefill" if j == 0 else f"decode step {j - 1}")
                        + " logits, kernels vs gather",
                        kern[(b, j)], gath[(b, j)], tol)
    destroy_model_parallel()


# -- phase: four chips --------------------------------------------------------


def phase_four_chips(size, seed, devices, on_tpu):
    import jax

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger
    from neuronx_distributed_tpu.parallel.mesh import destroy_model_parallel

    f = size["four"]
    four = devices[:4]
    log(f"[four-chips] depth cut to {f['layers']} layers for training and "
        f"serving; batch {f['batch']}, sequence {f['seq']}")

    log("[four-chips] train, tp=4 with sequence parallel")
    loss_tp4 = run_training(
        f, size, seed, dict(tensor_parallel_size=4), four, on_tpu,
        f["steps"], sequence_parallel=True,
        expect_collectives=("all-gather", "reduce-scatter"))
    destroy_model_parallel()
    gc.collect()  # drop the phase's device arrays

    log("[four-chips] train, dp=2 x tp=2 with ZeRO-1 and sequence parallel")
    loss_2x2 = run_training(
        f, size, seed, dict(tensor_parallel_size=2), four, on_tpu, 2,
        zero1=True, sequence_parallel=True,
        expect_collectives=("all-gather", "reduce-scatter"))
    destroy_model_parallel()
    gc.collect()  # drop the phase's device arrays

    log("[four-chips] serve, tp=4: paged requests through the shard_map'd "
        "kernel")
    nxd.initialize_model_parallel(tensor_parallel_size=4, devices=four)

    def over_heads(pool, new):
        # the pool's kv heads over tp where tp divides them (kvcache.pool)
        from neuronx_distributed_tpu.parallel.mesh import named_sharding

        tp = "tp" if pool[0].shape[1] % 4 == 0 else None
        return (jax.device_put(pool, named_sharding(None, tp, None, None)),
                jax.device_put(new, named_sharding(None, None, tp, None)))

    for layout, S, _, _, new, _, pool, table, offs, starts in \
            paged_cell_cases(size, seed):
        write_rows_checked(pool, new, table, offs, starts, on_tpu,
                           place=over_heads)
        log(f" pool write S={S} cell layout {layout}: bit for bit at tp=4")
    ledger = CompileLedger()
    cfg, module, params, model = build_server(f, size, seed, ledger)
    serve_requests(f, cfg, model, ledger, seed, on_tpu)
    model.compile_ledger = None
    rehearsal = size.get("rehearsal", False)
    pre4, dec4 = logits_checks(f, cfg, module, params, model, seed, rehearsal)
    del cfg, module, params, model
    destroy_model_parallel()
    gc.collect()  # drop the phase's device arrays

    log("[four-chips] the same seeded model on a one-device mesh")
    loss_one = run_training(
        f, size, seed, dict(tensor_parallel_size=1), four[:1], on_tpu, 1)
    destroy_model_parallel()
    gc.collect()  # drop the phase's device arrays
    for name, got in (("tp=4", loss_tp4[0]), ("dp2 x tp2 ZeRO-1", loss_2x2[0])):
        err = abs(got - loss_one[0]) / abs(loss_one[0])
        log(f" step-0 loss {name} {got:.5f} vs one device {loss_one[0]:.5f}: "
            f"rel diff {err:.2e} (tol {LOSS_MESH_TOL})")
        if err > LOSS_MESH_TOL:
            raise AssertionError(f"step-0 loss on {name} differs from the "
                                 f"one-device run by {err:.2e}")
    nxd.initialize_model_parallel(tensor_parallel_size=1, devices=four[:1])
    cfg, module, params, model = build_server(f, size, seed)
    pre1, dec1 = CacheProbe(f, cfg, model, seed).run(
        paged_kernel=model.paged_kernel)
    logits_tol = REHEARSAL_LOGITS_TOL if rehearsal else LOGITS_TOL
    check_close("tp=4 vs one device, prefill logits", pre4[0], pre1[0],
                logits_tol)
    check_close("tp=4 vs one device, first-decode logits", dec4[0], dec1[0],
                logits_tol)
    destroy_model_parallel()


# -- main ---------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the tp=4 / dp2 x tp2 path on four devices "
                         "and the one-device run it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform is there (kernels "
                         "interpreted off the TPU); prints no ok result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from neuronx_distributed_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()  # before the first compile
    import jax

    cache_events = {"requests": 0, "hits": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            cache_events["requests"] += 1
        elif name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    devices = phase_device(args, cache_dir)
    on_tpu = devices[0].platform == "tpu"
    size = TINY if args.rehearse else REAL
    if args.four_chips:
        phase_four_chips(size, args.seed, devices, on_tpu)
    else:
        import neuronx_distributed_tpu as nxd
        from neuronx_distributed_tpu.parallel.mesh import (
            destroy_model_parallel,
        )

        log("[kernels] compiled kernels against their references")
        nxd.initialize_model_parallel(tensor_parallel_size=1,
                                      devices=devices[:1])
        phase_kernels(size, args.seed, on_tpu)
        destroy_model_parallel()
        gc.collect()  # drop the phase's device arrays
        phase_train(size, args.seed, devices, on_tpu)
        phase_serve(size, args.seed, devices, on_tpu)
        gc.collect()
        phase_serve_hybrid(size, args.seed, devices, on_tpu)
        gc.collect()
        phase_serve_hybrid(size, args.seed, devices, on_tpu, kind="ssm")
    log(f"[cache] {cache_events['requests']} compile requests, "
        f"{cache_events['hits']} served from the persistent cache, "
        f"{cache_events['requests'] - cache_events['hits']} compiled")
    log(f"all phases passed in {time.perf_counter() - t0:.0f} s")
    print(json.dumps({
        "ok": not args.rehearse,
        **({"rehearsal": True} if args.rehearse else {}),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": 4 if args.four_chips else len(devices)},
    }), flush=True)


if __name__ == "__main__":
    main()
