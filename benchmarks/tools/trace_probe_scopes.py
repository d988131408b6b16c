#!/usr/bin/env python
"""trace_probe_scopes.py — record the SMALL trace that
``benchmarks/tests/test_trace_scopes.py`` reads: a two-layer toy through the
real ``ServingEngine`` (paged, chunked prefill, the pipelined decode loop) and
three real train steps (flash attention, selective remat, the chunked loss
head, AdamW), under the benchmark's own ``bench/`` annotations and profiler
options.

Not part of any cell.  ``harness/trace_scopes.py`` is written against what the
chip's profiler puts into an ``.xplane.pb`` (name stacks in ``tf_op``, run ids
on programs and on the host's enqueue events, annotation arguments as stats);
this trace holds all of it at a size that can be checked in.  Run on the chip::

    chiprun -- python benchmarks/tools/trace_probe_scopes.py

It writes ``chiprun_out/trace_probe_scopes/probe_scopes.xplane.pb`` (copied to
``benchmarks/tests/data/``) and prints what the reader makes of it.  To stay
under 300 KB the file is slimmed after recording, and only so: planes other
than the first chip's and the host's are dropped, and of the host's events all
but the annotations and the three runtime events that lead from a run id to its
launch; an operation's HLO text is cut after 200 characters, or after its
custom-call target where it has one (the reader needs the name, the opcode and
that mark); of an operation's metadata only ``tf_op`` stays, and of an
operation event's own stats none (its offset and duration are fields).  Times,
names, ``tf_op`` and run ids are as recorded.
"""

import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

KEEP_META_STATS = ("tf_op",)
TEXT_CUT = 200
HOST_EVENTS = ("tpu::System::Execute", "DoEnqueueProgram",
               "tpu::System::Execute=>IssueSequencedEvent")


def serve(jax, jnp, np, common, log):
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from neuronx_distributed_tpu.parallel.layers import init_sharded_params
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    page, chunk, context, total, slots = 16, 64, 128, 192, 4
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=128, max_seq_len=total,
        sequence_parallel=False, remat="none", dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    module = LlamaForCausalLM(cfg)
    params, _ = init_sharded_params(module, jax.random.PRNGKey(0),
                                    jnp.zeros((1, page), jnp.int32))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=slots, context_len=context,
                        max_total_len=total, kv_cache_dtype=jnp.bfloat16))
    engine = ServingEngine(model, page_size=page,
                           num_pages=slots * (total // page) + 2,
                           prefill_chunk_tokens=chunk)
    rs = np.random.RandomState(0)

    def request(rid, length, new):
        return Request(request_id=rid, max_new_tokens=new,
                       prompt_ids=rs.randint(1, cfg.vocab_size,
                                             size=length).tolist())

    # warm-up as the serve runner's: every program, then the second insert
    for rid, (length, new) in enumerate(((chunk + chunk // 3, 4), (12, 2))):
        engine.submit(request(-1 - rid, length, new))
        engine.run_until_complete(max_steps=200)
    # two requests decoding, then a third arrives inside the traced steps:
    # its two chunks ride with the others' decode steps
    engine.submit(request(0, 40, 24))
    engine.submit(request(1, 70, 24))
    for _ in range(6):
        engine.step()

    def traced():
        for i in range(7):
            with common.annotate("submit"):
                if i == 1:
                    engine.submit(request(2, 100, 8))
            with common.annotate("engine_step"):
                engine.step()

    return engine, traced


def train(jax, jnp, np, common, log):
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        initialize_parallel_model,
        initialize_parallel_optimizer,
    )
    from neuronx_distributed_tpu.trainer.trainer import make_train_step

    seq, batch = 512, 2
    config = nxd.training_config(
        learning_rate=3e-4, zero_one_enabled=True, compute_dtype="bfloat16",
        param_dtype="float32", seed=0, tensor_parallel_size=1)
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=128, sliding_window=256,
        attention_impl="flash", remat="selective", sequence_parallel=False,
        max_seq_len=seq, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, seq), jnp.int32),), seed=0)
    opt = initialize_parallel_optimizer(config, model)
    spec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    step = make_train_step(config, model, opt,
                           make_causal_lm_loss_sum(chunk_size=128),
                           batch_spec=spec)
    rs = np.random.RandomState(0)
    state = [model.params, opt.state]

    def one():
        ids = rs.randint(1, cfg.vocab_size, size=(batch, seq + 1))
        b = {"ids": jnp.asarray(ids[:, :-1], jnp.int32),
             "labels": jnp.asarray(ids[:, 1:], jnp.int32)}
        state[0], state[1], m = step(state[0], state[1], b, None)
        return float(m["loss"])

    log(f"train losses (warm-up): {[round(one(), 4) for _ in range(2)]}")

    def traced():
        for _ in range(3):
            with common.annotate("train_step"):
                one()

    return traced


def slim(space):
    """See the module's docstring: what is dropped and nothing else."""
    from benchmarks.harness import trace_reduce, trace_scopes

    pb2 = trace_scopes.xplane_pb2()
    out = pb2.XSpace()
    for plane in space.planes:
        host = plane.name == trace_reduce.HOST_PLANE
        if not host and plane.name != "/device:TPU:0":
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        new = out.planes.add()
        new.CopyFrom(plane)
        used = set()
        for line in list(new.lines):
            keep = [e for e in line.events if not host or (
                new.event_metadata[e.metadata_id].name.startswith(
                    trace_scopes.SPAN_PREFIXES)
                or new.event_metadata[e.metadata_id].name in HOST_EVENTS)]
            if not host and line.name not in (trace_reduce.OPS_LINE,
                                              trace_reduce.MODULES_LINE):
                keep = []
            del line.events[:]
            line.events.extend(keep)
            if line.name == trace_reduce.OPS_LINE:
                for e in line.events:
                    del e.stats[:]
            used.update(e.metadata_id for e in keep)
        for line in [ln for ln in new.lines if not ln.events]:
            new.lines.remove(line)
        for mid in list(new.event_metadata):
            meta = new.event_metadata[mid]
            if mid not in used:
                del new.event_metadata[mid]
                continue
            cut = meta.name.find(trace_reduce.MOSAIC_MARK)
            meta.name = (meta.name[:cut + len(trace_reduce.MOSAIC_MARK)]
                         if cut >= 0 else meta.name[:TEXT_CUT])
            stats = [s for s in meta.stats
                     if names.get(s.metadata_id) in KEEP_META_STATS]
            del meta.stats[:]
            meta.stats.extend(stats)
    return out


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common, trace_reduce, trace_scopes
    from neuronx_distributed_tpu.parallel.mesh import destroy_model_parallel

    log = common.log
    log(f"devices: {jax.devices()}")
    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    engine, traced_serve = serve(jax, jnp, np, common, log)
    traced_train = train(jax, jnp, np, common, log)

    out = os.path.join("chiprun_out", "trace_probe_scopes")
    shutil.rmtree(out, ignore_errors=True)
    window = common.ProfilerWindow("_probe_scopes")
    window.dir = out
    window.start()
    traced_serve()
    traced_train()
    window.stop()
    engine.close()
    destroy_model_parallel()

    raw = trace_reduce.find_xplane(out)
    final = os.path.join(out, "probe_scopes.xplane.pb")
    with open(final, "wb") as f:
        f.write(slim(trace_scopes.read_space(raw)).SerializeToString())
    log(f"trace bytes: raw {os.path.getsize(raw)}, slimmed "
        f"{os.path.getsize(final)}")
    shutil.copy(raw, os.path.join(out, "raw.xplane.pb"))
    shutil.rmtree(os.path.join(out, "plugins"))
    trace = trace_reduce.load(final, chips=1)
    sc = trace_scopes.build(trace_scopes.read_space(final), trace)
    for line in trace_scopes.report(sc):
        log(line)
    for p in sc.devices[0].programs if sc.devices else []:
        log(f"program {p.name} run {p.run_id} launched under "
            f"{p.span.name if p.span else None}")
    for s in sc.spans:
        log(f"span {s.name} {s.dur * 1e3:.3f} ms {s.attrs}")
    seen = set()
    for op in sc.devices[0].ops if sc.devices else []:
        key = (op.group, op.tf_op)
        if key not in seen:
            seen.add(key)
            log(f"op {op.group:10s} {trace_reduce.hlo_name(op.text):34s} "
                f"{op.tf_op}")


if __name__ == "__main__":
    main()
