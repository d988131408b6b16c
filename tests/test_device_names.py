"""What the device runs has a name, and the serve loop's phases sit on the
profiler's clock (the ``tracing`` PR after the first benchmark).

- the Pallas kernels carry ``name=`` (``flash_fwd`` / ``flash_dq_dkv``, or
  ``flash_dq`` + ``flash_dkv`` for a sequence past the one backward call's
  VMEM budget / ``paged_attention_decode`` / ``paged_attention_chunk``) and
  the parts of the jitted programs that no flax module scopes carry a
  ``jax.named_scope`` (``optimizer``, ``loss_head``, ``sample``,
  ``pack_tokens``, ``kv_write``, ``kv_valid``): read off the jaxprs;
- ``ServingEngine.step`` wraps its phases in ``obs.tracing.phase`` spans
  (``nxd/serve/*``): read off a CPU ``jax.profiler`` trace, and free when no
  profile is taken;
- ``benchmarks/harness/trace_scopes.py`` reads name stacks, run ids and span
  arguments out of a raw ``.xplane.pb``: checked on the trace recorded on
  the v5e in PR 22;
- the compile ledger hears every compiler request through ``jax.monitoring``
  and books a recompile inside jit dispatch as a ``jit_dispatch`` storm.
"""

import functools
import glob
import os
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params, split_flash_backward
from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import CompileLedger, MetricRegistry
from neuronx_distributed_tpu.obs import compile_ledger as compile_ledger_mod
from neuronx_distributed_tpu.obs import tracing
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.serving import engine as engine_mod
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = os.path.join(REPO, "benchmarks", "tests", "data", "probe.xplane.pb")


# -- reading a jaxpr ----------------------------------------------------------

def _sub_jaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk(jaxpr, outer=""):
    """``(primitive, name stack, params)`` of every equation, nested
    programs included; an inner equation's stack is under its caller's."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, stack, eqn.params
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _walk(sub, stack)


def _names(fn, *args):
    """The name stacks and the Pallas kernel names of ``fn``'s jaxpr."""
    stacks, kernels = set(), set()
    for prim, stack, params in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        stacks.add(stack)
        if prim == "pallas_call":
            kernels.add(params["name"])
    return stacks, kernels


def _has(stacks, scope):
    return any(scope in tracing_components(s) for s in stacks)


def tracing_components(stack):
    return [c for c in stack.replace("(", "/").replace(")", "/").split("/")
            if c]


# -- fixtures -----------------------------------------------------------------

@pytest.fixture
def tiny_paged(devices8):
    """A B=3 paged serving model on one device (page 4, C=8, T=16)."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(sequence_parallel=False, dtype=jnp.float32,
                           param_dtype=jnp.float32, max_seq_len=32,
                           remat="none")
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    return cfg, pool


def _request(cfg, rid, plen, new):
    rs = np.random.RandomState(100 + rid)
    return Request(request_id=rid, max_new_tokens=new,
                   prompt_ids=rs.randint(1, cfg.vocab_size, plen).tolist())


# -- (3) the names are in the programs ---------------------------------------

def test_train_step_jaxpr_names_optimizer_loss_head_and_flash_kernels(
        devices8):
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        initialize_parallel_model,
        initialize_parallel_optimizer,
    )
    from neuronx_distributed_tpu.trainer.trainer import make_train_step

    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    config = nxd.training_config(
        learning_rate=3e-4, zero_one_enabled=True, compute_dtype="bfloat16",
        param_dtype="float32", seed=0, tensor_parallel_size=1)
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=16, sliding_window=96,
        attention_impl="flash", remat="selective", dtype=jnp.bfloat16,
        param_dtype=jnp.float32, max_seq_len=128)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, 128), jnp.int32),), seed=0)
    opt = initialize_parallel_optimizer(config, model)
    spec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    batch = {"ids": jnp.zeros((2, 128), jnp.int32),
             "labels": jnp.zeros((2, 128), jnp.int32)}

    def names_of_a_step():  # traced anew: the backward reads its budget then
        step = make_train_step(config, model, opt,
                               make_causal_lm_loss_sum(chunk_size=64),
                               batch_spec=spec)
        return _names(step, model.params, opt.state, batch, None)

    stacks, kernels = names_of_a_step()
    # ONE backward call under the budget, under a name the benchmark's
    # readers book as `flash_bwd` (the prefix `flash_dq`)
    assert {"flash_fwd", "flash_dq_dkv"} <= kernels
    assert not {"flash_dq", "flash_dkv"} & kernels
    with split_flash_backward():  # what a sequence past the budget takes
        _, past = names_of_a_step()
    assert {"flash_fwd", "flash_dq", "flash_dkv"} <= past
    assert "flash_dq_dkv" not in past
    for scope in ("optimizer", "loss_head", "mlp", "attn", "input_norm"):
        assert _has(stacks, scope), scope
    # the head inside the chunk scan is under the loss's scope, and the
    # update is under no module's
    assert any("loss_head" in s and "lm_head" in s for s in stacks)
    assert not any("optimizer" in tracing_components(s)
                   and "layer_0" in tracing_components(s) for s in stacks)


def test_sampler_jaxprs_carry_their_scopes():
    B, V = 3, 32
    args = (jnp.zeros((B, V)), jnp.zeros((B, 2), jnp.uint32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,)),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,)))
    for fn in (engine_mod._sample_rows, engine_mod._propose_rows):
        stacks, _ = _names(fn, *args)
        assert _has(stacks, "sample"), fn
    stacks, _ = _names(engine_mod._pack_tokens, jnp.zeros((B,), jnp.int32),
                       jnp.ones((B,), bool))
    assert _has(stacks, "pack_tokens")


@pytest.mark.parametrize("width,update_valid,kernel", [
    (1, True, "paged_attention_decode"),
    (4, False, "paged_attention_chunk"),
])
def test_paged_programs_name_their_kernel_and_their_cache_writes(
        tiny_paged, width, update_valid, kernel):
    cfg, pool = tiny_paged
    B = 3 if update_valid else 1
    caches = pool.make_page_pool(16, 4).caches
    fn = functools.partial(pool._paged_step_fn, paged_kernel=True,
                           update_valid=update_valid, last_only=True)
    stacks, kernels = _names(
        fn, pool.params, jnp.zeros((B, width), jnp.int32),
        jnp.full((B,), 8, jnp.int32), jnp.zeros((B, 4), jnp.int32), caches,
        jnp.zeros((B, 16), jnp.int32))
    # the paged kernel, and the pool's writer beside it
    assert kernels == {kernel, "kv_pool_write"}
    assert _has(stacks, "kv_write") and _has(stacks, "kv_valid")
    # the pool write sits inside the attention module's scope
    assert any("kv_write" in s and "attn" in tracing_components(s)
               for s in stacks)
    stacks, _ = _names(pool._insert_valid_fn, jnp.zeros((3, 16), jnp.int32),
                       jnp.zeros((1, 16), jnp.int32), jnp.int32(1))
    assert _has(stacks, "kv_valid")


def test_a_mamba2_decode_names_its_step_kernel_under_its_scope(devices8):
    """A Mamba-2 decode program where the paged kernels run holds the Pallas
    call ``ssm_step`` under the scope ``ssm_step`` (what ``ssm_time_share``,
    ``ssm_roofline`` and ``ssm_step_roofline`` book by), inside the layer's
    ``attn`` module; its chunk program holds no such call."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16, max_seq_len=32,
        sequence_parallel=False, remat="none", dtype=jnp.float32,
        param_dtype=jnp.float32, mixer_types=["mamba2", "attention"],
        ffn_types=["mlp", "mlp"], ssm_heads=8, ssm_head_dim=8, ssm_groups=2,
        ssm_state_size=16, ssm_conv_kernel=4, ssm_chunk_rows=4)
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    caches = pool.make_page_pool(16, 4).caches
    for rows, width, update_valid, kernels in (
            (3, 1, True, {"ssm_step", "paged_attention_decode",
                          "kv_pool_write"}),
            (1, 4, False, {"paged_attention_chunk", "kv_pool_write"})):
        fn = functools.partial(pool._paged_step_fn, paged_kernel=True,
                               update_valid=update_valid, last_only=True)
        names = [(prim, stack, prm) for prim, stack, prm in _walk(
            jax.make_jaxpr(fn)(
                pool.params, jnp.zeros((rows, width), jnp.int32),
                jnp.full((rows,), 8, jnp.int32),
                jnp.zeros((rows, 4), jnp.int32), caches,
                jnp.zeros((rows, 16), jnp.int32),
                state_rows=jnp.arange(rows, dtype=jnp.int32)).jaxpr)]
        calls = {prm["name"]: stack for prim, stack, prm in names
                 if prim == "pallas_call"}
        assert set(calls) == kernels
        if width == 1:
            assert {"ssm_step", "attn", "layer_0"} <= set(
                tracing_components(calls["ssm_step"]))


# -- (1) the serve loop's phases in a profile ---------------------------------

def _host_spans(trace_dir, prefix):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((e.name[len(prefix):], e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def test_engine_steps_are_nested_phase_spans_in_a_profile(tiny_paged,
                                                          tmp_path):
    cfg, pool = tiny_paged
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4)
    engine.submit(_request(cfg, 0, 6, 3))          # warm-up: every program
    engine.run_until_complete(max_steps=100)
    engine.submit(_request(cfg, 1, 5, 8))
    engine.submit(_request(cfg, 2, 7, 8))
    for _ in range(5):
        engine.step()

    attended = []
    dispatch = engine._dispatch_decode

    def recording(active, offs, ahead):
        # independent of the engine's offsets: a slot has attended its
        # prompt and all it generated — the token still in flight counts,
        # the step is launched one ahead — but the token this step feeds in
        attended.append(sum(req.prompt_len + len(req.generated)
                            + int(ahead[slot]) - 1 for slot, req in active))
        return dispatch(active, offs, ahead)

    engine._dispatch_decode = recording
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    first = engine._steps + 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(4):
            if i == 1:
                engine.submit(_request(cfg, 3, 8, 4))   # two chunks ride
            engine.step()
    finally:
        jax.profiler.stop_trace()
    engine.close()

    spans = _host_spans(str(tmp_path), "nxd/serve/")
    steps = [s for s in spans if s[0] == "step"]
    assert [int(s[3]["step"]) for s in steps] == list(range(first, first + 4))
    # what a step emits is SERVE_PHASES and nothing else
    assert {s[0] for s in spans} == set(engine_mod.SERVE_PHASES)
    ctx = []
    chunks = first_tokens = 0
    for _, lo, hi, attrs in steps:
        assert {"active", "queued"} <= set(attrs)
        inner = [s for s in spans if s[0] != "step" and lo <= s[1]
                 and s[2] <= hi]
        collect = next(s for s in inner if s[0] == "collect")
        # a prompt's last chunk ends in a first-token tail of its own (span
        # ``first_token``, its blocking fetch inside it), before the
        # dispatch: not the collect's fetch
        mine = [s for s in inner if s[0] == "first_token"]
        for ft in mine:
            first_tokens += 1
            assert int(ft[3]["request_id"]) == 3
            (fetch,) = [s for s in inner if s[0] == "fetch"
                        and ft[1] <= s[1] and s[2] <= ft[2]]
        inner = [s for s in inner if s[0] != "first_token"
                 and (s[0] != "fetch"
                      or (collect[1] <= s[1] and s[2] <= collect[2]))]
        order = [s[0] for s in inner if s[0] != "prefill_chunk"]
        # the next step is launched BEFORE the one in flight is collected;
        # what follows ``finish`` has a span of its own, and ends the step
        assert order == ["admit", "dispatch", "collect", "fetch", "finish",
                         "tail"]
        by = {s[0]: s for s in inner}
        assert by["collect"][1] <= by["fetch"][1] \
            and by["fetch"][2] <= by["collect"][2]
        assert by["finish"][2] <= by["tail"][1] and by["tail"][2] <= hi
        assert "granted" in by["admit"][3] and "tokens" in by["finish"][3]
        assert int(by["dispatch"][3]["active"]) >= 2
        ctx.append(int(by["dispatch"][3]["ctx_tokens"]))
        for s in inner:
            if s[0] == "prefill_chunk":
                chunks += 1
                assert by["admit"][2] <= s[1] and s[2] <= by["dispatch"][1]
                assert int(s[3]["request_id"]) == 3
                assert int(s[3]["width"]) == 4
                # the chunk's last row attends the prompt up to its end
                assert int(s[3]["ctx_tokens"]) == 4 * chunks
                for ft in mine:     # the first-token tail follows the chunk
                    assert s[2] <= ft[1] and ft[2] <= by["dispatch"][1]
    assert first_tokens == 1
    assert chunks == 2
    assert ctx == attended and len(ctx) == 4


# -- (2) nothing to turn on, nothing paid when off ----------------------------

def test_phase_spans_cost_no_tracer_span_and_touch_no_registry(tiny_paged):
    cfg, pool = tiny_paged
    before = tracing.SPANS_CREATED
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4)
    for rid in range(3):
        engine.submit(_request(cfg, rid, 5 + rid, 4))
    outs = engine.run_until_complete(max_steps=200)
    engine.close()
    assert len(outs) == 3
    assert tracing.SPANS_CREATED == before
    assert not any("nxd/" in m.name or "serve/" in m.name
                   for m in engine.registry.metrics())
    span = tracing.phase("serve/step", step=1)
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span as s:
        s.set_metadata(granted=0)
    assert tracing.SPANS_CREATED == before


# -- (4) the raw-trace reader on the trace recorded on the v5e ----------------

@pytest.fixture(scope="module")
def probe():
    sys.path.insert(0, REPO)
    from benchmarks.harness import trace_reduce, trace_scopes

    trace = trace_reduce.load(PROBE)
    return trace_reduce, trace, trace_scopes.build(
        trace_scopes.read_space(PROBE), trace)


def test_trace_scopes_gives_every_kernel_its_name_stack(probe):
    trace_reduce, trace, sc = probe
    ops = sc.devices[0].ops
    mosaic = [op for op in ops if trace_reduce.is_mosaic(op.text)]
    # 3 paged calls and the flash kernels of the two train steps whose
    # device events fall inside the annotations' window
    assert len(mosaic) == 9
    assert all("pallas_call" in op.tf_op for op in mosaic)
    paged = [op for op in mosaic if op.group == "paged_chunk"]
    assert len(paged) == 3 and all(
        "paged_attention" in op.tf_op.split("/") for op in paged)
    progs = sc.devices[0].programs
    assert {progs[op.program].name for op in paged} == {"jit__lambda"}


def test_trace_scopes_groups_add_up_to_the_busy_time(probe):
    trace_reduce, trace, sc = probe
    by = sc.group_seconds()
    assert sum(by.values()) == pytest.approx(trace.busy_s(), rel=1e-3)
    from benchmarks.layer_metrics.paged_time_share import is_paged

    assert by["paged_chunk"] == pytest.approx(trace.time_of(is_paged),
                                              rel=1e-4)
    assert sc.busy_s == trace.busy_s() and sc.window == trace.window


def test_trace_scopes_reads_span_arguments_and_launch_times(probe):
    _, trace, sc = probe
    assert [s.attrs["step"] for s in sc.named("bench/step")] == [0, 1, 2]
    # the same spans, on the same clock, as trace_reduce's annotations
    assert sorted(s.start for s in sc.spans) == pytest.approx(
        sorted(a.start for a in trace.annotations), abs=1e-9)
    launched = [p for p in sc.devices[0].programs if p.launched is not None]
    assert len(launched) == len(sc.devices[0].programs) == 5
    for p in launched:     # under the annotation that made the call
        inside = [s.name for s in sc.spans
                  if s.start <= p.launched <= s.end]
        assert ("bench/decode" if p.name == "jit__lambda"
                else "bench/train") in inside
    least, most = sc.clock_offset_bounds()
    assert 1e-3 < least < 2e-3 and most is None    # no fetch span in it


@pytest.mark.parametrize("tf_op,text,group", [
    ("jit(_step)/transpose(jvp(LlamaForCausalLM.hidden))/model/checkpoint/"
     "rematted_computation/layer_1/mlp/jit(silu)/mul:", "%fusion.1 = f32[]",
     "mlp"),
    ("jit(_step)/jvp(LlamaForCausalLM.hidden)/model/layer_0/attn/qkv/"
     "dot_general:", "%fusion.2 = f32[]", "attn_proj"),
    ("jit(_unknown)/LlamaForCausalLM/model/layer_0/attn/kv_write/scatter:",
     "%scatter.3 = f32[]", "kv_write"),
    ("jit(_step)/transpose(jvp(loss_head))/while/body/closed_call/checkpoint/"
     "LlamaForCausalLM.head/lm_head/dot_general:", "%fusion.4 = f32[]",
     "loss_head"),
    ("jit(_unknown)/LlamaForCausalLM/lm_head/dot_general:",
     "%fusion.5 = f32[]", "head"),
    ("jit(_step)/optimizer/mul:", "%fusion.6 = f32[]", "optimizer"),
    ("jit(_sample_rows)/sample/vmap(sort):", "%sort.7 = f32[]", "sample"),
    ("jit(_step)/jvp(LlamaForCausalLM.hidden)/model/layer_1/attn/core/cond/"
     "branch_0_fun/flash_fwd/pallas_call:",
     '%flash_fwd.8 = f32[] custom-call(), '
     'custom_call_target="tpu_custom_call"', "flash_fwd"),
    ("x/pallas_call:", '%flash_dkv.9 = f32[] custom-call(), '
     'custom_call_target="tpu_custom_call"', "flash_bwd"),
    ("x/pallas_call:", '%flash_dq_dkv.9 = f32[] custom-call(), '
     'custom_call_target="tpu_custom_call"', "flash_bwd"),
    ("x/pallas_call:", '%paged_attention_decode.1 = f32[] custom-call(), '
     'custom_call_target="tpu_custom_call"', "paged_decode"),
    ("", "%all-gather.3 = f32[8]{0} all-gather(f32[2]{0} %p)", "collective"),
    ("", "%copy.17 = f32[8]{0} copy(f32[8]{0} %p)", "other"),
    # what the compiler made up: named for the argument it copies, or not
    # at all (then it is its program's)
    ("caches[14][1]:", "%copy.26 = bf16[4161,8,16,128]{3,1,2,0} copy(%c)",
     "pool_copy"),
    ("", "%fusion.46 = f32[32,1188,128]{1,0,2} fusion(%copy.4)", "sample"),
])
def test_the_table_sees_through_the_transform_wrappers(tf_op, text, group):
    sys.path.insert(0, REPO)
    from benchmarks.harness import trace_scopes

    program = "jit__sample_rows" if group == "sample" else "jit__step"
    assert trace_scopes.group_of(text, tf_op, program) == group
    assert group in trace_scopes.GROUPS


# -- the compile ledger hears jit dispatch ------------------------------------

def test_compile_ledger_books_a_dispatch_recompile_as_a_storm():
    reg = MetricRegistry()
    led = CompileLedger(registry=reg)
    fn = jax.jit(lambda x: x * 2 + 1)
    fn(jnp.zeros((3,), jnp.int32))                # warm-up: counted only
    assert reg.counter("trace/compile_requests_total").value >= 1
    assert led.compile_count() == 0
    led.declare_warmup_done()
    mark = led.mark()
    fn(jnp.zeros((3,), jnp.int32))                # cached: nothing
    assert led.compiles_since(mark) == 0 and led.storms == 0
    fn(jnp.zeros((3,), jnp.float32))              # recompiles in dispatch
    assert led.compiles_since(mark) == 1 and led.storms == 1
    row = led.rows[-1]
    assert row["family"] == "jit_dispatch" and row["kind"] == "jit"
    assert "lambda" in row["key"] and row["wall_ms"] > 0 and row["storm"]
    assert reg.counter("trace/compile_storms_total").value == 1
    assert led.summary()["families"]["jit_dispatch"]["compiles"] == 1


def test_an_explicit_row_accounts_for_the_requests_it_timed():
    led = CompileLedger()
    led.declare_warmup_done()
    fn = jax.jit(lambda x: x - 3)
    with led.timed("mine", "k", kind="jit"):
        fn(jnp.zeros((5,), jnp.int32))
    assert [r["family"] for r in led.rows if r["event"] == "compile"] \
        == ["mine"]
    assert led.reconcile() == 0 and led.storms == 1
    # a poll that only saw a jit cache grow stands for what dispatch compiled
    fn(jnp.zeros((5,), jnp.float32))
    led.record_compile("jit:sample_rows", "cache_size_2", None, kind="jit")
    assert led.reconcile() == 0
    assert [r["family"] for r in led.rows if r["event"] == "compile"] \
        == ["mine", "jit:sample_rows"]


def test_second_admission_after_a_decode_compiles_nothing(tiny_paged):
    """The validity insert used to compile a second time for the first
    request admitted after a decode (its array came back committed): seen
    only by the listener, and gone since the insert commits its argument."""
    cfg, pool = tiny_paged
    led = CompileLedger()
    pool.compile_ledger = led
    try:
        engine = ServingEngine(pool, page_size=4, num_pages=16,
                               prefill_chunk_tokens=4, compile_ledger=led)
        engine.submit(_request(cfg, 0, 6, 3))
        engine.run_until_complete(max_steps=100)
        engine.declare_warmup_done()
        before = compile_ledger_mod.LEDGER_ROWS
        engine.submit(_request(cfg, 1, 6, 3))
        engine.submit(_request(cfg, 2, 5, 3))
        outs = engine.run_until_complete(max_steps=100)
        engine.close()
    finally:
        pool.compile_ledger = None
    assert len(outs) == 2
    assert led.storms == 0, [r for r in led.rows if r.get("storm")]
    assert compile_ledger_mod.LEDGER_ROWS == before
