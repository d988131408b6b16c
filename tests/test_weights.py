"""Live-weights subsystem tests (fast tier: CPU mesh).

Four layers, mirroring the PR's split:

- **hot swap** on one engine: envelope validation (structure / shape /
  dtype mismatches refused with the OLD weights still serving), the
  zero-recompile guarantee (compile ledger pins zero post-warmup rows
  across a live swap), the exact version boundary (outputs before the
  swap match a solo reference on the old params, outputs after match the
  new params — and every output is stamped with the version that decoded
  it), donation safety (the memory source copies, so deleting the
  caller's buffers — what the jitted train step's ``donate_argnums``
  does — never kills the engine), and the ``weights/pre_swap`` chaos
  fault proving transactionality;
- **fleet rolling update**: drain → swap → rejoin one replica at a time
  under live traffic — zero accepted requests lost, mixed versions
  visible mid-roll, every replica on the new version at the end, and the
  autopilot's drain-restart never targets the draining replica;
- **exporter round-trip**: ``save_nxd_checkpoint`` is the exact inverse
  of ``load_nxd_checkpoint`` (plain, fused-stride, GQA-replicated KV,
  and pp-split layouts);
- **artifacts**: the ``weight_swap/1`` schema, the obs-report "weights"
  section, and the ``--compare`` deploy gates (new failures,
  non-monotonic versions).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params, solo_generate
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import CompileLedger, MetricRegistry
from neuronx_distributed_tpu.obs.schemas import validate_jsonl, validate_record
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import clear_plan, install_plan
from neuronx_distributed_tpu.serving import (
    FleetRouter,
    Replica,
    Request,
    ServingEngine,
)
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel
from neuronx_distributed_tpu.weights import (
    SwapError,
    WeightSwapper,
    param_envelope,
)

pytestmark = pytest.mark.weights


# -- shared tiny-Llama serving rig -------------------------------------------

@pytest.fixture
def swap_rig():
    """One compiled tiny-Llama pool (B=2) with TWO envelope-identical
    param sets (different init seeds) plus B=1 solo references over each —
    greedy tokens under params0 vs params1 differ, so the reference pins
    WHICH weights decoded an output."""
    initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)
    ids0 = jnp.zeros((2, 8), jnp.int32)
    params0 = sharded_params(module.init(jax.random.PRNGKey(0), ids0))
    params1 = sharded_params(module.init(jax.random.PRNGKey(7), ids0))
    icfg = InferenceConfig(batch_size=2, context_len=8, max_total_len=16,
                           kv_cache_dtype=jnp.float32)
    pool = ParallelInferenceModel(module, params0, icfg)
    solo_cfg = InferenceConfig(batch_size=1, context_len=8, max_total_len=16,
                               kv_cache_dtype=jnp.float32)
    solo0 = ParallelInferenceModel(module, params0, solo_cfg)
    solo1 = ParallelInferenceModel(module, params1, solo_cfg)
    return cfg, pool, params1, solo0, solo1


def _serve_one(engine, rid, prompt_ids, max_new=4):
    engine.submit(Request(request_id=rid, prompt_ids=prompt_ids,
                          max_new_tokens=max_new))
    outs = engine.run_until_complete(max_steps=500)
    (out,) = [o for o in outs if o.request_id == rid]
    assert out.state == "finished"
    return out


# -- hot swap: one engine -----------------------------------------------------

def test_live_swap_zero_compiles_and_exact_version_boundary(swap_rig, tmp_path):
    """The tentpole acceptance bar on one engine: a warmed engine swaps
    with ZERO compile-ledger rows, outputs flip from the params0 solo
    reference to the params1 reference exactly at the swap, and every
    output / serving_stats record is stamped with the version that decoded
    it."""
    cfg, pool, params1, solo0, solo1 = swap_rig
    rs = np.random.RandomState(3)
    prompt = rs.randint(1, cfg.vocab_size, size=6).tolist()
    ledger = CompileLedger()
    stats_path = str(tmp_path / "serving_stats.jsonl")
    swaps_path = str(tmp_path / "weight_swaps.jsonl")
    engine = ServingEngine(pool, registry=MetricRegistry(),
                           compile_ledger=ledger, stats_path=stats_path,
                           page_size=4, num_pages=9)
    swapper = WeightSwapper(engine, path=swaps_path)

    before = _serve_one(engine, 0, prompt)
    assert list(before.token_ids) == solo_generate(solo0, prompt, 4)
    assert before.weights_version == 0
    engine.declare_warmup_done()

    mark = ledger.mark()
    version = swapper.swap(params1, source="memory")
    assert version == 1 and engine.weights_version == 1
    assert ledger.compiles_since(mark) == 0, (
        "a live swap must not compile anything")

    after = _serve_one(engine, 1, prompt)
    assert ledger.compile_count(after_warmup_only=True) == 0
    assert after.weights_version == 1
    assert list(after.token_ids) == solo_generate(solo1, prompt, 4), (
        "post-swap output must come from the NEW weights")
    assert list(after.token_ids) != list(before.token_ids), (
        "the rig's two param sets must disagree for the boundary to mean "
        "anything")
    engine.close()
    swapper.close()

    # artifacts: one committed weight_swap record; serving_stats v6 carries
    # the per-request version across the live swap
    assert validate_jsonl("weight_swap", swaps_path) == 1
    (srec,) = [json.loads(l) for l in open(swaps_path)]
    assert srec["ok"] and srec["version"] == 1 and srec["source"] == "memory"
    assert validate_jsonl("serving_stats", stats_path) == 2
    stats = [json.loads(l) for l in open(stats_path)]
    assert [r["weights_version"] for r in stats] == [0, 1]

    # the registry surface the fleet_watch wver column reads
    snap = engine.registry.snapshot()
    assert snap["weights/weights_version"] == 1.0
    assert snap["weights/swaps_total"] == 1.0
    assert snap.get("weights/swap_failures_total", 0.0) == 0.0


def test_envelope_mismatches_refused_with_old_weights_serving(swap_rig):
    """Transactionality, validation half: wrong shape, wrong dtype, and
    wrong structure each raise SwapError BEFORE the engine is touched —
    the next request still decodes under version 0 / params0."""
    cfg, pool, params1, solo0, _ = swap_rig
    rs = np.random.RandomState(5)
    prompt = rs.randint(1, cfg.vocab_size, size=5).tolist()
    engine = ServingEngine(pool, registry=MetricRegistry(),
                           page_size=4, num_pages=9)
    swapper = WeightSwapper(engine)

    leaves, treedef = jax.tree_util.tree_flatten(params1)

    def rebuild(i, fn):
        return jax.tree_util.tree_unflatten(
            treedef, [fn(l) if j == i else l for j, l in enumerate(leaves)])

    with pytest.raises(SwapError, match="shape"):
        swapper.swap(rebuild(0, lambda l: np.zeros(
            tuple(d + 1 for d in l.shape), np.asarray(l).dtype)))
    with pytest.raises(SwapError, match="dtype"):
        # float16, not float64: with x64 disabled jax folds f64 back to f32
        swapper.swap(rebuild(0, lambda l: np.asarray(l).astype(np.float16)))
    with pytest.raises(SwapError, match="structure"):
        swapper.swap({"not": "the model tree"})
    assert engine.weights_version == 0
    out = _serve_one(engine, 0, prompt)
    assert out.weights_version == 0
    assert list(out.token_ids) == solo_generate(solo0, prompt, 4)
    assert engine.registry.snapshot()["weights/swap_failures_total"] == 3.0
    engine.close()


def test_pre_swap_chaos_fault_is_transactional(swap_rig, tmp_path):
    """Transactionality, chaos half: a ``weights/pre_swap`` fault fires
    before ANY engine state is touched — audited as a failed attempt, old
    weights keep serving, and the NEXT swap commits as version 1 (the
    failure never burned a version number)."""
    cfg, pool, params1, solo0, solo1 = swap_rig
    rs = np.random.RandomState(11)
    prompt = rs.randint(1, cfg.vocab_size, size=4).tolist()
    swaps_path = str(tmp_path / "weight_swaps.jsonl")
    engine = ServingEngine(pool, registry=MetricRegistry(),
                           page_size=4, num_pages=9)
    swapper = WeightSwapper(engine, path=swaps_path)

    install_plan({"faults": [{"point": "weights/pre_swap",
                              "action": "exception", "count": 1,
                              "message": "test: injected pre-swap kill"}]})
    try:
        with pytest.raises(Exception, match="pre-swap kill"):
            swapper.swap(params1, source="memory")
    finally:
        clear_plan()
    assert engine.weights_version == 0
    assert list(_serve_one(engine, 0, prompt).token_ids) == \
        solo_generate(solo0, prompt, 4)

    assert swapper.swap(params1, source="memory") == 1
    assert list(_serve_one(engine, 1, prompt).token_ids) == \
        solo_generate(solo1, prompt, 4)
    engine.close()
    swapper.close()

    recs = [json.loads(l) for l in open(swaps_path)]
    assert validate_jsonl("weight_swap", swaps_path) == 2
    assert [r["ok"] for r in recs] == [False, True]
    assert recs[0]["event"] == "swap_failed" and recs[0]["version"] == 0
    assert recs[1]["version"] == 1


def test_memory_swap_survives_donated_source_buffers(swap_rig):
    """The donation hazard, reproduced: the memory source COPIES by
    default, so deleting the caller's device buffers right after the swap
    (exactly what the jitted train step's ``donate_argnums`` does at the
    next optimizer step) leaves the engine serving untouched."""
    cfg, pool, params1, _, solo1 = swap_rig
    rs = np.random.RandomState(17)
    prompt = rs.randint(1, cfg.vocab_size, size=5).tolist()
    engine = ServingEngine(pool, registry=MetricRegistry(),
                           page_size=4, num_pages=9)
    swapper = WeightSwapper(engine)

    donated = jax.tree.map(lambda x: jax.device_put(np.asarray(x)), params1)
    swapper.swap(donated, source="memory")
    for leaf in jax.tree_util.tree_leaves(donated):
        leaf.delete()  # what donation does to the trainer's old pytree
    out = _serve_one(engine, 0, prompt)
    assert out.weights_version == 1
    assert list(out.token_ids) == solo_generate(solo1, prompt, 4)
    engine.close()


def test_param_envelope_prefers_compiled_arg_specs(swap_rig):
    """The acceptance surface is what the phase programs were COMPILED
    against: with ``_arg_specs`` present the envelope comes from it, and
    it matches the live params leaf-for-leaf (shape + dtype)."""
    _, pool, _, _, _ = swap_rig
    env = param_envelope(pool)
    env_leaves = jax.tree_util.tree_leaves(env)
    live_leaves = jax.tree_util.tree_leaves(pool.params)
    assert len(env_leaves) == len(live_leaves)
    for spec, live in zip(env_leaves, live_leaves):
        assert tuple(spec.shape) == tuple(jnp.shape(live))
        assert spec.dtype == jnp.result_type(live)


# -- fleet rolling update -----------------------------------------------------

def test_rolling_update_zero_loss_mixed_versions(swap_rig, tmp_path):
    """The fleet acceptance bar, in-process: a 3-replica roll under live
    traffic loses zero accepted requests, versions are MIXED mid-roll
    (the deploy is visible in ``Replica.describe()``), every replica ends
    on version 1, and each replica's audit file validates."""
    cfg, pool, params1, _, _ = swap_rig
    rs = np.random.RandomState(23)
    prompts = [rs.randint(1, cfg.vocab_size,
                          size=int(rs.randint(3, 7))).tolist()
               for _ in range(9)]

    def factory():
        return ServingEngine(pool, registry=MetricRegistry(),
                             page_size=4, num_pages=9)

    router = FleetRouter([Replica(i, factory) for i in range(3)],
                         policy="round_robin", seed=1)
    outs = {}
    mixed_seen = False
    submitted = 0
    roll_started = False
    for _ in range(400):
        for _ in range(2):
            if submitted < len(prompts):
                router.submit(Request(request_id=submitted,
                                      prompt_ids=prompts[submitted],
                                      max_new_tokens=3))
                submitted += 1
        for o in router.step():
            outs[router.client_id(o.request_id)] = o
        if not roll_started and submitted >= 3:
            router.rolling_update(params1, swaps_dir=str(tmp_path),
                                  cause="test_roll")
            roll_started = True
        if roll_started and router.roll_status() is not None:
            versions = {r.describe().get("weights_version", 0)
                        for r in router.replicas.values() if r.alive}
            mixed_seen = mixed_seen or len(versions) > 1
        if (roll_started and router.roll_status() is None
                and submitted == len(prompts) and not router.inflight):
            break
    assert router.last_roll is not None, "roll never completed"
    assert sorted(router.last_roll["done"]) == [0, 1, 2]
    assert router.last_roll["failed"] == []
    assert router.last_roll["skipped"] == []
    assert mixed_seen, "the mixed-version fleet must be observable mid-roll"
    assert len(outs) == len(prompts)
    assert all(o.state == "finished" for o in outs.values()), (
        "zero accepted requests lost across the roll")
    for r in router.replicas.values():
        assert r.describe()["weights_version"] == 1
    router.assert_invariants()
    router.close()
    for rid in range(3):
        path = str(tmp_path / f"replica{rid}_weight_swaps.jsonl")
        assert validate_jsonl("weight_swap", path) == 1
        (rec,) = [json.loads(l) for l in open(path)]
        assert rec["ok"] and rec["version"] == 1 and rec["replica"] == rid


def test_rolling_update_failed_swap_rejoins_on_old_weights(swap_rig, tmp_path):
    """A replica whose swap fails (chaos fault on the first attempt) lands
    in the roll's ``failed`` list, rejoins rotation serving version 0, and
    the rest of the fleet still rolls to version 1 — capacity over
    currency."""
    cfg, pool, params1, _, _ = swap_rig
    factory = lambda: ServingEngine(pool, registry=MetricRegistry(),  # noqa: E731
                                    page_size=4, num_pages=9)
    router = FleetRouter([Replica(i, factory) for i in range(2)],
                         policy="round_robin", seed=1)
    install_plan({"faults": [{"point": "weights/pre_swap",
                              "action": "exception", "count": 1,
                              "message": "test: injected swap kill"}]})
    try:
        router.rolling_update(params1, swaps_dir=str(tmp_path))
        for _ in range(100):
            router.step()
            if router.roll_status() is None:
                break
    finally:
        clear_plan()
    assert router.last_roll is not None
    assert router.last_roll["failed"] == [0]
    assert router.last_roll["done"] == [1]
    assert router.replicas[0].describe()["weights_version"] == 0
    assert router.replicas[1].describe()["weights_version"] == 1
    # both replicas are back in rotation: traffic still lands everywhere
    outs = {}
    for i in range(4):
        router.submit(Request(request_id=i, prompt_ids=[1, 2, 3],
                              max_new_tokens=2))
    for _ in range(200):
        for o in router.step():
            outs[router.client_id(o.request_id)] = o
        if len(outs) == 4:
            break
    assert all(o.state == "finished" for o in outs.values())
    router.close()


def test_exactly_one_roll_at_a_time_and_arg_validation(swap_rig):
    cfg, pool, params1, _, _ = swap_rig
    factory = lambda: ServingEngine(pool, registry=MetricRegistry(),  # noqa: E731
                                    page_size=4, num_pages=9)
    router = FleetRouter([Replica(i, factory) for i in range(2)],
                         policy="round_robin", seed=1)
    with pytest.raises(ValueError, match="exactly one"):
        router.rolling_update()
    with pytest.raises(ValueError, match="exactly one"):
        router.rolling_update(params1, ckpt_dir="/nope")
    router.rolling_update(params1)
    with pytest.raises(ValueError, match="already in progress"):
        router.rolling_update(params1)
    for _ in range(100):
        router.step()
        if router.roll_status() is None:
            break
    assert router.last_roll is not None
    router.close()


def test_autopilot_drain_restart_skips_draining_replica(swap_rig):
    """The autopilot never fights a roll: a replica-scoped restart edge
    for the DRAINING replica is not dispatchable, the fleet-scope fallback
    refuses to take the only other replica offline, and the drain's swap
    plan survives untouched."""
    from neuronx_distributed_tpu.serving.fleet import Autopilot, AutopilotConfig

    cfg, pool, params1, _, _ = swap_rig
    factory = lambda: ServingEngine(pool, registry=MetricRegistry(),  # noqa: E731
                                    page_size=4, num_pages=9)
    router = FleetRouter([Replica(i, factory) for i in range(2)],
                         policy="round_robin", seed=1)
    pilot = Autopilot(router, None, config=AutopilotConfig())
    router.drain(0, then="swap", payload={"params": params1})
    assert router.draining() == {0: "swap"}
    emitted = []
    pilot._drain_restart({"rule": "compile_storm", "replica": 0,
                          "state": "firing"}, now=0.0, emitted=emitted)
    assert emitted == [], "autopilot must not act on a draining replica"
    assert router.draining() == {0: "swap"}, "the swap plan must survive"
    assert router.registry.snapshot().get("router/restarts_total", 0.0) == 0.0
    with pytest.raises(ValueError, match="already draining"):
        router.drain(0, then="restart")
    router.close()


# -- exporter round-trip ------------------------------------------------------

def _roundtrip_state(rng):
    H, I, V = 8, 16, 32
    return {
        "model.embed_tokens.weight": rng.randn(V, H).astype(np.float32),
        "model.layers.0.self_attn.qkv_proj.weight":
            rng.randn(3 * H, H).astype(np.float32),
        "model.layers.0.self_attn.o_proj.weight":
            rng.randn(H, H).astype(np.float32),
        "model.layers.0.mlp.gate_up_proj.weight":
            rng.randn(2 * I, H).astype(np.float32),
        "model.layers.0.mlp.down_proj.weight":
            rng.randn(H, I).astype(np.float32),
        "model.layers.0.input_layernorm.weight":
            rng.randn(H).astype(np.float32),
        "model.norm.weight": rng.randn(H).astype(np.float32),
        "lm_head.weight": rng.randn(V, H).astype(np.float32),
    }


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_save_nxd_checkpoint_roundtrips_through_importer(tmp_path, tp):
    """``load(save(state)) == state`` bit-exactly at every tp width — the
    fused qkv/gate_up strides interleave and de-interleave through the
    same ``create_local_weight`` rule."""
    from neuronx_distributed_tpu.convert import (
        LLAMA_TP_RULES,
        load_nxd_checkpoint,
        save_nxd_checkpoint,
    )

    state = _roundtrip_state(np.random.RandomState(2))
    mdir = str(tmp_path / "model")
    files = save_nxd_checkpoint(mdir, state, tp=tp)
    assert len(files) == tp
    assert sorted(os.path.basename(f) for f in files) == [
        f"dp_rank_00_tp_rank_{t:02d}_pp_rank_00.pt" for t in range(tp)]
    back = load_nxd_checkpoint(mdir, LLAMA_TP_RULES)
    assert set(back) == set(state)
    for k in state:
        np.testing.assert_array_equal(back[k], state[k], err_msg=k)


def test_save_nxd_checkpoint_shards_match_reference_interleave(tmp_path):
    """The on-disk shards ARE the reference layout, not merely something
    the importer tolerates: rank r of a fused (stride s) tensor holds
    chunks ``[r::tp]`` of the ``tp*s``-way split."""
    import torch

    from neuronx_distributed_tpu.convert import save_nxd_checkpoint

    state = _roundtrip_state(np.random.RandomState(4))
    mdir = str(tmp_path / "model")
    save_nxd_checkpoint(mdir, state, tp=2)
    for t, (name, stride) in enumerate([
            ("model.layers.0.self_attn.qkv_proj.weight", 3),
            ("model.layers.0.mlp.gate_up_proj.weight", 2)]):
        full = state[name]
        chunks = np.split(full, 2 * stride, axis=0)
        for r in range(2):
            sd = torch.load(os.path.join(
                mdir, f"dp_rank_00_tp_rank_{r:02d}_pp_rank_00.pt"),
                weights_only=True)
            want = np.concatenate(chunks[r::2], axis=0)
            np.testing.assert_array_equal(np.asarray(sd[name]), want)
            # unruled params are replicated bit-identically (the importer's
            # round-trip condition for rule-less tensors)
            np.testing.assert_array_equal(
                np.asarray(sd["model.norm.weight"]), state["model.norm.weight"])


def test_save_nxd_checkpoint_fuses_and_replicates_gqa_kv(tmp_path):
    """The HF-split path (``fuse_llama=True``) re-fuses q/k/v + gate/up
    before sharding, and ``kv_size_multiplier > 1`` re-applies the
    reference's KV replication — both invert through the importer."""
    from neuronx_distributed_tpu.convert import (
        load_nxd_checkpoint,
        save_nxd_checkpoint,
    )

    rng = np.random.RandomState(6)
    H = 8
    split_state = {
        "model.layers.0.self_attn.q_proj.weight":
            rng.randn(H, H).astype(np.float32),
        "model.layers.0.self_attn.k_proj.weight":
            rng.randn(H, H).astype(np.float32),
        "model.layers.0.self_attn.v_proj.weight":
            rng.randn(H, H).astype(np.float32),
        "model.layers.0.mlp.gate_proj.weight":
            rng.randn(16, H).astype(np.float32),
        "model.layers.0.mlp.up_proj.weight":
            rng.randn(16, H).astype(np.float32),
        "model.norm.weight": rng.randn(H).astype(np.float32),
    }
    mdir = str(tmp_path / "fused")
    save_nxd_checkpoint(mdir, split_state, tp=2, fuse_llama=True)
    back = load_nxd_checkpoint(mdir)
    np.testing.assert_array_equal(
        back["model.layers.0.self_attn.qkv_proj.weight"],
        np.concatenate([split_state[f"model.layers.0.self_attn.{p}_proj.weight"]
                        for p in "qkv"], axis=0))
    np.testing.assert_array_equal(
        back["model.layers.0.mlp.gate_up_proj.weight"],
        np.concatenate([split_state["model.layers.0.mlp.gate_proj.weight"],
                        split_state["model.layers.0.mlp.up_proj.weight"]],
                       axis=0))

    # GQA replication: weight_k saved with multiplier 2 tiles on disk and
    # inverts on load with the explicit multiplier
    kv_state = {
        "model.layers.0.self_attn.weight_k": rng.randn(4, H).astype(np.float32),
        "model.norm.weight": rng.randn(H).astype(np.float32),
    }
    kdir = str(tmp_path / "kv")
    save_nxd_checkpoint(kdir, kv_state, tp=2, kv_size_multiplier=2)
    back = load_nxd_checkpoint(kdir, kv_size_multiplier=2)
    np.testing.assert_array_equal(
        back["model.layers.0.self_attn.weight_k"],
        kv_state["model.layers.0.self_attn.weight_k"])


def test_save_nxd_checkpoint_pp_split(tmp_path):
    """``pp_assign`` routes params to stages; each stage's files hold only
    its params and the importer re-merges the union."""
    from neuronx_distributed_tpu.convert import (
        load_nxd_checkpoint,
        save_nxd_checkpoint,
    )

    state = _roundtrip_state(np.random.RandomState(8))
    assign = {k: (1 if k in ("model.norm.weight", "lm_head.weight") else 0)
              for k in state}
    mdir = str(tmp_path / "model")
    files = save_nxd_checkpoint(mdir, state, tp=2, pp=2, pp_assign=assign)
    assert len(files) == 4
    back = load_nxd_checkpoint(mdir)
    assert set(back) == set(state)
    for k in state:
        np.testing.assert_array_equal(back[k], state[k], err_msg=k)
    with pytest.raises(ValueError, match="out of range"):
        save_nxd_checkpoint(str(tmp_path / "bad"), state, pp=2,
                            pp_assign={k: 5 for k in state})


def test_shard_for_rank_indivisible_raises():
    from neuronx_distributed_tpu.convert import shard_for_rank

    with pytest.raises(ValueError, match="divide"):
        shard_for_rank(np.zeros((10, 4), np.float32), 0, tp=4,
                       partition_dim=0)


# -- artifacts: schema, report section, compare gates ------------------------

def _swap_rec(version, ok=True, mono=1.0, source="memory", replica=-1):
    return {"schema": "weight_swap/1", "time": 100.0 + mono, "mono": mono,
            "event": "swap" if ok else "swap_failed", "version": version,
            "source": source, "ok": ok,
            "swap_ms": 2.5 if ok else None,
            "error": None if ok else "injected", "replica": replica}


def _write_jsonl(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(path)


def test_weight_swap_schema_floor():
    validate_record("weight_swap", _swap_rec(1))
    with pytest.raises(ValueError, match="missing required field"):
        validate_record("weight_swap", {"schema": "weight_swap/1"})
    with pytest.raises(ValueError, match="expected"):
        validate_record("weight_swap", dict(_swap_rec(1), version="1"))


def test_summarize_weights_section_and_report(tmp_path):
    from neuronx_distributed_tpu.obs.report import (
        build_report,
        render_markdown,
        summarize_weights,
    )

    assert summarize_weights([str(tmp_path / "absent.jsonl")]) is None
    a = _write_jsonl(tmp_path / "replica0_weight_swaps.jsonl",
                     [_swap_rec(1, mono=1.0, replica=0),
                      _swap_rec(2, mono=2.0, replica=0)])
    b = _write_jsonl(tmp_path / "replica1_weight_swaps.jsonl",
                     [_swap_rec(1, mono=1.5, replica=1),
                      _swap_rec(1, ok=False, mono=2.5, replica=1,
                                source="checkpoint")])
    s = summarize_weights([a, b])
    assert s["swaps"] == 3 and s["failures"] == 1
    assert s["monotonic"] is True
    assert s["replicas"]["0"]["version"] == 2
    assert s["replicas"]["1"]["failures"] == 1
    assert s["by_source"] == {"memory": 3, "checkpoint": 1} or \
        s["by_source"].get("memory", 0) >= 3

    report = build_report(weights_paths=[a, b])
    validate_record("obs_report", report)
    assert report["weights"]["swaps"] == 3
    assert report["health"]["weights"]["failures"] == 1
    assert "live swap" in render_markdown(report)

    # non-monotonic versions are flagged per replica
    c = _write_jsonl(tmp_path / "replica2_weight_swaps.jsonl",
                     [_swap_rec(3, mono=1.0, replica=2),
                      _swap_rec(2, mono=2.0, replica=2)])
    s2 = summarize_weights([c])
    assert s2["monotonic"] is False
    assert s2["replicas"]["2"]["monotonic"] is False


def test_compare_gates_on_new_failures_and_non_monotonic(tmp_path):
    """The threshold-free deploy gates: swap failures appearing in run B
    when every swap in A committed, and any replica's version going
    non-monotonic in B, each regress ``--compare`` on their own."""
    from neuronx_distributed_tpu.obs.report import compare_resources

    run_a = tmp_path / "a"
    run_b = tmp_path / "b"
    run_c = tmp_path / "c"
    for d in (run_a, run_b, run_c):
        d.mkdir()
    _write_jsonl(run_a / "weight_swaps.jsonl", [_swap_rec(1), _swap_rec(2, mono=2.0)])
    _write_jsonl(run_b / "weight_swaps.jsonl",
                 [_swap_rec(1), _swap_rec(2, ok=False, mono=2.0)])
    _write_jsonl(run_c / "weight_swaps.jsonl",
                 [_swap_rec(2), _swap_rec(1, mono=2.0)])

    same = compare_resources(str(run_a), str(run_a))
    assert not [r for r in same["regressions"] if "swap" in r or "monotonic" in r]
    assert not same["regressed"]

    diff = compare_resources(str(run_a), str(run_b))
    assert diff["regressed"]
    assert any("swap failure" in r for r in diff["regressions"])

    diff = compare_resources(str(run_a), str(run_c))
    assert diff["regressed"]
    assert any("monotonic" in r for r in diff["regressions"])
