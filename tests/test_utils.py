"""Utils: timeline trace format, head padding parity, serialization
roundtrips, distributed wrappers (single-process semantics)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.parallel.pad import (
    pad_axis_to,
    pad_llama_params,
    pad_to_multiple,
)
from neuronx_distributed_tpu.utils.distributed import (
    broadcast_from_host0,
    initialize_distributed,
    is_primary,
    rendezvous,
)
from neuronx_distributed_tpu.utils.serialization import (
    TensorMeta,
    decode_obj,
    deserialize_tree,
    encode_obj,
    find_loss_from_output_and_spec,
    serialize_tree,
)
from neuronx_distributed_tpu.utils.timeline import Timeline


def test_timeline_writes_chrome_trace(tmp_path):
    path = str(tmp_path / "trace.json")
    tl = Timeline(path)
    with tl.event("outer"):
        tl.mark_event_start("inner")
        tl.mark_event_end("inner")
    tl.mark_step_end(step=0)
    with tl.event("second_flush"):
        pass
    tl.mark_step_end(step=1)

    raw = open(path).read()
    events = json.loads(raw.rstrip().rstrip(",") + "]")  # perfetto-style open array
    names = [e["name"] for e in events]
    assert "outer" in names and "inner" in names and "second_flush" in names
    outer = next(e for e in events if e["name"] == "outer")
    inner = next(e for e in events if e["name"] == "inner")
    assert outer["ph"] == "X" and outer["dur"] >= inner["dur"]


def test_timeline_disabled_is_noop():
    tl = Timeline(None)
    with tl.event("x"):
        pass
    tl.mark_step_end()  # must not raise or write


def test_pad_helpers():
    assert pad_to_multiple(6, 8) == 8
    assert pad_to_multiple(8, 8) == 8
    x = jnp.ones((2, 3))
    y = pad_axis_to(x, 1, 5)
    assert y.shape == (2, 5) and float(y[:, 3:].sum()) == 0.0
    with pytest.raises(ValueError):
        pad_axis_to(x, 1, 2)


def test_padded_llama_matches_unpadded(devices8):
    """6-head model padded to 8 heads for tp=8 must compute identical logits
    (the reference pad_model invariant, parallel_layers/pad.py:7-103)."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)

    nxd.initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    cfg6 = LlamaConfig.tiny(num_heads=6, num_kv_heads=6, head_dim=8, remat="none",
                            sequence_parallel=False,
                            dtype=jnp.float32, param_dtype=jnp.float32)
    model6 = LlamaForCausalLM(cfg6)
    from flax import linen as nn

    params6 = nn.unbox(model6.init(jax.random.PRNGKey(1), ids))
    want = np.asarray(jax.jit(model6.apply)(params6, ids))
    nxd.destroy_model_parallel()

    # pad to 8 heads and run TP=8
    nxd.initialize_model_parallel(tensor_parallel_size=8, devices=devices8)
    cfg8 = LlamaConfig.tiny(num_heads=8, num_kv_heads=8, head_dim=8, remat="none",
                            sequence_parallel=False,
                            dtype=jnp.float32, param_dtype=jnp.float32)
    model8 = LlamaForCausalLM(cfg8)
    params8 = pad_llama_params(params6, old_heads=6, new_heads=8, head_dim=8)
    # sanity: padded tree matches the 8-head model's shapes
    shapes8 = jax.tree.map(jnp.shape, nn.unbox(model8.init(jax.random.PRNGKey(2), ids)))
    assert jax.tree.map(jnp.shape, params8) == shapes8
    from flax.core import freeze  # noqa: F401  (params are plain dicts here)

    from jax.sharding import NamedSharding
    from neuronx_distributed_tpu.parallel.mesh import get_mesh

    specs = nn.get_partition_spec(model8.init(jax.random.PRNGKey(2), ids))
    mesh = get_mesh()
    from jax.sharding import PartitionSpec as P

    p8 = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params8, specs,
        is_leaf=lambda x: isinstance(x, P) or not isinstance(x, dict),
    )
    got = np.asarray(jax.jit(model8.apply)(p8, ids))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_serialize_tree_roundtrip():
    tree = {
        "a": jnp.arange(6).reshape(2, 3),
        "b": {"c": np.ones((4,), np.float32), "d": "metadata", "e": 7},
    }
    skeleton, arrays = serialize_tree(tree)
    assert isinstance(skeleton["a"], TensorMeta) and skeleton["b"]["d"] == "metadata"
    assert len(arrays) == 2
    back = deserialize_tree(skeleton, arrays)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(back["b"]["c"]), tree["b"]["c"])

    with pytest.raises(ValueError, match="mismatch"):
        deserialize_tree(skeleton, [arrays[1], arrays[0]])


def test_find_loss_from_output_and_spec():
    out = {"loss": jnp.float32(1.5), "logits": jnp.zeros((2, 3))}
    spec = {"loss": True, "logits": None}
    assert float(find_loss_from_output_and_spec(out, spec)) == 1.5
    assert float(find_loss_from_output_and_spec(jnp.float32(2.0), True)) == 2.0
    with pytest.raises(ValueError, match="exactly one"):
        find_loss_from_output_and_spec(out, {"loss": True, "logits": True})


def test_obj_codec_roundtrip():
    obj = {"shapes": [(1, 2), (3,)], "tag": "step_5"}
    assert decode_obj(encode_obj(obj)) == obj


def test_distributed_single_process():
    initialize_distributed()  # no coordinator → no-op
    rendezvous("test")  # single process → no-op
    assert is_primary()
    tree = {"x": jnp.ones((2,))}
    out = broadcast_from_host0(tree)
    np.testing.assert_array_equal(np.asarray(out["x"]), np.ones((2,)))


def test_padded_gqa_llama_matches_unpadded(devices8):
    """GQA padding must preserve the q-per-kv grouping: 6q/3kv -> 8q/4kv."""
    from conftest import sharded_params
    from flax import linen as nn
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    nxd.initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(num_heads=6, num_kv_heads=3, head_dim=8, remat="none",
                           sequence_parallel=False,
                           dtype=jnp.float32, param_dtype=jnp.float32)
    m = LlamaForCausalLM(cfg)
    p = nn.unbox(m.init(jax.random.PRNGKey(1), ids))
    want = np.asarray(jax.jit(m.apply)(p, ids))
    nxd.destroy_model_parallel()

    nxd.initialize_model_parallel(tensor_parallel_size=4, devices=devices8[:4])
    cfg8 = LlamaConfig.tiny(num_heads=8, num_kv_heads=4, head_dim=8, remat="none",
                            sequence_parallel=False,
                            dtype=jnp.float32, param_dtype=jnp.float32)
    m8 = LlamaForCausalLM(cfg8)
    p8 = pad_llama_params(p, old_heads=6, new_heads=8, head_dim=8,
                          old_kv_heads=3, new_kv_heads=4)
    got = np.asarray(jax.jit(m8.apply)(p8, ids))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    with pytest.raises(ValueError, match="group size"):
        pad_llama_params(p, 6, 8, 8, old_kv_heads=3, new_kv_heads=8)


def test_cost_report_and_roofline():
    from neuronx_distributed_tpu.utils.profiling import jit_cost_report

    import jax.numpy as jnp

    a = jnp.ones((256, 256), jnp.float32)
    rep = jit_cost_report(lambda x: x @ x, a, peak_flops=1e12, hbm_bytes_per_s=1e11)
    # 2*256^3 = 33.5 MFLOP; CPU backend reports cost analysis too
    assert rep["cost"].get("flops", 0) >= 2 * 256**3 * 0.9
    rl = rep["roofline"]
    assert rl["lower_bound_s"] == max(rl["compute_s"], rl["memory_s"]) > 0
    assert rl["bound"] in ("compute", "memory")


# -- device specs -------------------------------------------------------------

def test_device_spec_is_keyed_by_device_kind():
    from types import SimpleNamespace as NS

    from neuronx_distributed_tpu.utils.profiling import device_spec

    # exact device_kind strings, both spellings jax knows a chip under
    assert device_spec(NS(device_kind="TPU v5 lite")).peak_flops == 197e12
    assert device_spec(NS(device_kind="TPU v5e")).hbm_bytes_per_s == 819e9
    assert device_spec(NS(device_kind="TPU v5p")).peak_flops == 459e12
    assert device_spec(NS(device_kind="TPU v6 lite")).kind == "TPU v6 lite"


def test_unknown_device_kind_raises():
    """One peak table, no default: an unknown accelerator — and the CPU
    the tests run on — has no roofline; a caller that wants a cost model
    there passes one explicitly."""
    from types import SimpleNamespace as NS

    from neuronx_distributed_tpu.utils.profiling import (
        UnknownDeviceError,
        calibrate_cpu_spec,
        device_spec,
    )

    with pytest.raises(UnknownDeviceError, match="mystery accelerator"):
        device_spec(NS(device_kind="mystery accelerator"))
    with pytest.raises(UnknownDeviceError):
        device_spec()  # jax.devices()[0] is the CPU here
    a = calibrate_cpu_spec()
    assert a is calibrate_cpu_spec()    # calibrated once, cached
    assert a.kind == "cpu" and a.peak_flops >= 1e9 and a.hbm_bytes_per_s >= 1e9


# -- cost model ---------------------------------------------------------------

def test_cost_report_defaults_missing_keys_to_zero():
    from conftest import FakeCompiled
    from neuronx_distributed_tpu.utils.profiling import cost_report

    rep = cost_report(FakeCompiled({"flops": 5.0}))
    assert rep["flops"] == 5.0
    assert rep["bytes_accessed"] == 0.0         # defaulted, not absent
    assert rep["transcendentals"] == 0.0
    assert rep["cost_keys_missing"] == 2
    full = cost_report(FakeCompiled(
        {"flops": 1.0, "bytes accessed": 2.0, "transcendentals": 3.0}))
    assert "cost_keys_missing" not in full
