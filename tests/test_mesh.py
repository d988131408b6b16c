"""Mesh / parallel-state tests — analogue of the reference's
``test/integration/parallel_layers/test_parallel_state.py:42-60`` group-math
checks, expressed as mesh-topology assertions."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.mesh import (
    MeshConfig,
    destroy_model_parallel,
    get_data_parallel_size,
    get_kv_size_multiplier,
    get_mesh,
    get_pipeline_parallel_size,
    get_tensor_parallel_size,
    initialize_model_parallel,
    model_parallel_is_initialized,
)


def test_default_init_is_all_dp():
    mesh = initialize_model_parallel()
    n = len(jax.devices())
    assert get_data_parallel_size() == n
    assert get_tensor_parallel_size() == 1
    assert get_pipeline_parallel_size() == 1
    assert mesh.shape["dp"] == n


def test_tp_dp_split(devices8):
    initialize_model_parallel(tensor_parallel_size=4, devices=devices8)
    assert get_tensor_parallel_size() == 4
    assert get_data_parallel_size() == 2
    assert get_pipeline_parallel_size() == 1


def test_tp_pp_dp_split(devices8):
    initialize_model_parallel(
        tensor_parallel_size=2, pipeline_parallel_size=2, devices=devices8
    )
    assert get_tensor_parallel_size() == 2
    assert get_pipeline_parallel_size() == 2
    assert get_data_parallel_size() == 2


def test_tp_contiguity(devices8):
    """TP ranks must be adjacent device ids (reference builds contiguous TP
    groups, parallel_state.py:109-122) so TP collectives ride ICI."""
    mesh = initialize_model_parallel(tensor_parallel_size=4, devices=devices8)
    arr = mesh.devices  # shape (dp, ep, pp, cp, kvr, tp)
    ids = np.vectorize(lambda d: d.id)(arr)
    flat_tp0 = ids[0, 0, 0, 0].flatten()
    assert list(flat_tp0) == [0, 1, 2, 3]


def test_kv_multiplier_axes(devices8):
    mesh = initialize_model_parallel(
        tensor_parallel_size=8, kv_size_multiplier=2, devices=devices8
    )
    assert get_tensor_parallel_size() == 8  # combined kvr*tp
    assert get_kv_size_multiplier() == 2
    assert mesh.shape["kvr"] == 2
    assert mesh.shape["tp"] == 4


def test_invalid_sizes(devices8):
    with pytest.raises(ValueError):
        initialize_model_parallel(tensor_parallel_size=3, devices=devices8)
    destroy_model_parallel()
    with pytest.raises(ValueError):
        initialize_model_parallel(tensor_parallel_size=4, kv_size_multiplier=3, devices=devices8)


def test_double_init_raises(devices8):
    initialize_model_parallel(devices=devices8)
    with pytest.raises(RuntimeError):
        initialize_model_parallel(devices=devices8)


def test_destroy_and_reinit(devices8):
    initialize_model_parallel(devices=devices8)
    assert model_parallel_is_initialized()
    destroy_model_parallel()
    assert not model_parallel_is_initialized()
    initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    assert get_tensor_parallel_size() == 2


def test_mesh_config_model_parallel_size():
    cfg = MeshConfig(tensor_parallel_size=8, pipeline_parallel_size=4, context_parallel_size=2)
    assert cfg.model_parallel_size == 64


def test_sharding_roundtrip(devices8):
    """An array sharded over ('kvr','tp') splits across the full TP degree."""
    initialize_model_parallel(tensor_parallel_size=8, kv_size_multiplier=2, devices=devices8)
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    sharded = jax.device_put(x, mesh_lib.named_sharding(None, mesh_lib.TENSOR_AXES))
    assert len(sharded.addressable_shards) == 8
    assert sharded.addressable_shards[0].data.shape == (8, 1)
    np.testing.assert_array_equal(np.asarray(sharded), x)


def test_explicit_data_parallel_size_with_ep(devices8):
    mesh_lib.initialize_model_parallel(expert_parallel_size=2, data_parallel_size=8, devices=jax.devices()[:8])
    assert get_data_parallel_size() == 8
    mesh_lib.destroy_model_parallel()
    with pytest.raises(ValueError):
        mesh_lib.initialize_model_parallel(expert_parallel_size=2, data_parallel_size=4, devices=jax.devices()[:8])


def test_mesh_context_derives_config(devices8):
    from neuronx_distributed_tpu.parallel.mesh import get_mesh_config, mesh_context
    m = initialize_model_parallel(tensor_parallel_size=4, devices=devices8)
    destroy_model_parallel()
    with mesh_context(m):
        cfg = get_mesh_config()
        assert cfg.tensor_parallel_size == 4
        assert cfg.data_parallel_size == 2
    assert not model_parallel_is_initialized()


def test_training_config_sub_objects():
    from neuronx_distributed_tpu.config import training_config
    cfg = training_config(mesh=MeshConfig(tensor_parallel_size=2), policy="full", schedule="gpipe")
    assert cfg.mesh.tensor_parallel_size == 2
    assert cfg.activation_checkpoint.policy == "full"
    assert cfg.pipeline.schedule == "gpipe"
    with pytest.raises(TypeError):
        training_config(mesh=MeshConfig(), tensor_parallel_size=2)


def test_multislice_device_layout():
    """Multi-slice jobs split dp across slices so only gradient traffic rides
    DCN (mesh-layout form of the reference's EFA-across-nodes topology,
    run_llama_70b_tp_pp.sh:7-15); a non-divisible dp must error clearly."""
    from unittest import mock

    from neuronx_distributed_tpu.parallel.mesh import _build_device_array

    class FakeDev:
        platform = "tpu"

        def __init__(self, i, slice_index):
            self.id = i
            self.slice_index = slice_index

        def __repr__(self):
            return f"d{self.id}@s{self.slice_index}"

    devs = [FakeDev(i, i // 4) for i in range(8)]  # 2 slices x 4 devices

    captured = {}

    def fake_hybrid(local_shape, dcn_shape, devices=None):
        captured["local"] = tuple(local_shape)
        captured["dcn"] = tuple(dcn_shape)
        import numpy as np

        return np.asarray(devices).reshape(tuple(d * l for d, l in zip(dcn_shape, local_shape)))

    with mock.patch("jax.experimental.mesh_utils.create_hybrid_device_mesh", fake_hybrid):
        arr = _build_device_array(devs, (4, 1, 1, 1, 1, 2))  # dp=4, tp=2
    assert captured["dcn"] == (2, 1, 1, 1, 1, 1)
    assert captured["local"] == (2, 1, 1, 1, 1, 2)
    assert arr.shape == (4, 1, 1, 1, 1, 2)

    # dp=1 over 2 slices (pp/tp across DCN) is legitimate: create_device_mesh
    # chooses the layout
    def fake_mesh(shape, devices=None):
        import numpy as np

        captured["single"] = tuple(shape)
        return np.asarray(devices).reshape(tuple(shape))

    with mock.patch("jax.experimental.mesh_utils.create_device_mesh", fake_mesh):
        arr2 = _build_device_array(devs, (1, 1, 1, 1, 1, 8))
    assert captured["single"] == arr2.shape == (1, 1, 1, 1, 1, 8)

    # what mesh_utils cannot lay out is an error: a real TPU mesh never
    # falls back to a topology-blind reshape behind the caller's back
    def refuse(shape, devices=None):
        raise NotImplementedError("no such topology")

    with mock.patch("jax.experimental.mesh_utils.create_device_mesh", refuse):
        with pytest.raises(NotImplementedError, match="no such topology"):
            _build_device_array(devs[:4], (1, 1, 1, 1, 1, 4))
