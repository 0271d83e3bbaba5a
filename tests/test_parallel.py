"""Mesh / strategy / collectives tests on the 8-device virtual CPU mesh.

SURVEY.md §4 test plan item 2: single-process multi-device is the JAX analog
of TF's MirroredStrategy tests; the key invariant asserted here is the
strategy contract from tf:python/distribute/strategy_test_lib.py — replicated
variable placement, reduce semantics, and grad-psum == single-device gradient
of the concatenated batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from tpu_dist.parallel import (
    CollectiveCommunication,
    MirroredStrategy,
    MultiWorkerMirroredStrategy,
    ParameterServerStrategy,
    ReduceOp,
    DefaultStrategy,
    all_reduce,
    get_strategy,
    make_mesh,
    replicate,
    shard_batch,
)


class TestMesh:
    def test_default_mesh_all_devices(self, eight_devices):
        mesh = make_mesh()
        assert mesh.axis_names == ("data",)
        assert mesh.devices.size == 8

    def test_explicit_axes_with_inference(self, eight_devices):
        mesh = make_mesh({"data": -1, "model": 2})
        assert dict(mesh.shape) == {"data": 4, "model": 2}

    def test_bad_shapes_raise(self, eight_devices):
        with pytest.raises(ValueError):
            make_mesh({"data": 3})  # 8 not divisible
        with pytest.raises(ValueError):
            make_mesh({"data": -1, "model": -1})

    def test_replicate_places_on_every_device(self, eight_devices):
        mesh = make_mesh()
        params = {"w": np.ones((4, 4), np.float32), "b": np.zeros((4,), np.float32)}
        placed = replicate(params, mesh)
        assert placed["w"].sharding.is_fully_replicated
        assert len(placed["w"].addressable_shards) == 8
        np.testing.assert_array_equal(np.asarray(placed["w"]), params["w"])

    def test_shard_batch_splits_leading_dim(self, eight_devices):
        mesh = make_mesh()
        batch = {"x": np.arange(32, dtype=np.float32).reshape(16, 2)}
        placed = shard_batch(batch, mesh)
        shards = placed["x"].addressable_shards
        assert len(shards) == 8
        assert all(s.data.shape == (2, 2) for s in shards)
        np.testing.assert_array_equal(np.asarray(placed["x"]), batch["x"])


class TestStrategies:
    def test_mirrored_uses_all_local_devices(self, eight_devices):
        s = MirroredStrategy()
        assert s.num_replicas_in_sync == 8

    def test_mirrored_explicit_devices(self, eight_devices):
        s = MirroredStrategy(devices=eight_devices[:4])
        assert s.num_replicas_in_sync == 4

    def test_scope_sets_current(self, eight_devices):
        s = MirroredStrategy()
        assert isinstance(get_strategy(), DefaultStrategy)
        with s.scope():
            assert get_strategy() is s
        assert isinstance(get_strategy(), DefaultStrategy)

    def test_multiworker_single_process_degrades_to_local(self, eight_devices,
                                                          monkeypatch):
        # README.md:34: 1 worker / no cluster -> MirroredStrategy behavior.
        monkeypatch.delenv("TF_CONFIG", raising=False)
        s = MultiWorkerMirroredStrategy(
            communication=CollectiveCommunication.AUTO)
        assert s.num_replicas_in_sync == 8
        assert s.is_chief

    def test_multiworker_accepts_reference_enum_strings(self, eight_devices):
        for name in ("AUTO", "RING", "NCCL"):
            s = MultiWorkerMirroredStrategy(communication=name)
            assert s.communication in (CollectiveCommunication[name],)

    def test_parameter_server_is_a_real_strategy_now(self, tmp_path):
        """The long-documented non-goal is a second execution model since
        PR 18: a PS scope needs a session directory (loud ValueError naming
        the env knob, not a NotImplementedError stub) and a worker scope is
        single-device and collective-free by construction."""
        with pytest.raises(ValueError, match="TPU_DIST_PS_DIR"):
            ParameterServerStrategy()
        s = ParameterServerStrategy(str(tmp_path), role="worker", rank=1,
                                    num_workers=2, staleness=3, sync=False)
        assert s.is_worker and not s.is_server
        assert (s.rank, s.num_workers, s.staleness) == (1, 2, 3)
        # Single-device mesh: nothing to psum across, even by accident.
        assert s.mesh.devices.size == 1
        assert s.num_replicas_in_sync == 1


class TestCollectives:
    def test_grad_psum_equals_concatenated_batch_grad(self, eight_devices):
        """The core sync-DP invariant (SURVEY.md §4 item 2): mean-grad over a
        sharded global batch with replicated params == the single-device
        gradient of the full batch."""
        s = MirroredStrategy()
        w = np.ones((4, 1), np.float32)
        x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
        y = np.random.RandomState(1).randn(16, 1).astype(np.float32)

        def loss(w, x, y):
            return jnp.mean((x @ w - y) ** 2)

        # Distributed: batch sharded, params replicated; XLA inserts the
        # all-reduce because the grad output must be replicated.
        wd = replicate({"w": w}, s.mesh)["w"]
        xd, yd = shard_batch((x, y), s.mesh)
        g_dist = jax.jit(
            jax.grad(loss),
            out_shardings=s.param_sharding(),
        )(wd, xd, yd)
        # Single-device reference on the concatenated batch.
        g_ref = jax.grad(loss)(w, x, y)
        np.testing.assert_allclose(np.asarray(g_dist), g_ref, rtol=1e-5)

    def test_all_reduce_ops_under_shard_map(self, eight_devices):
        mesh = make_mesh()
        x = np.arange(8, dtype=np.float32)

        def f(x):
            return (
                all_reduce(x, "data", ReduceOp.SUM),
                all_reduce(x, "data", ReduceOp.MEAN),
                all_reduce(x, "data", ReduceOp.MAX),
            )

        smap = jax.shard_map(f, mesh=mesh, in_specs=PartitionSpec("data"),
                             out_specs=PartitionSpec("data"))
        ssum, smean, smax = jax.jit(smap)(x)
        np.testing.assert_allclose(np.asarray(ssum), np.full(8, x.sum()))
        np.testing.assert_allclose(np.asarray(smean), np.full(8, x.mean()))
        np.testing.assert_allclose(np.asarray(smax), np.full(8, x.max()))

    def test_mean_is_sum_div_group_size(self, eight_devices):
        # MEAN = SUM / group_size (tf:...cross_device_ops.py:1170-1180).
        mesh = make_mesh()
        x = np.random.RandomState(2).randn(8).astype(np.float32)

        def f(x):
            s = all_reduce(x, "data", ReduceOp.SUM)
            m = all_reduce(x, "data", ReduceOp.MEAN)
            return s / 8.0 - m

        smap = jax.shard_map(f, mesh=mesh, in_specs=PartitionSpec("data"),
                             out_specs=PartitionSpec("data"))
        np.testing.assert_allclose(np.asarray(jax.jit(smap)(x)),
                                   np.zeros(8), atol=1e-6)

    def test_communication_enum_resolve(self):
        assert CollectiveCommunication.resolve(None) is CollectiveCommunication.AUTO
        assert CollectiveCommunication.resolve("ring") is CollectiveCommunication.RING
        assert (CollectiveCommunication.resolve(CollectiveCommunication.ICI)
                is CollectiveCommunication.ICI)
