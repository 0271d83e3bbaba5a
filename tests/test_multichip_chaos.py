"""Multi-chip chaos truth on the 8-virtual-device harness.

Every test here runs a REAL fit on a real multi-axis mesh in a fresh
subprocess (``tests/multidevice_harness.py``), injects a fault from the
compact plan grammar mid-training, and gates on the strictest outcome the
architecture promises: EXACT rollback-and-replay loss parity (the chaos
run's final epoch loss equals the clean run's bit-for-bit, delta 0.0) with
zero supervisor involvement — the recovery is entirely in-process.

Topology x fault coverage:

* ``bitflip`` under tensor parallelism (``{data: 4, model: 2}``): the
  shard-aware SDC audit must name the culprit leaf, shard-group, device
  and replica from checksums alone (0 comm bytes).
* ``nan_loss`` under a pipelined LM (``{data: 2, pipe: 4}``): nonfinite
  detection + rollback, with a 1F1B-schedule step over the recovered
  params pinned bit-identical to the clean run's.
* ``nan_loss`` under ring attention (``{data: 2, seq: 4}``): the fault
  fires inside a step whose attention is a shard_map ring over ``seq``,
  and rollback-and-replay parity holds through that compiled collective
  path exactly as it does for the dense one.
* ``corrupt_batch`` under MoE (``{data: 2, expert: 4}``): garbled token
  ids (out-of-range labels included — what buffer corruption actually
  looks like for an LM batch) surface as a nonfinite loss and roll back.

Plus the PR-13 residual: a collectives-capable ``bootstrap.reinitialize``
proof — an explicit single-process bring-up is a REAL distributed client,
so generation bump means real teardown + re-init on a fresh coordinator
port, with a psum executing before and after.
"""

import numpy as np
import pytest

from tests.multidevice_harness import HarnessFailure, run_with_devices
from tests.multiprocess_harness import free_ports
from tpu_dist.resilience.events import read_events


def _leg_events(tmp_path, name):
    return read_events(tmp_path / f"{name}-events.jsonl")


_CHAOS_PRELUDE = """
import numpy as np

import tpu_dist as td


def _leg_env(workdir, name, plan, audit_n):
    import os

    os.environ.pop("TPU_DIST_FAULT_PLAN", None)
    os.environ["TPU_DIST_INTEGRITY"] = "1"
    os.environ["TPU_DIST_INTEGRITY_BUDGET"] = "3"
    os.environ["TPU_DIST_INTEGRITY_AUDIT_N"] = str(audit_n)
    os.environ["TPU_DIST_EVENT_LOG"] = workdir + "/" + name + "-events.jsonl"
    if plan:
        os.environ["TPU_DIST_FAULT_PLAN"] = plan
"""


class TestChaosParity:
    """One fault kind per parallelism topology, each with exact parity."""

    def test_bitflip_under_tp(self, tmp_path):
        """TP mesh: one mantissa bit flipped in device 5's shard of the
        column-parallel kernel (leaf 1). The audit's shard-group compare
        must name leaf + shard-group + device + replica, the rollback must
        restore the pre-fault epoch checkpoint, and the replayed run must
        land on the clean run's losses EXACTLY — with zero supervisor
        restarts (recovery is all in-process)."""
        body = _CHAOS_PRELUDE + f"""

def leg(name, plan):
    _leg_env({str(tmp_path)!r}, name, plan, audit_n=2)
    strategy = td.MirroredStrategy(axis_shapes={{"data": 4, "model": 2}})
    with strategy.scope():
        m = td.Sequential([td.models.Dense(8, activation="relu"),
                           td.models.Dense(4)], input_shape=(4,))
        m.compile(
            loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
            optimizer=td.ops.SGD(learning_rate=0.1))
        rng = np.random.RandomState(0)
        x = rng.rand(64, 4).astype(np.float32)
        y = rng.randint(0, 4, size=(64,)).astype(np.int32)
        # Cardinality == steps_per_epoch: a rolled-back epoch replays the
        # identical batch sequence, which is what makes parity exact.
        ds = td.data.Dataset.from_tensor_slices((x, y)).batch(16)
        h = m.fit(ds, epochs=3, steps_per_epoch=4, verbose=0,
                  checkpoint_dir={str(tmp_path)!r} + "/" + name + "-ckpt")
    return [float(v) for v in h.history["loss"]]


clean = leg("clean", None)
chaos = leg("chaos", "bitflip@step9:leaf1:replica5")
emit({{"clean": clean, "chaos": chaos}})
"""
        result = run_with_devices(body, 8)
        clean, chaos = result["clean"], result["chaos"]
        # The fault fires at step 9 (epoch 2); epochs 0-1 never saw it and
        # epoch 2 was replayed clean — the WHOLE history matches, and the
        # accepted delta is exactly 0.0, not a tolerance.
        assert chaos == clean
        assert abs(chaos[-1] - clean[-1]) == 0.0

        events = _leg_events(tmp_path, "chaos")
        fired = [e for e in events if e.get("event") == "fault_fired"]
        assert len(fired) == 1 and fired[0]["kind"] == "bitflip"
        assert fired[0]["leaf_index"] == 1
        assert fired[0]["replica"] == 5
        assert fired[0]["effective_bit"] == 22  # f32 leaf: bit as asked

        (sdc,) = [e for e in events if e.get("event") == "integrity_sdc"]
        (culprit,) = sdc["culprits"]
        assert culprit["leaf"] == fired[0]["leaf"]
        assert culprit["replica"] == 5
        assert culprit["device"] == fired[0]["device"]
        # Device 5 on a data-major [4, 2] mesh sits in model column 1 —
        # the audit localized the flip to the right shard group.
        assert culprit["shard_group"] == 1

        (rb,) = [e for e in events if e.get("event") == "integrity_rollback"]
        assert rb["kind"] == "sdc"
        assert rb["restored_step"] == 1  # epoch-1 checkpoint: pre-fault
        assert rb["next_epoch"] == 2
        # Zero supervisor restarts: no worker lifecycle events at all.
        assert not [e for e in events
                    if str(e.get("event", "")).startswith("worker_")]
        assert not [e for e in events
                    if e.get("event") == "integrity_budget_exhausted"]

    # Tier-1 duration audit: ~13s subprocess fit. check.sh's
    # integrity-smoke arms the same nan_loss fault with exact-parity
    # rollback gates on every push, and the ring-attention sibling below
    # keeps a nan-under-exotic-mesh variant in tier-1.
    @pytest.mark.slow
    def test_nan_loss_under_pipeline(self, tmp_path):
        """Pipelined LM on {data: 2, pipe: 4}: a poisoned step-9 batch goes
        nonfinite, rolls back to the epoch-1 checkpoint, and replays to the
        clean run's losses exactly. The recovered params then drive a 1F1B
        train step to the bit-identical loss the clean params produce —
        the schedule-level tie-in for the pipeline chaos story."""
        body = _CHAOS_PRELUDE + f"""
from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.parallel import make_1f1b_train_step

V, L = 29, 16
seq = np.arange(280) * 3 % V
xs = np.stack([seq[i:i + L] for i in range(0, 256, 4)]).astype(np.int32)
ys = np.stack([seq[i + 1:i + L + 1] for i in range(0, 256, 4)]).astype(np.int32)


def leg(name, plan):
    import jax

    _leg_env({str(tmp_path)!r}, name, plan, audit_n=0)
    strategy = td.MirroredStrategy(axis_shapes={{"data": 2, "pipe": 4}})
    with strategy.scope():
        m = build_transformer_lm(V, L, d_model=32, depth=4, num_heads=4,
                                 pipeline_stages=4, pipeline_microbatches=4)
        m.compile(
            loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
            optimizer=td.ops.SGD(learning_rate=0.05))
        ds = td.data.Dataset.from_tensor_slices((xs, ys)).batch(16)
        h = m.fit(ds, epochs=3, steps_per_epoch=4, verbose=0,
                  checkpoint_dir={str(tmp_path)!r} + "/" + name + "-ckpt")
    params = jax.device_get(m._trainer.variables["params"])
    return m, strategy, [float(v) for v in h.history["loss"]], params


m1, s1, clean, p1 = leg("clean", None)
m2, s2, chaos, p2 = leg("chaos", "nan_loss@step9")

loss = td.ops.SparseCategoricalCrossentropy(from_logits=True)
step = make_1f1b_train_step(m2, loss, strategy=s2)
l_clean, _ = step(p1, xs[:16], ys[:16])
l_chaos, _ = step(p2, xs[:16], ys[:16])
emit({{"clean": clean, "chaos": chaos,
      "f1b_clean": float(l_clean), "f1b_chaos": float(l_chaos)}})
"""
        result = run_with_devices(body, 8)
        clean, chaos = result["clean"], result["chaos"]
        assert chaos[-1] == clean[-1]
        assert abs(chaos[-1] - clean[-1]) == 0.0
        # 1F1B over recovered vs clean params: bit-identical loss.
        assert result["f1b_chaos"] == result["f1b_clean"]
        assert np.isfinite(result["f1b_clean"])

        events = _leg_events(tmp_path, "chaos")
        fired = [e for e in events if e.get("event") == "fault_fired"]
        assert len(fired) == 1 and fired[0]["kind"] == "nan_loss"
        (rb,) = [e for e in events if e.get("event") == "integrity_rollback"]
        assert rb["restored_step"] == 1 and rb["next_epoch"] == 2
        assert not [e for e in events
                    if str(e.get("event", "")).startswith("worker_")]

    def test_nan_loss_under_ring_attention(self, tmp_path):
        """Ring-attention LM on {data: 2, seq: 4}: a step-9 nonfinite
        loss rolls back to the epoch-1 checkpoint and replays to the
        clean run's losses EXACTLY. Attention here is the shard_map ring
        over the 'seq' axis (batch kept sharded over 'data'), so the
        rollback/replay path is exercised through a step whose forward
        pass is itself a compiled cross-device collective loop — not the
        dense single-device path the other legs compile."""
        body = _CHAOS_PRELUDE + f"""
import functools

from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.parallel import ring_attention

V, L = 29, 16
seq = np.arange(280) * 7 % V
xs = np.stack([seq[i:i + L] for i in range(0, 256, 4)]).astype(np.int32)
ys = np.stack([seq[i + 1:i + L + 1] for i in range(0, 256, 4)]).astype(np.int32)


def leg(name, plan):
    _leg_env({str(tmp_path)!r}, name, plan, audit_n=0)
    strategy = td.MirroredStrategy(axis_shapes={{"data": 2, "seq": 4}})
    with strategy.scope():
        attn = functools.partial(ring_attention, mesh=strategy.mesh,
                                 axis_name="seq", causal=True,
                                 batch_axis="data")
        m = build_transformer_lm(V, L, d_model=32, depth=2, num_heads=4,
                                 attention_fn=attn)
        m.compile(
            loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
            optimizer=td.ops.SGD(learning_rate=0.05))
        ds = td.data.Dataset.from_tensor_slices((xs, ys)).batch(16)
        h = m.fit(ds, epochs=3, steps_per_epoch=4, verbose=0,
                  checkpoint_dir={str(tmp_path)!r} + "/" + name + "-ckpt")
    return [float(v) for v in h.history["loss"]]


clean = leg("clean", None)
chaos = leg("chaos", "nan_loss@step9")
emit({{"clean": clean, "chaos": chaos}})
"""
        result = run_with_devices(body, 8)
        clean, chaos = result["clean"], result["chaos"]
        assert chaos == clean
        assert abs(chaos[-1] - clean[-1]) == 0.0
        assert all(np.isfinite(v) for v in clean)

        events = _leg_events(tmp_path, "chaos")
        fired = [e for e in events if e.get("event") == "fault_fired"]
        assert len(fired) == 1 and fired[0]["kind"] == "nan_loss"
        (rb,) = [e for e in events if e.get("event") == "integrity_rollback"]
        assert rb["restored_step"] == 1 and rb["next_epoch"] == 2
        assert not [e for e in events
                    if str(e.get("event", "")).startswith("worker_")]

    # Tier-1 duration audit: ~14s subprocess fit. The corrupt-batch
    # rollback-and-replay contract stays in tier-1 in
    # test_integrity.py::TestRollbackAndReplay, expert sharding parity in
    # test_expert.py, and check.sh's multichip-chaos-smoke drives this
    # exact 8-device harness (bitflip_under_tp) on every push.
    @pytest.mark.slow
    def test_corrupt_batch_under_moe(self, tmp_path):
        """MoE LM on {data: 2, expert: 4}: a corrupted token batch (garbled
        ids, out-of-range labels) at step 9 is detected as a nonfinite
        loss, rolled back, and replayed to exact parity — expert-sharded
        params restore bit-faithfully too."""
        body = _CHAOS_PRELUDE + f"""
from tpu_dist.models.transformer import build_transformer_lm

V, L = 29, 16
seq = np.arange(280) * 5 % V
xs = np.stack([seq[i:i + L] for i in range(0, 256, 4)]).astype(np.int32)
ys = np.stack([seq[i + 1:i + L + 1] for i in range(0, 256, 4)]).astype(np.int32)


def leg(name, plan):
    _leg_env({str(tmp_path)!r}, name, plan, audit_n=0)
    strategy = td.MirroredStrategy(axis_shapes={{"data": 2, "expert": 4}})
    with strategy.scope():
        m = build_transformer_lm(V, L, d_model=32, depth=2, num_heads=2,
                                 ff_dim=64, moe_experts=8, moe_groups=8)
        m.compile(
            loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
            optimizer=td.ops.SGD(learning_rate=0.05))
        ds = td.data.Dataset.from_tensor_slices((xs, ys)).batch(16)
        h = m.fit(ds, epochs=3, steps_per_epoch=4, verbose=0,
                  checkpoint_dir={str(tmp_path)!r} + "/" + name + "-ckpt")
    return [float(v) for v in h.history["loss"]]


clean = leg("clean", None)
chaos = leg("chaos", "corrupt_batch@step9")
emit({{"clean": clean, "chaos": chaos}})
"""
        result = run_with_devices(body, 8)
        clean, chaos = result["clean"], result["chaos"]
        assert chaos[-1] == clean[-1]
        assert abs(chaos[-1] - clean[-1]) == 0.0

        events = _leg_events(tmp_path, "chaos")
        fired = [e for e in events if e.get("event") == "fault_fired"]
        assert len(fired) == 1 and fired[0]["kind"] == "corrupt_batch"
        (rb,) = [e for e in events if e.get("event") == "integrity_rollback"]
        assert rb["restored_step"] == 1 and rb["next_epoch"] == 2
        assert not [e for e in events
                    if str(e.get("event", "")).startswith("worker_")]


class TestReinitializeCollectives:
    def test_real_teardown_and_reinit_with_psum(self, tmp_path):
        """PR-13 residual: an EXPLICIT single-process bring-up starts a
        real distributed client, so ``reinitialize`` must really tear the
        clique down and re-dial a fresh coordinator port at g+1 — proven
        by a psum over all 8 devices executing both before and after, and
        by the coordinator address actually changing."""
        port_a, port_b = free_ports(2)
        body = f"""
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_dist.cluster import bootstrap


def coord_addr():
    try:
        from jax._src import distributed

        return str(getattr(distributed.global_state,
                           "coordinator_address", None))
    except Exception:
        return None


bootstrap.initialize(coordinator_address="127.0.0.1:{port_a}",
                     num_processes=1, process_id=0)
gen0 = bootstrap.current_generation()
addr0 = coord_addr()

assert jax.device_count() == _want, jax.device_count()
mesh = Mesh(np.array(jax.devices()), ("d",))
fn = jax.jit(jax.shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                           in_specs=P("d"), out_specs=P(), check_vma=False))
before = float(fn(jnp.arange(8.0))[0])

gen1 = bootstrap.reinitialize(generation=gen0 + 1,
                              coordinator_port={port_b})
addr1 = coord_addr()
after = float(fn(jnp.arange(8.0))[0])

emit({{"gen0": gen0, "gen1": gen1, "before": before, "after": after,
      "addr0": addr0, "addr1": addr1}})
"""
        result = run_with_devices(body, 8, init_backend=False)
        assert result["before"] == 28.0
        assert result["after"] == 28.0  # the collective survives the reform
        assert result["gen1"] == result["gen0"] + 1
        # The re-init really re-dialed: the live client's coordinator
        # address moved to the fresh generation-derived port.
        assert result["addr0"] and str(port_a) in result["addr0"]
        assert result["addr1"] and str(port_b) in result["addr1"]


class TestHarnessFailureModes:
    """run_with_devices failures are NAMED — a hang, a crash, and a torn
    result line must be distinguishable without parsing message text."""

    def test_timeout_is_named(self):
        with pytest.raises(HarnessFailure) as ei:
            run_with_devices("import time\ntime.sleep(600)\n", 2, timeout=3)
        assert ei.value.mode == "timeout"
        assert "timed out" in str(ei.value)

    def test_nonzero_exit_is_named(self):
        with pytest.raises(HarnessFailure) as ei:
            run_with_devices("raise SystemExit(3)\n", 2)
        assert ei.value.mode == "nonzero_exit"
        assert "exited 3" in str(ei.value)

    def test_torn_result_is_named(self):
        body = "print('HARNESS_RESULT:{\"a\": 1', flush=True)\n"
        with pytest.raises(HarnessFailure) as ei:
            run_with_devices(body, 2)
        assert ei.value.mode == "torn_result"
        assert "torn" in str(ei.value)

    def test_no_result_is_named(self):
        with pytest.raises(HarnessFailure) as ei:
            run_with_devices("x = 1\n", 2)
        assert ei.value.mode == "no_result"
