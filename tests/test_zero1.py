"""ZeRO-1 sharded weight update (distribute(zero=1), parallel/zero.py).

The contract under test: reduce-scatter grads -> per-shard optimizer
update -> all-gather params is NUMERICALLY the replicated update — only
the layout of the update computation and the opt-state residency change.
Runs on the 8-device virtual CPU mesh the conftest configures.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.data import DataSet, NumpyDataSetIterator
from deeplearning4j_tpu.models import SequentialModel
from deeplearning4j_tpu.nn import Adam
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf import (
    Dense,
    InputType,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.losses import Loss
from deeplearning4j_tpu.parallel import ParallelConfig, distribute
from deeplearning4j_tpu.parallel import zero as zmod
from deeplearning4j_tpu.runtime.mesh import DATA_AXIS
from deeplearning4j_tpu.train.listeners import TrainingListener

N_DEV = 8
IN = 8      # divisible by the mesh width -> first Dense W shards


def two_class_data(n=256, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, IN)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(axis=1) > 0).astype(int)]
    return x, y


def mlp_conf(seed=9):
    return (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Adam(1e-2))
        .activation(Activation.RELU)
        .list()
        .layer(Dense(n_out=32))
        .layer(Dense(n_out=32))
        .layer(OutputLayer(n_out=2, loss=Loss.MCXENT,
                           activation=Activation.SOFTMAX))
        .set_input_type(InputType.feed_forward(IN))
        .build()
    )


def params_allclose(a, b, rtol=2e-4, atol=2e-5):
    for lname in a:
        for pname in a[lname]:
            np.testing.assert_allclose(
                np.asarray(a[lname][pname]), np.asarray(b[lname][pname]),
                rtol=rtol, atol=atol, err_msg=f"{lname}/{pname}",
            )


def opt_specs(model):
    return {
        str(getattr(leaf, "sharding", None) and leaf.sharding.spec)
        for leaf in jax.tree.leaves(model.opt_state)
    }


# ---------------------------------------------------------------------------
class TestNumericsParity:
    def test_sharded_matches_replicated_across_fit_evaluate(self):
        """Same seed, same feed, interleaved fit/evaluate: the ZeRO-1
        param trajectory must match the replicated one within f32
        tolerance, and evaluate() (replicated params path) must agree."""
        x, y = two_class_data(256)
        it = lambda s: NumpyDataSetIterator(x, y, batch_size=64, seed=s)

        rep = SequentialModel(mlp_conf()).init()
        distribute(rep, ParallelConfig(data=N_DEV, zero=0))
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))

        rep.fit(it(3), epochs=2)
        z.fit(it(3), epochs=2)
        params_allclose(rep.params, z.params)

        # an evaluate() between fits must not perturb either stream
        acc_rep = rep.evaluate(DataSet(x, y)).accuracy()
        acc_z = z.evaluate(DataSet(x, y)).accuracy()
        assert acc_rep == pytest.approx(acc_z, abs=0.02)

        rep.fit(it(5), epochs=1)
        z.fit(it(5), epochs=1)
        params_allclose(rep.params, z.params)

    def test_sharded_matches_single_device(self):
        """Transitively with test_parallel's DP parity: ZeRO-1 == pure
        DP == single device."""
        x, y = two_class_data(256)
        it = lambda s: NumpyDataSetIterator(x, y, batch_size=64, seed=s)
        single = SequentialModel(mlp_conf()).init()
        single.fit(it(3), epochs=3)
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        z.fit(it(3), epochs=3)
        params_allclose(single.params, z.params)

    def test_graph_model_sharded_update(self):
        from deeplearning4j_tpu.models.computation_graph import GraphModel
        from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder

        def gconf():
            return (
                GraphBuilder()
                .updater(Adam(1e-2))
                .seed(9)
                .add_inputs("in")
                .set_input_types(InputType.feed_forward(IN))
                .add_layer("d", Dense(n_out=32), "in")
                .add_layer(
                    "out",
                    OutputLayer(n_out=2, loss=Loss.MCXENT,
                                activation=Activation.SOFTMAX),
                    "d",
                )
                .set_outputs("out")
                .build()
            )

        x, y = two_class_data(128)
        batches = [DataSet(x[i:i + 32], y[i:i + 32]) for i in range(0, 128, 32)]
        rep = GraphModel(gconf()).init()
        distribute(rep, ParallelConfig(data=N_DEV))
        z = GraphModel(gconf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        for b in batches:
            rep.fit_batch(b)
            z.fit_batch(b)
        assert any(DATA_AXIS in s for s in opt_specs(z))
        for pk in rep.params:
            for pn in rep.params[pk]:
                np.testing.assert_allclose(
                    np.asarray(rep.params[pk][pn]),
                    np.asarray(z.params[pk][pn]),
                    rtol=2e-4, atol=2e-5,
                )


class TestPlacement:
    def test_opt_state_actually_sharded_and_params_replicated(self):
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        specs = opt_specs(z)
        assert any(DATA_AXIS in s for s in specs), specs
        # params stay replicated (ZeRO-1, not ZeRO-3)
        for leaf in jax.tree.leaves(z.params):
            assert str(leaf.sharding.spec) == "PartitionSpec()"
        # the divisible leaves' per-replica bytes shrink 1/n
        rep = SequentialModel(mlp_conf()).init()
        distribute(rep, ParallelConfig(data=N_DEV))
        b_z = zmod.opt_state_bytes_per_replica(z.opt_state)
        b_rep = zmod.opt_state_bytes_per_replica(rep.opt_state)
        assert b_z < b_rep
        # stays sharded THROUGH training (donated buffers round-trip)
        x, y = two_class_data(128)
        z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=1), epochs=1)
        assert any(DATA_AXIS in s for s in opt_specs(z))
        assert zmod.opt_state_bytes_per_replica(z.opt_state) == b_z

    def test_step_programs_registered_with_zero_marker(self):
        from deeplearning4j_tpu.observe import cost

        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        x, y = two_class_data(64)
        z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=1), epochs=1)
        assert any("zero1" in k for k in z._step_fns)
        recs = [r for r in cost.registry().programs()
                if r.owner_ref() is z and r.kind.startswith("train")]
        assert recs and all("zero1" in str(r.key) for r in recs)

    def test_redistribute_without_zero_clears_placement(self):
        m = SequentialModel(mlp_conf()).init()
        distribute(m, ParallelConfig(data=N_DEV, zero=1))
        assert m._zero_placement is not None
        distribute(m, ParallelConfig(data=N_DEV))
        assert m._zero_placement is None
        for leaf in jax.tree.leaves(m.opt_state):
            assert str(leaf.sharding.spec) == "PartitionSpec()"

    def test_env_knob_enables_zero(self, monkeypatch):
        from deeplearning4j_tpu.runtime.flags import environment

        monkeypatch.setattr(environment(), "zero", 1)
        m = SequentialModel(mlp_conf()).init()
        distribute(m, ParallelConfig(data=N_DEV))        # zero=None -> env
        assert m._zero_placement is not None
        # explicit zero=0 overrides the env knob
        m2 = SequentialModel(mlp_conf()).init()
        distribute(m2, ParallelConfig(data=N_DEV, zero=0))
        assert m2._zero_placement is None

    def test_composition_errors(self):
        m = SequentialModel(mlp_conf()).init()
        with pytest.raises(ValueError, match="pure data parallelism"):
            distribute(m, ParallelConfig(data=2, model=4, zero=1))
        with pytest.raises(ValueError, match="pure data parallelism"):
            distribute(
                m, ParallelConfig(data=N_DEV, zero=1,
                                  grad_compression="int8"),
            )
        with pytest.raises(ValueError, match="zero stage"):
            distribute(m, ParallelConfig(data=N_DEV, zero=3))

    def test_spec_rule_prefers_largest_divisible_dim(self):
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.parallel.strategy import zero1_spec_for_leaf

        a = np.zeros((5, 5, 1, 32), np.float32)     # conv HWIO: O shards
        assert zero1_spec_for_leaf(a, 8) == P(None, None, None, DATA_AXIS)
        b = np.zeros((16, 4), np.float32)
        assert zero1_spec_for_leaf(b, 8) == P(DATA_AXIS)
        c = np.zeros((2450, 500), np.float32)       # nothing divides 8
        assert zero1_spec_for_leaf(c, 8) == P()
        d = np.zeros((), np.float32)
        assert zero1_spec_for_leaf(d, 8) == P()


class TestCheckpointRoundTrip:
    def test_zip_checkpoint_save_restore_resume(self, tmp_path):
        """ModelSerializer path: save a ZeRO model, restore, re-place
        into a fresh distributed model, resume training — trajectory
        matches an uninterrupted run."""
        from deeplearning4j_tpu.train.checkpoint import ModelSerializer

        x, y = two_class_data(128)
        it = lambda s: NumpyDataSetIterator(x, y, batch_size=64, seed=s)

        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        z.fit(it(3), epochs=1)
        path = str(tmp_path / "zero.zip")
        ModelSerializer.write_model(z, path)

        restored = ModelSerializer.restore(path)
        distribute(restored, ParallelConfig(data=N_DEV, zero=1))
        assert any(DATA_AXIS in s for s in opt_specs(restored))
        for a, b in zip(jax.tree.leaves(z.opt_state),
                        jax.tree.leaves(restored.opt_state)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            )
        restored.fit(it(5), epochs=1)
        z.fit(it(5), epochs=1)
        params_allclose(z.params, restored.params)

    def test_orbax_sharded_checkpoint_gather_free_round_trip(self, tmp_path):
        """ShardedCheckpointer saves the ZeRO opt state PER SHARD and
        restores each leaf directly into its sharding — no host-side
        full-tree materialization, byte-exact round-trip, training
        resumes."""
        pytest.importorskip("orbax.checkpoint")
        from deeplearning4j_tpu.train.sharded_checkpoint import (
            ShardedCheckpointer,
        )

        x, y = two_class_data(128)
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=3), epochs=1)

        ck = ShardedCheckpointer(str(tmp_path / "ck"), async_save=False)
        step = ck.save(z)
        ck.wait()

        m2 = SequentialModel(mlp_conf()).init()
        distribute(m2, ParallelConfig(data=N_DEV, zero=1))
        ck.restore_into(m2, step)
        assert any(DATA_AXIS in s for s in opt_specs(m2))
        for a, b in zip(jax.tree.leaves(z.opt_state),
                        jax.tree.leaves(m2.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        m2.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=5), epochs=1)
        assert np.isfinite(m2.score_value)
        ck.close()


class TestShardAwareGuards:
    def test_listener_stashing_sharded_opt_state_trips_guard(self):
        class Stasher(TrainingListener):
            def iteration_done(self, model, iteration, epoch, score):
                self.stash = model.opt_state

        x, y = two_class_data(128)
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        z.set_listeners(Stasher())
        with pytest.raises(RuntimeError, match="DONATES"):
            z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=1),
                  epochs=1)

    def test_shard_view_alias_cannot_dodge_guard(self):
        """A listener stashing per-shard VIEWS (different Python
        objects, same device buffers) must still trip — buffer-pointer
        tracking, not id() tracking."""
        class ShardStasher(TrainingListener):
            def iteration_done(self, model, iteration, epoch, score):
                leaf = jax.tree.leaves(model.opt_state)[1]
                self.stash = [s.data for s in leaf.addressable_shards]

        x, y = two_class_data(128)
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        z.set_listeners(ShardStasher())
        with pytest.raises(RuntimeError, match="DONATES"):
            z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=1),
                  epochs=1)

    def test_copying_listener_passes(self):
        class Copier(TrainingListener):
            def iteration_done(self, model, iteration, epoch, score):
                self.snapshot = jax.tree.map(
                    lambda a: np.asarray(a), model.opt_state
                )

        x, y = two_class_data(128)
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        z.set_listeners(Copier())
        z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=1), epochs=1)
        assert z.iteration == 2


class TestRecoveryPlacement:
    def test_policy_attaches_to_single_process_distributed_model(self):
        from deeplearning4j_tpu.train.recovery import RecoveryPolicy

        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        policy = RecoveryPolicy(store=None)
        policy.attach(z)           # must NOT raise on one process
        assert z._recovery is policy
        policy.detach(z)

    def test_install_replaces_restored_state_onto_shardings(self, tmp_path):
        """Rollback path: a checkpoint restored to host arrays must be
        re-placed onto the recorded shardings (replicated params,
        ZeRO-sharded opt state) — then training continues sharded."""
        from deeplearning4j_tpu.train.checkpoint import ModelSerializer
        from deeplearning4j_tpu.train.recovery import RecoveryPolicy

        x, y = two_class_data(128)
        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=3), epochs=1)
        path = str(tmp_path / "ck.zip")
        ModelSerializer.write_model(z, path)

        restored = ModelSerializer.restore(path)     # host placement
        RecoveryPolicy._install(z, restored)
        assert any(DATA_AXIS in s for s in opt_specs(z))
        for leaf in jax.tree.leaves(z.params):
            assert str(leaf.sharding.spec) == "PartitionSpec()"
        z.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=5), epochs=1)
        assert np.isfinite(z.score_value)
        assert any(DATA_AXIS in s for s in opt_specs(z))


class TestAttribution:
    def test_opt_state_bytes_gauge_and_counter(self):
        from deeplearning4j_tpu.observe.metrics import registry

        z = SequentialModel(mlp_conf()).init()
        distribute(z, ParallelConfig(data=N_DEV, zero=1))
        g = registry().gauge("dl4jtpu_opt_state_bytes")
        assert g.value(mode="sharded") == zmod.opt_state_bytes_per_replica(
            z.opt_state
        )
        c = registry().counter("dl4jtpu_update_seconds_total")
        before = c.value(mode="sharded")
        secs = zmod.measure_update_seconds(z, iters=2)
        assert secs > 0
        assert c.value(mode="sharded") > before

    def test_update_seconds_measures_replicated_too(self):
        from deeplearning4j_tpu.observe.metrics import registry

        m = SequentialModel(mlp_conf()).init()
        distribute(m, ParallelConfig(data=N_DEV))
        c = registry().counter("dl4jtpu_update_seconds_total")
        before = c.value(mode="replicated")
        assert zmod.measure_update_seconds(m, iters=2) > 0
        assert c.value(mode="replicated") > before


class TestShardMapEntryPoint:
    """runtime/mesh.shard_map — the framework's one call of
    `jax.shard_map`."""

    def test_psum_and_axis_size(self):
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.runtime.mesh import (
            MeshSpec, make_mesh, shard_map,
        )

        mesh = make_mesh(MeshSpec.data_parallel())
        f = shard_map(
            lambda x: (jax.lax.psum(x, DATA_AXIS) * 0
                       + jax.lax.axis_size(DATA_AXIS)),
            mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P(DATA_AXIS),
            check_vma=False,
        )
        out = np.asarray(jax.jit(f)(jnp.arange(float(N_DEV))))
        np.testing.assert_array_equal(out, np.full(N_DEV, N_DEV))

    def test_size_one_auto_axes_fold_into_manual(self):
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.runtime.mesh import MeshSpec, make_mesh, shard_map

        mesh = make_mesh(
            MeshSpec.of(data=1, pipe=4), jax.devices()[:4]
        )
        f = shard_map(
            lambda x: x * 2, mesh=mesh, in_specs=(P("pipe"),),
            out_specs=P("pipe"), axis_names={"pipe"}, check_vma=False,
        )
        np.testing.assert_array_equal(
            np.asarray(jax.jit(f)(jnp.arange(4.0))), np.arange(4.0) * 2
        )

    def test_partial_auto_axis_stays_gspmd(self):
        """A body manual over "pipe" with a 2-wide data axis left
        GSPMD-auto around it — the layout `distribute(pipe=k, data>1)`
        and the planner's pipe x data candidates rely on."""
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.runtime.mesh import MeshSpec, make_mesh, shard_map

        mesh = make_mesh(MeshSpec.of(data=2, pipe=4))
        f = shard_map(
            lambda x: x + jax.lax.axis_index("pipe").astype(x.dtype),
            mesh=mesh, in_specs=(P("pipe"),), out_specs=P("pipe"),
            axis_names={"pipe"}, check_vma=False,
        )
        np.testing.assert_array_equal(
            np.asarray(jax.jit(f)(jnp.zeros(4))), np.arange(4.0)
        )
