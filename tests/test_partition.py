"""Mesh + sharding-rule tests on the 8-virtual-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.parallel.partition import (
    DEFAULT_RULES,
    batch_sharding,
    make_mesh,
    state_shardings,
)
from progen_tpu.training.optimizer import make_optimizer
from progen_tpu.training.step import init_train_state

TINY = ProGenConfig(
    num_tokens=32,
    dim=32,
    seq_len=32,
    depth=3,
    window_size=8,
    global_mlp_depth=1,
    heads=2,
    dim_head=16,
    ff_mult=2,
    dtype="float32",
)


class TestMakeMesh:
    def test_all_data(self):
        mesh = make_mesh()
        assert mesh.shape == {"data": 8, "seq": 1, "model": 1}

    def test_explicit_shape(self):
        mesh = make_mesh(data=2, seq=2, model=2)
        assert mesh.shape == {"data": 2, "seq": 2, "model": 2}

    def test_data_inferred(self):
        mesh = make_mesh(model=4)
        assert mesh.shape == {"data": 2, "seq": 1, "model": 4}

    def test_bad_shape_raises(self):
        with pytest.raises(ValueError):
            make_mesh(data=3, model=3)


class TestShardings:
    @pytest.fixture(scope="class")
    def state_and_shardings(self):
        mesh = make_mesh(data=2, seq=1, model=4)
        model = ProGen(TINY)
        optimizer = make_optimizer()
        state, shardings = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len, mesh=mesh
        )
        return mesh, state, shardings

    def test_qkv_sharded_over_model(self, state_and_shardings):
        mesh, state, _ = state_and_shardings
        kernel = state.params["attn0"]["to_qkv"]["kernel"]
        spec = kernel.sharding.spec
        assert spec == P(None, "model")

    def test_embed_table_sharded_over_vocab(self, state_and_shardings):
        _, state, _ = state_and_shardings
        emb = state.params["embed"]["embedding"]
        assert emb.sharding.spec == P("model", None)

    def test_norm_scale_replicated(self, state_and_shardings):
        _, state, _ = state_and_shardings
        scale = state.params["attn0"]["ScaleNorm_0"]["norm"]["scale"]
        assert scale.sharding.spec == P(None)

    def test_opt_state_inherits_param_sharding(self, state_and_shardings):
        """ZeRO-ish property: AdamW moments shard exactly like their params
        because optax preserves the Partitioned boxes."""
        _, state, _ = state_and_shardings
        # chain(clip, adamw) -> opt_state[1] is adamw's inner chain;
        # its first element is ScaleByAdamState
        adam = state.opt_state[1][0]
        mu_qkv = adam.mu["attn0"]["to_qkv"]["kernel"]
        assert mu_qkv.sharding.spec == P(None, "model")

    def test_step_counter_replicated(self, state_and_shardings):
        _, state, _ = state_and_shardings
        assert state.step.sharding.spec == P()

    def test_batch_sharding_layout(self, state_and_shardings):
        mesh, _, _ = state_and_shardings
        assert batch_sharding(mesh).spec == P("data", None)
        assert batch_sharding(mesh, accum_axis=True).spec == P(
            None, "data", None
        )


class TestLogicalCoverage:
    def test_every_logical_name_has_a_rule(self):
        """Every logical axis name used by the model must appear in
        DEFAULT_RULES — an unmapped name silently replicates."""
        model = ProGen(TINY)
        abstract = jax.eval_shape(
            model.init,
            jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((1, TINY.seq_len), jnp.int32),
        )
        from flax.core import meta

        used = set()
        jax.tree.map(
            lambda x: used.update(
                n for n in x.get_partition_spec() if n is not None
            )
            if isinstance(x, meta.AxisMetadata)
            else None,
            abstract,
            is_leaf=lambda x: isinstance(x, meta.AxisMetadata),
        )
        ruled = {name for name, _ in DEFAULT_RULES}
        assert used <= ruled, f"unruled logical axes: {used - ruled}"


class TestInitializeDistributed:
    """Decision-matrix tests for the pod bootstrap (the real initialize is
    monkeypatched out: this suite runs single-process, already-initialized
    backends would make a real call raise)."""

    def _run(self, monkeypatch, env, init_behavior):
        from progen_tpu.parallel import partition

        for k in (
            "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
            "TPU_WORKER_HOSTNAMES", "TPU_SKIP_MDS_QUERY", "TPU_WORKER_ID",
        ):
            monkeypatch.delenv(k, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)

        calls = []

        def fake_init(*a, **kw):
            calls.append(1)
            if init_behavior == "raise":
                raise ValueError("no cluster detected")

        monkeypatch.setattr(jax.distributed, "initialize", fake_init)
        monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
        partition.initialize_distributed()
        return len(calls)

    def test_already_initialized_is_noop(self, monkeypatch):
        from progen_tpu.parallel import partition

        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "localhost:1234")
        monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)
        monkeypatch.setattr(
            jax.distributed, "initialize",
            lambda *a, **kw: pytest.fail("must not re-initialize"),
        )
        partition.initialize_distributed()

    def test_explicit_env_path(self, monkeypatch):
        n = self._run(
            monkeypatch, {"JAX_COORDINATOR_ADDRESS": "localhost:1234"}, "ok"
        )
        assert n == 1

    def test_gke_pod_initializes(self, monkeypatch):
        n = self._run(
            monkeypatch, {"TPU_WORKER_HOSTNAMES": "w0,w1,w2,w3"}, "ok"
        )
        assert n == 1

    def test_gke_pod_failure_is_loud(self, monkeypatch):
        with pytest.raises(RuntimeError, match="4 workers"):
            self._run(
                monkeypatch,
                {"TPU_WORKER_HOSTNAMES": "w0,w1,w2,w3"},
                "raise",
            )

    def test_single_worker_entry_is_noop(self, monkeypatch):
        n = self._run(
            monkeypatch, {"TPU_WORKER_HOSTNAMES": "localhost"}, "ok"
        )
        assert n == 0

    def test_cpu_host_is_noop(self, monkeypatch):
        assert self._run(monkeypatch, {}, "ok") == 0

    def test_single_host_with_chips_dials_nothing(self, monkeypatch):
        # a chip host with no multi-worker evidence (the chip tool's sealed
        # machine: device files present, no metadata server) must reach
        # the step without calling initialize — jax's GCE auto-detect
        # retries the metadata query for minutes before giving up
        import os

        real_exists = os.path.exists
        monkeypatch.setattr(
            os.path, "exists",
            lambda p: p in ("/dev/accel0", "/dev/vfio/0") or real_exists(p),
        )
        assert self._run(monkeypatch, {"TPU_WORKER_ID": "0"}, "raise") == 0


class TestLargeConfigHbmFit:
    """BASELINE.md config 3 (ProGen-large, 1.2B): the TP sharding plan must
    actually fit v5e HBM. Exact per-chip byte accounting from the abstract
    state + the production sharding rules on a model=8 mesh — metadata
    only, no 1.2B arrays are materialized."""

    def test_fits_v5e_at_model8(self):
        from flax.core import meta

        from progen_tpu.config import ProGenConfig, load_toml_config
        from progen_tpu.training.step import abstract_train_state
        from progen_tpu.training.optimizer import make_optimizer

        cfg = ProGenConfig.from_dict(
            load_toml_config("configs/model/large.toml")
        )
        model = ProGen(cfg)
        boxed, _ = abstract_train_state(model, make_optimizer(), cfg.seq_len)
        mesh = make_mesh(data=1, seq=1, model=8)
        shardings = state_shardings(boxed, mesh)
        unboxed = meta.unbox(boxed)

        leaves = jax.tree.leaves(unboxed)
        shard_leaves = jax.tree.leaves(shardings)
        assert len(leaves) == len(shard_leaves)
        total = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in leaves
        )
        per_chip = sum(
            int(np.prod(s.shard_shape(leaf.shape))) * leaf.dtype.itemsize
            for leaf, s in zip(leaves, shard_leaves)
        )
        # sanity: ~1.2B params x 12 B (f32 params + Adam m/v) ~ 14.7 GB
        assert total > 12 * 1.2e9
        # TP must actually cut the footprint — the big matrices (qkv, mlp,
        # vocab) shard over `model`, so per-chip state must land well
        # under one v5e chip's 16 GB with room for grads + activations
        assert per_chip < 4 * 2**30, f"per-chip state {per_chip/2**30:.2f} GB"
        # and sharding must not LOSE anything: per-chip x 8 >= total
        assert per_chip * 8 >= total


class TestHybridMultiSliceMesh:
    """make_mesh's DCN x ICI branch: devices spanning multiple slices must
    lay the data axis OVER slices (gradient all-reduce rides DCN once per
    step) and keep seq/model intra-slice (halo/TP collectives ride ICI).
    Fake v5e-shaped devices carry the attributes mesh_utils consults."""

    class FakeDev:
        def __init__(self, i, s):
            self.id = i
            self.slice_index = s
            self.platform = "tpu"
            self.process_index = s
            self.device_kind = "fake-tpu"
            local = i % 4
            self.coords = (local % 2, local // 2, 0)
            self.core_on_chip = 0

        def __repr__(self):
            return f"D{self.id}s{self.slice_index}"

    def _slice_devices(self, n_slices, per_slice=4):
        return [
            self.FakeDev(i, i // per_slice)
            for i in range(n_slices * per_slice)
        ]

    def test_two_slices_data_over_dcn(self):
        mesh = make_mesh(
            data=2, seq=2, model=2, devices=self._slice_devices(2)
        )
        assert dict(mesh.shape) == {"data": 2, "seq": 2, "model": 2}
        arr = mesh.devices
        for i in range(2):
            row_slices = {d.slice_index for d in arr[i].flat}
            assert row_slices == {i}, (
                f"data row {i} spans slices {row_slices}; seq/model "
                "collectives would cross DCN"
            )

    def test_four_slices_pure_dp(self):
        # 4 slices x 2 chips, all on the data axis: DCN outermost means
        # consecutive data rows group by slice (row i -> slice i // 2)
        mesh = make_mesh(data=8, devices=self._slice_devices(4, 2))
        assert dict(mesh.shape) == {"data": 8, "seq": 1, "model": 1}
        arr = mesh.devices
        for i in range(8):
            (dev,) = arr[i].flat
            assert dev.slice_index == i // 2, (
                f"data row {i} on slice {dev.slice_index}"
            )
