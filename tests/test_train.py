"""Training-stack tests: loss semantics, grad accumulation, pjit==single.

The pjit test is the SURVEY §4 recommendation: run the real sharded train
step on the 8-virtual-CPU-device mesh and assert bit-comparable results with
the single-device step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.parallel.partition import make_mesh
from progen_tpu.training.loss import cross_entropy, eos_loss_mask
from progen_tpu.training.optimizer import make_optimizer, weight_decay_mask
from progen_tpu.training.step import (
    compile_train_step,
    init_train_state,
    make_eval_step,
    make_train_step,
)

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


def synthetic_batch(key, shape, vocab=32):
    """Token sequences with trailing padding, so the EOS mask matters."""
    ids = jax.random.randint(key, shape, 1, vocab)
    lengths = jax.random.randint(
        jax.random.fold_in(key, 1), shape[:-1] + (1,), shape[-1] // 2, shape[-1]
    )
    pos = jnp.arange(shape[-1])
    return jnp.where(pos < lengths, ids, 0)


class TestCrossEntropy:
    def test_mask_keeps_first_pad_only(self):
        targets = jnp.array([[5, 3, 0, 0, 0]])
        mask = eos_loss_mask(targets)
        np.testing.assert_array_equal(
            mask[0], jnp.array([True, True, True, False, False])
        )

    def test_no_padding_full_mask(self):
        targets = jnp.array([[5, 3, 2, 7]])
        np.testing.assert_array_equal(eos_loss_mask(targets)[0], jnp.ones(4, bool))

    def test_matches_reference_formula(self):
        """Hand-rolled reference semantics (utils.py:45-59), per sequence."""
        key = jax.random.PRNGKey(0)
        logits = jax.random.normal(key, (2, 6, 8))
        targets = jnp.array([[3, 1, 4, 0, 0, 0], [2, 2, 2, 2, 2, 2]])
        out = cross_entropy(logits, targets)

        logprobs = jax.nn.log_softmax(logits, axis=-1)
        for b in range(2):
            nll = -np.take_along_axis(
                np.asarray(logprobs[b]), np.asarray(targets[b])[:, None], axis=-1
            )[:, 0]
            t = np.asarray(targets[b])
            mask = t != 0
            eos = (~mask).cumsum(-1) == 1
            m = mask | eos
            expected = (nll * m).sum() / m.sum()
            np.testing.assert_allclose(out[b], expected, rtol=1e-6)

    def test_f32_even_for_bf16_logits(self):
        logits = jnp.ones((1, 4, 8), jnp.bfloat16)
        targets = jnp.ones((1, 4), jnp.int32)
        assert cross_entropy(logits, targets).dtype == jnp.float32


class TestWeightDecayMask:
    def test_matrices_only(self):
        params = {"w": jnp.ones((3, 3)), "b": jnp.ones((3,)), "s": jnp.ones(())}
        mask = weight_decay_mask(params)
        assert mask["w"] and not mask["b"] and not mask["s"]


@pytest.fixture(scope="module")
def tiny_setup():
    model = ProGen(TINY)
    optimizer = make_optimizer(learning_rate=1e-3)
    state, _ = init_train_state(
        model, optimizer, jax.random.PRNGKey(0), TINY.seq_len
    )
    return model, optimizer, state


class TestTrainStep:
    def test_loss_decreases(self, tiny_setup):
        model, optimizer, _ = tiny_setup
        # fresh state: the donated argument must not alias the shared fixture
        state, _ = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len
        )
        step = jax.jit(make_train_step(model, optimizer), donate_argnums=0)
        batch = synthetic_batch(jax.random.PRNGKey(1), (4, TINY.seq_len + 1))[
            None
        ]  # (1, 4, L+1)
        losses = []
        for _ in range(30):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.7, losses

    def test_grad_accum_equivalence(self, tiny_setup):
        """(1, 4, L) in one micro-batch == (2, 2, L) accumulated, since both
        average per-micro means of equal size."""
        model, optimizer, _ = tiny_setup
        step = jax.jit(make_train_step(model, optimizer))
        data = synthetic_batch(jax.random.PRNGKey(2), (4, TINY.seq_len + 1))

        def fresh():
            s, _ = init_train_state(
                model, optimizer, jax.random.PRNGKey(0), TINY.seq_len
            )
            return s

        s1, m1 = step(fresh(), data[None])
        s2, m2 = step(fresh(), data.reshape(2, 2, TINY.seq_len + 1))
        np.testing.assert_allclose(m1["loss"], m2["loss"], rtol=1e-6)
        leaves1 = jax.tree.leaves(s1.params)
        leaves2 = jax.tree.leaves(s2.params)
        for a, b in zip(leaves1, leaves2):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_eval_step_matches_train_loss(self, tiny_setup):
        model, optimizer, state = tiny_setup
        data = synthetic_batch(jax.random.PRNGKey(3), (4, TINY.seq_len + 1))
        train = jax.jit(make_train_step(model, optimizer))
        ev = jax.jit(make_eval_step(model))
        _, metrics = train(state, data[None])
        np.testing.assert_allclose(
            float(ev(state, data)), float(metrics["loss"]), rtol=1e-6
        )


class TestPjitParity:
    def test_seq_parallel_step_matches_single_device(self):
        """Sequence parallelism = mesh seq axis: shard activations' sequence
        dim + SGU spatial rows over 4 devices; results must equal the
        single-device step."""
        model = ProGen(TINY)
        optimizer = make_optimizer(learning_rate=1e-3)
        data = synthetic_batch(jax.random.PRNGKey(11), (4, TINY.seq_len + 1))
        batch = data[None]

        s_single, _ = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len
        )
        s_single, m_single = jax.jit(make_train_step(model, optimizer))(
            s_single, batch
        )

        mesh = make_mesh(data=2, seq=4, model=1)
        s_mesh, shardings = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len, mesh=mesh
        )
        step_mesh = compile_train_step(
            model, optimizer, s_mesh, shardings, mesh
        )
        with mesh:
            s_mesh, m_mesh = step_mesh(s_mesh, batch)
        np.testing.assert_allclose(
            float(m_mesh["loss"]), float(m_single["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(s_single.params),
            jax.tree.leaves(jax.device_get(s_mesh.params)),
        ):
            np.testing.assert_allclose(a, b, atol=2e-5)

    def test_sharded_step_matches_single_device(self):
        """The full sharded train step on a (2, 1, 4) mesh must reproduce the
        single-device step: same loss, same updated params."""
        model = ProGen(TINY)
        optimizer = make_optimizer(learning_rate=1e-3)
        data = synthetic_batch(jax.random.PRNGKey(7), (8, TINY.seq_len + 1))
        batch = data[None]  # (1, 8, L+1)

        # single device
        s_single, _ = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len
        )
        step_single = jax.jit(make_train_step(model, optimizer))
        s_single, m_single = step_single(s_single, batch)

        # sharded: data=2 x model=4
        mesh = make_mesh(data=2, seq=1, model=4)
        s_mesh, shardings = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len, mesh=mesh
        )
        step_mesh = compile_train_step(
            model, optimizer, s_mesh, shardings, mesh
        )
        with mesh:
            s_mesh, m_mesh = step_mesh(s_mesh, batch)

        np.testing.assert_allclose(
            float(m_mesh["loss"]), float(m_single["loss"]), rtol=1e-5
        )
        single_leaves = jax.tree.leaves(s_single.params)
        mesh_leaves = jax.tree.leaves(jax.device_get(s_mesh.params))
        for a, b in zip(single_leaves, mesh_leaves):
            np.testing.assert_allclose(a, b, atol=2e-5)


class TestBlockedSguParity:
    def test_blocked_sgu_seq_parallel_matches_single_device(self):
        """The long8k recipe combination — block-triangular SGU mix on a
        sequence-parallel mesh — must reproduce the single-device dense-SGU
        step (same math twice reassociated: blocked mix + GSPMD sharding of
        the sliced spatial weights)."""
        import dataclasses

        cfg = dataclasses.replace(TINY, sgu_block_size=8)  # 32 -> 16 -> 8
        model_b = ProGen(cfg)
        model_d = ProGen(TINY)
        optimizer = make_optimizer(learning_rate=1e-3)
        data = synthetic_batch(jax.random.PRNGKey(13), (4, TINY.seq_len + 1))
        batch = data[None]

        s_single, _ = init_train_state(
            model_d, optimizer, jax.random.PRNGKey(0), TINY.seq_len
        )
        s_single, m_single = jax.jit(make_train_step(model_d, optimizer))(
            s_single, batch
        )

        mesh = make_mesh(data=2, seq=4, model=1)
        s_mesh, shardings = init_train_state(
            model_b, optimizer, jax.random.PRNGKey(0), TINY.seq_len,
            mesh=mesh,
        )
        step_mesh = compile_train_step(
            model_b, optimizer, s_mesh, shardings, mesh
        )
        with mesh:
            s_mesh, m_mesh = step_mesh(s_mesh, batch)
        np.testing.assert_allclose(
            float(m_mesh["loss"]), float(m_single["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(s_single.params),
            jax.tree.leaves(jax.device_get(s_mesh.params)),
        ):
            np.testing.assert_allclose(a, b, atol=2e-5)


class TestLrSchedule:
    def test_cosine_schedule_shape(self):
        from progen_tpu.training.optimizer import _make_schedule

        sched = _make_schedule(1e-3, "cosine", warmup_steps=10,
                               total_steps=100)
        assert float(sched(0)) == 0.0
        np.testing.assert_allclose(float(sched(10)), 1e-3, rtol=1e-6)
        # decays to the 10% floor at the horizon
        np.testing.assert_allclose(float(sched(100)), 1e-4, rtol=1e-5)
        assert float(sched(55)) < 1e-3

    def test_constant_is_reference_parity(self):
        from progen_tpu.training.optimizer import _make_schedule

        assert _make_schedule(2e-4, "constant", 0, 0) == 2e-4

    def test_bad_schedule_raises(self):
        from progen_tpu.training.optimizer import make_optimizer

        with pytest.raises(ValueError, match="unknown schedule"):
            make_optimizer(schedule="nope")
        with pytest.raises(ValueError, match="total_steps"):
            make_optimizer(schedule="cosine", warmup_steps=5, total_steps=5)

    def test_scheduled_optimizer_trains(self):
        from progen_tpu.training.optimizer import make_optimizer
        from progen_tpu.training.step import make_train_step

        model = ProGen(TINY)
        optimizer = make_optimizer(
            1e-3, schedule="cosine", warmup_steps=1, total_steps=4
        )
        state, _ = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len
        )
        step = jax.jit(make_train_step(model, optimizer))
        batch = synthetic_batch(
            jax.random.PRNGKey(2), (2, TINY.seq_len + 1)
        )[None]
        p0 = jax.tree.leaves(state.params)[0]
        for _ in range(3):
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        # warmup step 0 has lr 0: params must still change by step 3
        assert not np.allclose(
            np.asarray(p0), np.asarray(jax.tree.leaves(state.params)[0])
        )


class TestZero1:
    """ZeRO-1 optimizer-state sharding (partition.zero1_opt_shardings):
    moments shard over ``data``, params keep their layout, and the training
    trajectory is unchanged."""

    def _steps(self, zero1, n=3):
        model = ProGen(TINY)
        optimizer = make_optimizer(learning_rate=1e-3)
        mesh = make_mesh(data=4, seq=1, model=2)
        state, shardings = init_train_state(
            model, optimizer, jax.random.PRNGKey(0), TINY.seq_len,
            mesh=mesh, zero1=zero1,
        )
        step = compile_train_step(model, optimizer, state, shardings, mesh)
        with mesh:
            for i in range(n):
                batch = synthetic_batch(
                    jax.random.PRNGKey(100 + i), (8, TINY.seq_len + 1)
                )[None]
                state, metrics = step(state, batch)
        return state, shardings, mesh, metrics

    def test_trajectory_matches_baseline(self):
        s0, _, _, m0 = self._steps(zero1=False)
        s1, _, _, m1 = self._steps(zero1=True)
        np.testing.assert_allclose(
            float(m1["loss"]), float(m0["loss"]), rtol=1e-6
        )
        for a, b in zip(
            jax.tree.leaves(jax.device_get(s0.params)),
            jax.tree.leaves(jax.device_get(s1.params)),
        ):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_moments_sharded_params_not(self):
        """Per-device optimizer-moment bytes shrink ~1/data vs the base
        layout (exact factor depends on the few leaves with no free
        divisible dim, e.g. model-sharded biases); params keep a
        data-replicated layout; every 2-D moment with a free divisible dim
        carries 'data' in its spec."""
        s_base, *_ = self._steps(zero1=False, n=1)
        s_z1, _, mesh, _ = self._steps(zero1=True, n=1)
        data_size = mesh.shape["data"]

        def device_bytes(tree):
            return sum(
                leaf.addressable_shards[0].data.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(tree)
                if hasattr(leaf, "addressable_shards")
            )

        base_b, z1_b = device_bytes(s_base.opt_state), device_bytes(
            s_z1.opt_state
        )
        # kernels dominate; allow slack for unupgradeable small leaves
        assert z1_b <= base_b / data_size * 1.5, (base_b, z1_b)

        for leaf in jax.tree.leaves(s_z1.opt_state):
            if getattr(leaf, "ndim", 0) == 2:
                spec = list(leaf.sharding.spec) + [None] * (
                    2 - len(leaf.sharding.spec)
                )
                has_free_divisible = any(
                    ax is None and d % data_size == 0 and d >= data_size
                    for d, ax in zip(leaf.shape, spec)
                )
                assert "data" in spec or not has_free_divisible, (
                    leaf.shape,
                    spec,
                )
        # params stay in their base layout (no data-axis sharding)
        for leaf in jax.tree.leaves(s_z1.params):
            assert "data" not in [ax for ax in leaf.sharding.spec if ax], (
                leaf.sharding.spec
            )

    def test_checkpoint_roundtrip_across_zero1(self, tmp_path):
        """A checkpoint written with ZeRO-1 shardings restores into the
        plain layout (and the moments carry identical values)."""
        from progen_tpu.checkpoint import (
            Package,
            get_checkpoint_fns,
            sharded_abstract_state,
        )
        from progen_tpu.training.step import abstract_train_state

        model = ProGen(TINY)
        optimizer = make_optimizer(learning_rate=1e-3)
        state, _, mesh, _ = self._steps(zero1=True, n=1)
        _, get_last, save = get_checkpoint_fns(str(tmp_path / "ck"))
        save(Package(next_seq_index=8, state=state,
                     model_config=TINY.to_dict(), run_id=None))

        boxed, abstract = abstract_train_state(model, optimizer, TINY.seq_len)
        from progen_tpu.parallel.partition import state_shardings

        plain_sh = state_shardings(boxed, mesh)
        restored = get_last(sharded_abstract_state(abstract, plain_sh)).state
        for a, b in zip(
            jax.tree.leaves(jax.device_get(state.opt_state)),
            jax.tree.leaves(jax.device_get(restored.opt_state)),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
