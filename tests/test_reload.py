"""Hot weight reload: checkpoint swaps under live traffic.

The contract under test (serving/reload.py + engine.prepare_params/
commit_params): new weights of identical tree/shape/dtype swap in
between decode steps with ZERO recompiles and ZERO dropped requests;
anything else — corrupt bytes, truncated files, an incomplete save, an
incompatible architecture — is rejected on the background thread while
the current weights keep serving, untouched.
"""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.checkpoint import Package, get_checkpoint_fns
from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.serving import (
    Request,
    Scheduler,
    ServeEngine,
    WeightReloader,
)

TINY = ProGenConfig(
    num_tokens=32,
    dim=32,
    seq_len=32,
    depth=2,
    window_size=8,
    global_mlp_depth=1,
    heads=2,
    dim_head=16,
    ff_mult=2,
    dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    model = ProGen(TINY)
    tokens = jnp.zeros((1, TINY.seq_len), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    from flax.core import meta

    return model, meta.unbox(variables)["params"]


def _ckpt_name(path: str) -> str:
    return pathlib.Path(path).name


def _save(ck_dir, params, step=0, config=TINY):
    _, _, save = get_checkpoint_fns(str(ck_dir))
    return save(Package(step, {"params": params}, config.to_dict(), "run"))


def _first_leaf(tree):
    return np.asarray(jax.tree.leaves(tree)[0])


def _reload(reloader):
    """Kick + wait for the background load; commit stays the caller's."""
    assert reloader.request_reload()
    reloader.join(120)


class TestPackagePath:
    def test_restore_and_peek_report_source_dir(
        self, tmp_path, model_and_params
    ):
        """Reload decides 'is this new?' by comparing checkpoint dir
        names, so every restore surface must report where it read from."""
        _, params = model_and_params
        saved = _save(tmp_path / "ck", params)
        _, get_last, _ = get_checkpoint_fns(str(tmp_path / "ck"))
        pkg = get_last.restore_params()
        assert pkg.path is not None and _ckpt_name(pkg.path) == \
            _ckpt_name(saved)
        assert _ckpt_name(get_last.peek().path) == _ckpt_name(saved)


class TestHotSwap:
    def test_swap_under_live_traffic_no_recompile_no_drops(
        self, tmp_path, model_and_params
    ):
        """Serve from checkpoint A, stage B mid-decode, commit between
        steps: every request completes, the decode program never
        recompiles, and the engine ends up on B's weights."""
        model, params = model_and_params
        ck = tmp_path / "ck"
        name_a = _ckpt_name(_save(ck, params))
        params_b = jax.tree.map(lambda x: x * 1.5, params)

        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        sched = Scheduler(engine)
        for i in range(3):
            ok, reason = sched.submit(Request(
                id=f"r{i}", prime=np.asarray([3 + i, 5], np.int32),
                length=20, seed=60 + i,
            ))
            assert ok, reason
        for _ in range(3):
            sched.step()  # decode program is compiled and running
        c0 = ServeEngine.decode_compile_count()

        name_b = _ckpt_name(_save(ck, params_b, step=1))
        reloader = WeightReloader(
            engine, ck, metrics=sched.metrics, current=name_a
        )
        _reload(reloader)
        # the serve loop's tick(): commit lands between decode steps
        assert reloader.maybe_commit() == name_b
        assert reloader.current == name_b and reloader.last_error is None

        _, comp = sched.run_to_completion(max_steps=200)
        done = {c.request_id for c in comp}
        assert done == {"r0", "r1", "r2"}  # zero dropped/rejected
        assert ServeEngine.decode_compile_count() == c0  # zero recompiles
        np.testing.assert_array_equal(
            _first_leaf(engine.params), _first_leaf(params) * 1.5
        )
        assert sched.metrics.counters["reloads"] == 1
        assert sched.metrics.counters["reload_rejected"] == 0

    def test_reload_onto_current_checkpoint_is_rejected(
        self, tmp_path, model_and_params
    ):
        model, params = model_and_params
        ck = tmp_path / "ck"
        name_a = _ckpt_name(_save(ck, params))
        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        reloader = WeightReloader(engine, ck, current=name_a)
        _reload(reloader)
        assert reloader.maybe_commit() is None
        assert reloader.last_error == "no_new_checkpoint"

    def test_empty_store_is_rejected(self, tmp_path, model_and_params):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        reloader = WeightReloader(engine, tmp_path / "nothing_here")
        _reload(reloader)
        assert reloader.maybe_commit() is None
        assert reloader.last_error == "no_checkpoint"

    def test_int8_engine_requantizes_on_commit(
        self, tmp_path, model_and_params
    ):
        """An int8 engine must not serve new fp weights against stale
        quantized tables: commit swaps params, q-tables, and the
        calibration report together."""
        model, params = model_and_params
        ck = tmp_path / "ck"
        name_a = _ckpt_name(_save(ck, params))
        engine = ServeEngine(
            model, params, max_slots=2, max_len=24, quantize_int8=True
        )
        q_before = engine.served_params
        report_before = engine.quant_report
        assert report_before["quantized_leaves"] > 0

        _save(ck, jax.tree.map(lambda x: x * 1.5, params), step=1)
        reloader = WeightReloader(engine, ck, current=name_a)
        _reload(reloader)
        assert reloader.maybe_commit() is not None
        assert engine.served_params is not q_before
        assert engine.quant_report is not report_before
        assert engine.quant_report["quantized_leaves"] == \
            report_before["quantized_leaves"]


class TestRejectionPaths:
    """Every bad checkpoint is refused on the background thread; the
    live params must be bit-identical before and after the attempt."""

    def _engine_on_a(self, tmp_path, model, params):
        ck = tmp_path / "ck"
        name_a = _ckpt_name(_save(ck, params))
        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        reloader = WeightReloader(engine, ck, current=name_a)
        return ck, engine, reloader

    def _state_files(self, ckpt_dir):
        return [
            f for f in (pathlib.Path(ckpt_dir) / "state").rglob("*")
            if f.is_file() and f.stat().st_size > 0
        ]

    def test_flipped_byte_quarantined_params_untouched(
        self, tmp_path, model_and_params
    ):
        model, params = model_and_params
        ck, engine, reloader = self._engine_on_a(tmp_path, model, params)
        target = _save(ck, jax.tree.map(lambda x: x * 2.0, params), step=1)
        victim = self._state_files(target)[0]
        blob = bytearray(victim.read_bytes())
        blob[0] ^= 0xFF
        victim.write_bytes(bytes(blob))

        before = _first_leaf(engine.params).copy()
        _reload(reloader)
        assert reloader.maybe_commit() is None
        # the digest walk quarantined B and fell back to A == current
        assert reloader.last_error == "no_new_checkpoint"
        assert any(
            p.name.endswith(".corrupt") for p in pathlib.Path(ck).iterdir()
        )
        np.testing.assert_array_equal(before, _first_leaf(engine.params))

    def test_truncated_file_quarantined_params_untouched(
        self, tmp_path, model_and_params
    ):
        model, params = model_and_params
        ck, engine, reloader = self._engine_on_a(tmp_path, model, params)
        target = _save(ck, jax.tree.map(lambda x: x + 1.0, params), step=1)
        victim = max(self._state_files(target), key=lambda f: f.stat().st_size)
        victim.write_bytes(victim.read_bytes()[: victim.stat().st_size // 2])

        before = _first_leaf(engine.params).copy()
        _reload(reloader)
        assert reloader.maybe_commit() is None
        assert reloader.last_error == "no_new_checkpoint"
        assert any(
            p.name.endswith(".corrupt") for p in pathlib.Path(ck).iterdir()
        )
        np.testing.assert_array_equal(before, _first_leaf(engine.params))

    def test_missing_meta_is_invisible_not_quarantined(
        self, tmp_path, model_and_params
    ):
        """No meta.json == save never finished: the dir is skipped by the
        walk (it may still be mid-write), not condemned as corrupt."""
        model, params = model_and_params
        ck, engine, reloader = self._engine_on_a(tmp_path, model, params)
        target = pathlib.Path(
            _save(ck, jax.tree.map(lambda x: x + 1.0, params), step=1)
        )
        (target / "meta.json").unlink()

        _reload(reloader)
        assert reloader.maybe_commit() is None
        assert reloader.last_error == "no_new_checkpoint"
        assert target.exists()  # still there, still meta-less
        assert not any(
            p.name.endswith(".corrupt") for p in pathlib.Path(ck).iterdir()
        )

    def test_incompatible_tree_rejected(self, tmp_path, model_and_params):
        """A checkpoint from a different architecture can never be
        hot-swapped (the compiled programs are shape-specialized): the
        compatibility check refuses it by name."""
        model, params = model_and_params
        import dataclasses

        other = dataclasses.replace(TINY, dim=16, dim_head=8)
        other_params = ProGen(other).init(
            jax.random.PRNGKey(1), jnp.zeros((1, other.seq_len), jnp.int32)
        )
        from flax.core import meta

        other_params = meta.unbox(other_params)["params"]
        ck = tmp_path / "ck"
        _save(ck, other_params, config=other)

        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        before = _first_leaf(engine.params).copy()
        reloader = WeightReloader(engine, ck)
        _reload(reloader)
        assert reloader.maybe_commit() is None
        assert "incompatible" in reloader.last_error
        np.testing.assert_array_equal(before, _first_leaf(engine.params))


class TestPins:
    """The deploy controller's per-replica seam: a ``reload.pin``
    control file overrides newest-wins watching, and every pin outcome
    is answered through the adjacent ``reload.pin.ack``."""

    def _fleet_of_two(self, tmp_path, model_and_params):
        """Checkpoints A and B on disk, engine serving B (the newest)."""
        model, params = model_and_params
        ck = tmp_path / "ck"
        name_a = _ckpt_name(_save(ck, params))
        params_b = jax.tree.map(lambda x: x * 1.5, params)
        name_b = _ckpt_name(_save(ck, params_b, step=1))
        engine = ServeEngine(model, params_b, max_slots=2, max_len=24)
        pin_path = tmp_path / "reload.pin"
        reloader = WeightReloader(
            engine, ck, current=name_b, pin_path=pin_path
        )
        return ck, name_a, name_b, engine, pin_path, reloader

    def _ack(self, pin_path):
        ack = pin_path.with_name(pin_path.name + ".ack")
        import json

        return json.loads(ack.read_text())

    def test_pin_to_older_checkpoint_commits_and_acks(
        self, tmp_path, model_and_params
    ):
        """A pin is not 'newest-wins': the controller can roll a replica
        BACK to an older verified checkpoint by name."""
        _, params = model_and_params
        ck, name_a, name_b, engine, pin_path, reloader = \
            self._fleet_of_two(tmp_path, model_and_params)
        pin_path.write_text(name_a + "\n")
        assert reloader.poll_watch(0.0) is True
        reloader.join(120)
        assert reloader.maybe_commit() == name_a
        assert reloader.current == name_a
        np.testing.assert_array_equal(
            _first_leaf(engine.params), _first_leaf(params)
        )
        ack = self._ack(pin_path)
        assert ack["pin"] == name_a and ack["status"] == "committed"

    def test_pin_to_missing_name_rejected_weights_untouched(
        self, tmp_path, model_and_params
    ):
        ck, name_a, name_b, engine, pin_path, reloader = \
            self._fleet_of_two(tmp_path, model_and_params)
        before = _first_leaf(engine.params).copy()
        pin_path.write_text("ckpt_99999999\n")
        assert reloader.poll_watch(0.0) is True
        reloader.join(120)
        assert reloader.maybe_commit() is None
        assert reloader.last_error == "pin_unavailable"
        assert reloader.current == name_b
        np.testing.assert_array_equal(before, _first_leaf(engine.params))
        ack = self._ack(pin_path)
        assert ack["pin"] == "ckpt_99999999"
        assert ack["status"] == "rejected"
        assert ack["reason"] == "pin_unavailable"

    def test_rejected_pin_not_retried_until_it_changes(
        self, tmp_path, model_and_params
    ):
        """No hot retry loop on a pin that keeps failing — the watcher
        re-attempts only when the controller writes a different name."""
        ck, name_a, name_b, engine, pin_path, reloader = \
            self._fleet_of_two(tmp_path, model_and_params)
        pin_path.write_text("ckpt_99999999\n")
        assert reloader.poll_watch(0.0) is True
        reloader.join(120)
        assert reloader.maybe_commit() is None
        assert reloader.poll_watch(0.0) is False  # same bad pin: no kick
        pin_path.write_text(name_a + "\n")  # rollback to a real one
        assert reloader.poll_watch(0.0) is True
        reloader.join(120)
        assert reloader.maybe_commit() == name_a

    def test_pin_overrides_newest_wins(self, tmp_path, model_and_params):
        """While the canary bakes, the rest of the fleet is pinned to
        the fleet checkpoint: a newer dir on disk must NOT be loaded."""
        _, params = model_and_params
        ck, name_a, name_b, engine, pin_path, reloader = \
            self._fleet_of_two(tmp_path, model_and_params)
        # a newer checkpoint appears, but the pin says stay on B
        _save(ck, jax.tree.map(lambda x: x + 1.0, params), step=2)
        pin_path.write_text(name_b + "\n")
        assert reloader.poll_watch(0.0) is False
        assert reloader.current == name_b
        # the already-satisfied pin is still answered (the controller
        # needs the ack even when no reload was necessary)
        ack = self._ack(pin_path)
        assert ack["pin"] == name_b and ack["status"] == "committed"

    def test_pin_removal_resumes_newest_wins(
        self, tmp_path, model_and_params
    ):
        _, params = model_and_params
        ck, name_a, name_b, engine, pin_path, reloader = \
            self._fleet_of_two(tmp_path, model_and_params)
        pin_path.write_text(name_b + "\n")
        assert reloader.poll_watch(0.0) is False  # pinned in place
        name_c = _ckpt_name(
            _save(ck, jax.tree.map(lambda x: x + 1.0, params), step=2)
        )
        assert reloader.poll_watch(0.0) is False  # still pinned
        pin_path.unlink()
        assert reloader.poll_watch(0.0) is True  # back to newest-wins
        reloader.join(120)
        assert reloader.maybe_commit() == name_c

    def test_startup_pin_answered_without_reload(
        self, tmp_path, model_and_params
    ):
        """A pin file that predates the process: committed when startup
        restored exactly the pinned checkpoint, rejected when it had to
        fall back — the controller must never wait forever."""
        ck, name_a, name_b, engine, pin_path, reloader = \
            self._fleet_of_two(tmp_path, model_and_params)
        pin_path.write_text(name_b + "\n")
        reloader.note_startup_pin()
        ack = self._ack(pin_path)
        assert ack["pin"] == name_b and ack["status"] == "committed"

        pin_path.write_text("ckpt_99999999\n")
        reloader.note_startup_pin()
        ack = self._ack(pin_path)
        assert ack["status"] == "rejected"
        assert ack["reason"] == "pin_unavailable_at_startup"
        # and the watcher will not hot-retry the startup rejection
        assert reloader.poll_watch(0.0) is False


class TestWatcher:
    def test_poll_watch_kicks_on_new_checkpoint(
        self, tmp_path, model_and_params
    ):
        model, params = model_and_params
        ck = tmp_path / "ck"
        name_a = _ckpt_name(_save(ck, params))
        engine = ServeEngine(model, params, max_slots=2, max_len=24)
        reloader = WeightReloader(engine, ck, current=name_a)

        assert reloader.poll_watch(0.0) is False  # nothing newer
        name_b = _ckpt_name(
            _save(ck, jax.tree.map(lambda x: x * 1.5, params), step=1)
        )
        assert reloader.poll_watch(0.0) is True  # kicked
        reloader.join(120)
        assert reloader.maybe_commit() == name_b
        assert reloader.poll_watch(0.0) is False  # already current
