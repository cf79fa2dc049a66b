"""Mesh-sharded checkpointing with the reference's factory interface.

Interface parity (/root/reference/progen_transformer/checkpoint.py:85-109):
``get_checkpoint_fns(path) -> (reset, get_last, save)`` with ``keep_last_n``
retention and ``ckpt_{unix_time}`` naming (lexicographic sort = latest,
checkpoint.py:27-30). Package schema parity (/root/reference/train.py:196-202):
``{next_seq_index, params, optim_state, model_config, run_id}`` — with
params/optim_state generalized to the whole TrainState so the model config
stored in the checkpoint can rebuild the model on resume, overriding the TOML
(train.py:94-100; sample.py:46-47 reconstructs purely from the checkpoint).

TPU-first deltas:
  * arrays are written per-shard through Orbax/TensorStore — each host
    writes only the shards it owns, no single-host pickle of the full model
    (the reference cloudpickles everything on one process,
    checkpoint.py:25-30; impossible at 1.2B on a v5e host);
  * the save is atomic (Orbax's tmp-dir + rename commit) and multi-host
    coordinated, so a preempted write never corrupts the latest checkpoint —
    the reference's recovery-by-restart story (SURVEY §5) needs this;
  * restore takes an abstract TrainState + shardings so every leaf lands
    directly on its mesh position (no host round-trip);
  * GCS works through the same code path (TensorStore speaks gs:// natively)
    instead of a parallel download-to-/tmp implementation
    (checkpoint.py:41-81).
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import time
from pathlib import Path
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import orbax.checkpoint as ocp
from jax.sharding import NamedSharding

from progen_tpu import telemetry
from progen_tpu.resilience.retry import retry_call
from progen_tpu.telemetry.registry import get_registry

CKPT_PREFIX = "ckpt_"
CORRUPT_SUFFIX = ".corrupt"
_CKPT_NAME_RE = re.compile(re.escape(CKPT_PREFIX) + r"\d+")
DEFAULT_KEEP_LAST_N = 500  # reference default, train.py:48


# ---------------------------------------------------------------------------
# Integrity manifest: per-entry digests riding meta.json
# ---------------------------------------------------------------------------
#
# A checkpoint is only as good as its worst byte: Orbax's tmp+rename
# commit protects against dying MID-write, but not against truncation,
# bit rot, or a partially-synced network filesystem discovered at
# restore time — which used to be discovered as an opaque TensorStore
# error that killed the run. The manifest records (size, sha256) for
# every file under ``state/`` at save time; restore verifies it and
# walks BACKWARD through older complete checkpoints when it fails,
# renaming the bad directory to ``ckpt_N.corrupt`` (quarantine, never
# delete — the evidence matters) instead of crashing.
#
# Local-path only: digesting a gs:// checkpoint means re-downloading it.
# Env gates: PROGEN_CKPT_DIGEST=0 skips writing manifests,
# PROGEN_CKPT_VERIFY=0 skips verification (both default on).


def _digest_enabled() -> bool:
    return os.environ.get("PROGEN_CKPT_DIGEST", "1") != "0"


def _verify_enabled() -> bool:
    return os.environ.get("PROGEN_CKPT_VERIFY", "1") != "0"


def digest_manifest(state_dir) -> Optional[dict]:
    """{relpath: [size, sha256hex]} for every file under ``state_dir``;
    None for non-local paths (CloudPath) or when digests are disabled."""
    if not _digest_enabled() or not isinstance(state_dir, Path):
        return None
    manifest = {}
    for p in sorted(state_dir.rglob("*")):
        if not p.is_file():
            continue
        h = hashlib.sha256()
        with p.open("rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        rel = p.relative_to(state_dir).as_posix()
        manifest[rel] = [p.stat().st_size, h.hexdigest()]
    return manifest


def verify_manifest(state_dir, manifest: Optional[dict]) -> bool:
    """True when every manifest entry exists with matching size+digest.
    A legacy checkpoint (no manifest) verifies trivially; extra files on
    disk are tolerated (forward compat with Orbax layout changes)."""
    if not manifest or not isinstance(state_dir, Path):
        return True
    for rel, (size, digest) in manifest.items():
        p = state_dir / rel
        try:
            if p.stat().st_size != int(size):
                return False
            h = hashlib.sha256()
            with p.open("rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    h.update(chunk)
            if h.hexdigest() != digest:
                return False
        except OSError:
            return False
    return True


def checkpoint_digest(ckpt_dir) -> Optional[str]:
    """Content identity of ONE checkpoint directory: sha256 over its
    meta.json integrity manifest (the per-file digests, already paid at
    save time — no re-hashing of array bytes). Two saves of identical
    weights agree; any differing byte under ``state/`` disagrees. Falls
    back to hashing the whole meta.json when the manifest was disabled
    (PROGEN_CKPT_DIGEST=0); None when meta.json is absent/unreadable
    (the save never completed)."""
    meta_path = Path(ckpt_dir) / "meta.json"
    try:
        meta = json.loads(meta_path.read_text())
    except (OSError, ValueError):
        return None
    payload = meta.get("integrity") or meta
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode("utf-8")
    ).hexdigest()


def digest_gauge(digest: Optional[str]) -> float:
    """The first 48 bits of a hex digest as a float gauge (exact in the
    52-bit float64 mantissa) — how a replica publishes its live
    checkpoint identity through Prometheus exposition so the deploy
    controller and the router can see fleet skew. -1.0 = unknown."""
    if not digest:
        return -1.0
    return float(int(digest[:12], 16))


class Package(NamedTuple):
    """What one checkpoint holds — reference schema, train.py:196-202,
    plus ``train_config``: optimizer-structure-affecting run settings
    (lr schedule etc.). Resume must rebuild the optimizer EXACTLY as
    saved — a schedule mismatch changes the optax state pytree and the
    sharded restore fails structurally — so these ride the checkpoint the
    same way the model config does."""

    next_seq_index: int
    state: Any  # TrainState (params + opt_state + step)
    model_config: dict
    run_id: Optional[str]
    train_config: Optional[dict] = None
    # which checkpoint directory the restore walk actually selected —
    # the hot-reload path compares this against the checkpoint it is
    # already serving (a corrupt newest quarantined by the fallback walk
    # must not be mistaken for "new weights arrived")
    path: Optional[str] = None


def _is_gcs(path: str) -> bool:
    return str(path).startswith("gs://")


def sharded_abstract_state(abstract_state: Any, shardings: Any) -> Any:
    """Attach shardings (a pytree prefix: one NamedSharding per flax
    Partitioned box / plain leaf — see partition.state_shardings) to an
    abstract state pytree, producing the restore template Orbax needs to
    place every shard directly on the mesh."""
    sh_leaves = jax.tree.leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
    )
    ab_leaves, treedef = jax.tree.flatten(abstract_state)
    assert len(sh_leaves) == len(ab_leaves), "sharding/state leaf mismatch"
    return treedef.unflatten(
        jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s)
        for l, s in zip(ab_leaves, sh_leaves)
    )


def get_checkpoint_fns(
    path: str,
    keep_last_n: int = DEFAULT_KEEP_LAST_N,
    *,
    async_save: bool = False,
) -> Tuple[Callable, Callable, Callable]:
    """(reset, get_last, save) over local or gs:// ``path``.

    save(package: Package) -> str
    get_last(abstract_state=None) -> Optional[Package]; without an abstract
        state only the metadata is loaded eagerly and ``state`` is restored
        unsharded; with one (see ``sharded_abstract_state``) every array
        restores straight to its mesh shard.
    reset() -> None: wipe the checkpoint directory (guarded by --new +
        interactive confirm at the CLI layer, train.py:85-88).

    ``async_save``: the array write overlaps subsequent training steps —
    Orbax copies device arrays to host synchronously (so the donated
    TrainState buffers are safe to reuse immediately) and commits to
    storage in the background. The ``meta.json`` finalizer runs at the
    NEXT ``save`` (or at ``save.flush()``, which the train loop calls on
    exit): until then the checkpoint has no meta.json and restore skips it
    as incomplete — the same invariant the sync path relies on for
    crash-atomicity, so a death mid-write can never be mistaken for a
    complete checkpoint.
    """
    # TensorStore requires absolute paths; the reference-parity default
    # ('./ckpts', train.py:47) arrives relative
    root = (
        ocp.path.utils.to_path(path) if _is_gcs(path) else Path(path).resolve()
    )

    def _list() -> list:
        if not _exists(root):
            return []
        # fullmatch excludes quarantined ``ckpt_N.corrupt`` dirs — they
        # stay on disk as evidence but never re-enter the rotation (and
        # never confuse the stamp arithmetic in _save)
        return sorted(
            (
                p
                for p in root.iterdir()
                if _CKPT_NAME_RE.fullmatch(p.name)
            ),
            key=lambda p: p.name,
        )

    def _exists(p) -> bool:
        try:
            return p.exists()
        except OSError:
            return False

    def reset() -> None:
        if _is_gcs(path):
            for p in _list():
                _rmtree(p)
        elif Path(path).exists():
            shutil.rmtree(path)

    def _rmtree(p) -> None:
        if isinstance(p, Path):
            shutil.rmtree(p)
        else:  # CloudPath-like
            p.rmtree()

    # async machinery: one AsyncCheckpointer reused across saves; the
    # (target, meta) awaiting its meta.json finalizer
    _async: dict = {}

    def _retain() -> None:
        """Drop complete checkpoints beyond keep_last_n (reference
        semantics, checkpoint.py:33-37) — shared by sync and async."""
        stale = _complete(_list())[:-keep_last_n] if keep_last_n else []
        for p in stale:
            _rmtree(p)

    def _finalize_pending() -> None:
        """Wait for the in-flight async array write, then publish its
        meta.json + run retention (coordinator only)."""
        import jax

        if not _async:
            return  # sync mode / nothing in flight: span-free no-op
        with telemetry.span("ckpt/finalize"):
            if "ckptr" in _async:
                _async["ckptr"].wait_until_finished()
            item = _async.pop("pending", None)
            if item is not None and jax.process_index() == 0:
                target, meta = item
                # arrays are fully committed now — digest them before
                # the manifest-bearing meta.json publishes the checkpoint
                meta["integrity"] = digest_manifest(target / "state")
                retry_call(
                    _write_text,
                    target / "meta.json",
                    json.dumps(meta),
                    label="ckpt/io/meta_write",
                )
                _retain()

    def _close() -> None:
        """Publish any pending save, then shut the background commit
        thread down deterministically (otherwise a non-daemon Orbax thread
        outlives the last flush and delays interpreter exit on aborts).
        Safe to call repeatedly; the next save() recreates the
        checkpointer."""
        _finalize_pending()
        ckptr = _async.pop("ckptr", None)
        if ckptr is not None:
            ckptr.close()

    def _save(package: Package) -> str:
        # unix-time naming (checkpoint.py:27-30) made collision-proof: two
        # saves within the same second get strictly increasing names, so
        # lexicographic order == save order always holds. Multi-host: every
        # process must pass the SAME path into the collective Orbax save, so
        # process 0's stamp is broadcast; meta.json and retention are
        # coordinator-only side effects.
        import jax

        _finalize_pending()  # no-op unless an async save is in flight

        stamp = int(time.time())
        existing = _list()
        if existing:
            last_stamp = int(existing[-1].name[len(CKPT_PREFIX):])
            stamp = max(stamp, last_stamp + 1)
        if jax.process_count() > 1:
            import numpy as _np
            from jax.experimental import multihost_utils

            stamp = int(
                multihost_utils.broadcast_one_to_all(_np.int64(stamp))
            )
        name = f"{CKPT_PREFIX}{stamp}"
        target = root / name
        if not _is_gcs(path) and jax.process_index() == 0:
            root.mkdir(parents=True, exist_ok=True)
        meta = {
            "next_seq_index": int(package.next_seq_index),
            "model_config": package.model_config,
            "run_id": package.run_id,
            "train_config": package.train_config,
        }
        if async_save:
            if "ckptr" not in _async:
                _async["ckptr"] = ocp.AsyncCheckpointer(
                    ocp.StandardCheckpointHandler()
                )
            # device->host copy happens before this returns (donation-safe);
            # storage commit runs in the background; meta.json publishes at
            # the next save()/flush()
            _async["ckptr"].save(
                target / "state", args=ocp.args.StandardSave(package.state)
            )
            _async["pending"] = (target, meta)
            return str(target)

        def _commit():
            # a failed earlier attempt can leave a partial target that
            # Orbax refuses to overwrite — clear it before re-trying
            state_dir = target / "state"
            if isinstance(state_dir, Path) and state_dir.exists():
                shutil.rmtree(state_dir)
            with ocp.StandardCheckpointer() as ckptr:
                ckptr.save(state_dir, package.state)  # collective

        if jax.process_count() > 1:
            _commit()  # collective op: per-host retry would deadlock
        else:
            retry_call(_commit, label="ckpt/io/save")
        if jax.process_index() == 0:
            # metadata written after the state commit; a checkpoint without
            # meta.json is treated as incomplete and skipped on restore.
            # The integrity manifest digests what actually hit storage.
            meta["integrity"] = digest_manifest(target / "state")
            retry_call(
                _write_text,
                target / "meta.json",
                json.dumps(meta),
                label="ckpt/io/meta_write",
            )
            _retain()
        return str(target)

    def save(package: Package) -> str:
        # the span (B with no E in events.jsonl = died mid-save) rides
        # the process telemetry; goodput crediting stays with the caller
        with telemetry.span("ckpt/save", async_mode=async_save):
            return _save(package)

    def _check_error() -> None:
        """Non-blocking poll of the background commit thread; the train
        loop calls this once per step so a fatal commit error surfaces at
        the NEXT step rather than the next flush (which may be minutes of
        silently-doomed training away). On failure: emit a
        ``ckpt_commit_failed`` event, drop the pending finalizer (a
        failed commit must never publish meta.json — the incomplete dir
        stays meta-less and restore skips it), retire the checkpointer
        (so the finally-path ``close()`` is a clean no-op), and re-raise
        to the step loop."""
        ckptr = _async.get("ckptr")
        if ckptr is None:
            return  # sync mode / nothing in flight
        check = getattr(ckptr, "check_for_errors", None)
        if check is None:
            return  # orbax without the poll API: flush-time surfacing
        try:
            check()
        except BaseException as e:
            get_registry().inc("ckpt_commit_failures")
            telemetry.get_telemetry().emit({
                "ev": "ckpt_commit_failed",
                "ts": time.time(),
                "error": f"{type(e).__name__}: {e}",
            })
            _async.pop("pending", None)
            bad = _async.pop("ckptr", None)
            if bad is not None:
                try:
                    bad.close()
                except Exception:
                    pass
            raise

    save.flush = _finalize_pending  # await + publish the in-flight save
    save.close = _close  # flush + stop the background commit thread
    save.check_error = _check_error  # per-step async commit health poll
    save._async = _async  # test seam: inject a failing checkpointer

    def _complete(candidates):
        return [p for p in candidates if _exists(p / "meta.json")]

    def _quarantine(p, reason: str) -> None:
        """Rename a bad checkpoint dir to ``<name>.corrupt`` so it leaves
        the rotation but stays on disk as evidence. Coordinator-only (on a
        shared filesystem every host sees the rename); best-effort — a
        failed rename just means the next walk re-discovers the same
        verdict."""
        import jax

        print(
            f"[checkpoint] quarantining {getattr(p, 'name', p)}: {reason}",
            flush=True,
        )
        get_registry().inc("ckpt_quarantines")
        telemetry.get_telemetry().emit({
            "ev": "ckpt_quarantine",
            "ts": time.time(),
            "ckpt": getattr(p, "name", str(p)),
            "reason": reason,
        })
        if jax.process_index() != 0 or not isinstance(p, Path):
            return
        try:
            p.rename(p.with_name(p.name + CORRUPT_SUFFIX))
        except OSError:
            pass

    # checkpoints whose manifest verified this process — peek_last and a
    # following get_last hash the same bytes once, not twice
    _verified: set = set()

    def _verify_candidate(cand) -> Optional[tuple]:
        """(dir, meta) when ``cand``'s manifest verifies; None after
        quarantining it otherwise."""
        try:
            meta = json.loads(
                retry_call(
                    _read_text,
                    cand / "meta.json",
                    label="ckpt/io/meta_read",
                )
            )
        except (OSError, ValueError):
            _quarantine(cand, "unreadable meta.json")
            return None
        if _verify_enabled() and cand.name not in _verified:
            if not verify_manifest(cand / "state", meta.get("integrity")):
                _quarantine(cand, "integrity manifest mismatch")
                return None
            _verified.add(cand.name)
        return cand, meta

    def _select_last() -> Optional[tuple]:
        """Newest COMPLETE checkpoint whose integrity manifest verifies,
        walking backward through older ones and quarantining failures —
        the fallback chain replacing the old newest-or-crash behavior.
        Returns (dir, meta) or None."""
        for cand in reversed(_complete(_list())):
            sel = _verify_candidate(cand)
            if sel is not None:
                return sel
        return None

    def _select_pinned(at) -> Optional[tuple]:
        """The SPECIFIC checkpoint ``at`` (a ``ckpt_<stamp>`` directory
        name, or a path whose basename is one), verified. A pin never
        falls back: when the target is missing, incomplete, or fails its
        digest walk (quarantined), the answer is None — serving some
        OTHER checkpoint under a pin would defeat the deploy
        controller's canary isolation."""
        name = os.path.basename(str(at).rstrip("/"))
        for cand in _complete(_list()):
            if cand.name == name:
                return _verify_candidate(cand)
        return None

    def _select(at=None) -> Optional[tuple]:
        return _select_last() if at is None else _select_pinned(at)

    def _get_last(abstract_state: Any = None) -> Optional[Package]:
        import jax

        sel = _select_last()
        if sel is None:
            return None
        last, meta = sel

        def _restore():
            with ocp.StandardCheckpointer() as ckptr:
                return ckptr.restore(last / "state", abstract_state)

        # a restore failure on a digest-verified checkpoint is structural
        # (template mismatch), not corruption — re-raise, don't walk: a
        # silent fallback would mask a real bug with stale weights
        if jax.process_count() > 1:
            state = _restore()  # collective: per-host retry would deadlock
        else:
            state = retry_call(_restore, label="ckpt/io/restore")
        return Package(
            next_seq_index=meta["next_seq_index"],
            state=state,
            model_config=meta["model_config"],
            run_id=meta["run_id"],
            train_config=meta.get("train_config"),
            path=str(last),
        )

    def get_last(abstract_state: Any = None) -> Optional[Package]:
        with telemetry.span("ckpt/restore"):
            return _get_last(abstract_state)

    def _restore_params(
        abstract_params: Any = None, at=None
    ) -> Optional[Package]:
        """Params-only restore for inference (sample CLI): skips the Adam
        moments — ~2/3 of the checkpoint bytes, which matters at 1.2B on a
        small sampling box. ``state`` in the returned Package is just the
        params pytree. ``at`` pins the restore to one specific checkpoint
        (no newest-walk, no fallback) — the hot-reload pin seam."""
        sel = _select(at)
        if sel is None:
            return None
        last, meta = sel
        with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
            if abstract_params is None:
                # shape/dtype skeleton from the checkpoint's own metadata,
                # restored whole onto the default device — exactly what
                # single-host inference wants
                dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
                meta_tree = ckptr.metadata(
                    last / "state"
                ).item_metadata.tree["params"]
                abstract_params = jax.tree.map(
                    lambda m: jax.ShapeDtypeStruct(
                        m.shape, m.dtype, sharding=dev
                    ),
                    meta_tree,
                )
            # explicit per-leaf restore args: a ShapeDtypeStruct sharding
            # alone is NOT forwarded to deserialization by this orbax, and
            # a checkpoint written from a mesh-sharded train state refuses
            # to restore without a concrete sharding (the "train on a pod,
            # sample on one host" path)
            restore_args = jax.tree.map(
                lambda a: ocp.ArrayRestoreArgs(sharding=a.sharding)
                if getattr(a, "sharding", None) is not None
                else ocp.RestoreArgs(),
                abstract_params,
            )
            restored = ckptr.restore(
                last / "state",
                args=ocp.args.PyTreeRestore(
                    item={"params": abstract_params},
                    restore_args={"params": restore_args},
                    partial_restore=True,
                ),
            )
        return Package(
            next_seq_index=meta["next_seq_index"],
            state=restored["params"],
            model_config=meta["model_config"],
            run_id=meta["run_id"],
            train_config=meta.get("train_config"),
            path=str(last),
        )

    def restore_params(
        abstract_params: Any = None, at=None
    ) -> Optional[Package]:
        with telemetry.span("ckpt/restore_params"):
            return _restore_params(abstract_params, at=at)

    get_last.restore_params = restore_params

    def peek_last(at=None) -> Optional[Package]:
        """Metadata only (state=None) — decide model config / resume point
        without paying the array restore (train.py:94-100 reads only the
        config before building the model). Runs the same verify+fallback
        walk as get_last (cached, so the bytes hash once) — otherwise the
        model could be built from a config whose checkpoint get_last later
        quarantines. ``at`` pins the peek to one specific checkpoint."""
        sel = _select(at)
        if sel is None:
            return None
        last, meta = sel
        return Package(
            next_seq_index=meta["next_seq_index"],
            state=None,
            model_config=meta["model_config"],
            run_id=meta["run_id"],
            train_config=meta.get("train_config"),
            path=str(last),
        )

    get_last.peek = peek_last  # exposed without widening the triple

    def _write_text(p, text: str) -> None:
        if isinstance(p, Path):
            p.write_text(text)
        else:
            with p.open("w") as f:
                f.write(text)

    def _read_text(p) -> str:
        if isinstance(p, Path):
            return p.read_text()
        with p.open("r") as f:
            return f.read()

    return reset, get_last, save
