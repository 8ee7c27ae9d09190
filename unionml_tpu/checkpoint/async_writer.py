"""Async checkpointing: host snapshot now, durable commit in the background.

The Orbax path (:mod:`unionml_tpu.checkpoint.sharded`) already writes
asynchronously, but the training loop still pays a per-save stall that
the goodput layer attributes to the ``checkpoint`` badput bucket, and
the ``train_goodput`` attribution cannot see *inside* Orbax's worker.
This module is the framework-owned replacement for the single-process
case (CheckFreq / async-Orbax lineage): ``save`` snapshots the state
pytree to host memory — the device→host copy is the ONLY synchronous
cost — and a background thread serializes, writes, and **commits
atomically** (write into a ``*.tmp-*`` dir, fsync, drop a
``_COMMITTED`` marker, then ``os.replace`` onto the final name).
A kill at ANY point therefore leaves either the previous complete
checkpoint or the new complete checkpoint — never a torn one:

- crash before the rename → only an uncommitted ``*.tmp-*`` dir
  exists; :meth:`AsyncCheckpointManager.latest_step` ignores it and a
  restart resumes from the previous step (stale tmp dirs are swept on
  the next manager construction);
- a ``step_N`` dir missing its ``_COMMITTED`` marker (external
  interference, partial copy) is **refused** by restore and skipped by
  ``latest_step`` — a torn checkpoint can never be silently loaded.

Telemetry splits the two legs (docs/observability.md "Which metrics
each layer emits"): ``unionml_checkpoint_save_ms{kind="async"}``
records the caller stall (wait-for-previous-commit + snapshot +
launch), ``unionml_checkpoint_commit_ms{kind="async"}`` the background
serialize/write/rename, and the ``unionml_checkpoint_pending`` gauge
counts launched-but-not-yet-durable commits. A failed background
commit is logged, counted out of ``pending``, and re-raised on the
strict barrier (:meth:`~AsyncCheckpointWriter.wait`) — ``close`` is
best-effort cleanup and only logs, so a trainer's ``finally`` block
never masks the real exception with a checkpoint one.

Multi-process meshes keep the Orbax path (each host writes only its
addressable shards); :func:`make_checkpoint_manager` picks per
``jax.process_count()`` — and sticks with Orbax when ``root`` already
holds marker-less (Orbax-format) step dirs, so a resume never silently
restarts from scratch after a framework upgrade.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Callable, Optional, Union

from unionml_tpu._logging import logger
from unionml_tpu.checkpoint._metrics import checkpoint_metrics, tree_nbytes

__all__ = [
    "AsyncCheckpointManager",
    "AsyncCheckpointWriter",
    "COMMIT_MARKER",
    "is_committed",
    "make_checkpoint_manager",
]

#: Marker file a committed checkpoint dir must contain. Written inside
#: the tmp dir BEFORE the atomic rename, so a final-named dir without
#: it can only mean external interference — restore refuses it.
COMMIT_MARKER = "_COMMITTED"

_DATA_FILE = "state.msgpack"


def is_committed(path: Union[str, os.PathLike]) -> bool:
    """True iff ``path`` is a fully committed async checkpoint dir."""
    p = Path(path)
    return (p / COMMIT_MARKER).is_file() and (p / _DATA_FILE).is_file()


def _fsync_dir(path: Path) -> None:
    """fsync a DIRECTORY's entries: file-content fsyncs alone do not
    make creations/renames inside it durable across power loss."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _host_snapshot(state: Any) -> Any:
    """Device→host copy of every array leaf (the one synchronous cost
    of an async save). Forces any in-flight donated step to finish —
    after this returns, the training loop may freely donate/overwrite
    the device buffers."""
    import jax

    return jax.device_get(state)


def _replace_leaves(target: Any, restored: Any) -> Any:
    """Re-place restored host leaves per ``target``'s device placement:
    leaves that are jax.Arrays in the target keep their sharding
    (device_put of the host value), everything else stays host-side."""
    import jax

    def put(t, v):
        if isinstance(t, jax.Array):
            return jax.device_put(v, t.sharding)
        return v

    return jax.tree_util.tree_map(put, target, restored)


class AsyncCheckpointWriter:
    """One-at-a-time background committer for host-snapshotted pytrees.

    ``save(path, state)`` blocks only for (1) the previous commit —
    normally already durable, it ran during the intervening training
    steps — and (2) the device→host snapshot, then launches the
    serialize/write/rename on a daemon thread and returns. ``wait()``
    is the strict barrier: it blocks until the launched commit is
    durable and re-raises its failure, if any.

    ``commit_hook(final_path)`` is a test/chaos seam (the elastic
    trainer's ``fault_hook`` analog): it runs on the background thread
    just before the atomic rename, so a kill-mid-commit is an injected
    raise — the tmp dir stays uncommitted and the previous checkpoint
    remains the newest restorable one.
    """

    def __init__(
        self,
        *,
        registry: Optional[Any] = None,
        kind: str = "async",
        commit_hook: Optional[Callable[[Path], None]] = None,
    ):
        self.kind = kind
        self.commit_hook = commit_hook
        self._metrics = checkpoint_metrics(registry)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._pending = 0
        self._lock = threading.Lock()
        self._seq = 0

    # -- save --------------------------------------------------------------

    def save(
        self,
        path: Union[str, os.PathLike],
        state: Any,
        *,
        inline: bool = False,
    ) -> None:
        """Snapshot ``state`` to host and launch the background commit
        of ``path`` (a directory). Caller stall = wait-for-previous +
        snapshot + launch, observed as ``save_ms{kind}``. With
        ``inline=True`` the commit runs on the CALLER thread — the
        whole serialize/write/rename lands inside the ``save_ms``
        window, since that is genuinely what the caller stalled on (the
        overlap-off baseline); the failure, if any, surfaces on the
        next :meth:`wait`, same as the background form."""
        t0 = time.perf_counter()
        # one commit in flight at a time: a second writer would contend
        # for host I/O (and interleaved commits would reorder durability)
        self.wait()
        host_state = _host_snapshot(state)
        final = Path(path).absolute()
        self._seq += 1
        tmp = final.parent / f"{final.name}.tmp-{os.getpid()}-{self._seq}"
        with self._lock:
            self._pending += 1
            self._metrics["pending"].set(float(self._pending))
        if inline:
            self._commit(tmp, final, host_state)
        else:
            self._thread = threading.Thread(
                target=self._commit, args=(tmp, final, host_state),
                name=f"ckpt-commit-{final.name}", daemon=True,
            )
            self._thread.start()
        self._metrics["save_ms"].labels(self.kind).observe(
            (time.perf_counter() - t0) * 1e3
        )
        self._metrics["save_bytes"].labels(self.kind).inc(
            tree_nbytes(host_state)
        )

    def _commit(self, tmp: Path, final: Path, host_state: Any) -> None:
        t0 = time.perf_counter()
        try:
            from flax import serialization

            payload = serialization.to_bytes(host_state)
            tmp.mkdir(parents=True, exist_ok=True)
            data = tmp / _DATA_FILE
            with open(data, "wb") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            marker = tmp / COMMIT_MARKER
            with open(marker, "w") as f:
                json.dump({"nbytes": len(payload)}, f)
                f.flush()
                os.fsync(f.fileno())
            # directory entries need their own fsync for the durability
            # contract to survive power loss, not just process death:
            # the tmp dir's entries before the rename, the parent's
            # rename record after
            _fsync_dir(tmp)
            if self.commit_hook is not None:
                self.commit_hook(final)
            # the atomic point: a crash strictly before this line leaves
            # only the tmp dir (ignored by restore); after it, the final
            # dir is complete WITH its marker. Re-saving an existing
            # step (manual manager use, a rolled-back run re-reaching
            # the step number): os.replace cannot replace a non-empty
            # directory (ENOTEMPTY kills the commit), so the committed
            # dir is first moved aside onto the tmp namespace — restore
            # ignores *.tmp-* names, and a crash inside the two-rename
            # window loses only this step (latest_step falls back to
            # the previous committed one; the old behavior failed the
            # whole run instead)
            if final.is_dir():
                stale = final.parent / f"{final.name}.tmp-resave"
                shutil.rmtree(stale, ignore_errors=True)
                os.replace(final, stale)
                os.replace(tmp, final)
                shutil.rmtree(stale, ignore_errors=True)
            else:
                os.replace(tmp, final)
            _fsync_dir(final.parent)
            self._metrics["commit_ms"].labels(self.kind).observe(
                (time.perf_counter() - t0) * 1e3
            )
        except BaseException as exc:  # surfaces on the next wait()/save()
            with self._lock:
                self._error = exc
            shutil.rmtree(tmp, ignore_errors=True)
            logger.warning(
                f"async checkpoint commit of {final.name} failed: {exc!r}"
            )
        finally:
            with self._lock:
                self._pending -= 1
                self._metrics["pending"].set(float(self._pending))

    # -- barriers ----------------------------------------------------------

    def wait(self) -> None:
        """Block until the launched commit (if any) is durable;
        re-raises a background commit failure exactly once."""
        thread = self._thread
        if thread is not None:
            thread.join()
            self._thread = None
        with self._lock:
            error, self._error = self._error, None
        if error is not None:
            raise RuntimeError(
                "async checkpoint commit failed (the previous checkpoint "
                "is still the newest restorable one)"
            ) from error

    def close(self) -> None:
        """Best-effort drain: waits for the in-flight commit but only
        LOGS a failure — safe inside a trainer's ``finally`` where
        raising would mask the real exception."""
        try:
            self.wait()
        except RuntimeError as exc:
            logger.warning(f"async checkpoint writer closed dirty: {exc}")

    # -- restore -----------------------------------------------------------

    def restore(self, path: Union[str, os.PathLike], target: Any) -> Any:
        """Restore a committed checkpoint dir into ``target``'s
        structure and device placement. Refuses torn checkpoints: a dir
        without its commit marker raises instead of loading garbage."""
        t0 = time.perf_counter()
        self.wait()
        final = Path(path).absolute()
        if not final.is_dir():
            raise FileNotFoundError(f"no checkpoint at {final}")
        if not is_committed(final):
            raise ValueError(
                f"refusing torn checkpoint {final}: commit marker "
                f"{COMMIT_MARKER!r} missing (crash mid-write or partial "
                "copy) — restore an earlier committed step instead"
            )
        from flax import serialization

        payload = (final / _DATA_FILE).read_bytes()
        restored = serialization.from_bytes(target, payload)
        out = _replace_leaves(target, restored)
        self._metrics["restore_ms"].labels(self.kind).observe(
            (time.perf_counter() - t0) * 1e3
        )
        self._metrics["restore_bytes"].labels(self.kind).inc(len(payload))
        return out


class AsyncCheckpointManager:
    """Step-indexed checkpoint rotation over :class:`AsyncCheckpointWriter`.

    Same surface as the Orbax :class:`~unionml_tpu.checkpoint.sharded.
    CheckpointManager` (``save/restore/latest_step/wait/close``), so the
    elastic trainer swaps between them per
    :func:`make_checkpoint_manager`. Differences that matter:

    - ``save`` stalls the caller for the device→host snapshot only;
      the disk write overlaps the following training steps
      (``async_commit=False`` commits inline — the overlap-off
      baseline);
    - ``latest_step``/``restore`` see only COMMITTED checkpoints, so a
      kill mid-commit resumes from the previous step instead of a torn
      dir (uncommitted ``*.tmp-*`` leftovers are swept at construction);
    - ``restore`` requires a ``state_target`` (the msgpack wire format
      needs the pytree structure to restore into).
    """

    def __init__(
        self,
        root: Union[str, os.PathLike],
        *,
        max_to_keep: int = 3,
        async_commit: bool = True,
        registry: Optional[Any] = None,
        commit_hook: Optional[Callable[[Path], None]] = None,
    ):
        if max_to_keep is not None and max_to_keep < 0:
            raise ValueError(
                f"max_to_keep must be >= 0 or None, got {max_to_keep}"
            )
        self.root = Path(root).absolute()
        self.max_to_keep = max_to_keep
        self.async_commit = async_commit
        self.root.mkdir(parents=True, exist_ok=True)
        self._writer = AsyncCheckpointWriter(
            registry=registry, commit_hook=commit_hook
        )
        # a crashed predecessor leaves *.tmp-* dirs: uncommitted garbage,
        # safe to sweep (the atomic rename means a commit either fully
        # happened or left only this)
        for stale in self.root.glob("step_*.tmp-*"):
            shutil.rmtree(stale, ignore_errors=True)
        # a directory holding ONLY marker-less step dirs is a different
        # format (an Orbax-era run): refusing beats what backend="sync"
        # / "async" forced here would otherwise do — see no committed
        # steps and silently restart the run from step 0 ("auto" detects
        # this and picks Orbax). A dir with at least one committed step
        # is ours: a stray marker-less dir there is a torn external copy,
        # skipped per the restore contract.
        markerless = [
            p.name for p in self.root.glob("step_*")
            if p.is_dir() and "tmp" not in p.name and not is_committed(p)
        ]
        if markerless and not self._steps():
            raise ValueError(
                f"{self.root} holds checkpoint dirs without commit "
                f"markers ({sorted(markerless)[:3]}…): an Orbax-format "
                "run this manager cannot restore — resuming here would "
                "silently restart from step 0. Use backend='orbax' (or "
                "'auto') for this directory."
            )

    def _steps(self):
        steps = []
        for p in self.root.glob("step_*"):
            try:
                step = int(p.name.split("_", 1)[1])
            except ValueError:
                continue  # in-flight *.tmp-* dirs and strangers
            if is_committed(p):
                steps.append(step)
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        """Newest COMMITTED step (torn/in-flight dirs never count)."""
        steps = self._steps()
        return steps[-1] if steps else None

    def _prune(self) -> None:
        if not self.max_to_keep:
            return  # 0/None keep everything
        # only called after wait(): every counted dir is committed, so
        # the durable count never drops below max_to_keep
        for victim in self._steps()[: -self.max_to_keep or None]:
            shutil.rmtree(self.root / f"step_{victim}", ignore_errors=True)

    def save(self, step: int, state: Any) -> None:
        """Launch the commit of ``step``; caller pays snapshot only
        (plus the wait for the previous commit, normally already done —
        the writer waits INSIDE its timed window, so ``save_ms`` records
        the whole documented stall). Pruning needs no barrier: it only
        ever removes COMMITTED dirs, never an in-flight rename target.
        With ``async_commit=False`` the commit runs inline on the
        caller thread — the full serialize/write/rename stall lands in
        ``save_ms``, which is exactly what the caller paid."""
        self._prune()
        self._writer.save(
            self.root / f"step_{step}", state,
            inline=not self.async_commit,
        )
        if not self.async_commit:
            self._writer.wait()  # surfaces the inline commit's failure

    def wait(self) -> None:
        """Strict barrier: block until every launched save is durable
        (re-raising background failures), then prune."""
        self._writer.wait()
        self._prune()

    def restore(self, state_target: Any = None, step: Optional[int] = None) -> Any:
        if state_target is None:
            raise ValueError(
                "AsyncCheckpointManager.restore needs a state_target: the "
                "msgpack wire format restores INTO a pytree structure "
                "(pass the freshly-initialized state)"
            )
        self._writer.close()  # drain, but let restore pick the survivor
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoints under {self.root}")
        return self._writer.restore(self.root / f"step_{step}", state_target)

    def close(self) -> None:
        """Best-effort drain + prune (logs, never raises — safe in
        ``finally`` blocks)."""
        self._writer.close()
        self._prune()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def make_checkpoint_manager(
    root: Union[str, os.PathLike],
    *,
    max_to_keep: int = 3,
    backend: str = "auto",
    async_commit: bool = True,
    registry: Optional[Any] = None,
):
    """The checkpoint-manager factory the trainer loops use.

    ``backend="auto"`` picks :class:`AsyncCheckpointManager`
    single-process and the Orbax
    :class:`~unionml_tpu.checkpoint.sharded.CheckpointManager` under
    ``jax.process_count() > 1`` (each host must write only its
    addressable shards) — and falls back to Orbax when ``root``
    already holds marker-less (Orbax-format) step dirs, so resuming an
    existing run never silently restarts from step 0. ``"async"`` /
    ``"orbax"`` force a side; ``"sync"`` (or ``async_commit=False``)
    is the async manager with INLINE commits — the caller pays
    serialize+write+rename: the overlap-off baseline.
    """
    if backend not in ("auto", "async", "orbax", "sync"):
        raise ValueError(
            f"unknown checkpoint backend {backend!r}: "
            "expected 'auto', 'async', 'orbax' or 'sync'"
        )
    if backend == "sync":
        backend, async_commit = "async", False
    if backend == "auto":
        import jax

        backend = "orbax" if jax.process_count() > 1 else "async"
        if backend == "async":
            for p in Path(root).absolute().glob("step_*"):
                if "tmp" in p.name or not p.is_dir():
                    continue
                if not is_committed(p):
                    # pre-existing Orbax-format checkpoints: stay Orbax
                    backend = "orbax"
                    break
    if backend == "async":
        return AsyncCheckpointManager(
            root, max_to_keep=max_to_keep, async_commit=async_commit,
            registry=registry,
        )
    from unionml_tpu.checkpoint.sharded import CheckpointManager

    return CheckpointManager(
        root, max_to_keep=max_to_keep, async_save=async_commit,
        registry=registry,
    )
