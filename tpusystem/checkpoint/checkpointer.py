"""Async sharded pytree checkpointer (Orbax-backed), preemption-hardened.

Replaces the reference's ``torch.save(model.nn, f'{root}/{id}.pth')`` +
``load_state_dict`` pair (``examples/tinysys/tinysys/repository.py:13-17``)
with a TPU-appropriate design:

* **sharded**: each host writes only the array shards it owns, so an 8B
  model on a v5p-64 checkpoints at aggregate disk bandwidth instead of
  funnelling through one host;
* **async**: the save is snapshotted and committed in the background, so the
  training loop resumes immediately (the analogue of keeping the bus off the
  hot path — SURVEY.md §7.3);
* **versioned by step**: one directory per identity, one step dir per
  version — historically one per *epoch*; with step-granular resume the
  version is any monotonic global step. :meth:`Checkpointer.latest` drives
  the reference's create-or-resume decision
  (``.../services/compilation.py:41-57``);
* **preemption-safe**: a save may be torn mid-write by a kill — restore and
  latest :meth:`verify` every candidate step dir and *fall back* to the
  newest committed one (logging what was discarded) instead of crashing on
  a truncated directory; :meth:`fence` records the newest committed step in
  a monotonic commit-fence file, the durability receipt an emergency
  (SIGTERM) checkpoint needs before the process exits.

Host-side resume metadata — the data-loader cursor, wall-clock, anything
JSON-able — rides each step as ``extras`` (:meth:`save` /
:meth:`extras`): device arrays go through Orbax, the cursor through an
atomically-renamed sidecar, and :meth:`resume` returns both.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import pathlib
import shutil
import time
from typing import Any

import jax
import orbax.checkpoint as ocp

logger = logging.getLogger('tpusystem.checkpoint')

# sidecar directories under {root}/{identity}; the leading dot keeps them
# out of Orbax's integer step scan
_EXTRAS_DIR = '.extras'
_FENCE_FILE = '.fence'


def abstract_like(tree: Any) -> Any:
    """Abstract pytree (shape/dtype/sharding) used as a restore target.

    Restoring onto the *current* mesh layout — not the layout at save time —
    is what makes checkpoints portable across topology changes (e.g. resume
    a v4-8 run on a v4-32).
    """
    def spec(leaf: Any) -> Any:
        if isinstance(leaf, jax.Array):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=leaf.sharding)
        return leaf
    return jax.tree.map(spec, tree)


def _has_leaves(node: Any) -> bool:
    return bool(jax.tree.leaves(node))


def _shrink_empty_fields(node: Any) -> Any:
    """Image of a restore target without its leafless dataclass fields.

    A pytree dataclass that grew an *optional* field (``TrainState.health``,
    None when unused) no longer structure-matches checkpoints written
    before the field existed — Orbax compares tree keys, and the empty
    field still contributes one. This maps dataclass/struct nodes to plain
    dicts of their leaf-bearing fields (and prunes leafless dict entries),
    while sequences keep their exact type and arity — an optax chain tuple
    is saved as a list and must stay positional.
    """
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        return {field.name: _shrink_empty_fields(getattr(node, field.name))
                for field in dataclasses.fields(node)
                if _has_leaves(getattr(node, field.name))}
    if isinstance(node, dict):
        return {key: _shrink_empty_fields(value)
                for key, value in node.items() if _has_leaves(value)}
    if isinstance(node, (list, tuple)):
        rebuilt = [_shrink_empty_fields(value) for value in node]
        if hasattr(node, '_fields'):          # namedtuple (optax states)
            return type(node)(*rebuilt)
        return type(node)(rebuilt)
    return node


def _graft_restored(abstract: Any, image: Any) -> Any:
    """Reassemble ``abstract``'s structure from a shrunken-image restore:
    restored arrays land in their positions, pruned (leafless) fields keep
    the target's own value (e.g. ``health=None``)."""
    if dataclasses.is_dataclass(abstract) and not isinstance(abstract, type):
        fields = {}
        for field in dataclasses.fields(abstract):
            value = getattr(abstract, field.name)
            fields[field.name] = (_graft_restored(value, image[field.name])
                                  if _has_leaves(value) else value)
        return type(abstract)(**fields)
    if isinstance(abstract, dict):
        return {key: (_graft_restored(value, image[key])
                      if _has_leaves(value) else value)
                for key, value in abstract.items()}
    if isinstance(abstract, (list, tuple)):
        rebuilt = [_graft_restored(value, image[index])
                   for index, value in enumerate(abstract)]
        if hasattr(abstract, '_fields'):
            return type(abstract)(*rebuilt)
        return type(abstract)(rebuilt)
    return image


def _atomic_write(path: pathlib.Path, text: str) -> None:
    """Write-then-rename so readers never see a torn file (the same
    atomicity discipline Orbax applies to whole step dirs)."""
    path.parent.mkdir(parents=True, exist_ok=True)
    staging = path.with_name(path.name + '.tmp')
    staging.write_text(text)
    os.replace(staging, path)


class Checkpointer:
    """Identity-keyed, step-versioned pytree store.

    Layout: ``{root}/{identity}/{step}/...`` — the identity is the registry
    hash of the aggregate (deterministic across hosts and restarts), so every
    worker independently computes the same directory and the restore decision
    needs no coordination.
    """

    def __init__(self, root: str | pathlib.Path, *, max_to_keep: int | None = 3,
                 keep_every: int | None = None,
                 async_save: bool = True, save_retries: int = 2,
                 retry_backoff: float = 0.5, tracer: Any = None) -> None:
        """``max_to_keep`` bounds the rolling window; ``keep_every`` pins
        every Nth step forever in addition (GC policy: a long run keeps
        recent checkpoints for resume plus periodic ones for analysis
        /rollback instead of losing all history to the window).
        ``save_retries`` bounds the retry loop a flaky filesystem gets
        before :meth:`save` gives up (exponential backoff starting at
        ``retry_backoff`` seconds). ``tracer`` (an
        :class:`~tpusystem.observe.Tracer`, default None = no tracing
        work) wraps every save/restore dispatch in a span, so checkpoint
        cost shows on the same timeline as the recoveries it bounds."""
        self.root = pathlib.Path(root).absolute()
        self.max_to_keep = max_to_keep
        self.keep_every = keep_every
        self.async_save = async_save
        self.save_retries = save_retries
        self.retry_backoff = retry_backoff
        self.tracer = tracer
        self._managers: dict[str, ocp.CheckpointManager] = {}

    def _span(self, name: str, identity: str, epoch: Any):
        """A tracing span around one checkpoint operation (nullcontext
        when tracing is off — the default costs nothing)."""
        if self.tracer is None:
            import contextlib
            return contextlib.nullcontext()
        return self.tracer.span(name, cat='checkpoint',
                                args={'identity': identity, 'epoch': epoch})

    def _manager(self, identity: str) -> ocp.CheckpointManager:
        if identity not in self._managers:
            options = ocp.CheckpointManagerOptions(
                max_to_keep=self.max_to_keep,
                keep_period=self.keep_every,
                enable_async_checkpointing=self.async_save)
            self._managers[identity] = ocp.CheckpointManager(
                self.root / identity, options=options)
        return self._managers[identity]

    def save(self, identity: str, epoch: int, state: Any, *,
             extras: Any | None = None) -> None:
        """Snapshot ``state`` under (identity, epoch); returns immediately.

        ``epoch`` is the version number — an epoch index or a global step;
        versions must be saved in increasing order. With ``async_save`` the
        device buffers are copied out synchronously (cheap) and serialized in
        a background thread; call :meth:`wait` (or rely on save-on-next-epoch
        barriers) before reading the files, and :meth:`fence` for a
        durability receipt.

        ``extras`` is optional host-side resume metadata (anything
        JSON-able: the data-loader cursor, host step, wall time). It is
        written synchronously to an atomically-renamed sidecar — it never
        blocks on the array serialization — and comes back via
        :meth:`extras` / :meth:`resume`.

        Failure surfacing: a *previous* async save that failed in the
        background raises here (and at :meth:`newest`) instead of hiding
        until :meth:`wait`/:meth:`fence` — the training loop learns its
        durability story broke at the very next step, while the state that
        could re-save is still alive. The save itself gets a bounded
        retry with exponential backoff (``save_retries`` / ``retry_backoff``)
        against transient filesystem errors before giving up.
        """
        self._surface_async_errors(identity)
        with self._span('checkpoint-save', identity, epoch):
            if extras is not None:
                # sidecar BEFORE the array commit: a kill between the two
                # must not leave a committed step with no cursor (an orphan
                # sidecar for a never-committed step is harmless, pruned
                # later)
                _atomic_write(self._extras_path(identity, epoch),
                              json.dumps(extras))
            manager = self._manager(identity)
            for attempt in range(self.save_retries + 1):
                try:
                    manager.save(epoch, args=ocp.args.StandardSave(state))
                    break
                except OSError as error:
                    if attempt == self.save_retries:
                        raise
                    delay = self.retry_backoff * (2 ** attempt)
                    logger.warning(
                        'checkpoint save %s/%s/%d failed (%s); retry %d/%d '
                        'in %.1fs', self.root, identity, epoch, error,
                        attempt + 1, self.save_retries, delay)
                    time.sleep(delay)
            self._prune_extras(identity)

    def _surface_async_errors(self, identity: str) -> None:
        """Re-raise a background async-save failure at the *next* call.

        Orbax parks exceptions from the commit thread until someone asks;
        without this probe they only surfaced at ``wait``/``fence`` —
        potentially thousands of steps after the durability story silently
        broke. Gated on the public ``check_for_errors`` where this Orbax
        has it."""
        manager = self._managers.get(identity)
        check = getattr(manager, 'check_for_errors', None)
        if check is not None:
            check()

    def _extras_path(self, identity: str, epoch: int) -> pathlib.Path:
        return self.root / identity / _EXTRAS_DIR / f'{int(epoch)}.json'

    def _prune_extras(self, identity: str) -> None:
        """Drop sidecars whose step dir Orbax's GC already collected.

        Only steps *below* the newest on-disk step are candidates: an async
        save still in flight has no committed dir yet (its tmp dir is not
        integer-named), and its sidecar — written synchronously — must
        survive until the commit lands."""
        extras_dir = self.root / identity / _EXTRAS_DIR
        if not extras_dir.is_dir():
            return
        on_disk = self._disk_steps(identity)
        if not on_disk:
            return
        live = set(on_disk)
        for sidecar in extras_dir.glob('*.json'):
            if not sidecar.stem.isdigit():
                continue
            step = int(sidecar.stem)
            if step < on_disk[-1] and step not in live:
                sidecar.unlink(missing_ok=True)

    def extras(self, identity: str, epoch: int) -> Any | None:
        """Host-side resume metadata saved with (identity, epoch), or None."""
        path = self._extras_path(identity, epoch)
        if not path.is_file():
            return None
        return json.loads(path.read_text())

    # ------------------------------------------------------------------
    # integrity: verify / committed steps / fence

    def _disk_steps(self, identity: str) -> list[int]:
        """Integer-named step dirs on disk, ascending — committed or not.

        Read from the filesystem (not the manager's cached step list) so a
        fresh process sees exactly what a kill left behind, including torn
        dirs a crashed writer never renamed away.
        """
        home = self.root / identity
        if not home.is_dir():
            return []
        return sorted(int(entry.name) for entry in home.iterdir()
                      if entry.is_dir() and entry.name.isdigit())

    def verify(self, identity: str, epoch: int) -> bool:
        """Integrity probe: is (identity, epoch) a *committed* checkpoint?

        A committed Orbax step dir carries a ``_CHECKPOINT_METADATA`` commit
        marker and at least one item payload with its ``_METADATA``
        manifest. A dir missing either is incomplete — a save torn by a
        preemption mid-write, or a partial copy — and must be skipped by
        the resume path, never handed to a restore that would crash on it.

        Orbax's public ``is_checkpoint_finalized`` is consulted where
        available but cannot replace the marker probe: on the pinned 0.7.0
        it only checks the commit-by-rename naming convention, so a
        planted/truncated dir with a plain integer name passes it.
        """
        step_dir = self.root / identity / str(int(epoch))
        if not step_dir.is_dir():
            return False
        is_tmp = getattr(ocp.utils, 'is_tmp_checkpoint', None)
        if is_tmp is not None and is_tmp(step_dir):
            return False
        if not (step_dir / '_CHECKPOINT_METADATA').is_file():
            return False
        items = [entry for entry in step_dir.iterdir() if entry.is_dir()]
        if not items:
            return False
        return all((item / '_METADATA').is_file() for item in items)

    def committed(self, identity: str) -> list[int]:
        """Committed (verified) steps for the identity, ascending; torn or
        corrupt step dirs are skipped and logged."""
        steps = []
        for step in self._disk_steps(identity):
            if self.verify(identity, step):
                steps.append(step)
            else:
                logger.warning(
                    'checkpoint %s/%s/%d is incomplete or corrupt; skipping',
                    self.root, identity, step)
        return steps

    def fence(self, identity: str) -> int | None:
        """Commit fence: block until in-flight saves land, then record the
        newest committed step in a monotonic fence file.

        The fence is the durability receipt of the preemption path — an
        emergency save followed by ``fence()`` guarantees the checkpoint is
        on disk before the process exits with a restartable code. The
        recorded step never decreases: a reader of :meth:`fenced` can trust
        that at least that step survived, whatever a later kill tore.
        """
        self.wait()
        steps = self.committed(identity)
        newest = steps[-1] if steps else None
        if newest is None:
            return self.fenced(identity)
        previous = self.fenced(identity)
        if previous is not None and previous > newest:
            return previous
        _atomic_write(self.root / identity / _FENCE_FILE,
                      json.dumps({'step': newest}))
        return newest

    def fenced(self, identity: str) -> int | None:
        """The fenced (guaranteed-durable) step, or None before any fence."""
        path = self.root / identity / _FENCE_FILE
        if not path.is_file():
            return None
        return int(json.loads(path.read_text())['step'])

    # ------------------------------------------------------------------
    # restore

    def restore(self, identity: str, target: Any, epoch: int | None = None) -> Any:
        """Restore the pytree saved under (identity, epoch or latest).

        ``target`` may be a concrete pytree (its shapes/dtypes/shardings are
        used, see :func:`abstract_like`) or an abstract one. Each shard is
        read straight onto its mesh device.

        An **explicit** ``epoch`` must exist and verify — a missing or
        corrupt one raises :class:`FileNotFoundError` naming the committed
        epochs, so the caller sees what it *can* restore instead of an
        opaque Orbax error. With ``epoch=None`` the newest committed step is
        used, falling back over torn/corrupt dirs (each discard logged).
        """
        abstract = abstract_like(target)
        with self._span('checkpoint-restore', identity, epoch):
            if epoch is not None:
                if not self.verify(identity, epoch):
                    available = self.committed(identity)
                    raise FileNotFoundError(
                        f'no committed checkpoint for identity {identity!r} '
                        f'at epoch {epoch} under {self.root} '
                        f'(committed epochs: {available or "none"})')
                return self._restore_step(identity, epoch, abstract)
            return self._restore_newest(identity, abstract)[0]

    def _restore_step(self, identity: str, epoch: int, abstract: Any) -> Any:
        """One step's restore, with the legacy-shape fallback.

        A target pytree that grew optional (leafless) dataclass fields
        since the checkpoint was written — ``TrainState.health`` is the
        canonical case — fails Orbax's structure match even though every
        *array* still lines up. On that specific mismatch the restore
        retries with the leafless fields pruned from the target
        (:func:`_shrink_empty_fields`) and grafts the arrays back into the
        caller's structure, so pre-upgrade runs keep resuming. A target
        whose new fields carry arrays (an armed guard against a pre-guard
        checkpoint) still fails loudly: restore unarmed, then arm.
        """
        manager = self._manager(identity)
        try:
            return manager.restore(epoch, args=ocp.args.StandardRestore(abstract))
        except ValueError as error:
            # orbax 0.11's wording for a restore target whose tree keys
            # differ from the checkpoint's
            if 'structures do not match' not in str(error):
                raise
            logger.warning(
                'restore target for %s/%d has fields the checkpoint '
                'predates; retrying with the legacy-shape subset (%s)',
                identity, epoch, str(error)[:200])
            image = manager.restore(
                epoch, args=ocp.args.StandardRestore(
                    _shrink_empty_fields(abstract)))
            return _graft_restored(abstract, image)

    def _restore_newest(self, identity: str, abstract: Any) -> tuple[Any, int]:
        """Restore the newest committed step, falling back over steps whose
        payload fails to load despite passing the probe (each discard
        logged); returns ``(state, step)``.

        If *every* committed step fails, the last underlying error is
        re-raised — a wrong restore target (model-config drift since the
        save) fails every step identically, and masking that as
        FileNotFoundError would let a create-or-resume caller silently
        reinitialize over good checkpoints.
        """
        candidates = self.committed(identity)
        errors: list[tuple[int, Exception]] = []
        for step in reversed(candidates):
            try:
                state = self._restore_step(identity, step, abstract)
                return state, step
            except Exception as error:  # torn payload that passed the probe
                errors.append((step, error))
                logger.warning(
                    'restore of %s/%s/%d failed (%s); falling back to the '
                    'previous committed step', self.root, identity, step, error)
        if errors:
            raise errors[-1][1]
        raise FileNotFoundError(
            f'no restorable checkpoint for identity {identity!r} under '
            f'{self.root}')

    def resume(self, identity: str, target: Any) -> tuple[Any, int, Any | None]:
        """One-call resume: ``(state, step, extras)`` from the newest
        committed checkpoint — the restart half of the preemption cycle.

        Uses the same newest-to-oldest fallback as the implicit
        :meth:`restore`: a step whose payload is torn despite a passing
        probe is logged and skipped, not crashed on. ``extras`` is whatever
        host metadata :meth:`save` stored (e.g. the data-loader cursor to
        :meth:`~tpusystem.data.Loader.seek`), or None.
        """
        state, step = self._restore_newest(identity, abstract_like(target))
        return state, step, self.extras(identity, step)

    def latest(self, identity: str) -> int | None:
        """Latest *committed* step for the identity, or ``None`` if fresh.

        Torn or corrupt step dirs (a kill mid-save, a truncated copy) are
        skipped with a logged warning — the create-or-resume decision
        (``.../services/compilation.py:41-57``) must land on a checkpoint
        that will actually restore. For allocating the *next* version
        number use :meth:`newest` — an async save still in flight has no
        committed dir yet and must not have its step reused.
        """
        steps = self.committed(identity)
        return steps[-1] if steps else None

    def newest(self, identity: str) -> int | None:
        """Newest *known* step — on disk (committed or torn) or still in
        flight as an async save. Version allocation only
        (``Repository.store``'s auto increment), never resume: a torn dir
        still owns its number (saving over it would collide) and an
        in-flight step has nothing readable on disk yet, so no integrity
        probe runs here. Like :meth:`save`, re-raises a background
        async-save failure instead of deferring it to ``wait``/``fence``."""
        self._surface_async_errors(identity)
        on_disk = self._disk_steps(identity)
        candidates = [step for step in (on_disk[-1] if on_disk else None,
                                        self._manager(identity).latest_step())
                      if step is not None]
        return max(candidates) if candidates else None

    def discard_after(self, identity: str, step: int) -> list[int]:
        """Drop every step dir newer than ``step`` — the rollback epilogue.

        After a sentinel rollback (:class:`tpusystem.train.Sentinel`), the
        steps beyond the rollback target are a dead branch: their params
        carry (or postdate) the anomaly, and leaving them on disk would
        make the retrained steps collide with their numbers
        (StepAlreadyExists) and make ``latest``/``resume`` prefer the bad
        branch after a crash. Waits out in-flight saves first, removes the
        dead steps (committed or torn) plus their sidecars, and lowers the
        commit fence to ``step`` if it pointed into the discarded range —
        the fence's "at least this step survived" promise transfers to the
        rollback target. Returns the discarded step numbers.
        """
        self.wait()
        dead = [at for at in self._disk_steps(identity) if at > step]
        manager = self._managers.get(identity)
        for at in dead:
            delete = getattr(manager, 'delete', None)
            try:
                if delete is not None:
                    delete(at)
                else:
                    shutil.rmtree(self.root / identity / str(at))
            except (OSError, ValueError):
                shutil.rmtree(self.root / identity / str(at),
                              ignore_errors=True)
            (self._extras_path(identity, at)).unlink(missing_ok=True)
            logger.warning('discarded dead-branch checkpoint %s/%s/%d '
                           '(rollback to %d)', self.root, identity, at, step)
        fenced = self.fenced(identity)
        if fenced is not None and fenced > step:
            _atomic_write(self.root / identity / _FENCE_FILE,
                          json.dumps({'step': int(step)}))
        return dead

    def epochs(self, identity: str) -> list[int]:
        """All retained committed epochs for the identity, ascending."""
        return self.committed(identity)

    def wait(self) -> None:
        """Block until every in-flight async save has committed."""
        for manager in self._managers.values():
            manager.wait_until_finished()

    def close(self) -> None:
        """Finalize pending saves and release resources."""
        for manager in self._managers.values():
            manager.wait_until_finished()
            manager.close()
        self._managers.clear()

    def __enter__(self) -> 'Checkpointer':
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
