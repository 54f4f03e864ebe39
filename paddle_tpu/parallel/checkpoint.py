"""Step-managed checkpointing over orbax (no reference analogue — the
reference's save_persistables writes one host-side npz per save; orbax
adds step retention, atomic writes, and per-host parallel shard writes
when the saved values are device-resident jax Arrays).

Restore materializes host arrays (the executor re-places them on next
run). Pod-scale sharded restore-in-place would need the target layouts
from the compiled program; not wired yet — restores are host-replicated.

Used directly, through ``fluid.io.save_persistables(...,
use_orbax=True)`` / ``load_persistables(..., use_orbax=True)``, or via
``fluid.resilience.TrainGuard`` (periodic auto-save + crash-resume).

Read-path contract (the resume path must never explode on a fresh run
directory): ``latest_step`` on a missing/empty/garbage directory returns
None; ``load_checkpoint`` raises an IOError naming the directory instead
of surfacing raw orbax internals.
"""
import json
import os
import threading
import time
import warnings

import numpy as np

__all__ = ["save_checkpoint", "load_checkpoint", "latest_step",
           "restore_latest", "finalize", "verify_checkpoint", "all_steps",
           "worker_dir", "mark_save_complete", "latest_consensus_step",
           "restore_latest_consensus", "CONSENSUS_DIR",
           "compile_cache_dir", "COMPILE_CACHE_SUBDIR",
           "INTEGRITY_SUBDIR", "manifest_path"]

# managers kept open across saves so async writes can complete in the
# background; finalize()/Executor.close()/process exit flushes them
_managers = {}

# orbax numbers every save with ONE process-wide "current operation id"
# (orbax ..._src/futures/synchronization.OperationIdGenerator): a save
# advances it and then reads it back, several times, while it enqueues —
# the step- and item-directory creation signals are keyed by what those
# reads return. Two saves that enqueue at the same moment from two
# threads (two managers, two directories: the elastic fleet's workers)
# read each other's id and then create, await and remove each other's
# temporary directories. Only the enqueue reads the global; the
# background write carries the id it captured, so background writes of
# different managers still overlap, and wait=False keeps its meaning.
_enqueue_lock = threading.Lock()

# digest-manifest finisher threads for wait=False saves (dir -> list);
# finalize() joins them so a flushed directory always has its manifests
_pending_manifests = {}

# The persistent AOT compile cache rides next to the checkpoints it
# warm-starts: a crash-resumed trainer finds BOTH its state and its
# compiled executables under the one run directory. The subdir name is
# non-numeric so the step-scanning read paths (all_steps, orbax's
# layout walk) never mistake it for a checkpoint step.
COMPILE_CACHE_SUBDIR = "compile-cache"

# Per-step content-digest manifests (paddle_tpu/integrity/) live in a
# sibling of the orbax step dirs — non-numeric, so the step scanners
# skip it, and OUTSIDE the step dir, so orbax's own layout never sees
# a foreign file. PADDLE_TPU_CHECKPOINT_DIGEST=0 opts a save out.
INTEGRITY_SUBDIR = "integrity"
_DIGEST_ENV = "PADDLE_TPU_CHECKPOINT_DIGEST"


def manifest_path(dirname, step):
    """Path of the per-tensor digest manifest for checkpoint `step`."""
    return os.path.join(dirname, INTEGRITY_SUBDIR,
                        "step%012d.json" % int(step))


def _digests_enabled():
    return os.environ.get(_DIGEST_ENV, "1") not in ("0", "off", "")


def compile_cache_dir(dirname):
    """The co-located persistent compile-cache directory for checkpoint
    root `dirname` (see ``fluid.compile_cache`` /
    ``TrainGuard(compile_cache=True)``). Layout helper only — nothing is
    created until the executor stores an entry."""
    return os.path.join(dirname, COMPILE_CACHE_SUBDIR)


def _manager(dirname, max_to_keep=None):
    import orbax.checkpoint as ocp

    key = os.path.abspath(dirname)
    mgr = _managers.get(key)
    if mgr is None:
        mgr = ocp.CheckpointManager(
            key,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True,
            ),
        )
        _managers[key] = mgr
    return mgr


def finalize(dirname=None):
    """Flush and close the manager(s): pending async saves complete.
    Idempotent — unknown dirnames and repeat calls are no-ops, and a
    manager is dropped from the registry even if its close() raises (so
    a second finalize can't re-raise on a half-dead manager)."""
    keys = (
        [os.path.abspath(dirname)] if dirname
        else list(set(_managers) | set(_pending_manifests)))
    first_error = None
    for k in keys:
        mgr = _managers.pop(k, None)
        if mgr is not None:
            try:
                mgr.close()
            except Exception as e:  # noqa: BLE001 — keep flushing the rest
                if first_error is None:
                    first_error = e
        for fin in _pending_manifests.pop(k, ()):
            fin.join(timeout=60.0)
    if first_error is not None:
        raise first_error


def save_checkpoint(dirname, state, step=0, max_to_keep=None, wait=True):
    """Write `state` (a flat dict name -> array; jax Arrays may be
    device-resident) as checkpoint `step` under `dirname`. Re-saving an
    existing step REPLACES it (a trainer overwriting its own step means
    newer state). With wait=False the write runs in the background —
    call finalize()/a later save to join it.

    Unless ``PADDLE_TPU_CHECKPOINT_DIGEST=0``, per-tensor sha256
    digests of the handed-off state are computed concurrently with the
    orbax write and recorded in a per-step integrity manifest (see
    :func:`manifest_path`). Returns the digest dict (feed it to
    :func:`mark_save_complete`) for blocking saves; for ``wait=False``
    the manifest finisher runs behind the async write and the return
    is None — ``finalize()`` joins it."""
    import orbax.checkpoint as ocp

    from ..fluid.resilience import fault_check
    from .. import observability as obs

    # fault-injection hook (site "save"): BEFORE the manager touches
    # disk, modeling a process killed mid-save — the previous complete
    # checkpoint must stay the resume point
    fault_check("save")
    t0 = time.monotonic()
    # per-tensor digests of exactly what is being handed to orbax,
    # computed CONCURRENTLY with orbax's background write (both only
    # read the buffers, and hashlib releases the GIL on large updates)
    # so the digest cost hides inside the write's own wall-clock. The
    # thread starts only AFTER the synchronous enqueue (which copies
    # the arrays) so it never competes with the trainer-facing part of
    # the call. Callers must not mutate the passed arrays in place
    # before finalize()/join — jax Arrays (the paved trainer path) are
    # immutable, so this only constrains raw-numpy callers, the same
    # way orbax's own async snapshot does. The manifest is written
    # only after the save call succeeds, so a manifest never outlives
    # a step that was never enqueued.
    digests = None
    digest_box = None
    if _digests_enabled():
        from ..integrity.digest import digest_state

        digest_box = {}

        def _digest():
            td0 = time.monotonic()
            try:
                digest_box["digests"] = digest_state(state)
            except Exception as e:  # noqa: BLE001 — re-raised at join
                digest_box["error"] = e
            obs.observe("integrity.checkpoint_digest_seconds",
                        time.monotonic() - td0)

        digest_thread = threading.Thread(
            target=_digest, daemon=True, name="checkpoint-digest")
    with _enqueue_lock:
        mgr = _manager(dirname, max_to_keep)
        saved = mgr.save(int(step), args=ocp.args.StandardSave(dict(state)))
        if not saved:
            # orbax skips steps that already exist — delete and rewrite
            mgr.delete(int(step))
            saved = mgr.save(
                int(step), args=ocp.args.StandardSave(dict(state)))
            if not saved:
                raise RuntimeError(
                    "orbax refused to save step %s under %r"
                    % (step, dirname))
    if digest_box is not None:
        from ..integrity import envelope

        digest_thread.start()

        def _finish_manifest(raising):
            digest_thread.join()
            if "error" in digest_box:
                if raising:
                    raise digest_box["error"]
                obs.inc("integrity.checkpoint_digest_errors")
                warnings.warn(
                    "checkpoint digest for step %s under %r failed "
                    "(%s); no integrity manifest was written"
                    % (step, dirname, digest_box["error"]))
                return None
            envelope.write_manifest(
                manifest_path(dirname, step),
                envelope.make_manifest(digest_box["digests"],
                                       kind="checkpoint",
                                       step=int(step), time=time.time()))
            obs.inc("integrity.checkpoint_manifests_written")
            return digest_box["digests"]

        if wait:
            digests = _finish_manifest(raising=True)
        else:
            # async save: the manifest finisher rides behind the orbax
            # background write; finalize()/the next blocking call joins
            # it. The trainer-facing call returns at enqueue cost — the
            # digest never extends the hot path.
            fin = threading.Thread(
                target=_finish_manifest, args=(False,), daemon=True,
                name="checkpoint-manifest")
            fin.start()
            _pending_manifests.setdefault(
                os.path.abspath(dirname), []).append(fin)
    if wait:
        mgr.wait_until_finished()
    # with wait=False this measures the enqueue, not the disk write —
    # the histogram still distinguishes sync from async save costs
    obs.observe("checkpoint.save_seconds", time.monotonic() - t0)
    return digests


def latest_step(dirname):
    """The newest complete checkpoint step under `dirname`, or None.
    A missing, empty, or unreadable directory is "no checkpoint yet"
    (None) — the resume path must survive a fresh run directory."""
    if not os.path.isdir(dirname):
        return None
    try:
        mgr = _manager(dirname)
        mgr.wait_until_finished()
        return mgr.latest_step()
    except Exception:  # noqa: BLE001 — unreadable layout == no checkpoint
        return None


def load_checkpoint(dirname, step=None):
    """Restore the state dict saved at `step` (newest VERIFIED step when
    None — steps failing :func:`verify_checkpoint` are skipped with a
    warning). Raises IOError naming `dirname` when the directory is
    missing or holds no (readable) checkpoint — never a raw orbax
    traceback."""
    import orbax.checkpoint as ocp

    from .. import observability as obs

    if not os.path.isdir(dirname):
        raise IOError(
            "no checkpoint directory %r (nothing was ever saved there, "
            "or the path is wrong)" % dirname)
    t0 = time.monotonic()
    try:
        mgr = _manager(dirname)
        mgr.wait_until_finished()
        if step is None:
            for cand in all_steps(dirname):
                if verify_checkpoint(dirname, cand):
                    step = cand
                    break
                warnings.warn(
                    "skipping corrupt/incomplete checkpoint step %d "
                    "under %r" % (cand, dirname))
        if step is None:
            raise IOError(
                "checkpoint directory %r contains no complete "
                "checkpoint" % dirname)
        restored = mgr.restore(int(step), args=ocp.args.StandardRestore())
    except IOError:
        raise
    except Exception as e:  # noqa: BLE001 — orbax internals stay internal
        raise IOError(
            "failed to restore checkpoint step %s from %r (%s: %s)"
            % (step, dirname, type(e).__name__, e)) from e
    state = {k: np.asarray(v) for k, v in restored.items()}
    # digest verification of what actually came off the disk; an
    # IntegrityError is an IOError, so every existing fallback path
    # (restore_latest & co) skips past the lying step
    from ..integrity import envelope

    manifest = envelope.read_manifest(manifest_path(dirname, step))
    if manifest is not None:
        td0 = time.monotonic()
        _verify_digests(state, manifest, dirname, step, raising=True)
        obs.observe("integrity.checkpoint_verify_seconds",
                    time.monotonic() - td0)
    obs.observe("checkpoint.restore_seconds", time.monotonic() - t0)
    return state


def all_steps(dirname):
    """Step numbers present under `dirname` (complete or not), newest
    first. Reads the directory layout directly — unlike the orbax
    manager it cannot be wedged by one corrupt step dir."""
    if not os.path.isdir(dirname):
        return []
    steps = []
    for entry in os.listdir(dirname):
        if entry.isdigit() and os.path.isdir(os.path.join(dirname, entry)):
            steps.append(int(entry))
    return sorted(steps, reverse=True)


def verify_checkpoint(dirname, step, state=None):
    """Integrity verification for checkpoint `step`.

    Always runs the structural probe (step directory exists, holds at
    least one regular file, no leftover orbax tmp entries from an
    interrupted atomic-rename save, no zero-byte payload file), then
    upgrades to digest verification where the evidence exists: a
    present-but-unreadable digest manifest fails the step (a manifest
    that cannot be trusted must not silently disable verification),
    and when the restored ``state`` dict is passed, every tensor is
    verified against its recorded sha256. Used by every restore path
    before a step is trusted; without ``state`` a True result still
    does not guarantee a readable payload — restore failures (and
    post-restore digest mismatches, see :func:`load_checkpoint`) fall
    back to older steps."""
    from .. import observability as obs
    from ..integrity import envelope
    from ..integrity.digest import IntegrityError

    step_dir = os.path.join(dirname, str(int(step)))
    if not os.path.isdir(step_dir):
        return False
    saw_file = False
    for root, dirs, files in os.walk(step_dir):
        if any("tmp" in d.lower() for d in dirs):
            return False
        for f in files:
            if "tmp" in f.lower():
                return False
            saw_file = True
            try:
                size = os.path.getsize(os.path.join(root, f))
            except OSError:
                return False
            # zero-byte markers are legitimate (orbax commit sentinels);
            # zero-byte DATA is truncation
            if size == 0 and not (f.startswith("commit")
                                  or f.startswith(".")):
                return False
    if not saw_file:
        return False
    mpath = manifest_path(dirname, step)
    try:
        manifest = envelope.read_manifest(mpath)
    except IntegrityError as e:
        obs.inc("integrity.checkpoint_manifest_corrupt")
        obs.event("integrity_violation", source="checkpoint",
                  path=mpath, step=int(step), check="manifest",
                  error=str(e))
        warnings.warn(
            "checkpoint step %d under %r has a corrupt digest manifest "
            "(%s)" % (int(step), dirname, e))
        return False
    if manifest is not None and state is not None:
        bad = _verify_digests(state, manifest, dirname, step, raising=False)
        if bad:
            return False
    return True


def _verify_digests(state, manifest, dirname, step, raising=True):
    """Compare a restored state dict against its manifest; attribute
    the first mismatch to its tensor and file. Returns the mismatch
    list (``raising=False``) or raises IntegrityError."""
    from .. import observability as obs
    from ..integrity.digest import IntegrityError, state_mismatches

    mism = state_mismatches(state, manifest.get("digests", {}))
    if not mism:
        obs.inc("integrity.checkpoint_verified")
        return []
    name, want, got = mism[0]
    mpath = manifest_path(dirname, step)
    obs.inc("integrity.checkpoint_digest_mismatch", len(mism))
    obs.event("integrity_violation", source="checkpoint",
              path=os.path.join(dirname, str(int(step))),
              step=int(step), check="digest", tensor=name,
              mismatches=len(mism))
    if not raising:
        return mism
    raise IntegrityError(
        "checkpoint step %d under %r failed digest verification: "
        "tensor %r want %s got %s (%d tensor(s) total; manifest %s)"
        % (int(step), dirname, name, want, got, len(mism), mpath),
        path=os.path.join(dirname, str(int(step))), tensor=name,
        want=want, got=got)


def restore_latest(dirname):
    """Resume helper: ``(step, state)`` for the newest complete
    checkpoint under `dirname`, or None when there is nothing to resume
    from. The one call sites need at process start. A corrupt or
    partially-written newest step (failed integrity probe OR failed
    restore) is skipped with a warning and the previous step is used —
    a crash mid-save must never cost more than one checkpoint
    interval."""
    for step in all_steps(dirname):
        if not verify_checkpoint(dirname, step):
            warnings.warn(
                "skipping corrupt/incomplete checkpoint step %d under "
                "%r" % (step, dirname))
            continue
        try:
            return int(step), load_checkpoint(dirname, step=step)
        except IOError as e:
            warnings.warn(
                "checkpoint step %d under %r failed to restore (%s); "
                "falling back to the previous step" % (step, dirname, e))
    return None


# ---------------------------------------------------------------------------
# fleet-consistent (consensus) checkpoints
# ---------------------------------------------------------------------------
#
# A checkpoint only counts for elastic resume once EVERY worker finished
# (and flushed) its save of that step: a step some worker never wrote
# would desynchronise the fleet on restore. Each worker writes payload
# under worker_dir(dirname, i) and then an atomic per-worker done-marker;
# the newest step with a full marker set is the fleet-consistent resume
# point. Markers record the world size at save time, so survivors of a
# shrink still recognise pre-failure checkpoints as complete.

CONSENSUS_DIR = "fleet-consensus"


def worker_dir(dirname, worker_index):
    """Per-worker checkpoint payload root under a shared `dirname` —
    the one place the elastic on-disk layout is defined."""
    return os.path.join(dirname, "worker%05d" % int(worker_index))


def mark_save_complete(dirname, step, worker_index, world_size,
                       members=None, digests=None):
    """Atomically record that `worker_index` finished saving `step`.
    `members` is the fleet membership at save time (worker indices;
    default ``range(world_size)``) — after an elastic shrink the
    survivors are NOT a contiguous range, and consensus requires a
    marker from exactly the members that were supposed to save.
    `digests` (what :func:`save_checkpoint` returned) rides in the
    marker so the consensus restore verifies this worker's shard
    against the digests recorded at the moment consensus formed. Call
    only AFTER the save was flushed (``save_checkpoint(..., wait=True)``
    or ``finalize()``)."""
    d = os.path.join(dirname, CONSENSUS_DIR, "%012d" % int(step))
    os.makedirs(d, exist_ok=True)
    marker = os.path.join(d, "worker%05d.done" % int(worker_index))
    tmp = marker + ".tmp"
    if members is None:
        members = range(int(world_size))
    doc = {"worker": int(worker_index), "world": int(world_size),
           "members": sorted(int(m) for m in members),
           "step": int(step), "time": time.time()}
    if digests:
        doc["digests"] = dict(digests)
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, marker)
    return marker


def _consensus_required(markers, world_size):
    """The worker set whose markers make a step fleet-consistent."""
    if world_size is not None:
        return set(range(int(world_size)))
    for m in markers:
        if m.get("members"):
            return set(m["members"])
    world = max(m.get("world", 0) for m in markers)
    return set(range(int(world))) if world else None


def _consensus_markers(dirname, step):
    d = os.path.join(dirname, CONSENSUS_DIR, "%012d" % int(step))
    if not os.path.isdir(d):
        return []
    out = []
    for entry in sorted(os.listdir(d)):
        if not entry.endswith(".done"):
            continue
        try:
            with open(os.path.join(d, entry)) as f:
                out.append(json.load(f))
        except (OSError, ValueError):
            continue  # torn marker == not written
    return out


def latest_consensus_step(dirname, world_size=None):
    """Newest step for which all workers wrote done-markers, or None.
    With `world_size` None the required count comes from the markers
    themselves (the world recorded at save time) — so a shrunken fleet
    can still find checkpoints saved by the larger pre-failure fleet."""
    root = os.path.join(dirname, CONSENSUS_DIR)
    if not os.path.isdir(root):
        return None
    steps = sorted((int(e) for e in os.listdir(root) if e.isdigit()),
                   reverse=True)
    for step in steps:
        markers = _consensus_markers(dirname, step)
        if not markers:
            continue
        need = _consensus_required(markers, world_size)
        have = {m.get("worker") for m in markers}
        if need and have >= need:
            return step
    return None


def restore_latest_consensus(dirname, worker_index, world_size=None):
    """Elastic resume: ``(step, state)`` for this worker's payload at
    the newest fleet-consistent step, or None. Consensus steps whose
    payload fails the integrity probe or the restore are skipped with a
    warning (same fallback contract as :func:`restore_latest`)."""
    root = os.path.join(dirname, CONSENSUS_DIR)
    if not os.path.isdir(root):
        return None
    wdir = worker_dir(dirname, worker_index)
    steps = sorted((int(e) for e in os.listdir(root) if e.isdigit()),
                   reverse=True)
    for step in steps:
        markers = _consensus_markers(dirname, step)
        if not markers:
            continue
        need = _consensus_required(markers, world_size)
        have = {m.get("worker") for m in markers}
        if not need or not have >= need:
            continue
        if not verify_checkpoint(wdir, step):
            warnings.warn(
                "consensus step %d: worker %d payload under %r failed "
                "the integrity probe; trying an older consensus step"
                % (step, worker_index, wdir))
            continue
        try:
            state = load_checkpoint(wdir, step=step)
        except IOError as e:
            warnings.warn(
                "consensus step %d: worker %d restore failed (%s); "
                "trying an older consensus step"
                % (step, worker_index, e))
            continue
        # the done-marker may carry the digests recorded when consensus
        # formed — a shard that drifted since (bit rot, tampering)
        # fails here even if its own manifest was rewritten with it
        mine = next((m for m in markers
                     if m.get("worker") == int(worker_index)), None)
        if mine and mine.get("digests"):
            from .. import observability as obs
            from ..integrity.digest import state_mismatches

            mism = state_mismatches(state, mine["digests"])
            if mism:
                name = mism[0][0]
                obs.inc("integrity.checkpoint_digest_mismatch",
                        len(mism))
                obs.event("integrity_violation", source="checkpoint",
                          path=wdir, step=int(step), check="done-marker",
                          tensor=name, mismatches=len(mism))
                warnings.warn(
                    "consensus step %d: worker %d shard disagrees with "
                    "its done-marker digests (first mismatch: tensor %r "
                    "under %r); trying an older consensus step"
                    % (step, worker_index, name, wdir))
                continue
        return int(step), state
    return None
