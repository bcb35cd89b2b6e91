"""Checkpoint/resume for train-state pytrees.

The reference delegates checkpointing entirely to training containers
(SURVEY.md §5.4: elastic demo mounts /checkpoint hostPath); here it is a
framework citizen because TPU elasticity *is* restart-from-checkpoint — a
collective job cannot shrink below its compiled mesh, so preemption recovery
= whole-slice restart from the newest step (see elastic/sync.py epoch).

Format (v2): one directory per step, `state.npz` (flat path -> array) +
`manifest.json` (treedef + dtypes + membership epoch + per-leaf CRC32
checksums + a terminal COMMIT marker). No file outgrows
:data:`PART_BYTES` (or the process's RLIMIT_FSIZE, if lower): a bigger
state is the same npz byte stream cut into `state.npz.000`, `.001`, ...
with the count in the manifest — the v5e machine refused a 1.95 GB
`state.npz` with EFBIG. Atomic via tmp-dir rename so a
preempted writer never leaves a half checkpoint on a POSIX filesystem —
and crash-safe beyond that: on storage where rename is not atomic (NFS,
FUSE-mounted object stores) a torn write leaves either an unparseable or
an uncommitted manifest, which readers skip. :func:`latest_step` answers
the newest *committed* step; :func:`restore_latest` walks back past
checksum-failing steps, quarantining them with a ``.corrupt`` rename, so
one bad write can never wedge resume forever. :func:`gc_checkpoints`
bounds disk to the newest ``keep_last_n`` valid steps plus a small cap of
quarantined corpses.

Recovery events (saves, corrupt skips, restores, duplicate-save dedup)
flow into the process trace and an optional observer callback —
:func:`set_checkpoint_observer` is how the chaos harness and the per-job
metrics layer (obs.JobMetrics) count them.
"""

from __future__ import annotations

import bisect
import io
import json
import logging
import os
import resource
import shutil
import tempfile
import threading
import time
import zlib
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from .trace import tracer

log = logging.getLogger("tpujob.checkpoint")

#: manifest format carrying checksums + the commit marker
FORMAT_VERSION = 2
#: terminal manifest key: written last, so a torn manifest either fails to
#: parse or visibly lacks the marker — both read as "uncommitted"
COMMIT_MARKER = "COMMIT"
#: largest file the npz writer produces; a bigger state is cut into parts
PART_BYTES = 32 << 20


class CorruptCheckpointError(ValueError):
    """A step directory exists but cannot be trusted: manifest missing or
    torn, checksum mismatch, or shard coverage holes. Subclasses ValueError
    so legacy callers catching ValueError keep working."""


# -- recovery-event observer -------------------------------------------------

_observer_lock = threading.Lock()
_observer: Optional[Callable[[str, dict], None]] = None


def set_checkpoint_observer(fn: Optional[Callable[[str, dict], None]]) -> None:
    """Install a process-wide recovery-event observer ``fn(event, detail)``.
    Events: ``save``, ``restore``, ``corrupt_skipped``,
    ``duplicate_save_skipped``, ``gc``. Pass None to uninstall."""
    global _observer
    with _observer_lock:
        _observer = fn


def _notify(event: str, **detail: Any) -> None:
    tracer().event("checkpoint_%s" % event, **detail)
    with _observer_lock:
        fn = _observer
    if fn is not None:
        try:
            fn(event, detail)
        except Exception:  # observer must never break a save/restore
            log.exception("checkpoint observer failed on %r", event)


def _leaf_crc(arr: Any) -> int:
    """CRC32 over the leaf's raw bytes; dtype-agnostic (bf16 void views
    hash identically to their unsigned round-trip form)."""
    a = np.ascontiguousarray(np.asarray(arr))
    return zlib.crc32(a.tobytes())


def _owned_host(arr: Any) -> np.ndarray:
    """Host snapshot that OWNS its memory.

    ``np.asarray``/``device_get`` of a CPU-backend jax array returns a
    zero-copy VIEW of the device buffer. If the training loop has already
    dispatched the next step and that step DONATES the state, the runtime
    overwrites the viewed memory while the checkpoint writer is still
    serializing it — the manifest's CRC then hashes different bytes than
    the npz receives (self-corrupting checkpoints, found by the recovery
    bit-identity tests once cache-reloaded executables started honoring
    donation in place). An owned copy pins the snapshot; accelerator
    backends already return owned host arrays (OWNDATA), so the copy
    costs nothing there.
    """
    a = np.asarray(arr)
    if not a.flags["OWNDATA"]:
        a = np.array(a)
    return a


def _flatten(tree: Any, prefix: str = "") -> Dict[str, Any]:
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], "%s%s/" % (prefix, k)))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, "%s%d/" % (prefix, i)))
    else:
        out[prefix[:-1]] = tree
    return out


def _structure(tree: Any) -> Any:
    if isinstance(tree, dict):
        return {k: _structure(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_structure(v) for v in tree]
    return None  # leaf marker


def _unflatten(structure: Any, flat: Dict[str, Any], prefix: str = "") -> Any:
    if isinstance(structure, dict):
        return {
            k: _unflatten(v, flat, "%s%s/" % (prefix, k))
            for k, v in structure.items()
        }
    if isinstance(structure, list):
        return [
            _unflatten(v, flat, "%s%d/" % (prefix, i))
            for i, v in enumerate(structure)
        ]
    return flat[prefix[:-1]]


def _part_bytes() -> int:
    """:data:`PART_BYTES`, or what the process may write to one file if
    that is less (past RLIMIT_FSIZE a write fails with EFBIG)."""
    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    return PART_BYTES if soft == resource.RLIM_INFINITY \
        else max(1, min(PART_BYTES, soft))


def _part_names(parts: int) -> List[str]:
    if parts <= 1:
        return ["state.npz"]
    return ["state.npz.%03d" % i for i in range(parts)]


class _PartWriter(io.RawIOBase):
    """Write-only byte stream over files of at most ``part_bytes`` each.
    It cannot seek, so ``zipfile`` writes a streamed archive (member
    sizes after the data) and never goes back into an earlier part."""

    def __init__(self, dirpath: str, part_bytes: int):
        super().__init__()
        self._dir = dirpath
        self._part_bytes = part_bytes
        self._fh: Optional[io.BufferedWriter] = None
        self._room = 0
        self.parts = 0

    def writable(self) -> bool:
        return True

    def write(self, data) -> int:
        view = memoryview(data).cast("B")
        done = 0
        while done < len(view):
            if self._room == 0:
                self._next_part()
            chunk = view[done:done + self._room]
            self._fh.write(chunk)
            self._room -= len(chunk)
            done += len(chunk)
        return done

    def _next_part(self) -> None:
        if self._fh is not None:
            self._fh.close()
        self._fh = open(os.path.join(
            self._dir, "state.npz.%03d" % self.parts), "wb")
        self._room = self._part_bytes
        self.parts += 1

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        super().close()


class _PartReader(io.RawIOBase):
    """The parts read back as the one seekable stream they were cut
    from — what ``np.load`` needs to find the zip directory."""

    def __init__(self, paths: List[str]):
        super().__init__()
        self._paths = paths
        self._starts = [0]
        for path in paths:  # a lost part is FileNotFoundError here
            self._starts.append(self._starts[-1] + os.path.getsize(path))
        self._pos = 0
        self._open_part = -1
        self._fh: Optional[io.BufferedReader] = None

    def readable(self) -> bool:
        return True

    def seekable(self) -> bool:
        return True

    def tell(self) -> int:
        return self._pos

    def seek(self, offset: int, whence: int = os.SEEK_SET) -> int:
        base = {os.SEEK_SET: 0, os.SEEK_CUR: self._pos,
                os.SEEK_END: self._starts[-1]}[whence]
        self._pos = max(0, base + offset)
        return self._pos

    def readinto(self, buf) -> int:
        if self._pos >= self._starts[-1]:
            return 0
        part = bisect.bisect_right(self._starts, self._pos) - 1
        if part != self._open_part:
            self._close_part()
            self._fh = open(self._paths[part], "rb")
            self._open_part = part
        self._fh.seek(self._pos - self._starts[part])
        want = min(len(buf), self._starts[part + 1] - self._pos)
        got = self._fh.readinto(memoryview(buf)[:want])
        self._pos += got
        return got

    def _close_part(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
            self._open_part = -1

    def close(self) -> None:
        self._close_part()
        super().close()


def save_checkpoint(ckpt_dir: str, step: int, state: Any,
                    meta: Optional[dict] = None, keep: int = 3,
                    part_bytes: Optional[int] = None) -> str:
    """Write state atomically; prune to the newest `keep` checkpoints.

    Crash-safe (format v2): the manifest carries per-leaf CRC32 checksums
    and ends with the COMMIT marker, written after every array byte — a
    reader never trusts a step whose manifest is missing, torn, or
    uncommitted. ``part_bytes`` overrides the file-size bound (tests).
    """
    flat = _flatten(state)
    # owned snapshots: a zero-copy view of a donated device buffer would
    # let in-flight training overwrite the bytes mid-serialization
    arrays = {k: _owned_host(v) for k, v in flat.items()}

    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, "step_%012d" % step)
    tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_")
    try:
        with _PartWriter(tmp, part_bytes or _part_bytes()) as out:
            np.savez(out, **arrays)
        if out.parts == 1:  # the usual case keeps the plain name
            os.rename(os.path.join(tmp, "state.npz.000"),
                      os.path.join(tmp, "state.npz"))
        manifest = {
            "step": step,
            "structure": _structure(state),
            "meta": meta or {},
            "format_version": FORMAT_VERSION,
            "state_parts": out.parts,
            "checksums": {k: _leaf_crc(a) for k, a in arrays.items()},
            # terminal key: json preserves insertion order, so a torn
            # manifest write truncates BEFORE the marker
            "commit": COMMIT_MARKER,
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise

    _notify("save", dir=ckpt_dir, step=step)
    gc_checkpoints(ckpt_dir, keep_last_n=keep)
    return final


class AsyncCheckpointer:
    """Background-thread checkpoint writer: the train loop pays only the
    device→host snapshot (arrays are immutable, but an eager snapshot
    releases the HBM references instead of pinning an extra copy of the
    whole state until the disk write finishes); serialization + atomic
    rename + pruning happen off-thread, so checkpoint_every stops costing
    a disk write's worth of step time. Stall per save is not measured
    in any cell of the benchmark (ROADMAP Queue 2 B.7).

    Semantics (matching what restart-from-checkpoint needs):

    * one save in flight: a new :meth:`save` first waits for the previous
      write — checkpoints land in order, and a slow disk backpressures
      the snapshot cadence instead of queueing unbounded host copies;
    * :meth:`wait` drains the pending write — call before process
      exit/elastic restart so the interrupt checkpoint is durable;
    * a failed background write re-raises on the NEXT save/wait: a
      checkpoint that silently failed to persist must not look saved.

    Single-host (npz) format only: the sharded multi-host writer
    serializes on a cross-host barrier anyway, so backgrounding it buys
    nothing and complicates the process-0 index write.
    """

    def __init__(self):
        self._thread = None
        self._error = None
        self._lock = threading.Lock()
        # (dir, step) of the last accepted save: an elastic restart that
        # re-enters the same step boundary calls save twice; the second
        # is a deterministic no-op (it would race the first on the
        # step dir and rewrite identical bytes for nothing)
        self._last_accepted: Optional[Tuple[str, int]] = None

    def save(self, ckpt_dir: str, step: int, state: Any,
             meta: Optional[dict] = None, keep: int = 3) -> None:
        import jax

        # drain FIRST: a previous write's failure must re-raise here (the
        # class contract) and clears the dedup marker — checking the
        # marker before wait() would silently swallow the retry of a
        # failed same-step save
        self.wait()  # one in flight; raises a previous write's error
        if self._last_accepted == (ckpt_dir, step):
            _notify("duplicate_save_skipped", dir=ckpt_dir, step=step)
            return
        # snapshot before returning — OWNED host copies, not zero-copy
        # views (the loop keeps training while the writer serializes;
        # donated device buffers mutate under a view — see _owned_host)
        host_state = jax.tree_util.tree_map(_owned_host, state)

        def write():
            try:
                save_checkpoint(ckpt_dir, step, host_state,
                                meta=meta, keep=keep)
            except BaseException as e:  # surfaced on next save/wait
                with self._lock:
                    self._error = e

        self._thread = threading.Thread(
            target=write, name="ckpt-write-%d" % step, daemon=True)
        self._thread.start()
        # marker set LAST: a synchronous failure above (device_get, thread
        # start) left nothing on disk and no stored error for wait() to
        # clear — the caller's retry of this step must be a real save
        self._last_accepted = (ckpt_dir, step)

    def sync_dedup(self, ckpt_dir: str, restored_step: int) -> None:
        """Called after a cycle restores: the duplicate-save marker stays
        valid only if it matches the step the restore actually landed on.
        A fallback BELOW the marked step means the marked write no longer
        exists on disk (quarantined corrupt) — retraining will legitimately
        reach that boundary again and the save must be real, not a dedup
        no-op."""
        if (self._last_accepted is not None
                and self._last_accepted != (ckpt_dir, restored_step)):
            self._last_accepted = None

    def wait(self, timeout: Optional[float] = None) -> None:
        """Drain the pending write; re-raise a failed write's exception
        (it must not die silently — a checkpoint that failed to persist
        must not look saved). With ``timeout``, raise ``TimeoutError``
        if the write is still in flight when it expires; the write
        thread keeps running and a later wait() can still drain it."""
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError(
                    "checkpoint write %r still in flight after %.1fs"
                    % (self._thread.name, timeout))
            self._thread = None
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            # the failed step never landed: a retry of the same
            # (dir, step) must be a real save, not a dedup no-op
            self._last_accepted = None
            raise err

    def close(self, timeout: float = 30.0) -> None:
        """Bounded join-on-close (thread-hygiene contract, opslint
        OPS202): drains the in-flight write for up to ``timeout``
        seconds and surfaces its exception, instead of the process
        exiting with a silently-unfinished (or silently-failed) write."""
        self.wait(timeout=timeout)


def _listed_steps(ckpt_dir: str,
                  _names: Optional[List[str]] = None) -> List[int]:
    """Step numbers with a manifest.json file present — no validity check.
    Quarantined ``.corrupt`` dirs and non-numeric names are skipped (never
    crash the listing on debris). ``_names`` lets gc_checkpoints share one
    directory listing across its phases (NFS round trips add up on the
    per-save path)."""
    if _names is None:
        if not os.path.isdir(ckpt_dir):
            return []
        _names = os.listdir(ckpt_dir)
    out = []
    for name in _names:
        if not name.startswith("step_"):
            continue
        try:
            step = int(name[len("step_"):])
        except ValueError:
            continue  # step_N.corrupt quarantine or foreign debris
        if os.path.exists(os.path.join(ckpt_dir, name, "manifest.json")):
            out.append(step)
    return sorted(out)


def _manifest_committed(manifest: dict) -> bool:
    """v2 manifests must carry the terminal COMMIT marker; v1 manifests
    (pre-checksum) are trusted if structurally complete — they were only
    ever published by an atomic rename."""
    try:
        if int(manifest.get("format_version") or 1) >= FORMAT_VERSION:
            return manifest.get("commit") == COMMIT_MARKER
    except (TypeError, ValueError):
        return False
    return "step" in manifest and "structure" in manifest


def _load_manifest(ckpt_dir: str, step: int) -> dict:
    """Read + validate one step's manifest; CorruptCheckpointError on a
    missing, torn, or uncommitted manifest (the torn-write signatures)."""
    path = os.path.join(ckpt_dir, "step_%012d" % step, "manifest.json")
    try:
        with open(path) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        raise CorruptCheckpointError(
            "checkpoint step %d under %s has no manifest.json "
            "(torn write?)" % (step, ckpt_dir))
    except (json.JSONDecodeError, UnicodeDecodeError, OSError) as e:
        raise CorruptCheckpointError(
            "checkpoint step %d under %s has an unreadable manifest "
            "(torn write?): %s" % (step, ckpt_dir, e))
    if not isinstance(manifest, dict) or not _manifest_committed(manifest):
        raise CorruptCheckpointError(
            "checkpoint step %d under %s is uncommitted (manifest lacks "
            "the %s marker)" % (step, ckpt_dir, COMMIT_MARKER))
    return manifest


# Committed-verdict cache: without it, every save (save -> gc ->
# all_steps) and every latest_step() would re-parse `keep` unchanged
# manifests, which for a large model embed the full parameter-tree
# structure + per-leaf checksums (multi-MB JSON). Keyed by the manifest's
# stat identity (mtime_ns, size), so the verdict costs one stat per
# listing and any replacement or tear of the file — which changes the
# identity — forces a real re-parse; only POSITIVE verdicts are cached.
_commit_cache_lock = threading.Lock()
_committed_manifests: Dict[str, Tuple[int, int]] = {}


def _forget_committed(paths: Iterable[str]) -> None:
    with _commit_cache_lock:
        for path in paths:
            _committed_manifests.pop(path, None)


def all_steps(ckpt_dir: str, _names: Optional[List[str]] = None):
    """Steps safe to restore from: manifest present, parseable, committed.
    An uncommitted/torn step is skipped with a warning — it must never
    become ``latest_step`` and wedge resume (it stays on disk for
    quarantine at restore time)."""
    out = []
    for step in _listed_steps(ckpt_dir, _names=_names):
        path = os.path.join(ckpt_dir, "step_%012d" % step)
        try:
            st = os.stat(os.path.join(path, "manifest.json"))
        except OSError:
            continue  # vanished between the listing and now
        ident = (st.st_mtime_ns, st.st_size)
        with _commit_cache_lock:
            cached = _committed_manifests.get(path) == ident
        if not cached:
            try:
                _load_manifest(ckpt_dir, step)
            except CorruptCheckpointError as e:
                log.warning("skipping unusable checkpoint step %d: %s",
                            step, e)
                continue
            with _commit_cache_lock:
                _committed_manifests[path] = ident
        out.append(step)
    return out


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def quarantine_step(ckpt_dir: str, step: int) -> Optional[str]:
    """Rename a corrupt step directory to ``step_N.corrupt`` so readers
    stop considering it while the bytes stay inspectable. Returns the
    quarantine path (None if the dir vanished underneath us)."""
    src = os.path.join(ckpt_dir, "step_%012d" % step)
    dst = src + ".corrupt"
    n = 0
    while os.path.exists(dst):  # same step corrupted twice across restarts
        n += 1
        dst = "%s.corrupt.%d" % (src, n)
    try:
        os.rename(src, dst)
    except OSError:
        return None
    _forget_committed([src])
    _notify("corrupt_skipped", dir=ckpt_dir, step=step, quarantine=dst)
    log.warning("quarantined corrupt checkpoint step %d -> %s", step, dst)
    return dst


# GC serialization: the async writer's background prune and a foreground
# save/GC may run concurrently in one process; rmtree of the same dir from
# two threads turns ENOENT races into spurious errors, so all pruning in
# this process funnels through one lock.
_gc_lock = threading.Lock()


def gc_checkpoints(ckpt_dir: str, keep_last_n: int = 3,
                   keep_corrupt: int = 2,
                   stale_grace_seconds: float = 3600.0) -> List[str]:
    """Retention GC: bound disk to the newest ``keep_last_n`` valid steps
    and at most ``keep_corrupt`` quarantined ``.corrupt`` corpses (oldest
    removed first). Also sweeps crash debris — abandoned ``.tmp_*`` /
    ``.partial_step_*`` staging (a SIGKILLed writer leaves a full-size
    state copy behind) and manifest-less step dirs (torn rename) — once
    older than ``stale_grace_seconds``, so a possibly-live writer's
    staging (another process, an NFS rename still propagating) is never
    yanked from under it. Returns the paths removed."""
    removed: List[str] = []
    if not os.path.isdir(ckpt_dir):
        return removed
    with _gc_lock:
        # ONE directory listing shared by every phase below — on the
        # network storage this module targets, per-save listdir round
        # trips are the cost that adds up
        try:
            names = sorted(os.listdir(ckpt_dir))
        except OSError:
            return removed
        listed = _listed_steps(ckpt_dir, _names=names)
        steps = all_steps(ckpt_dir, _names=names)
        if keep_last_n > 0:
            for old in steps[:-keep_last_n]:
                path = os.path.join(ckpt_dir, "step_%012d" % old)
                shutil.rmtree(path, ignore_errors=True)
                removed.append(path)
        # torn/uncommitted debris OLDER than the newest valid step can
        # never be a resume target (resume walks newest-first and the
        # valid step wins) and steps only ever publish in increasing
        # order, so nothing is concurrently mid-publish back there:
        # remove it instead of letting crashes accumulate directories
        # that cost a manifest parse + warning on every listing
        if steps:
            valid = set(steps)
            for dead in [s for s in listed
                         if s not in valid and s < steps[-1]]:
                path = os.path.join(ckpt_dir, "step_%012d" % dead)
                shutil.rmtree(path, ignore_errors=True)
                removed.append(path)
        corpses = [name for name in names
                   if name.startswith("step_") and ".corrupt" in name]
        for name in corpses[:max(0, len(corpses) - keep_corrupt)]:
            path = os.path.join(ckpt_dir, name)
            shutil.rmtree(path, ignore_errors=True)
            removed.append(path)
        now = time.time()
        for name in names:
            if name.startswith(".tmp_") or name.startswith(".partial_step_"):
                stale = True
            elif (name.startswith("step_") and ".corrupt" not in name
                    and not os.path.exists(
                        os.path.join(ckpt_dir, name, "manifest.json"))):
                try:
                    int(name[len("step_"):])
                except ValueError:
                    continue  # foreign debris: not ours to delete
                stale = True  # torn rename left a manifest-less step
            else:
                continue
            path = os.path.join(ckpt_dir, name)
            try:
                age = now - os.stat(path).st_mtime
            except OSError:
                continue  # vanished (its writer finished): not stale
            if age >= stale_grace_seconds:
                shutil.rmtree(path, ignore_errors=True)
                removed.append(path)
    if removed:
        _forget_committed(removed)  # keep the verdict cache bounded
        _notify("gc", dir=ckpt_dir, removed=len(removed))
    return removed


def save_checkpoint_sharded(ckpt_dir: str, step: int, state: Any,
                            meta: Optional[dict] = None, keep: int = 3) -> str:
    """Multi-host-safe save: each process writes only the shards its own
    devices hold — no host-side full gather (``jax.device_get`` of a sharded
    array is impossible on multi-host for models bigger than one host).

    Layout: ``step_N/<path>.sNN.npy`` per shard + ``shards.json`` index
    recording each shard's global-index slices, written by process 0 after a
    cross-host barrier. Completion is signalled by ``manifest.json`` (same
    atomicity contract as the npz format: readers key off the manifest).
    """
    import jax

    flat = _flatten(state)
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, "step_%012d" % step)
    # hidden from all_steps (no "step_" prefix); wiped before use so a
    # crashed prior attempt cannot leak stale shards into this one
    staging = os.path.join(ckpt_dir, ".partial_step_%012d" % step)
    if jax.process_count() > 1:  # pragma: no cover - needs real multihost
        from jax.experimental import multihost_utils

        if jax.process_index() == 0 and os.path.exists(staging):
            shutil.rmtree(staging)
        multihost_utils.sync_global_devices("ckpt_staging_clean_%d" % step)
    elif os.path.exists(staging):
        shutil.rmtree(staging)
    os.makedirs(staging, exist_ok=True)

    index: Dict[str, Any] = {}
    for path, arr in flat.items():
        safe = path.replace("/", "__")
        entries = []
        if hasattr(arr, "addressable_shards"):
            shards = [s for s in arr.addressable_shards if s.replica_id == 0]
            shape, dtype = arr.shape, str(arr.dtype)
        else:  # plain numpy / python leaf: single shard on process 0
            shards = []
            shape, dtype = np.asarray(arr).shape, str(np.asarray(arr).dtype)
            if jax.process_index() == 0:
                fname = "%s.s0.npy" % safe
                _save_arr(os.path.join(staging, fname), arr)
                entries.append({"file": fname, "slices": None,
                                "crc32": _leaf_crc(arr)})
        for shard in shards:
            fname = "%s.s%d.npy" % (safe, shard.device.id)
            # ONE device->host transfer feeds both the .npy write and the
            # CRC (np.asarray(shard.data) twice would move every shard's
            # bytes off-device twice, doubling save-path transfer time);
            # owned (not a view) so in-flight donation can't mutate it
            host = _owned_host(shard.data)
            _save_arr(os.path.join(staging, fname), host)
            entries.append({
                "file": fname,
                # replicated dims give slice(None): normalize to full extent
                "slices": [
                    [0 if s.start is None else int(s.start),
                     dim if s.stop is None else int(s.stop)]
                    for s, dim in zip(shard.index, shape)
                ],
                "crc32": _leaf_crc(host),
            })
        index[path] = {"shape": list(shape), "dtype": dtype,
                       "shards": entries}

    if jax.process_count() > 1:  # pragma: no cover - needs real multihost
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("ckpt_shards_written_%d" % step)
        # merge per-process indices: every process wrote disjoint files, so
        # process 0 re-lists the staging dir is unnecessary — instead each
        # process writes its partial index and p0 merges
        part = os.path.join(staging, "index.p%d.json" % jax.process_index())
        with open(part, "w") as f:
            json.dump(index, f)
        multihost_utils.sync_global_devices("ckpt_index_written_%d" % step)
        if jax.process_index() == 0:
            merged: Dict[str, Any] = {}
            for pi in range(jax.process_count()):
                part = os.path.join(staging, "index.p%d.json" % pi)
                with open(part) as f:  # missing partial = hard error, not
                    data = json.load(f)  # a silently thinner checkpoint
                for k, v in data.items():
                    if k in merged:
                        merged[k]["shards"].extend(v["shards"])
                    else:
                        merged[k] = v
                os.remove(part)
            index = merged

    if jax.process_index() == 0:
        for entry in index.values():
            _check_coverage(entry)
        with open(os.path.join(staging, "shards.json"), "w") as f:
            json.dump(index, f)
        # manifest is written INSIDE staging: the rename below atomically
        # publishes a complete checkpoint (readers key off manifest.json);
        # the terminal COMMIT marker additionally protects storage where
        # the rename itself can tear (see module docstring)
        with open(os.path.join(staging, "manifest.json"), "w") as f:
            json.dump({"step": step, "structure": _structure(state),
                       "meta": meta or {}, "format": "sharded",
                       "format_version": FORMAT_VERSION,
                       "commit": COMMIT_MARKER}, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(staging, final)
        _notify("save", dir=ckpt_dir, step=step, format="sharded")
        gc_checkpoints(ckpt_dir, keep_last_n=keep)
    if jax.process_count() > 1:  # pragma: no cover - needs real multihost
        from jax.experimental import multihost_utils

        # Publish barrier: without it a non-zero process can return from the
        # index barrier above, call latest_step() on shared storage while p0
        # is still mid-rename/prune, and restore a DIFFERENT step than its
        # peers — a collective desync. After this barrier every process sees
        # the final dir and the pruned listing.
        multihost_utils.sync_global_devices("ckpt_published_%d" % step)
    return final


def _check_coverage(entry: Dict[str, Any]) -> None:
    """Shard tiles must exactly tile the full array (assumes disjoint tiles,
    which distinct replica-0 shards are): catches lost index partials before
    they become a checkpoint that silently restores zeros."""
    total = 1
    for dim in entry["shape"]:
        total *= dim
    covered = 0
    for shard in entry["shards"]:
        if shard["slices"] is None:
            covered += total
            continue
        vol = 1
        for a, b in shard["slices"]:
            vol *= b - a
        covered += vol
    if covered != total:
        raise CorruptCheckpointError(
            "sharded checkpoint coverage mismatch: %d/%d elements "
            "(lost shards or overlapping tiles)" % (covered, total))


def _save_arr(path: str, a) -> None:
    """npy write; extension dtypes (bfloat16 etc., numpy kind 'V') round-trip
    as raw same-width unsigned views — np.load would otherwise hand back
    uncastable void arrays."""
    a = np.asarray(a)
    if a.dtype.kind == "V":
        a = a.view(np.dtype("u%d" % a.dtype.itemsize))
    np.save(path, a)


def _load_shards_index(path: str, step: int) -> dict:
    """Read a sharded step's ``shards.json``; CorruptCheckpointError on
    the torn-write signatures (one classification, shared by every
    sharded restore path — the manifest twin is :func:`_load_manifest`)."""
    try:
        with open(os.path.join(path, "shards.json")) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError, UnicodeDecodeError,
            OSError) as e:
        raise CorruptCheckpointError(
            "sharded checkpoint step %d has no usable shards.json: %s"
            % (step, e))


def _load_arr(path: str, dtype_str: str, crc: Optional[int] = None):
    want = np.dtype(dtype_str)
    try:
        data = np.load(path)
    except FileNotFoundError:
        raise CorruptCheckpointError("checkpoint shard %s is missing" % path)
    except (ValueError, OSError) as e:
        raise CorruptCheckpointError(
            "checkpoint shard %s is unreadable: %s" % (path, e))
    if crc is not None and _leaf_crc(data) != crc:
        raise CorruptCheckpointError(
            "checkpoint shard %s failed its CRC32 check "
            "(bit rot or torn write)" % path)
    if data.dtype != want:
        data = data.view(want)
    return data


def _restore_sharded_leaf(path_dir: str, entry: Dict[str, Any]):
    _check_coverage(entry)
    dtype = np.dtype(entry["dtype"])
    out = np.zeros(tuple(entry["shape"]), dtype)
    for shard in entry["shards"]:
        data = _load_arr(os.path.join(path_dir, shard["file"]),
                         entry["dtype"], crc=shard.get("crc32"))
        if shard["slices"] is None:
            return data
        sl = tuple(slice(a, b) for a, b in shard["slices"])
        out[sl] = data
    return out


def read_manifest(ckpt_dir: str, step: Optional[int] = None) -> dict:
    """Load one step's manifest; :class:`CorruptCheckpointError` (clear,
    actionable) instead of a bare open()/json error when the step dir
    exists but its manifest is missing or torn."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError("no checkpoints under %s" % ckpt_dir)
    return _load_manifest(ckpt_dir, step)


def restore_checkpoint_sharded(ckpt_dir: str, target_state: Any,
                               step: Optional[int] = None,
                               _manifest: Optional[dict] = None
                               ) -> Tuple[Any, dict]:
    """Shard-wise restore into ``target_state``'s shardings — the read-side
    twin of :func:`save_checkpoint_sharded`: each process materialises only
    the blocks its own devices need (never a full host copy), assembled from
    the overlapping saved tiles, so restore works for models bigger than one
    host and for a DIFFERENT mesh/sharding than the one that saved.
    """
    import jax

    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError("no checkpoints under %s" % ckpt_dir)
    path = os.path.join(ckpt_dir, "step_%012d" % step)
    # _manifest: restore_latest already parsed it for format dispatch —
    # a large model's manifest is multi-MB JSON, not worth parsing twice
    manifest = (_manifest if _manifest is not None
                else _load_manifest(ckpt_dir, step))
    if manifest.get("format") != "sharded":
        raise ValueError("checkpoint at step %d is not sharded format" % step)
    index = _load_shards_index(path, step)

    flat_t = _flatten(target_state)
    out_flat: Dict[str, Any] = {}
    for key, tgt in flat_t.items():
        entry = index[key]
        _check_coverage(entry)
        if not hasattr(tgt, "sharding"):
            out_flat[key] = _restore_sharded_leaf(path, entry)
            continue
        shape = tuple(entry["shape"])
        cache: Dict[str, Any] = {}

        def tile_data(tile):
            fname = tile["file"]
            if fname not in cache:
                cache[fname] = _load_arr(os.path.join(path, fname),
                                         entry["dtype"],
                                         crc=tile.get("crc32"))
            return cache[fname]

        blocks, devices = [], []
        for dshard in tgt.addressable_shards:
            tsl = [(0 if s.start is None else int(s.start),
                    dim if s.stop is None else int(s.stop))
                   for s, dim in zip(dshard.index, shape)]
            block = np.zeros([b - a for a, b in tsl], np.dtype(entry["dtype"]))
            for tile in entry["shards"]:
                til = (tile["slices"] if tile["slices"] is not None
                       else [(0, dim) for dim in shape])
                inter = [(max(a1, a2), min(b1, b2))
                         for (a1, b1), (a2, b2) in zip(tsl, til)]
                if any(a >= b for a, b in inter):
                    continue
                data = tile_data(tile)
                src = tuple(slice(a - ta, b - ta)
                            for (a, b), (ta, _) in zip(inter, til))
                dst = tuple(slice(a - qa, b - qa)
                            for (a, b), (qa, _) in zip(inter, tsl))
                block[dst] = data[src]
            blocks.append(jax.device_put(block, dshard.device))
            devices.append(dshard.device)
        out_flat[key] = jax.make_array_from_single_device_arrays(
            shape, tgt.sharding, blocks)
    state = _unflatten(manifest["structure"], out_flat)
    _notify("restore", dir=ckpt_dir, step=step, format="sharded")
    return state, manifest


def restore_checkpoint(ckpt_dir: str, step: Optional[int] = None,
                       sharding_tree: Any = None,
                       _manifest: Optional[dict] = None) -> Tuple[Any, dict]:
    """Load (state, manifest). If `sharding_tree` is given (a pytree of
    NamedSharding matching the state), leaves are device_put sharded.

    Raises :class:`CorruptCheckpointError` when the step's manifest is
    torn or a leaf fails its CRC32 check — a single attempt, no fallback;
    :func:`restore_latest` is the walk-back-past-corruption entry point.
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError("no checkpoints under %s" % ckpt_dir)
    path = os.path.join(ckpt_dir, "step_%012d" % step)
    manifest = (_manifest if _manifest is not None
                else _load_manifest(ckpt_dir, step))
    if manifest.get("format") == "sharded":
        index = _load_shards_index(path, step)
        flat = {k: _restore_sharded_leaf(path, v) for k, v in index.items()}
    else:
        import zipfile

        checksums = manifest.get("checksums") or {}
        try:
            names = _part_names(int(manifest.get("state_parts") or 1))
            with io.BufferedReader(_PartReader(
                    [os.path.join(path, n) for n in names])) as fh, \
                    np.load(fh) as npz:
                flat = {k: npz[k] for k in npz.files}
        except FileNotFoundError as e:
            raise CorruptCheckpointError(
                "checkpoint step %d has no %s"
                % (step, os.path.basename(e.filename or "state.npz")))
        except (ValueError, OSError, KeyError,
                zipfile.BadZipFile, zlib.error) as e:
            # zip directory/entry damage, npy header damage, payload
            # inflate failures — the torn-write / bit-rot signatures
            raise CorruptCheckpointError(
                "checkpoint step %d has an unreadable state.npz: %s"
                % (step, e))
        for key, want in checksums.items():
            if key not in flat:
                raise CorruptCheckpointError(
                    "checkpoint step %d is missing leaf %r" % (step, key))
            if _leaf_crc(flat[key]) != int(want):
                raise CorruptCheckpointError(
                    "checkpoint step %d leaf %r failed its CRC32 check "
                    "(bit rot or torn write)" % (step, key))
    state = _unflatten(manifest["structure"], flat)
    if sharding_tree is not None:
        import jax

        state = jax.tree_util.tree_map(
            lambda leaf, sh: jax.device_put(leaf, sh), state, sharding_tree
        )
    _notify("restore", dir=ckpt_dir, step=step)
    return state, manifest


def restore_latest(ckpt_dir: str, target_state: Any = None,
                   sharding_tree: Any = None) -> Tuple[Any, dict]:
    """Restore the newest step that actually loads: walk newest -> oldest,
    quarantining every step that turns out torn or checksum-corrupt
    (``.corrupt`` rename) so the next reader doesn't trip over it again.
    This is the crash-safe resume entry point the runner uses — a single
    bad write costs at most ``checkpoint_every`` steps of progress, never
    the whole run.

    ``target_state`` enables the shard-wise restore path for sharded
    manifests (each process reads only its devices' blocks); without it a
    sharded step is assembled host-side like :func:`restore_checkpoint`.
    Raises FileNotFoundError when no valid step survives.

    Multi-host: every process runs this loop over the same shared
    storage, but a shard-wise restore only CRC-checks the tiles ITS
    devices need — corruption confined to a peer's shards is invisible
    locally. Each round therefore agrees collectively: the candidate
    step is the oldest of the per-process newest (a process that
    already saw a quarantine lists fewer), and the restore only counts
    if EVERY process succeeded — one process's corruption fails the
    step for the whole gang, which falls back together instead of
    resuming from different steps and deadlocking in the first
    collective.
    """
    multi = False
    try:
        import jax

        multi = jax.process_count() > 1
    except Exception:  # jax absent/uninitialized: single-process semantics
        multi = False
    while True:
        # walk the raw listing, not all_steps(): a torn-manifest step is
        # not just skipped here but QUARANTINED, so it stops costing a
        # manifest parse on every future latest_step() call
        steps = _listed_steps(ckpt_dir)
        step = steps[-1] if steps else None
        if multi:  # pragma: no cover - needs real multihost
            from jax.experimental import multihost_utils

            gathered = multihost_utils.process_allgather(
                np.asarray(step if step is not None else -1))
            step = int(np.min(gathered))
            if step < 0:
                raise FileNotFoundError(
                    "no restorable checkpoints under %s" % ckpt_dir)
        elif step is None:
            raise FileNotFoundError(
                "no restorable checkpoints under %s" % ckpt_dir)
        result = None
        failure: Optional[CorruptCheckpointError] = None
        try:
            manifest = _load_manifest(ckpt_dir, step)
            if (manifest.get("format") == "sharded"
                    and target_state is not None):
                result = restore_checkpoint_sharded(
                    ckpt_dir, target_state, step=step, _manifest=manifest)
            else:
                result = restore_checkpoint(ckpt_dir, step=step,
                                            sharding_tree=sharding_tree,
                                            _manifest=manifest)
        except CorruptCheckpointError as e:
            failure = e
        ok = failure is None
        if multi:  # pragma: no cover - needs real multihost
            from jax.experimental import multihost_utils

            ok = bool(np.min(multihost_utils.process_allgather(
                np.asarray(1 if failure is None else 0))))
        if ok:
            return result
        log.warning("checkpoint step %d is unusable (%s); falling back "
                    "to the previous step", step,
                    failure if failure is not None
                    else "a peer process saw corruption")
        if quarantine_step(ckpt_dir, step) is None:
            # Rename failed. Losing the rename race because a PEER (or a
            # concurrent restorer) already quarantined the dir just means
            # it is gone from the next listing — keep walking. A dir
            # still present (permissions error) must raise, or this loop
            # would spin on it forever.
            if os.path.isdir(os.path.join(ckpt_dir,
                                          "step_%012d" % step)):
                raise failure if failure is not None else \
                    CorruptCheckpointError(
                        "step %d failed on a peer process and could not "
                        "be quarantined" % step)
        if multi:  # pragma: no cover - needs real multihost
            from jax.experimental import multihost_utils

            # the rename must be visible to every process before the
            # next round re-lists, or a fast peer re-picks the dead step
            multihost_utils.sync_global_devices(
                "ckpt_quarantine_%d" % step)
