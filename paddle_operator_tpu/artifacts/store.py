"""Fleet compile-artifact store: one compilation, ten thousand warm starts.

PR 8's compile ladder is host-local — every fresh VM, serving replica,
and preempt-resume re-pays full XLA compilation, and the goodput ledger
prices exactly that as fleet ``compile`` badput. This module promotes
``compile_cache.step_fingerprint`` to the key of a content-addressed
store with two tiers:

* **local** — a shared directory (``TPUJOB_ARTIFACT_STORE``, e.g. an
  NFS/ReadWriteMany volume every host mounts): bundles are published
  with the tmp + ``os.replace`` discipline, so readers never observe a
  torn file;
* **remote** — an operator-served HTTP endpoint
  (``TPUJOB_ARTIFACT_URL``, see :mod:`.server`): ``GET/PUT
  /v1/artifact`` move whole bundles, ``/v1/lease`` arbitrates who
  compiles.

Runners **publish** after first compile and peers **fetch by
fingerprint before compiling**. Every fetch is verified
(:mod:`.bundle`): CRC-pinned members, fingerprint-matched header — a
poisoned/torn/stale artifact is rejected, counted
(``tpujob_artifact_poisoned_rejected_total``), and the caller
recompiles; it can never produce a wrong answer (and the AOT member is
additionally first-call-fallback guarded in ``compile_cache``).

**Compile lease / singleflight**: a cold fleet must not stampede XLA —
50 replicas spawning should pay ONE compile. ``acquire_compile_lease``
grants at most one holder per fingerprint (in-process inflight table +
a lease file / HTTP lease in the configured tier); peers
``wait_fetch`` with a bounded deadline. A dead leaseholder cannot
wedge the fleet: leases carry TTL deadlines, an expired lease is
broken by the next acquirer, and every waiter's loop is bounded by
``TPUJOB_ARTIFACT_WAIT_S`` — on timeout the peer simply compiles
(duplicate work, never a hang, never corruption: publishes are
atomic and idempotent).

Thread-safety: counters + the inflight table live under ``_lock``
(declared in ``analysis/guards.py`` — ``make race`` enforces the
happens-before contract and OPS901 proves it statically); all file and
HTTP I/O happens outside the lock.
"""

from __future__ import annotations

import fcntl
import itertools
import json
import logging
import os
import socket
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
import zlib
from typing import Any, Callable, Dict, Optional, Tuple

from . import bundle
from .bundle import PoisonedArtifactError

log = logging.getLogger("tpujob.artifacts")

TIERS = ("local", "remote")

#: monotone per-process nonce for lease tokens (itertools.count is
#: atomic under the GIL)
_token_counter = itertools.count()

#: lease-table / lease-file TTL: how long one compiler may hold the
#: exclusive right to compile a fingerprint before peers break the lease
DEFAULT_LEASE_TTL_S = 300.0
#: how long a peer waits for the leaseholder's publish before giving up
#: and compiling itself (the bounded-deadline guarantee)
DEFAULT_WAIT_S = 240.0
DEFAULT_POLL_S = 0.2
DEFAULT_HTTP_TIMEOUT_S = 5.0
#: transient HTTP failures (connection reset, 5xx) get this many
#: RETRIES on top of the first attempt — one dropped packet mid
#:-migration must not abort a whole state pre-stage
DEFAULT_HTTP_RETRIES = 2
DEFAULT_RETRY_BACKOFF_S = 0.05
DEFAULT_RETRY_BACKOFF_CAP_S = 1.0


def enabled() -> bool:
    return os.environ.get("TPUJOB_ARTIFACTS", "1") != "0"


def _env_config() -> Optional[Tuple[str, str]]:
    """(local_dir, url) from the environment, or None when the store is
    disabled/unconfigured. ``TPUJOB_ARTIFACT_STORE=0`` disables the
    local tier the same way ``TPUJOB_ARTIFACTS=0`` disables both."""
    if not enabled():
        return None
    local = os.environ.get("TPUJOB_ARTIFACT_STORE", "")
    if local == "0":
        local = ""
    url = os.environ.get("TPUJOB_ARTIFACT_URL", "").rstrip("/")
    if not local and not url:
        return None
    return (local, url)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class CompileLease:
    """The result of one lease-acquire attempt. ``granted`` means THIS
    caller holds the exclusive right to compile the fingerprint and must
    :meth:`release` after publishing (or failing)."""

    def __init__(self, store: "ArtifactStore", fingerprint: str,
                 granted: bool, token: str) -> None:
        self._store = store
        self.fingerprint = fingerprint
        self.granted = granted
        self._token = token
        self._released = False

    def release(self) -> None:
        if self._released or not self.granted:
            return
        self._released = True
        self._store._release_lease(self.fingerprint, self._token)


class ArtifactStore:
    """One process's client to the configured tiers. Construct via
    :func:`get_store` (env-keyed singleton), not directly."""

    def __init__(self, local_dir: str = "", url: str = "",
                 lease_ttl_s: Optional[float] = None,
                 wait_s: Optional[float] = None,
                 poll_s: Optional[float] = None,
                 http_timeout_s: Optional[float] = None,
                 http_retries: Optional[int] = None) -> None:
        self.local_dir = local_dir
        self.url = url.rstrip("/")
        self.lease_ttl_s = (lease_ttl_s if lease_ttl_s is not None else
                            _env_float("TPUJOB_ARTIFACT_LEASE_TTL",
                                       DEFAULT_LEASE_TTL_S))
        self.wait_s = (wait_s if wait_s is not None else
                       _env_float("TPUJOB_ARTIFACT_WAIT_S", DEFAULT_WAIT_S))
        self.poll_s = max(0.001,
                          poll_s if poll_s is not None else
                          _env_float("TPUJOB_ARTIFACT_POLL_S",
                                     DEFAULT_POLL_S))
        self.http_timeout_s = (http_timeout_s if http_timeout_s is not None
                               else _env_float("TPUJOB_ARTIFACT_HTTP_TIMEOUT",
                                               DEFAULT_HTTP_TIMEOUT_S))
        self.http_retries = max(0, int(
            http_retries if http_retries is not None else
            _env_float("TPUJOB_ARTIFACT_HTTP_RETRIES",
                       DEFAULT_HTTP_RETRIES)))
        self.retry_backoff_s = DEFAULT_RETRY_BACKOFF_S
        # hostname:pid:nonce — the nonce distinguishes store instances
        # so a same-holder "refresh" can only come from THIS client
        # (pid reuse / two clients in one process must not alias)
        self._token = "%s:%d:%d" % (socket.gethostname(), os.getpid(),
                                    next(_token_counter))
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        # fingerprints whose compile lease THIS process currently holds
        # (the in-process half of singleflight: a second thread building
        # the same step must wait-then-fetch, not compile in parallel)
        self._inflight: set = set()
        self._stats: Dict[str, float] = {}
        for tier in TIERS:
            for k in ("hits", "misses", "publishes", "poisoned",
                      "fetch_seconds", "retries"):
                self._stats["%s_%s" % (k, tier)] = 0
        for k in ("lease_granted", "lease_waited", "lease_timeout",
                  "lease_broken"):
            self._stats[k] = 0
        # serializes this process's local-tier read-merge-replace so two
        # threads can't drop each other's members (cross-process merge
        # races are tolerated: publishes are idempotent and re-tried by
        # the next save — see docs/design.md)
        self._pub_lock = threading.Lock()
        self._warned: set = set()

    # -- stats -----------------------------------------------------------

    def _bump_locked(self, key: str, n: float = 1) -> None:
        self._stats[key] = self._stats.get(key, 0) + n

    def _bump(self, key: str, n: float = 1) -> None:
        with self._lock:
            self._bump_locked(key, n)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._stats)

    def _warn_once(self, key: str, msg: str, *args: Any) -> None:
        with self._lock:
            if key in self._warned:
                return
            self._warned.add(key)
        log.warning(msg, *args)

    # -- local tier ------------------------------------------------------

    def _bundle_path(self, fingerprint: str) -> str:
        return os.path.join(self.local_dir, fingerprint + bundle.SUFFIX)

    def _lease_path(self, fingerprint: str) -> str:
        return os.path.join(self.local_dir, fingerprint + ".lease")

    def _local_fetch(self, fingerprint: str, member: Optional[str] = None
                     ) -> Optional[Dict[str, bytes]]:
        """Read + verify the local-tier bundle (always verified WHOLE;
        ``member`` then narrows the result). Poisoned files are DELETED
        (the publisher re-publishes a good one on its next compile) and
        counted; a missing file/member is a plain miss. Raises
        PoisonedArtifactError so the caller can attribute the reject."""
        path = self._bundle_path(fingerprint)
        try:
            with open(path, "rb") as fh:
                data = fh.read()
        except OSError:
            return None
        try:
            members = bundle.parse(data, fingerprint)
        except PoisonedArtifactError:
            try:
                os.remove(path)
            except OSError:
                pass
            raise
        if member is not None:
            if member not in members:
                return None
            return {member: members[member]}
        return members

    def _local_publish(self, fingerprint: str,
                       members: Dict[str, bytes]) -> bool:
        """Merge-publish into the local tier: existing members the new
        payload does not carry are preserved (the cost sidecar lands
        after the executable), and the final write is atomic
        (tmp + ``os.replace``) so a concurrent fetch never sees a torn
        bundle."""
        path = self._bundle_path(fingerprint)
        with self._pub_lock:
            try:
                bundle.merge_write(path, fingerprint, members)
                return True
            except OSError as e:
                self._warn_once("local_publish",
                                "artifact store %s not writable (%s); "
                                "local publishes disabled",
                                self.local_dir, e)
                return False

    def _local_lease_acquire(self, fingerprint: str) -> bool:
        path = self._lease_path(fingerprint)
        payload = json.dumps({"holder": self._token,
                              "deadline": time.time() + self.lease_ttl_s}
                             ).encode()
        lock = None
        try:
            for _ in range(2):
                try:
                    os.makedirs(self.local_dir, exist_ok=True)
                    # the lease appears at its path WITH its deadline:
                    # written under a name of our own and linked into
                    # place (os.link never overwrites, so it arbitrates
                    # as O_EXCL would). A lease created empty and filled
                    # afterwards reads as torn, hence dead, to a peer
                    # that looks in between, which then breaks a LIVE
                    # lease and is granted it as well
                    fresh = "%s.fresh.%d.%d" % (path, os.getpid(),
                                                next(_token_counter))
                    fd = os.open(fresh,
                                 os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                    try:
                        try:
                            os.write(fd, payload)
                        finally:
                            os.close(fd)
                        os.link(fresh, path)
                    finally:
                        os.remove(fresh)
                    return True
                except FileExistsError:
                    if not self._local_lease_expired(path):
                        return False
                    if lock is None:
                        # ONE breaker at a time, from here to our own
                        # lease's link at the loop's top (the kernel
                        # drops a dead breaker's flock). Unserialised, a
                        # breaker whose expired-check read the dead
                        # lease renames aside the LIVE one a faster peer
                        # has put there since; it puts it back, but
                        # while the path is empty a third acquirer links
                        # in and two hold the lease. Where the volume
                        # has no flock the checks below still stand
                        lock = os.open(self.local_dir, os.O_RDONLY)
                        try:
                            fcntl.flock(lock, fcntl.LOCK_EX)
                        except OSError:
                            pass
                        if not os.path.exists(path):
                            continue  # released meanwhile: link at the top
                        if not self._local_lease_expired(path):
                            return False  # a peer broke it; they hold it
                    # the holder died (or wedged past its TTL): break the
                    # lease ATOMICALLY by renaming the inode aside — the
                    # source vanishes for every other breaker, so exactly
                    # one rename succeeds (a bare remove+create would let
                    # breaker B's remove delete the lease breaker A just
                    # freshly created — two "granted" holders)
                    stale = "%s.stale.%d.%d" % (path, os.getpid(),
                                                next(_token_counter))
                    try:
                        os.rename(path, stale)
                    except OSError:
                        return False  # someone else broke it; they hold it
                    if not self._local_lease_expired(stale):
                        # we stole a LIVE lease: our expired-check read the
                        # dead holder's file, but a peer broke it and
                        # created a fresh one before our rename landed —
                        # restore it (os.link never overwrites, so an even
                        # newer lease at path wins) and report "held"
                        try:
                            os.link(stale, path)
                        except OSError:
                            pass
                        try:
                            os.remove(stale)
                        except OSError:
                            pass
                        return False
                    self._bump("lease_broken")
                    try:
                        os.remove(stale)
                    except OSError:
                        pass
                    # loop: retry the exclusive create (another FRESH
                    # acquirer may still beat us — os.link arbitrates)
                except OSError:
                    return False  # unwritable store: no singleflight, no wedge
            return False
        finally:
            if lock is not None:
                os.close(lock)

    @staticmethod
    def _local_lease_expired(path: str) -> bool:
        try:
            with open(path) as fh:
                info = json.load(fh)
            return float(info.get("deadline", 0)) <= time.time()
        except (OSError, ValueError, TypeError):
            return True  # torn/garbage lease file counts as dead

    def _local_lease_state(self, fingerprint: str) -> str:
        path = self._lease_path(fingerprint)
        if not os.path.exists(path):
            return "free"
        return "expired" if self._local_lease_expired(path) else "held"

    def _local_lease_release(self, fingerprint: str, token: str) -> None:
        path = self._lease_path(fingerprint)
        try:
            with open(path) as fh:
                info = json.load(fh)
            if info.get("holder") == token:
                os.remove(path)
        except (OSError, ValueError):
            pass

    # -- remote tier -----------------------------------------------------

    def _retry_backoff(self, path: str, attempt: int) -> float:
        """Deterministic capped-exponential backoff: the jitter is
        crc32(path#attempt)-derived (the reconciler's ``_backoff_for``
        pattern) so chaos replays of a flaky-network migration sleep
        identically, yet concurrent clients de-synchronize."""
        base = min(self.retry_backoff_s * (2 ** (attempt - 1)),
                   DEFAULT_RETRY_BACKOFF_CAP_S)
        salt = zlib.crc32(("%s#%d" % (path, attempt)).encode())
        return base * (0.5 + 0.5 * (salt % 1000) / 999.0)

    def _http(self, method: str, path: str,
              body: Optional[bytes] = None) -> Tuple[int, bytes]:
        """One HTTP exchange with bounded transient-failure retries:
        connection-level failures (reset, refused, timeout) and 5xx
        responses re-try up to ``http_retries`` times with deterministic
        capped backoff, counted per tier
        (``tpujob_artifact_fetch_retries_total``); 4xx and other
        definitive answers return immediately. The last failure
        propagates exactly as the unretried call would have — callers'
        degrade-to-miss postures are unchanged."""
        attempts = self.http_retries + 1
        for attempt in range(attempts):
            if attempt:
                self._bump("retries_remote")
                time.sleep(self._retry_backoff(path, attempt))
            req = urllib.request.Request(self.url + path, data=body,
                                         method=method)
            if body is not None:
                req.add_header("Content-Type",
                               "application/octet-stream")
            try:
                with urllib.request.urlopen(
                        req, timeout=self.http_timeout_s) as resp:
                    return resp.status, resp.read()
            except urllib.error.HTTPError as e:
                data = e.read()
                if e.code < 500 or attempt == attempts - 1:
                    return e.code, data
            except (urllib.error.URLError, OSError):
                if attempt == attempts - 1:
                    raise
        raise AssertionError("unreachable")  # pragma: no cover

    def _remote_fetch(self, fingerprint: str, member: Optional[str] = None
                      ) -> Optional[Dict[str, bytes]]:
        url = "/v1/artifact?fp=%s" % fingerprint
        if member is not None:
            # member-scoped: the server re-packs just this member so a
            # cost-sidecar lookup never downloads the whole executable
            url += "&member=%s" % urllib.parse.quote(member, safe="")
        code, data = self._http("GET", url)
        if code != 200:
            return None
        members = bundle.parse(data, fingerprint)
        if member is not None and member not in members:
            return None
        return members

    def _remote_publish(self, fingerprint: str,
                        members: Dict[str, bytes]) -> bool:
        code, _ = self._http("PUT", "/v1/artifact?fp=%s" % fingerprint,
                             body=bundle.pack(fingerprint, members))
        return code == 200

    def _remote_lease_acquire(self, fingerprint: str) -> Tuple[bool, bool]:
        """(granted, broke): ``broke`` reports a dead holder's expired
        lease being taken over, so the ``broken`` outcome counts on the
        remote tier too."""
        body = json.dumps({"fp": fingerprint, "holder": self._token,
                           "ttl": self.lease_ttl_s}).encode()
        code, data = self._http("POST", "/v1/lease", body=body)
        if code != 200:
            return False, False
        try:
            d = json.loads(data)
            return bool(d.get("granted")), bool(d.get("broke"))
        except ValueError:
            return False, False

    def _remote_lease_state(self, fingerprint: str) -> str:
        code, data = self._http("GET", "/v1/lease?fp=%s" % fingerprint)
        if code != 200:
            return "free"
        try:
            return str(json.loads(data).get("state", "free"))
        except ValueError:
            return "free"

    def _remote_lease_release(self, fingerprint: str, token: str) -> None:
        self._http("DELETE",
                   "/v1/lease?fp=%s&holder=%s" % (fingerprint, token))

    # -- the public surface ---------------------------------------------

    def fetch(self, fingerprint: str, record: bool = True,
              member: Optional[str] = None
              ) -> Tuple[Optional[Dict[str, bytes]], Optional[str]]:
        """Try every configured tier in order (local first — it is the
        cheap one). Returns ``(members, tier)`` on a verified hit,
        ``(None, None)`` on miss. ``member`` narrows the fetch to one
        bundle member (the cost-sidecar lookup must not download the
        whole executable over HTTP). Poisoned artifacts are rejected +
        counted per tier and reported as misses; network/tier failures
        degrade to a miss with one warning, never raise. Fetch wall is
        accumulated for EVERY outcome — a tier burning its timeout on
        misses must show up in ``tpujob_artifact_fetch_seconds``."""
        for tier, impl in (("local", self._local_fetch),
                           ("remote", self._remote_fetch)):
            if not self._tier_configured(tier):
                continue
            t0 = time.perf_counter()
            members = None
            poisoned: Optional[PoisonedArtifactError] = None
            try:
                members = impl(fingerprint, member)
            except PoisonedArtifactError as e:
                poisoned = e
            except Exception as e:  # tier down: degrade, never raise
                self._warn_once("fetch_%s" % tier,
                                "artifact %s tier unavailable: %s", tier, e)
            dt = time.perf_counter() - t0
            with self._lock:
                self._bump_locked("fetch_seconds_%s" % tier, dt)
                if poisoned is not None:
                    self._bump_locked("poisoned_%s" % tier)
                if record:
                    self._bump_locked(
                        "hits_%s" % tier if members is not None
                        else "misses_%s" % tier)
            if poisoned is not None:
                log.warning("rejected poisoned artifact %s from %s tier: %s",
                            fingerprint[:12], tier, poisoned)
            if members is not None:
                return members, tier
        return None, None

    def _tier_configured(self, tier: str) -> bool:
        return bool(self.local_dir if tier == "local" else self.url)

    def publish(self, fingerprint: str, members: Dict[str, bytes]) -> None:
        """Publish/merge ``members`` under ``fingerprint`` into every
        configured tier. Best-effort and idempotent: a failed tier costs
        the fleet a recompile somewhere, never this process's run. Wakes
        any in-process waiter."""
        if not members:
            return
        if self.local_dir and self._local_publish(fingerprint, members):
            self._bump("publishes_local")
        if self.url:
            try:
                ok = self._remote_publish(fingerprint, members)
            except Exception as e:
                self._warn_once("publish_remote",
                                "artifact remote publish failed: %s", e)
                ok = False
            if ok:
                self._bump("publishes_remote")
        with self._lock:
            self._cond.notify_all()

    def note_first_call_reject(self, tier: Optional[str]) -> None:
        """The first-call fallback fired on a store-served executable: a
        CRC-valid but semantically stale artifact (foreign topology,
        sharding boundary drift). Counted with the poisoned rejects —
        same posture, later trigger."""
        self._bump("poisoned_%s" % (tier or "local"))

    # -- lease / singleflight -------------------------------------------

    def _lease_domain(self) -> str:
        """The tier that arbitrates compile leases: the remote one when
        configured (it spans the whole fleet), else the shared local
        directory."""
        return "remote" if self.url else "local"

    def acquire_compile_lease(self, fingerprint: str) -> CompileLease:
        """At most one granted lease per fingerprint across the lease
        domain (and across threads of this process). Not granted means
        someone else is compiling: wait-then-fetch with a bounded
        deadline, re-trying the acquire when the lease dies."""
        with self._lock:
            if fingerprint in self._inflight:
                self._bump_locked("lease_waited")
                return CompileLease(self, fingerprint, False, self._token)
        broke = False
        if self._lease_domain() == "remote":
            try:
                granted, broke = self._remote_lease_acquire(fingerprint)
            except Exception as e:
                self._warn_once("lease_remote",
                                "artifact lease endpoint unavailable "
                                "(%s); compiling without singleflight", e)
                granted = True  # no arbiter: never block on its absence
        else:
            # (_local_lease_acquire bumps lease_broken itself)
            granted = self._local_lease_acquire(fingerprint)
        with self._lock:
            if broke:
                self._bump_locked("lease_broken")
            if granted:
                self._inflight.add(fingerprint)
                self._bump_locked("lease_granted")
            else:
                self._bump_locked("lease_waited")
        return CompileLease(self, fingerprint, granted, self._token)

    def _release_lease(self, fingerprint: str, token: str) -> None:
        if self._lease_domain() == "remote":
            try:
                self._remote_lease_release(fingerprint, token)
            except Exception:
                pass  # TTL expiry reclaims it
        else:
            self._local_lease_release(fingerprint, token)
        with self._lock:
            self._inflight.discard(fingerprint)
            self._cond.notify_all()

    def lease_state(self, fingerprint: str) -> str:
        """``held`` | ``expired`` | ``free`` in the lease domain (the
        in-process table counts as held)."""
        with self._lock:
            if fingerprint in self._inflight:
                return "held"
        if self._lease_domain() == "remote":
            try:
                return self._remote_lease_state(fingerprint)
            except Exception:
                return "free"
        return self._local_lease_state(fingerprint)

    def wait_fetch(self, fingerprint: str, deadline_monotonic: float
                   ) -> Tuple[Optional[Dict[str, bytes]], Optional[str]]:
        """Wait for someone else's publish: poll-fetch until the bounded
        deadline. Returns early (a miss) when the lease frees/expires so
        the caller can re-try the acquire — a dead leaseholder costs its
        TTL, never the full wait budget, and never a wedge."""
        while True:
            members, tier = self.fetch(fingerprint, record=False)
            if members is not None:
                self._bump("hits_%s" % tier)
                return members, tier
            if time.monotonic() >= deadline_monotonic:
                self._bump("lease_timeout")
                return None, None
            if self.lease_state(fingerprint) != "held":
                return None, None  # holder gone: caller re-acquires
            with self._lock:
                self._cond.wait(timeout=self.poll_s)


# ---------------------------------------------------------------------------
# env-keyed singleton
# ---------------------------------------------------------------------------

class _SingletonState:
    """Module singleton holder (one store client per process config);
    fields under ``_lock`` per the declared guard spec."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.store: Optional[ArtifactStore] = None
        self.key: Optional[Tuple[str, str]] = None


_sing = _SingletonState()

# make race (TPUJOB_RACE_DETECT=1): the declared guard spec
# (analysis/guards.py) — every touch of the singleton fields must hold
# its lock; no-op with the detector off
from ..analysis import guards as _guards  # noqa: E402

_guards.guard_declared(_sing)


def get_store() -> Optional[ArtifactStore]:
    """The process's store client for the CURRENT environment config, or
    None when no tier is configured / ``TPUJOB_ARTIFACTS=0``. Re-keyed
    on env change (tests repoint the store per scenario); counters
    reset with the key, matching one-store-one-config semantics."""
    cfg = _env_config()
    with _sing._lock:
        if cfg == _sing.key:
            return _sing.store
        _sing.key = cfg
        if cfg is None:
            _sing.store = None
        else:
            _sing.store = _guards.guard_declared(
                ArtifactStore(local_dir=cfg[0], url=cfg[1]))
        return _sing.store


def reset_for_tests() -> None:
    with _sing._lock:
        _sing.store = None
        _sing.key = None


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def metrics_text() -> str:
    """Client-side ``tpujob_artifact_*`` exposition — registered into a
    Manager via ``add_metrics_provider`` or merged into the worker
    endpoint. Families declared here (opslint OPS401); every (family,
    tier) combination is always emitted so dashboards see stable
    zero-valued series while the store is idle/disabled."""
    store = get_store()
    s = store.stats() if store is not None else {}

    def v(key: str) -> float:
        return s.get(key, 0)

    lines = [
        "# HELP tpujob_artifact_hits_total verified artifact fetches "
        "served, by tier",
        "# TYPE tpujob_artifact_hits_total counter",
    ]
    lines += ['tpujob_artifact_hits_total{tier="%s"} %d' % (t, v("hits_%s" % t))
              for t in TIERS]
    lines += [
        "# HELP tpujob_artifact_misses_total artifact fetches that found "
        "nothing usable, by tier",
        "# TYPE tpujob_artifact_misses_total counter",
    ]
    lines += ['tpujob_artifact_misses_total{tier="%s"} %d'
              % (t, v("misses_%s" % t)) for t in TIERS]
    lines += [
        "# HELP tpujob_artifact_publishes_total bundles published after "
        "a first compile, by tier",
        "# TYPE tpujob_artifact_publishes_total counter",
    ]
    lines += ['tpujob_artifact_publishes_total{tier="%s"} %d'
              % (t, v("publishes_%s" % t)) for t in TIERS]
    lines += [
        "# HELP tpujob_artifact_poisoned_rejected_total fetched artifacts "
        "rejected by verification (bad CRC, torn file, stale fingerprint, "
        "first-call fallback), by tier",
        "# TYPE tpujob_artifact_poisoned_rejected_total counter",
    ]
    lines += ['tpujob_artifact_poisoned_rejected_total{tier="%s"} %d'
              % (t, v("poisoned_%s" % t)) for t in TIERS]
    lines += [
        "# HELP tpujob_artifact_fetch_seconds total wall seconds spent "
        "fetching + verifying artifacts, by tier",
        "# TYPE tpujob_artifact_fetch_seconds gauge",
    ]
    lines += ['tpujob_artifact_fetch_seconds{tier="%s"} %.3f'
              % (t, v("fetch_seconds_%s" % t)) for t in TIERS]
    lines += [
        "# HELP tpujob_artifact_fetch_retries_total transient HTTP "
        "failures (connection reset, 5xx) retried with deterministic "
        "capped backoff, by tier",
        "# TYPE tpujob_artifact_fetch_retries_total counter",
    ]
    lines += ['tpujob_artifact_fetch_retries_total{tier="%s"} %d'
              % (t, v("retries_%s" % t)) for t in TIERS]
    lines += [
        "# HELP tpujob_artifact_lease_total compile-lease outcomes "
        "(granted = this process compiles; waited = a peer holds the "
        "lease; timeout = bounded deadline hit, compiled anyway; broken "
        "= dead leaseholder's lease taken over)",
        "# TYPE tpujob_artifact_lease_total counter",
    ]
    lines += ['tpujob_artifact_lease_total{outcome="%s"} %d'
              % (o, v("lease_%s" % o))
              for o in ("granted", "waited", "timeout", "broken")]
    return "\n".join(lines) + "\n"


def stats_block() -> Dict[str, float]:
    """Compact summary for ``result["compile_cache"]``."""
    store = get_store()
    if store is None:
        return {"configured": False}
    s = store.stats()
    out: Dict[str, float] = {"configured": True}
    out.update({k: s[k] for k in sorted(s) if s[k]})
    return out


__all__ = [
    "ArtifactStore", "CompileLease", "PoisonedArtifactError", "TIERS",
    "enabled", "get_store", "metrics_text", "reset_for_tests",
    "stats_block",
]
