"""Controller runtime: informers, workqueue, manager, leader election.

The Python equivalent of the slice of sigs.k8s.io/controller-runtime the
reference uses (``SetupWithManager``, ``paddlejob_controller.go:535-571``):
watches on the primary kind plus owned kinds, owner-mapped enqueueing, a
deduplicating workqueue with requeue/requeue-after, and a manager hosting
controllers with leader election, metrics and health endpoints.

Two execution modes:

* **threaded** (production): `Manager.start()` spawns a worker per controller
  draining its queue continuously.
* **synchronous** (tests / the envtest analog): `Manager.drain()` processes all
  pending work on the caller's thread — deterministic, no sleeps.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

from .client import KubeClient
from .fake import FakeKubeClient
from .objects import get_controller_of
from ..utils.trace import tracer

log = logging.getLogger("tpujob.runtime")


def escape_label_value(value: str) -> str:
    """Prometheus text-exposition label escaping. Object names normally
    can't carry ``"``/``\\``, but webhook-bypassed writes can — an
    unescaped value would corrupt the whole scrape."""
    return (str(value).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def fold_suffix(metric: str, get_type: Callable[[str], Optional[str]]):
    """Resolve a sample's metric name to its family: the name itself if
    ``get_type`` knows it, else a ``_bucket``/``_sum``/``_count`` fold
    onto a histogram/summary base. The ONE implementation of the suffix
    rules — shared by the provider-block merger below and the strict
    parser in :mod:`..obs`, so they can never drift. Returns None when
    no declared family matches."""
    if get_type(metric) is not None:
        return metric
    for suffix, kinds in (("_bucket", ("histogram",)),
                          ("_sum", ("histogram", "summary")),
                          ("_count", ("histogram", "summary"))):
        if metric.endswith(suffix):
            base = metric[: -len(suffix)]
            if get_type(base) in kinds:
                return base
    return None


#: priority lanes: scheduler-eviction drains and deletes ride ``high`` so
#: they beat routine resyncs queued on ``normal`` (client-go has no lanes;
#: at fleet scale a 10k-key resync backlog must not delay a drain notice
#: whose grace window is ticking).
LANE_HIGH = "high"
LANE_NORMAL = "normal"
LANES = (LANE_HIGH, LANE_NORMAL)


class WorkQueue:
    """Deduplicating queue of (namespace, name) keys, safe for parallel
    consumers, with priority lanes and deferred entries.

    The client-go workqueue contract, extended with lanes:

    * **dedup while queued** — adding a queued key is a no-op (a high add
      promotes a normal-queued key);
    * **per-key exclusivity** — a popped key is *active* until the consumer
      calls :meth:`done`; re-adds meanwhile park in a dirty set and requeue
      at ``done()``, so a key is never processed by two workers at once and
      never lost;
    * **requeue-after** — :meth:`add_after` parks the earliest due time;
      :meth:`promote_due` moves expired entries into their lane (or the
      dirty set, if the key is active);
    * **lanes** — ``pop`` serves ``high`` first; after ``normal_share``
      consecutive high pops with normal work waiting it serves one normal
      key, so routine resyncs are bounded-starved, never unbounded.
    """

    def __init__(self, normal_share: int = 8):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # lane -> key -> normal-pop stamp at enqueue (for the starvation
        # audit); insertion order is the FIFO order
        self._lanes: Dict[str, "OrderedDict[Tuple[str, str], int]"] = {
            lane: OrderedDict() for lane in LANES}
        self._lane_of: Dict[Tuple[str, str], str] = {}
        self._deferred: Dict[Tuple[str, str], Tuple[float, str]] = {}
        # active key -> the lane it was popped from: a consumer requeue
        # (Result.requeue / requeue_after / error backoff) re-enters the
        # SAME lane, so an in-flight high-priority incident keeps beating
        # the resync backlog between passes instead of degrading to
        # normal the moment no fresh watch event re-promotes it
        self._active: Dict[Tuple[str, str], str] = {}
        self._dirty: Dict[Tuple[str, str], str] = {}
        self.normal_share = normal_share
        self._high_streak = 0
        self._pops = {lane: 0 for lane in LANES}
        # audit counters for the chaos storm's "priority lane never
        # starved" invariant: peak high-lane depth, and the most normal
        # pops any high key waited behind (bounded by the pick policy)
        self._max_high_depth = 0
        self._max_normal_behind_high = 0

    @staticmethod
    def _merge_lane(a: Optional[str], b: str) -> str:
        return LANE_HIGH if LANE_HIGH in (a, b) else b

    def add(self, key: Tuple[str, str], lane: str = LANE_NORMAL) -> None:
        with self._cv:
            deferred = self._deferred.pop(key, None)
            if deferred is not None:
                # a routine add must not demote a parked high retry (an
                # incident's requeue_after/error backoff waiting its turn)
                lane = self._merge_lane(deferred[1], lane)
            if key in self._active:
                # per-key exclusivity: requeue when the worker calls done()
                self._dirty[key] = self._merge_lane(self._dirty.get(key),
                                                    lane)
                return
            cur = self._lane_of.get(key)
            if cur is None:
                self._enqueue_locked(key, lane)
            elif lane == LANE_HIGH and cur == LANE_NORMAL:
                del self._lanes[cur][key]
                self._enqueue_locked(key, LANE_HIGH)
            self._cv.notify()

    def _enqueue_locked(self, key: Tuple[str, str], lane: str) -> None:
        self._lane_of[key] = lane
        self._lanes[lane][key] = self._pops[LANE_NORMAL]
        if lane == LANE_HIGH:
            self._max_high_depth = max(self._max_high_depth,
                                       len(self._lanes[LANE_HIGH]))

    def add_after(self, key: Tuple[str, str], delay: float,
                  lane: str = LANE_NORMAL) -> None:
        due = time.monotonic() + delay
        with self._cv:
            if key in self._lane_of:
                # already queued: the sooner signal wins, but a high
                # escalation must still promote (same as add())
                if lane == LANE_HIGH and self._lane_of[key] == LANE_NORMAL:
                    del self._lanes[LANE_NORMAL][key]
                    self._enqueue_locked(key, LANE_HIGH)
                return
            cur = self._deferred.get(key)
            if cur is None:
                self._deferred[key] = (due, lane)
            else:
                self._deferred[key] = (min(due, cur[0]),
                                       self._merge_lane(cur[1], lane))
            self._cv.notify()

    def promote_due(self, now: Optional[float] = None, force: bool = False) -> None:
        now = time.monotonic() if now is None else now
        promoted = 0
        with self._cv:
            for key, (due, lane) in list(self._deferred.items()):
                if force or due <= now:
                    del self._deferred[key]
                    if key in self._active:
                        self._dirty[key] = self._merge_lane(
                            self._dirty.get(key), lane)
                    elif key not in self._lane_of:
                        self._enqueue_locked(key, lane)
                        promoted += 1
            if promoted > 1:
                self._cv.notify_all()
            elif promoted or self._lane_of:
                self._cv.notify()

    def _pick_lane_locked(self) -> Optional[str]:
        high, normal = self._lanes[LANE_HIGH], self._lanes[LANE_NORMAL]
        if high:
            if normal and self._high_streak >= self.normal_share:
                return LANE_NORMAL
            return LANE_HIGH
        if normal:
            return LANE_NORMAL
        return None

    def pop(self, timeout: Optional[float] = None) -> Optional[Tuple[str, str]]:
        with self._cv:
            if not self._lane_of and timeout:
                self._cv.wait(timeout)
            lane = self._pick_lane_locked()
            if lane is None:
                return None
            key, stamp = self._lanes[lane].popitem(last=False)
            del self._lane_of[key]
            self._pops[lane] += 1
            if lane == LANE_HIGH:
                self._high_streak += 1
                self._max_normal_behind_high = max(
                    self._max_normal_behind_high,
                    self._pops[LANE_NORMAL] - stamp)
            else:
                self._high_streak = 0
            self._active[key] = lane
            return key

    def active_lane(self, key: Tuple[str, str]) -> str:
        """Lane ``key`` was popped from (``normal`` if not active) — what
        the consumer's own requeue should re-enter."""
        with self._lock:
            return self._active.get(key, LANE_NORMAL)

    def done(self, key: Tuple[str, str]) -> None:
        """The consumer finished ``key``: release its exclusivity and
        requeue it if adds arrived while it was being processed."""
        with self._cv:
            self._active.pop(key, None)
            lane = self._dirty.pop(key, None)
            if lane is not None and key not in self._lane_of:
                self._enqueue_locked(key, lane)
                self._cv.notify()

    def __len__(self) -> int:
        with self._lock:
            return len(self._lane_of)

    @property
    def pending_deferred(self) -> int:
        with self._lock:
            return len(self._deferred)

    @property
    def active(self) -> int:
        with self._lock:
            return len(self._active)

    def depth(self, lane: str) -> int:
        with self._lock:
            return len(self._lanes[lane])

    def stats(self) -> Dict[str, int]:
        """Deterministic audit counters (chaos storm invariants)."""
        with self._lock:
            return {
                "high_pops": self._pops[LANE_HIGH],
                "normal_pops": self._pops[LANE_NORMAL],
                "max_high_depth": self._max_high_depth,
                "max_normal_behind_high": self._max_normal_behind_high,
            }


def owner_key_mapper(api_version: str, kind: str) -> Callable:
    """Map an owned object event to its controller-owner's key
    (the Owns() relation, reference :555-567)."""

    def mapper(obj: dict) -> Optional[Tuple[str, str]]:
        ref = get_controller_of(obj)
        if ref is None:
            return None
        if ref.get("apiVersion") != api_version or ref.get("kind") != kind:
            return None
        return (obj.get("metadata", {}).get("namespace", "default"), ref["name"])

    return mapper


def self_key_mapper(obj: dict) -> Tuple[str, str]:
    m = obj.get("metadata", {})
    return (m.get("namespace", "default"), m.get("name", ""))


#: reconcile-latency histogram buckets: harness passes land in the
#: sub-millisecond buckets, real-apiserver passes in the tens-of-ms ones.
RECONCILE_BUCKETS = (0.001, 0.005, 0.025, 0.1, 0.5, 2.0, 10.0)


class Controller:
    """One reconciler + its watch set + its queue.

    Metrics are mutated under ``_mlock``: with ``--reconcile-workers`` > 1
    several workers finish passes concurrently, and unlocked ``+=`` on the
    counters would silently lose increments.
    """

    def __init__(self, name: str, reconcile: Callable, max_retries: int = 8,
                 lane_for: Optional[Callable[[str, dict], str]] = None):
        self.name = name
        self.reconcile = reconcile
        self.queue = WorkQueue()
        self.for_kind = ""  # primary kind; set by Manager.add_controller
        self.max_retries = max_retries
        # classifies a watch event into a workqueue lane (None = normal)
        self.lane_for = lane_for
        self._mlock = threading.Lock()
        self._failures: Dict[Tuple[str, str], int] = {}
        self.metrics = {"reconcile_total": 0, "reconcile_errors_total": 0,
                        "requeue_total": 0}
        # Prometheus-summary components for reconcile latency
        # (controller-runtime exposes the same as a histogram)
        self.duration_sum = 0.0
        self.duration_count = 0
        # tpujob_reconcile_seconds{outcome=}: outcome -> [bucket counts,
        # +Inf], with parallel sum/count maps
        self._hist: Dict[str, List[int]] = {}
        self._hist_sum: Dict[str, float] = {}
        self._hist_count: Dict[str, int] = {}
        # optional gauge: current max error-requeue backoff armed by the
        # reconciler (seconds); wired by whoever owns the reconciler
        self.backoff_provider: Optional[Callable[[], float]] = None

    def _enqueue_event(self, etype: str, obj: dict, mapper: Callable) -> None:
        key = mapper(obj)
        if key is not None:
            lane = self.lane_for(etype, obj) if self.lane_for else LANE_NORMAL
            self.queue.add(key, lane=lane)

    def watch(self, client, kind: str, mapper: Callable, namespace=None,
              cache=None) -> None:
        if cache is not None:
            # informer-fed: one shared watch per kind feeds the cache; the
            # controller just subscribes for key-mapping (reference: the
            # Watches/Owns wiring at paddlejob_controller.go:555-567 on top
            # of the manager's shared cache)
            def handler(etype, obj, mapper=mapper):
                self._enqueue_event(etype, obj, mapper)
            cache.informer(kind).add_handler(handler)
        elif isinstance(client, FakeKubeClient):
            def cb(etype, obj, mapper=mapper):
                self._enqueue_event(etype, obj, mapper)
            client.add_watch_callback(kind, namespace, cb)
        else:
            # there is exactly ONE list-then-watch/rv-resume/410 protocol
            # implementation (InformerCache._run_watch); Manager provides an
            # implicit cache for real clients rather than duplicating it here
            raise ValueError(
                "watching a real client requires an informer cache; "
                "construct the Controller through Manager.add_controller"
            )

    def _observe(self, outcome: str, seconds: float) -> None:
        with self._mlock:
            self.duration_sum += seconds
            self.duration_count += 1
            counts = self._hist.get(outcome)
            if counts is None:
                counts = self._hist[outcome] = \
                    [0] * (len(RECONCILE_BUCKETS) + 1)
            for i, le in enumerate(RECONCILE_BUCKETS):
                if seconds <= le:
                    counts[i] += 1
            counts[-1] += 1  # +Inf
            self._hist_sum[outcome] = \
                self._hist_sum.get(outcome, 0.0) + seconds
            self._hist_count[outcome] = self._hist_count.get(outcome, 0) + 1

    def process_one(self, key: Tuple[str, str]) -> bool:
        """Run one reconcile; enqueue follow-ups per the Result contract."""
        with self._mlock:
            self.metrics["reconcile_total"] += 1
        outcome = "error"
        t0 = time.monotonic()
        try:
            # duration observed in finally: an errored reconcile is usually
            # the SLOW one, and excluding it would flatline the latency
            # metric exactly when it matters (controller-runtime's histogram
            # likewise observes every outcome)
            with tracer().span("reconcile", controller=self.name,
                               namespace=key[0], obj=key[1]) as sp:
                try:
                    result = self.reconcile(*key)
                except Exception:
                    sp.set(outcome="error")
                    raise
                if result is not None and getattr(result, "requeue", False):
                    outcome = "requeue"
                    sp.set(outcome="requeue")
                elif result is not None and getattr(result, "requeue_after",
                                                    None):
                    outcome = "requeue_after"
                    sp.set(outcome="requeue_after",
                           delay_s=result.requeue_after)
                else:
                    outcome = "done"
                    sp.set(outcome="done")
        except Exception:
            log.exception("reconcile %s/%s panicked", *key)
            with self._mlock:
                self.metrics["reconcile_errors_total"] += 1
                n = self._failures.get(key, 0) + 1
                self._failures[key] = n
            tracer().event("reconcile_backoff", controller=self.name,
                           namespace=key[0], obj=key[1], failures=n)
            # NEVER drop a failing key: this controller is level-triggered,
            # so if the world stays quiet no watch event will ever
            # re-enqueue it and the object wedges forever (the chaos
            # harness caught exactly that under an 8+ burst of injected
            # 5xxs). controller-runtime's rate limiter has the same
            # retry-forever semantics; max_retries only caps the backoff
            # exponent, not the attempt count.
            self.queue.add_after(
                key, min(0.1 * (2 ** min(n, self.max_retries)), 30.0),
                lane=self.queue.active_lane(key))
            return True
        finally:
            self._observe(outcome, time.monotonic() - t0)
        with self._mlock:
            self._failures.pop(key, None)
        if result is not None and getattr(result, "requeue", False):
            with self._mlock:
                self.metrics["requeue_total"] += 1
            self.queue.add(key, lane=self.queue.active_lane(key))
        elif result is not None and getattr(result, "requeue_after", None):
            with self._mlock:
                self.metrics["requeue_total"] += 1
            self.queue.add_after(key, result.requeue_after,
                                 lane=self.queue.active_lane(key))
        return True

    def snapshot(self) -> Dict[str, object]:
        """Locked copy of every counter the /metrics scrape renders."""
        with self._mlock:
            return {
                "metrics": dict(self.metrics),
                "duration_sum": self.duration_sum,
                "duration_count": self.duration_count,
                "hist": {o: list(c) for o, c in self._hist.items()},
                "hist_sum": dict(self._hist_sum),
                "hist_count": dict(self._hist_count),
            }


class Manager:
    """Hosts controllers; wires watches; optional leader election."""

    def __init__(self, client: KubeClient, leader_election: bool = False,
                 leader_identity: str = "", namespace: Optional[str] = None,
                 lease_name: str = "tpujob-operator-lock",
                 lease_duration: float = 15.0, renew_deadline: float = 10.0,
                 retry_period: float = 2.0,
                 on_lost_lease: Optional[Callable[[], None]] = None,
                 cache=None, reconcile_workers: int = 1):
        self.client = client
        self.namespace = namespace
        # worker threads PER CONTROLLER in threaded mode: the workqueue's
        # per-key exclusivity (pop → active → done) is what makes N > 1
        # safe — a key is never reconciled by two workers at once
        self.reconcile_workers = max(1, int(reconcile_workers))
        if cache is None and not isinstance(client, FakeKubeClient):
            from .informer import CachedKubeClient, InformerCache

            if isinstance(client, CachedKubeClient):
                cache = client.cache
            else:
                # real client, no cache given: controllers still need the
                # shared watch plumbing (the only watch-loop implementation)
                cache = InformerCache(client, namespace)
        self.cache = cache
        self.controllers: List[Controller] = []
        self.leader_election = leader_election
        if not leader_identity:
            # client-go's default identity is hostname + "_" + uuid: unique
            # across processes AND restarts. id(self) would be neither — two
            # identically-started replicas can land the same heap address,
            # and a colliding standby would "renew" the live leader's lease.
            import socket
            import uuid

            leader_identity = "%s_%s" % (
                socket.gethostname(), uuid.uuid4().hex[:12])
        self.leader_identity = leader_identity
        self.elector = None
        if leader_election:
            from .leader import LeaderElector

            self.elector = LeaderElector(
                client, identity=self.leader_identity, lease_name=lease_name,
                namespace=namespace or "default",
                lease_duration=lease_duration, renew_deadline=renew_deadline,
                retry_period=retry_period,
            )
        self.on_lost_lease = on_lost_lease
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        # extra exposition blocks (chaos fault counters, subsystem gauges):
        # each provider returns fully formatted text-exposition lines
        self._metric_providers: List[Callable[[], str]] = []

    def add_controller(
        self,
        name: str,
        reconcile: Callable,
        for_kind: str,
        owns: Optional[List[str]] = None,
        owner_api_version: str = "",
        owner_kind: str = "",
        lane_for: Optional[Callable[[str, dict], str]] = None,
    ) -> Controller:
        ctrl = Controller(name, reconcile, lane_for=lane_for)
        ctrl.for_kind = for_kind
        ctrl.watch(self.client, for_kind, self_key_mapper, self.namespace,
                   cache=self.cache)
        for kind in owns or []:
            ctrl.watch(
                self.client, kind,
                owner_key_mapper(owner_api_version, owner_kind), self.namespace,
                cache=self.cache,
            )
        self.controllers.append(ctrl)
        return ctrl

    # -- synchronous mode (tests) --------------------------------------

    def drain(self, include_deferred: bool = True, max_iters: int = 1000,
              workers: int = 1) -> int:
        """Process queued work to quiescence on this thread.

        Deferred (requeue-after) items are promoted once per drain — the test
        clock "ticks" once per call. Returns number of reconciles run.

        ``workers`` > 1 models the sharded parallel queue DETERMINISTICALLY:
        up to ``workers`` keys are popped before any is processed, so the
        per-key exclusivity machinery (active set, dirty re-adds, lane
        picks with keys in flight) runs exactly as it would under real
        threads, while processing order stays reproducible — what the
        chaos scenarios need for their seed-replay fingerprint. Real
        thread parallelism is ``start()`` with ``reconcile_workers``.
        """
        ran = 0
        for ctrl in self.controllers:
            if include_deferred:
                ctrl.queue.promote_due(force=True)
        progress = True
        while progress and ran < max_iters:
            progress = False
            for ctrl in self.controllers:
                batch = []
                for _ in range(max(1, workers)):
                    key = ctrl.queue.pop()
                    if key is None:
                        break
                    batch.append(key)
                for key in batch:
                    try:
                        ctrl.process_one(key)
                    finally:
                        ctrl.queue.done(key)
                    ran += 1
                    progress = True
        return ran

    def enqueue_all(self) -> None:
        """Seed every controller's queue with its primary objects — the
        initial-list replay a fresh informer performs on startup (and what a
        new leader does after failover so jobs mutated during the previous
        leader's reign converge)."""
        for ctrl in self.controllers:
            if not ctrl.for_kind:
                continue
            try:
                objs = self.client.list(ctrl.for_kind, self.namespace)
            except Exception as e:
                log.warning("enqueue_all: list %s failed: %s", ctrl.for_kind, e)
                continue
            for obj in objs:
                key = self_key_mapper(obj)
                if key[1]:
                    ctrl.queue.add(key)

    # -- threaded mode (production) ------------------------------------

    def start(self, seed_queues: bool = True) -> None:
        """Blocks on leadership (if enabled), then starts workers. On a lost
        lease all workers halt and ``on_lost_lease`` fires (reference:
        controller-runtime exits the binary; manager.py wires that).
        ``seed_queues=False`` skips the initial-list replay — for harnesses
        that measure the drain of a hand-built backlog; production always
        seeds.

        A cleanly ``stop()``-ed manager may be ``start()``-ed again (the
        control-plane perf harness re-measures one fleet at several
        ``reconcile_workers`` settings); the restart gate requires every
        prior worker to have exited first, so a deposed-leader stop can
        never be silently resumed while old workers still run."""
        if self._stop.is_set():
            stuck = [t.name for t in self._threads if t.is_alive()]
            if stuck:
                # starting now would spawn workers that see _stop and exit
                # instantly — an operator that LOOKS started but reconciles
                # nothing. Fail loudly instead.
                raise RuntimeError(
                    "Manager.start() after an incomplete stop(): worker(s) "
                    "still running: %s" % ", ".join(stuck))
            if not self._threads:
                # stop requested before the first start (e.g. a SIGTERM
                # landing between signal-handler registration and start()):
                # honor it — clearing the flag here would discard the
                # shutdown request and run until a second signal
                return
            # prior workers existed and all exited: a cleanly stop()-ed
            # manager being start()-ed again (the perf harness does this)
            self._stop.clear()
            self._threads = []
        if self.cache is not None:
            self.cache.start()  # idempotent; may already serve coordination
            # workers must NOT start on an unsynced cache: a reconciler that
            # reads an empty Pod informer re-creates every child. Block like
            # controller-runtime does, retrying until sync or shutdown.
            while not self.cache.wait_for_sync(timeout=30.0):
                if self._stop.is_set():
                    return
                log.warning("informer cache still not synced after 30s; "
                            "waiting before starting workers")
        if self.elector is not None:
            if not self.elector.acquire(self._stop):
                return  # stopped before winning
            t = threading.Thread(
                target=self.elector.run_renewal,
                args=(self._stop, self._lost_leadership),
                daemon=True, name="lease-renewal",
            )
            t.start()
            self._threads.append(t)
        # initial-list replay for EVERY start path (not just failover):
        # objects that synced into the cache before handlers registered
        # produced no enqueue, and the rv-aware resync intentionally
        # re-emits nothing for unchanged objects — so seed the queues here
        if seed_queues:
            self.enqueue_all()
        for ctrl in self.controllers:
            for i in range(self.reconcile_workers):
                t = threading.Thread(
                    target=self._worker, args=(ctrl,), daemon=True,
                    name="ctrl-%s-%d" % (ctrl.name, i),
                )
                t.start()
                self._threads.append(t)

    def request_stop(self) -> None:
        """Signal-handler-safe stop: unblocks lease acquisition, renewal and
        workers without joining threads (stop() does the joining)."""
        self._stop.set()

    def _lost_leadership(self) -> None:
        self._stop.set()  # halt all workers: we no longer own the objects
        if self.on_lost_lease is not None:
            self.on_lost_lease()

    def _worker(self, ctrl: Controller) -> None:
        while not self._stop.is_set():
            ctrl.queue.promote_due()
            key = ctrl.queue.pop(timeout=0.2)
            if key is None:
                continue
            # re-check after the blocking pop: a deposed leader must not
            # reconcile work that arrived while it was being stopped
            if self._stop.is_set():
                # parks in dirty (same lane it held); done() requeues it
                ctrl.queue.add(key, lane=ctrl.queue.active_lane(key))
                ctrl.queue.done(key)
                return
            try:
                ctrl.process_one(key)
            finally:
                # release per-key exclusivity LAST: adds that raced this
                # reconcile are parked dirty and requeue here
                ctrl.queue.done(key)

    def stop(self, release_lease: bool = True) -> None:
        """Graceful shutdown. ``release_lease=False`` models a crash (the
        lease is left to expire; used by failover tests)."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)
        if self.elector is not None and release_lease:
            self.elector.release()

    # -- metrics -------------------------------------------------------

    def add_metrics_provider(self, provider: Callable[[], str]) -> None:
        """Register an extra exposition block (e.g. chaos fault counters).
        The provider returns complete text-exposition lines, HELP/TYPE
        headers included, with no trailing newline."""
        self._metric_providers.append(provider)

    # metric family -> (help, type). Families are emitted header-first with
    # every controller's sample under ONE header, as real Prometheus
    # scrapers require (a repeated header is a parse error).
    _FAMILIES = [
        ("tpujob_reconcile_total",
         "Reconcile invocations.", "counter"),
        ("tpujob_reconcile_errors_total",
         "Reconciles that raised (retried with backoff).", "counter"),
        ("tpujob_requeue_total",
         "Reconcile results that requested a requeue.", "counter"),
        ("tpujob_reconcile_duration_seconds",
         "Reconcile latency (all outcomes).", "summary"),
        ("tpujob_workqueue_depth",
         "Keys ready to be processed.", "gauge"),
        ("tpujob_workqueue_deferred",
         "Keys parked behind a requeue-after delay.", "gauge"),
        ("tpujob_workqueue_lane_depth",
         "Keys ready per priority lane (high = drains/deletes, "
         "normal = routine resyncs).", "gauge"),
        ("tpujob_workqueue_active",
         "Keys currently held exclusively by a reconcile worker.", "gauge"),
        ("tpujob_reconcile_seconds",
         "Reconcile latency by outcome (done | requeue | requeue_after "
         "| error).", "histogram"),
        ("tpujob_workqueue_backoff_seconds",
         "Max error-requeue backoff currently armed by the reconciler.",
         "gauge"),
    ]

    def metrics_text(self) -> str:
        """Prometheus text exposition of controller metrics
        (reference: controller-runtime /metrics on :8080).

        Hardened: label values are escaped, and provider blocks are MERGED
        family-wise — when two providers emit the same family, the samples
        are grouped under one ``# HELP``/``# TYPE`` pair (a repeated
        header, or a family's samples split across the scrape, is a parse
        error to real Prometheus scrapers)."""
        # family -> {"help": str|None, "type": str|None, "samples": [...]}
        blocks: "OrderedDict[str, Dict[str, object]]" = OrderedDict()

        def block(fam: str) -> Dict[str, object]:
            b = blocks.get(fam)
            if b is None:
                b = blocks[fam] = {"help": None, "type": None, "samples": []}
            return b

        for name, help_text, mtype in self._FAMILIES:
            b = block(name)
            b["help"], b["type"] = help_text, mtype
        for ctrl in self.controllers:
            label = 'controller="%s"' % escape_label_value(ctrl.name)
            # snapshot() holds the controller's metrics lock: with
            # reconcile_workers > 1 the scrape races live reconciles, and
            # unlocked reads could render a torn histogram
            snap = ctrl.snapshot()
            for metric, value in sorted(snap["metrics"].items()):
                fam = "tpujob_%s" % metric
                # controllers may grow ad-hoc counters; emit them untyped
                # rather than crashing the /metrics endpoint
                if blocks.get(fam) is None:
                    block(fam)["type"] = "untyped"
                blocks[fam]["samples"].append(
                    'tpujob_%s{%s} %d' % (metric, label, value))
            b = block("tpujob_reconcile_duration_seconds")
            b["samples"].append(
                'tpujob_reconcile_duration_seconds_sum{%s} %.6f'
                % (label, snap["duration_sum"]))
            b["samples"].append(
                'tpujob_reconcile_duration_seconds_count{%s} %d'
                % (label, snap["duration_count"]))
            b = block("tpujob_reconcile_seconds")
            for outcome in sorted(snap["hist"]):
                counts = snap["hist"][outcome]
                olabel = '%s,outcome="%s"' % (label, outcome)
                for i, le in enumerate(RECONCILE_BUCKETS):
                    b["samples"].append(
                        'tpujob_reconcile_seconds_bucket{%s,le="%s"} %d'
                        % (olabel, ("%g" % le), counts[i]))
                b["samples"].append(
                    'tpujob_reconcile_seconds_bucket{%s,le="+Inf"} %d'
                    % (olabel, counts[-1]))
                b["samples"].append(
                    'tpujob_reconcile_seconds_sum{%s} %.6f'
                    % (olabel, snap["hist_sum"][outcome]))
                b["samples"].append(
                    'tpujob_reconcile_seconds_count{%s} %d'
                    % (olabel, snap["hist_count"][outcome]))
            block("tpujob_workqueue_depth")["samples"].append(
                'tpujob_workqueue_depth{%s} %d' % (label, len(ctrl.queue)))
            block("tpujob_workqueue_deferred")["samples"].append(
                'tpujob_workqueue_deferred{%s} %d'
                % (label, ctrl.queue.pending_deferred))
            for lane in LANES:
                block("tpujob_workqueue_lane_depth")["samples"].append(
                    'tpujob_workqueue_lane_depth{%s,lane="%s"} %d'
                    % (label, lane, ctrl.queue.depth(lane)))
            block("tpujob_workqueue_active")["samples"].append(
                'tpujob_workqueue_active{%s} %d'
                % (label, ctrl.queue.active))
            if ctrl.backoff_provider is not None:
                block("tpujob_workqueue_backoff_seconds")["samples"].append(
                    'tpujob_workqueue_backoff_seconds{%s} %.3f'
                    % (label, ctrl.backoff_provider()))
        for provider in self._metric_providers:
            self._merge_provider_block(blocks, block, provider() or "")
        lines: List[str] = []
        for fam, b in blocks.items():
            if not b["samples"]:
                continue
            if b["help"]:
                lines.append("# HELP %s %s" % (fam, b["help"]))
            lines.append("# TYPE %s %s" % (fam, b["type"] or "untyped"))
            lines.extend(b["samples"])
        return "\n".join(lines) + "\n"

    @staticmethod
    def _merge_provider_block(blocks, block, text: str) -> None:
        """Fold one provider's preformatted exposition lines into the
        family map: first HELP/TYPE wins (duplicates dropped), samples
        append to their family so grouping survives multiple providers
        emitting the same family."""
        current = None
        for line in text.splitlines():
            if not line.strip():
                continue
            if line.startswith("# HELP "):
                parts = line.split(" ", 3)
                fam = parts[2] if len(parts) > 2 else ""
                if fam:
                    b = block(fam)
                    if b["help"] is None:
                        b["help"] = parts[3] if len(parts) > 3 else ""
                    current = fam
                continue
            if line.startswith("# TYPE "):
                parts = line.split(" ")
                fam = parts[2] if len(parts) > 2 else ""
                if fam:
                    b = block(fam)
                    if b["type"] is None and len(parts) > 3:
                        b["type"] = parts[3]
                    current = fam
                continue
            if line.startswith("#"):
                continue
            metric = line.split("{", 1)[0].split(" ", 1)[0]
            fam = fold_suffix(
                metric,
                lambda n: ((blocks[n]["type"] or "untyped")
                           if n in blocks else None))
            if fam is None:
                fam = current if current is not None else metric
            block(fam)["samples"].append(line)
