"""Request queue + iteration-level (continuous) batching scheduler.

Static batching pays a convoy tax: a batch runs until its LONGEST
sequence finishes, so short requests idle behind long ones and new
arrivals wait a full batch. Continuous batching (the Orca design)
schedules at token granularity instead: every decode iteration the
scheduler admits queued requests into the in-flight batch the moment a
slot (and KV blocks) free up, so the batch composition changes mid-
flight and device utilization tracks offered load, not batch shape.

The pieces:

* :class:`Request` — one user call: prompt ids, a token budget, and the
  timestamps the latency accounting derives ttft/tpot from;
* :class:`RequestQueue` — bounded admission with an explicit shed
  posture (``reject_new``: arrivals bounce when full — backpressure to
  the client; ``drop_oldest``: the stalest queued request is shed to
  admit the new one — freshness over fairness). Every shed is COUNTED:
  the serving_brownout invariant is that no request vanishes without a
  shed counter recording why;
* :class:`ContinuousBatcher` — the iteration loop: admit up to
  ``max_batch`` in FIFO order, run one engine step over the active set,
  retire finished sequences, account queue/prefill/decode seconds into
  :class:`.metrics.ServeMetrics`. The engine step is INJECTED (a
  callable), so the chaos scenario drives the identical scheduler with a
  deterministic fake step while production wires
  :meth:`.engine.ServingEngine.step_fn`.

The batcher times itself in an accumulator of its own
(``ContinuousBatcher.times``, a :class:`..utils.trace.StageTimes`
exported under ``"sched"``; always on, bounded, as the engine's):

========================  ==============================================
``sched.step``            one iteration, whole (``active``, ``admitted``,
                          ``retired``). Its span id is the iteration's:
                          everything below and the engine's
                          ``serve.admit`` / ``serve.step`` carry it
``sched.admit``           one request from the queue's pop to its slot or
                          its way back (``request_id``, ``outcome`` =
                          ``admitted`` | ``deferred`` | ``error``,
                          ``depth``: the queue left behind)
``sched.queue_wait``      ``t_admitted - t_arrival`` on the batcher's
                          clock, banked where the request leaves the
                          queue (``request_id``)
``sched.retire``          ``on_retire`` + ``metrics.observe_request`` of
                          one finished request (``request_id``,
                          ``tokens``)
``sched.between``         from the previous iteration's return to this
                          one's entry, where that return left sequences
                          in flight (``in_flight``): the CALLER's time,
                          which every live row's token gap holds
``sched.empty``           the same stretch where nothing was in flight:
                          the replica stood idle for want of requests
========================  ==============================================

``sched.between`` / ``sched.empty`` plus the ``sched.step`` that follows
tile the time from one return to the next with no remainder: the stamps
are the ``sched.step`` spans' own.

Thread safety: queue and batcher state are each owned by their ``_lock``
(declared in analysis/guards.py); the engine step itself runs outside
the batcher lock — it is model compute, not shared state.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# the canonical vocabulary lives in the API layer so the webhook/CRD can
# validate serving specs without importing the jax-backed data plane
from ..api.types import SERVING_SHED_POLICIES as SHED_POLICIES
from ..utils.trace import StageTimes, export_stage_times


@dataclass
class Request:
    """One serving call. Timestamps are filled in by the queue/batcher
    (monotonic clock seconds) and feed the ttft/tpot accounting."""

    request_id: str
    prompt: Sequence[int]
    max_new_tokens: int = 16
    t_arrival: float = 0.0
    t_admitted: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    generated: List[int] = field(default_factory=list)

    def ttft(self) -> float:
        return self.t_first_token - self.t_arrival

    def tpot(self) -> float:
        """Steady decode cadence: seconds per output token AFTER the
        first (the first token's latency is ttft's job)."""
        n = len(self.generated)
        if n <= 1:
            return 0.0
        return (self.t_done - self.t_first_token) / (n - 1)


class RequestQueue:
    """Bounded FIFO admission queue with a counted shed posture."""

    def __init__(self, capacity: int, shed_policy: str = "reject_new",
                 clock: Optional[Callable[[], float]] = None) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        if shed_policy not in SHED_POLICIES:
            raise ValueError("shed_policy must be one of %s, got %r"
                             % ("|".join(SHED_POLICIES), shed_policy))
        self.capacity = capacity
        self.shed_policy = shed_policy
        self._clock = clock or time.monotonic
        self._lock = threading.Lock()
        self._q: List[Request] = []
        self._counts: Dict[str, int] = {"submitted": 0, "admitted": 0,
                                        "shed_reject_new": 0,
                                        "shed_drop_oldest": 0}

    def submit(self, req: Request) -> Tuple[bool, Optional[Request]]:
        """Returns ``(accepted, shed)``: ``accepted`` says whether REQ
        got in; ``shed`` is the request dropped to make room (only under
        ``drop_oldest`` — it is the caller's to account/notify)."""
        req.t_arrival = self._clock()
        with self._lock:
            self._counts["submitted"] += 1
            if len(self._q) < self.capacity:
                self._q.append(req)
                return True, None
            if self.shed_policy == "reject_new":
                self._counts["shed_reject_new"] += 1
                return False, None
            shed = self._q.pop(0)
            self._counts["shed_drop_oldest"] += 1
            self._q.append(req)
            return True, shed

    def pop(self) -> Optional[Request]:
        with self._lock:
            if not self._q:
                return None
            req = self._q.pop(0)
            self._counts["admitted"] += 1
            return req

    def requeue_front(self, reqs: Sequence[Request]) -> List[Request]:
        """Preemption path: put in-flight requests BACK at the head (they
        were admitted first; FIFO order is preserved). Requests that no
        longer fit are returned to the caller to shed — never silently
        dropped."""
        overflow: List[Request] = []
        with self._lock:
            for req in reversed(list(reqs)):
                if len(self._q) < self.capacity:
                    self._q.insert(0, req)
                else:
                    overflow.append(req)
        return overflow

    def depth(self) -> int:
        with self._lock:
            return len(self._q)

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


class ContinuousBatcher:
    """Iteration-level scheduler over an injected engine step.

    ``engine_step(active) -> [(token_id, done), ...]`` runs ONE decode
    iteration for the current active set (admission implies the prefill
    for that request happens inside its first step — the engine decides
    how; the batcher only accounts it). ``on_admit`` / ``on_retire``
    hooks let the engine allocate/free KV pages in lockstep with
    scheduling decisions.
    """

    def __init__(self, queue: RequestQueue, max_batch: int,
                 clock: Optional[Callable[[], float]] = None,
                 metrics: Optional[Any] = None,
                 on_admit: Optional[Callable[[Request], bool]] = None,
                 on_retire: Optional[Callable[[Request], None]] = None,
                 label: str = "sched") -> None:
        if max_batch <= 0:
            raise ValueError("max_batch must be positive")
        self.queue = queue
        self.max_batch = max_batch
        self.metrics = metrics
        self.on_admit = on_admit
        self.on_retire = on_retire
        self._clock = clock or time.monotonic
        self._lock = threading.Lock()
        self._active: List[Request] = []
        self._counts: Dict[str, int] = {"completed": 0, "admit_deferred": 0,
                                        "preempted": 0, "iterations": 0}
        #: where the last iteration (or a preemption since) ended on
        #: ``time.perf_counter()`` and how many sequences it left in
        #: flight; None until the first iteration has returned
        self._left: Optional[Tuple[float, int]] = None
        #: this scheduler's spans (the module's docstring); hand it to
        #: ``ServeMetrics(stages=...)`` beside the engine's
        self.times = export_stage_times(label, StageTimes())

    # -- scheduling ------------------------------------------------------

    def _admit(self, span: Optional[int] = None) -> int:
        """Fill free slots from the queue head; returns how many
        requests took one. ``on_admit`` returning False (KV pool
        exhausted) defers the request — it goes back to the FRONT so
        admission order is preserved."""
        admitted = 0
        while True:
            with self._lock:
                if len(self._active) >= self.max_batch:
                    return admitted
            req = self.queue.pop()
            if req is None:
                return admitted
            try:
                # the outcome stands at "error" until the hook has
                # answered: an exception banks it on its way out
                with self.times.timed(
                        "sched.admit", request_id=req.request_id,
                        outcome="error", depth=self.queue.depth()) as timed:
                    ok = self.on_admit is None or self.on_admit(req)
                    timed.attrs["outcome"] = "admitted" if ok else "deferred"
                    if ok:
                        req.t_admitted = self._clock()
                        with self._lock:
                            self._active.append(req)
                    else:
                        self.queue.requeue_front([req])
                        with self._lock:
                            self._counts["admit_deferred"] += 1
            except BaseException:
                # the popped slot must not vanish with the exception:
                # retire it as an engine error so request conservation
                # holds, then surface the failure
                if self.metrics is not None:
                    self.metrics.observe_request(req, outcome="error")
                with self._lock:
                    self._counts["admit_error"] = (
                        self._counts.get("admit_error", 0) + 1)
                raise
            if not ok:
                return admitted
            admitted += 1
            self.times.add("sched.queue_wait",
                           req.t_admitted - req.t_arrival, span=span,
                           request_id=req.request_id)

    def step(self, engine_step: Callable[[List[Request]],
                                         List[Tuple[int, bool]]]) -> int:
        """One scheduler iteration: admit, run the engine step, retire.
        Returns how many sequences are still in flight."""
        timed = self.times.timed("sched.step", active=0, admitted=0,
                                 retired=0)
        try:
            with timed:
                with self._lock:
                    left = self._left
                self._bank_stretch(left, timed.t0, timed.span)
                return self._iterate(engine_step, timed)
        finally:
            with self._lock:
                self._left = (timed.t0 + timed.seconds, len(self._active))

    def _bank_stretch(self, left: Optional[Tuple[float, int]], until: float,
                      span: Optional[int] = None) -> None:
        """What lay between the last return (``left``) and ``until``:
        the caller's time while sequences waited, or an empty
        replica's."""
        if left is None:
            return
        since, in_flight = left
        if in_flight:
            self.times.add("sched.between", until - since, start=since,
                           span=span, in_flight=in_flight)
        else:
            self.times.add("sched.empty", until - since, start=since,
                           span=span)

    def _iterate(self, engine_step: Callable[[List[Request]],
                                             List[Tuple[int, bool]]],
                 timed: Any) -> int:
        timed.attrs["admitted"] = self._admit(timed.span)
        with self._lock:
            active = list(self._active)
            self._counts["iterations"] += 1
        if not active:
            return 0
        timed.attrs["active"] = len(active)
        results = engine_step(active)
        if len(results) != len(active):
            raise RuntimeError(
                "engine step returned %d results for %d sequences"
                % (len(results), len(active)))
        now = self._clock()
        finished: List[Request] = []
        for req, (token, done) in zip(active, results):
            first = not req.generated
            req.generated.append(int(token))
            if first:
                req.t_first_token = now
            if done or len(req.generated) >= req.max_new_tokens:
                req.t_done = now
                finished.append(req)
        with self._lock:
            for req in finished:
                self._active.remove(req)
                self._counts["completed"] += 1
        timed.attrs["retired"] = len(finished)
        for req in finished:
            with self.times.timed("sched.retire", request_id=req.request_id,
                                  tokens=len(req.generated)):
                if self.on_retire is not None:
                    self.on_retire(req)
                if self.metrics is not None:
                    self.metrics.observe_request(req, outcome="ok")
        with self._lock:
            return len(self._active)

    # -- disruption ------------------------------------------------------

    def preempt(self) -> List[Request]:
        """A preemption hit this replica: every in-flight sequence is
        pulled out of the batch (its partial generation is discarded —
        the paged cache dies with the replica) and handed to the caller
        to requeue or shed. Nothing is silently lost."""
        now = time.perf_counter()
        with self._lock:
            victims = list(self._active)
            self._active = []
            self._counts["preempted"] += len(victims)
            left, self._left = self._left, (now, 0)
        # the stretch since the last return ends here, and what follows
        # it is an empty replica's
        self._bank_stretch(left, now)
        for req in victims:
            req.generated = []
            req.t_admitted = req.t_first_token = req.t_done = 0.0
            if self.on_retire is not None:
                self.on_retire(req)
        return victims

    def drain(self,
              engine_step: Callable[[List[Request]],
                                    List[Tuple[int, bool]]],
              max_iterations: int = 10000) -> int:
        """Run to empty WITHOUT admitting new work (graceful shutdown):
        returns iterations used. Raises if the batch does not empty —
        a hung drain must fail loudly, not spin."""
        with self._lock:
            # closing the admission valve = pretending the batch is full
            saved, self.max_batch = self.max_batch, 0
        try:
            for i in range(max_iterations):
                with self._lock:
                    if not self._active:
                        return i
                self.step(engine_step)
            raise RuntimeError("drain did not empty in %d iterations"
                               % max_iterations)
        finally:
            with self._lock:
                self.max_batch = saved

    # -- introspection ---------------------------------------------------

    def in_flight(self) -> int:
        with self._lock:
            return len(self._active)

    def active_ids(self) -> List[str]:
        with self._lock:
            return [r.request_id for r in self._active]

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)
