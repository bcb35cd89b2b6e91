"""Input pipeline: background-producer host→device feeding.

The reference delegates data loading to the training containers; a TPU-first
framework must own it because input starvation is the easiest way to idle an
MXU. Design (the asynchronous host pipeline):

* a `Source` is any iterator of numpy batches (dict pytrees);
* `ShardedLoader` runs a dedicated producer thread that pulls from the
  source, slices each global batch to this process's data-parallel shard,
  issues async `jax.device_put`s, and feeds a bounded queue — so batch
  construction AND the H2D copy for step N+1 overlap step N's compute.
  `prefetch=0` degenerates to the old inline (synchronous) behavior;
* source exceptions are re-raised on the consumer thread, and `close()`
  (also a context manager / GC hook) shuts the producer down without
  leaking the thread;
* `job_window_source` + `stack_window` assemble the `[K, ...]` windows the
  `steps_per_call` fused path consumes, host-side (`np.asarray` fast path —
  no device round trip for host-resident batches), so the next window is
  built while the current one computes;
* `DeferredMetrics` starts the D2H copy for a metrics pytree at step N and
  resolves it at the next log boundary, so logging never stalls dispatch.

Per-stage host timings (batch-build / enqueue-wait / dequeue-wait /
device-put) are recorded into a :class:`~.utils.trace.StageTimes` when one
is passed, and reported by ``run_training`` (``result["host_stages"]``).
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
import time
import weakref
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

from .utils.trace import StageTimes

log = logging.getLogger("tpujob.data")


def synthetic_source(make_batch: Callable[[int], Any]) -> Iterator[Any]:
    """Infinite source from a step-indexed batch factory (numpy or jax)."""
    step = 0
    while True:
        yield make_batch(step)
        step += 1


def process_shard(batch, process_index: int, process_count: int):
    """Slice the global batch to this process's contiguous shard
    (multi-host data parallelism: host i feeds rows [i*b/H, (i+1)*b/H))."""
    if process_count == 1:
        return batch

    def slice_leaf(leaf):
        n = leaf.shape[0]
        if n % process_count:
            raise ValueError(
                "global batch dim %d does not divide across %d processes"
                % (n, process_count)
            )
        per = n // process_count
        return leaf[process_index * per:(process_index + 1) * per]

    import jax

    return jax.tree_util.tree_map(slice_leaf, batch)


def stack_window(batches, force_host: bool = False):
    """Stack K per-step batches into one ``[K, ...]`` window.

    Host-resident leaves stack via ``np.stack`` with NO device round trip
    (``np.asarray`` is a no-copy view for numpy inputs); device-resident
    leaves stack on device via ``jnp.stack`` unless ``force_host`` — the
    multi-host globalization wrapper consumes host windows, and a device
    stack there would be read straight back for re-sharding.
    """
    import jax

    def stack(*leaves):
        if not force_host and all(isinstance(l, jax.Array) for l in leaves):
            import jax.numpy as jnp

            return jnp.stack(leaves)
        return np.stack([np.asarray(l) for l in leaves])

    return jax.tree_util.tree_map(stack, *batches)


def job_window_source(make_batch, rng, start_step: int, total_steps: int,
                      steps_per_call: int = 1,
                      force_host_windows: bool = False) -> Iterator[Any]:
    """Adapt a ``TrainJob.make_batch`` into a loader source.

    Yields, in the exact order ``run_training`` consumes them: full
    ``[K, ...]`` windows (assembled via :func:`stack_window`) while at
    least K steps remain, then single per-step batches for the < K tail
    (and always singles when K == 1). The rng folding matches the old
    inline loop exactly — ``fold_in(rng, step)`` per step — so the
    pipelined path trains bit-identically to loop-inlined batch building.
    """
    import jax

    K = max(1, steps_per_call)
    step = start_step
    while step < total_steps:
        span = min(K, total_steps - step)
        if span == K and K > 1:
            window = [make_batch(jax.random.fold_in(rng, s), s)
                      for s in range(step, step + K)]
            yield stack_window(window, force_host=force_host_windows)
        else:
            for s in range(step, step + span):
                yield make_batch(jax.random.fold_in(rng, s), s)
        step += span


def _producer_main(loader_ref):
    """Producer thread body, module-level on purpose: between items it
    holds only the weakref, so dropping the last user reference to a
    loader lets GC collect it (running __del__ → close()) instead of the
    thread pinning it alive forever."""
    while True:
        loader = loader_ref()
        if loader is None:
            return
        try:
            status = loader._produce_step()
        except BaseException:  # defensive: _produce_step guards itself
            return
        if status == "done":
            return
        del loader


class ShardedLoader:
    """Background producer: shards per-process, places on device, prefetches.

    ``prefetch > 0``: a dedicated thread pulls from the source, shards,
    places, and feeds a bounded queue of that depth — batch construction
    and the (async) H2D issue overlap the consumer's compute, and a full
    queue backpressures the producer so at most ``prefetch + 1`` batches
    are ever materialized ahead of the consumer. Source exceptions are
    re-raised on the consumer thread at the point of ``next()``;
    :meth:`close` (or GC, or the context-manager exit) stops the producer
    without leaking the thread.

    ``prefetch=0``: fully inline — ``next()`` pulls, shards, and places
    synchronously (the comparison baseline, and the zero-thread option).

    ``batch_sharding`` may be a pytree of shardings, or a callable
    ``payload -> pytree`` for sources whose payload shape varies (e.g.
    ``job_window_source`` mixing [K, ...] windows and single-step tails).
    ``place=False`` skips device placement entirely (multi-host runners
    keep batches host-resident for the per-process globalization wrapper).
    """

    def __init__(self, source: Iterator[Any], batch_sharding=None,
                 prefetch: int = 2, place: bool = True,
                 timings: Optional[StageTimes] = None,
                 fault_hook: Optional[Callable[[str], None]] = None):
        import jax

        self._source = source
        self._sharding = batch_sharding
        self._prefetch = max(0, int(prefetch))
        self._do_place = place
        self._timings = timings
        # chaos hook: called with the stage name ("batch_build") right
        # before each source pull, ON the producer thread — sleep inside it
        # to inject a stall, raise to inject a transient source error (it
        # re-raises on the consumer exactly like a source exception)
        self._fault_hook = fault_hook
        self._proc = jax.process_index()
        self._nproc = jax.process_count()
        self._exhausted = False
        self._thread = None
        if self._prefetch:
            self._queue: "queue.Queue" = queue.Queue(maxsize=self._prefetch)
            self._stop = threading.Event()
            self._staged = None   # item built but not yet enqueued
            self._final = False   # staged item is the end/error sentinel
            self._enqueue_blocked = 0.0  # put() wait carried across retries
            # the thread holds only a WEAKREF between items: an abandoned
            # loader (never closed) stays collectable, its __del__ runs
            # close(), and the producer exits instead of leaking forever
            self._thread = threading.Thread(
                target=_producer_main, args=(weakref.ref(self),),
                name="sharded-loader", daemon=True)
            self._thread.start()

    def _timed(self, stage: str):
        if self._timings is None:
            return contextlib.nullcontext()
        return self._timings.timed(stage)

    def _place(self, batch):
        import jax

        if not self._do_place:
            return batch
        sharding = (self._sharding(batch) if callable(self._sharding)
                    else self._sharding)
        with self._timed("device_put"):
            if sharding is not None:
                if self._nproc > 1:
                    # multi-host: each host holds only its rows; assemble the
                    # global array from the process-local shard so the result's
                    # global shape matches what the jitted step was traced with
                    local = process_shard(batch, self._proc, self._nproc)
                    return jax.tree_util.tree_map(
                        lambda leaf, sh:
                            jax.make_array_from_process_local_data(sh, leaf),
                        local, sharding,
                    )
                return jax.tree_util.tree_map(
                    lambda leaf, sh: jax.device_put(leaf, sh),
                    batch, sharding,
                )
            batch = process_shard(batch, self._proc, self._nproc)
            return jax.tree_util.tree_map(jax.device_put, batch)

    # ---- producer thread ---------------------------------------------------

    def _produce_step(self) -> str:
        """One producer iteration: stage one item (pull + shard + place,
        exceptions becoming the error sentinel), then try to enqueue it
        within a bounded wait — so the loop stays responsive to close()
        and never holds a strong loader reference across a long block.
        Returns "again" (call me back) or "done" (producer finished)."""
        if self._stop.is_set():
            return "done"
        if self._staged is None:
            try:
                with self._timed("batch_build"):
                    if self._fault_hook is not None:
                        self._fault_hook("batch_build")
                    nxt = next(self._source)
            except StopIteration:
                self._staged, self._final = ("end", None), True
            except BaseException as exc:  # re-raised on the consumer
                self._staged, self._final = ("error", exc), True
            else:
                try:
                    self._staged = ("batch", self._place(nxt))
                except BaseException as exc:
                    self._staged, self._final = ("error", exc), True
        t0 = time.perf_counter()
        try:
            self._queue.put(self._staged, timeout=0.1)
        except queue.Full:
            # backpressure: keep the item staged, retry; accumulate the
            # blocked time so the whole wait lands as ONE enqueue_wait
            # entry (per-retry entries would skew count/mean_ms)
            self._enqueue_blocked += time.perf_counter() - t0
            return "again"
        if self._timings is not None:
            self._timings.add(
                "enqueue_wait",
                self._enqueue_blocked + time.perf_counter() - t0)
        self._enqueue_blocked = 0.0
        self._staged = None
        return "done" if self._final else "again"

    # ---- consumer ----------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        if not self._prefetch:
            with self._timed("batch_build"):
                try:
                    if self._fault_hook is not None:
                        self._fault_hook("batch_build")
                    nxt = next(self._source)
                except StopIteration:
                    self._exhausted = True
                    raise
            return self._place(nxt)
        with self._timed("dequeue_wait"):
            while True:
                try:
                    kind, payload = self._queue.get(timeout=0.5)
                    break
                except queue.Empty:
                    if self._thread is None or not self._thread.is_alive():
                        # closed, or producer died without a sentinel —
                        # never hang the training loop on it
                        self._exhausted = True
                        raise StopIteration from None
        if kind == "batch":
            return payload
        self._exhausted = True
        if kind == "error":
            raise payload
        raise StopIteration

    # ---- lifecycle ---------------------------------------------------------

    def queue_depth(self) -> int:
        """Batches/windows currently prestaged ahead of the consumer
        (0 for prefetch=0). Approximate by nature (the producer may be
        mid-put) — an observability gauge, not a synchronization API."""
        return self._queue.qsize() if self._prefetch else 0

    def producer_alive(self) -> bool:
        """True while the background producer thread exists and runs —
        False after close() (or for prefetch=0). The chaos harness's
        no-thread-leak invariant reads this."""
        return self._thread is not None and self._thread.is_alive()

    def close(self) -> None:
        """Stop the producer and join its thread (idempotent)."""
        if self._thread is None:
            return
        self._stop.set()
        # drain so a producer blocked mid-put observes the stop promptly
        # and queued device batches are released
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5)
        self._thread = None
        self._staged = None  # release a device batch caught mid-enqueue
        # drain AGAIN: a producer blocked in put() when stop was set may
        # have landed its item into the slot the first drain freed
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class DeferredMetrics:
    """Deferred metrics readback: start the D2H copy now, resolve later.

    ``start(step, metrics)`` begins an async device→host copy for every
    array leaf and returns the PREVIOUS submission resolved to host values
    (``None`` on the first call) — by the next log boundary the copy has
    had a full log interval to complete, so ``float(loss)`` never stalls
    the dispatch pipeline. ``resolve()`` flushes the pending entry (end of
    run / cycle).
    """

    def __init__(self):
        self._pending = None  # (step, perf_counter at submit, metrics)

    def start(self, step: int, metrics):
        import jax
        import time

        for leaf in jax.tree_util.tree_leaves(metrics):
            copy_async = getattr(leaf, "copy_to_host_async", None)
            if copy_async is not None:
                try:
                    copy_async()
                except Exception:
                    pass  # readback below still blocks correctly
        prev = self.resolve()
        self._pending = (step, time.perf_counter(), metrics)
        return prev

    def resolve(self):
        """Return (step, submit_time, host_metrics) for the pending entry,
        or None. Blocks only if the async copy has not finished yet."""
        if self._pending is None:
            return None
        step, t_submit, metrics = self._pending
        self._pending = None
        import jax

        host = jax.tree_util.tree_map(np.asarray, metrics)
        return step, t_submit, host


def numpy_file_source(paths, batch_size: int, shuffle_seed: Optional[int] = None,
                      loop: bool = True) -> Iterator[Dict[str, np.ndarray]]:
    """Stream batches from .npz shard files ({key: array} per file).

    A minimal file-backed source for real datasets; files are read one at a
    time and row-sliced, so memory stays bounded by one shard. A shard with
    fewer rows than ``batch_size`` is skipped with a warning (one short
    tail shard must not kill a long run); an epoch in which EVERY shard was
    short raises — silently yielding nothing forever would spin the
    training loop.
    """
    rng = np.random.default_rng(shuffle_seed) if shuffle_seed is not None else None
    while True:
        order = list(paths)
        if rng is not None:
            rng.shuffle(order)
        yielded = False
        for path in order:
            with np.load(path) as npz:
                arrays = {k: npz[k] for k in npz.files}
            n = min(a.shape[0] for a in arrays.values())
            if n < batch_size:
                log.warning(
                    "skipping shard %s: %d rows < batch_size %d",
                    path, n, batch_size)
                continue
            idx = np.arange(n)
            if rng is not None:
                rng.shuffle(idx)
            for lo in range(0, n - batch_size + 1, batch_size):
                sel = idx[lo:lo + batch_size]
                yield {k: a[sel] for k, a in arrays.items()}
                yielded = True
        if not yielded:
            raise ValueError(
                "every shard has rows < batch_size %d (%d shards); "
                "nothing to yield" % (batch_size, len(order)))
        if not loop:
            return
