"""How a ``serve`` cell is run and timed.

The program's server — ``RequestQueue`` + ``ContinuousBatcher`` +
``ServingEngine.step_fn`` — behind the benchmark's own single-threaded
open loop: submit every request now due, call ``batcher.step`` with a
wrapped ``engine.step_fn``, stamp every token at the step's return (the
step ends in token read-backs, so it has waited for the device). All
times are the benchmark's, from when the schedule said a request was
DUE, not from when it was submitted.

A traced run measures the same window, untraced, and starts the
profiler only once the window has closed and its requests have their
first tokens, over a tail of the same mix: starting and stopping the
profiler stalls this loop for seconds, and inside the window that read
as a 90th percentile of 3540 ms against 454 ms (my chip runs, PR 23).
So the host-clock metrics of a traced run are those of an untraced one.

The profiler is outside the loop's clock too. ``stop_trace`` costs
seconds in proportion to what was captured, and a faster server puts
more steps into the same ``trace_span_s``: 5.0 s for 85 steps (22 MB),
30.8 s for about 660 (101 MB; builder's chip runs, PR 31, quoted in
ISSUE 32). Counted on the loop's clock, the second outran ``drain_s``:
the loop gave up with the tail's requests in flight and a sound run
read ``unfinished_requests`` 4. So the clock that arrivals come due
on, and that the loop sleeps and gives up by, is wall time LESS the
seconds spent inside ``trace.start()`` and ``trace.stop()``: a stall
of the profiler neither eats the drain allowance nor turns the tail's
remaining arrivals into one burst. Every stamp that is matched against
the trace or the program's spans stays on the raw clock.
"""

from __future__ import annotations

import math
import random
import re
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import compare, device, schedule, stats
from benchmark.harness.compiles import CompileCounter
from benchmark.harness.loader import Cell, load_part
from benchmark.harness.tracing import TraceWindow

# the benchmark's own annotations, then the engine's spans inside
# ``engine.step_fn``, innermost last: an idle gap is booked to the phase
# that covers it
ANNOTATIONS = ("batcher.step", "queue.submit", "engine.step_fn",
               "serve.step",
               "serve.prefill.build", "serve.prefill.dispatch",
               "serve.prefill.scatter", "serve.prefill.wait",
               "serve.decode.tables", "serve.decode.put",
               "serve.decode.dispatch", "serve.decode.wait",
               "serve.decode.readback")


class Loop:
    """The open loop and everything it counts."""

    def __init__(self, engine, queue, batcher, request_cls) -> None:
        self.engine, self.queue, self.batcher = engine, queue, batcher
        self.request_cls = request_cls
        self.clock = time.perf_counter

    def engine_step(self, active):
        new = [r for r in active if not r.generated]
        # what the decode kernel will have to read: every row that is
        # past its prefill holds its prompt and the tokens so far
        live = sum(len(r.prompt) + len(r.generated)
                   for r in active if r.generated)
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("engine.step_fn"):
            out = self.engine.step_fn(active)
        self.calls.append({"t": t0, "seconds": self.clock() - t0,
                           "new": len(new), "active": len(active),
                           "live_tokens": live,
                           "decode_rows": len(active) - len(new)})
        self._stepped = active
        return out

    def run(self, arrivals: List[schedule.Arrival], seconds: float,
            drain_s: float, trace: Optional[TraceWindow] = None,
            trace_after_s: float = 0.0, trace_span_s: float = 0.0,
            first_tokens_of: int = 0) -> Dict[str, Any]:
        """Offer ``arrivals`` on their schedule, then drain. Returns the
        raw stamps; nothing here is a metric yet. The profiler starts
        ``trace_after_s`` in, once the first ``first_tokens_of``
        requests have their first token (or two seconds later). ``now``
        does not run while it starts or stops (the module's docstring);
        the stamps returned are on the raw clock."""
        self.calls: List[Dict[str, Any]] = []
        alloc = self.engine.cache.allocator
        requests = [self.request_cls(
            "w%d" % a.index, list(a.prompt), max_new_tokens=a.max_new_tokens)
            for a in arrivals]
        token_times: Dict[str, List[float]] = {r.request_id: []
                                               for r in requests}
        sent, shed, samples = [], set(), []
        trace_at = [None, None]
        i, n = 0, len(arrivals)
        in_profiler = 0.0
        t_open = self.clock()
        while True:
            now = self.clock() - t_open - in_profiler
            while i < n and arrivals[i].due_s <= now:
                with jax.profiler.TraceAnnotation("queue.submit"):
                    accepted, dropped = self.queue.submit(requests[i])
                sent.append(now)
                if not accepted:
                    shed.add(requests[i].request_id)
                if dropped is not None:
                    shed.add(dropped.request_id)
                i += 1
            in_flight, depth = self.batcher.in_flight(), self.queue.depth()
            if i >= n and not in_flight and not depth:
                break
            if now > seconds + drain_s:
                break
            if not in_flight and not depth:
                time.sleep(max(0.0, min(arrivals[i].due_s - now, 0.002)))
                continue
            if trace is not None and not trace.done:
                if not trace.active and now >= trace_after_s and (
                        now >= trace_after_s + 2.0 or all(
                            token_times[r.request_id]
                            for r in requests[:first_tokens_of])):
                    t_call = self.clock()
                    trace.start()
                    trace_at[0] = self.clock()
                    in_profiler += trace_at[0] - t_call
                elif trace.active and \
                        self.clock() - trace_at[0] >= trace_span_s:
                    t_call = self.clock()
                    trace.stop()
                    trace_at[1] = self.clock()
                    in_profiler += trace_at[1] - t_call
            self._stepped = None
            with jax.profiler.TraceAnnotation("batcher.step"):
                self.batcher.step(self.engine_step)
            t_ret = self.clock()
            for req in self._stepped or ():
                token_times[req.request_id].append(t_ret)
            st = alloc.stats()
            samples.append({
                "t": t_ret - t_open, "active": len(self._stepped or ()),
                "queue": depth,
                "reserved": st["blocks_used"] * alloc.block_size,
                "live": st["blocks_used"] * alloc.block_size
                - st["waste_slots"]})
        if trace is not None and trace.active:
            trace.stop()
            trace_at[1] = self.clock()
        return {"t_open": t_open, "t_end": self.clock(),
                "requests": requests, "token_times": token_times,
                "sent": sent, "shed": shed, "samples": samples,
                "trace_at": trace_at}


def build_server(cell: Cell, family, params):
    from paddle_operator_tpu.serving.batching import (
        ContinuousBatcher, Request, RequestQueue)

    traffic = cell.traffic
    engine = family.serving_engine(cell.config, traffic, params)
    queue = RequestQueue(traffic["queue"]["capacity"],
                         traffic["queue"]["shed_policy"],
                         clock=time.perf_counter)
    batcher = ContinuousBatcher(queue, engine.max_batch,
                                clock=time.perf_counter,
                                on_admit=engine.admit,
                                on_retire=engine.retire)
    return Loop(engine, queue, batcher, Request)


def warm_up(loop: Loop, arrivals: List[schedule.Arrival], vocab: int,
            seed: int) -> int:
    """Every shape the window will use and no other: one prefill of each
    distinct prompt length of THIS schedule, and the decode step with
    every row of the batch in use (one program whatever is live: the
    engine sends a step's inputs in one ``device_put`` and reads all
    rows' tokens back in one ``device_get``). Returns the requests it
    served."""
    rnd = random.Random(seed ^ 0x5EED)
    lengths = sorted({len(a.prompt) for a in arrivals})
    rows = loop.engine.max_batch
    warm = [schedule.Arrival(i, 0.0, tuple(
        rnd.randrange(vocab) for _ in range(n)), 2)
        for i, n in enumerate(lengths)]
    warm += [schedule.Arrival(len(lengths) + j, 0.0, tuple(
        rnd.randrange(vocab) for _ in range(lengths[0])), 3)
        for j in range(rows)]
    out = loop.run(warm, 0.0, 600.0)
    unfinished = [r.request_id for r in out["requests"]
                  if len(r.generated) != r.max_new_tokens]
    if unfinished:
        raise RuntimeError("warm-up left %r unfinished" % unfinished)
    return len(warm)


def reduce_run(raw: Dict[str, Any], arrivals: List[schedule.Arrival],
               seconds: float, loop: Loop,
               until: Optional[float] = None) -> Dict[str, Any]:
    """From the loop's stamps to latencies and counters, over the
    requests of ``arrivals`` (the window's; a traced run's tail comes
    after them) and the steps that began before ``until`` on the loop's
    clock (where a traced run started the profiler)."""
    t_open = raw["t_open"]
    until = raw["t_end"] if until is None else until
    ttft, gaps, waits, failed = [], [], [], 0
    for a, req in zip(arrivals, raw["requests"]):
        times = raw["token_times"][req.request_id]
        done = len(req.generated) == req.max_new_tokens and \
            req.request_id not in raw["shed"]
        if not done:
            failed += 1
        # a request that never got its first token is beyond every
        # percentile; one that got it late is counted as late
        ttft.append((times[0] - t_open - a.due_s) * 1e3
                    if times else math.inf)
        if req.t_admitted:
            waits.append((req.t_admitted - t_open - a.due_s) * 1e3)
        gaps.extend((b - x) * 1e3 for x, b in zip(times, times[1:])
                    if b <= until)
    calls = [c for c in loop.calls if c["t"] < until]
    decode_only = [c["seconds"] for c in calls if c["new"] == 0]
    decode_med = stats.median(decode_only) if decode_only else None
    wall = until - t_open
    prefill_s = sum(c["seconds"] - (decode_med or 0.0)
                    * (1 if c["decode_rows"] else 0)
                    for c in calls if c["new"])
    samples = [s for s in raw["samples"] if s["t"] <= until - t_open]

    def depth_at(t: float) -> int:
        before = [s["queue"] for s in samples if s["t"] <= t]
        return before[-1] if before else 0

    return {
        "ttft_ms": ttft, "gap_ms": gaps, "queue_wait_ms": waits,
        "failed": failed, "wall_s": wall,
        "decode_step_ms": decode_med * 1e3 if decode_med else None,
        "prefill_share_pct": 100.0 * prefill_s / wall if wall else None,
        "occupancy_pct": 100.0 * np.mean([s["active"] for s in samples])
        / loop.engine.max_batch if samples else None,
        "kv_live_share_pct": 100.0 * sum(s["live"] for s in samples)
        / max(1, sum(s["reserved"] for s in samples)) if samples else None,
        # a queue deeper at the window's end than at its middle is
        # growing: the offered rate is past the knee
        "queue_mid": depth_at(seconds / 2.0),
        "queue_end": depth_at(seconds),
        "lateness": schedule.lateness([a.due_s for a in arrivals],
                                      raw["sent"]),
        "output_tokens": sum(len(r.generated) for r in raw["requests"]),
        "pool_peak_tokens": max((s["reserved"] for s in samples), default=0),
    }


def served_logit_gaps(family, config, params, sample, precision="f32",
                      pad_to: int = 0):
    """For each sampled request, at every position that produced a
    served token: (the reference's best logit - the reference's logit
    of the served token, the reference's best - its logit of the token
    that ``precision`` puts first). One forward over prompt + served
    tokens, all requests padded to one length (causal, so padding on
    the right changes nothing before it)."""
    width = pad_to or max(len(r.prompt) + len(r.generated) for r in sample)
    ids = np.zeros((len(sample), width), np.int32)
    for j, r in enumerate(sample):
        seq = list(r.prompt) + list(r.generated)
        ids[j, :len(seq)] = seq
    ref_logits = family.reference_logits(config, "f32")
    low_logits = family.reference_logits(config, precision)

    @jax.jit
    def gaps(p, ids):
        ref = ref_logits(p, ids)[:, :-1]
        best = jnp.max(ref, axis=-1)
        served = jnp.take_along_axis(ref, ids[:, 1:, None], axis=-1)[..., 0]
        if precision == "f32":
            return best - served, jnp.zeros_like(best)
        first = jnp.argmax(low_logits(p, ids)[:, :-1], axis=-1)
        low = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
        return best - served, best - low

    served, low = jax.device_get(gaps(params, jnp.asarray(ids)))
    out_served, out_low = [], []
    for j, r in enumerate(sample):
        lo, hi = len(r.prompt) - 1, len(r.prompt) + len(r.generated) - 1
        out_served.extend(served[j, lo:hi].tolist())
        out_low.extend(low[j, lo:hi].tolist())
    return out_served, out_low


def pick_sample(requests, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    done = [r for r in requests if len(r.generated) == r.max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.generated))
    rest = [r for r in done if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:k - 1]


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        clock0: float, dev: Dict[str, Any], peaks: Dict[str, float],
        say) -> Dict[str, Any]:
    family = load_part(cell, "families", cell.family)
    config, traffic = cell.config, cell.traffic
    vocab = config["vocab_size"]
    t_start = time.perf_counter()
    params = jax.block_until_ready(family.make_params(config, seed))
    loop = build_server(cell, family, params)
    arrivals = schedule.make_schedule(traffic, seed, seconds, vocab)
    offered, span = arrivals, float(traffic.get("trace_span_s", 0.0))
    if trace:
        # the same mix goes on after the window for the profiler to see
        tail = schedule.make_schedule(traffic, seed + 1, span + 2.0, vocab)
        offered = arrivals + [
            schedule.Arrival(len(arrivals) + a.index, seconds + a.due_s,
                             a.prompt, a.max_new_tokens) for a in tail]
    t_built = time.perf_counter()
    warmed = warm_up(loop, offered, vocab, seed)
    say("setup", before_driver_s=t_start - clock0,
        weights_and_server_s=t_built - t_start,
        warm_up_s=time.perf_counter() - t_built, warmed_requests=warmed)
    tw = TraceWindow() if trace else None
    counter = CompileCounter.get()
    counter.mark()
    setup_s = time.perf_counter() - clock0
    try:
        raw = loop.run(offered, seconds + (span + 2.0 if trace else 0.0),
                       float(traffic["drain_s"]), tw, seconds, span,
                       first_tokens_of=len(arrivals))
    except BaseException:
        if tw is not None:
            tw.abandon()
        raise
    lowered, compiled = counter.mark()
    memory_peak = device.memory_peak_bytes()
    # what the server held its weights and its cache in, read from the
    # arrays the window left behind
    bits = family.storage_bits(loop.engine)
    held = {"weights_bytes": sum(
        a.nbytes for a in jax.tree_util.tree_leaves(loop.engine.params)),
        "pool_bytes": sum(a.nbytes for a in loop.engine.cache.k_pages
                          + loop.engine.cache.v_pages)}
    red = reduce_run(raw, arrivals, seconds, loop, raw["trace_at"][0])
    say("loop", requests=len(arrivals), warmed=warmed,
        lateness=red["lateness"], wall_s=red["wall_s"],
        output_tokens=red["output_tokens"],
        queue_mid=red["queue_mid"], queue_end=red["queue_end"],
        counts=dict(loop.queue.counts(), **loop.batcher.counts()))
    eng = traffic["engine"]
    say("memory_held", pool_peak_share_pct=100.0 * red["pool_peak_tokens"]
        / (eng["num_blocks"] * eng["block_size"]), **held)
    # time to first token is recorded in every run and judged in none
    # (PERF.md, section 2): an untraced run prints it here and carries
    # it in its line under ``recorded``, which the driver ignores
    recorded = {}
    for q in (50, 90):
        try:
            recorded["ttft_p%d_ms" % q] = {
                "value": stats.percentile(red["ttft_ms"], q), "unit": "ms"}
        except stats.TooFewSamples:
            pass
    say("ttft", requests=len(arrivals),
        **{k: v["value"] for k, v in recorded.items()})
    trace_summary = tw.summary(prefer=ANNOTATIONS) if tw is not None else None

    alloc = loop.engine.cache.allocator
    pool = alloc.stats()
    audit = alloc.check()
    # the cache goes before the reference comes
    loop.engine.cache.k_pages = loop.engine.cache.v_pages = None
    t_ref0 = time.perf_counter()
    sample = pick_sample(raw["requests"], seed, int(traffic["check_requests"]))
    served, _ = served_logit_gaps(
        family, config, params, sample,
        pad_to=traffic["prompt_len"]["max"] + traffic["output_len"]["max"])
    say("reference", seconds=time.perf_counter() - t_ref0,
        requests=len(sample), tokens=len(served))
    limits = cell.extra["limits"]
    short = sum(1 for r in raw["requests"]
                if r.request_id not in raw["shed"]
                and len(r.generated) != r.max_new_tokens)
    bad_ids = sum(1 for r in raw["requests"] for t in r.generated
                  if not 0 <= t < vocab)
    stated = int(config["precision"]["serve_storage_bits"])
    checks = [
        compare.at_most("served_logit_gap", max(served, default=math.inf),
                        limits["served_logit_gap"],
                        "widest gap below the reference's best, %d tokens"
                        % len(served)),
        compare.exactly("param_bits", bits["param_bits"], stated),
        compare.exactly("cache_bits", bits["cache_bits"], stated),
        compare.exactly("unfinished_requests", short, 0),
        compare.exactly("token_ids_out_of_range", bad_ids, 0),
        compare.exactly("allocator_audit", len(audit), 0, "; ".join(audit)),
        compare.exactly("pool_blocks_left", pool["blocks_used"], 0),
        compare.exactly("pool_sequences_left", pool["sequences"], 0),
        compare.exactly("lowerings_in_window", lowered, 0),
        compare.exactly("compiles_in_window", compiled, 0),
    ]
    end_to_end: Dict[str, float] = {"setup_s": setup_s}
    # a traced run reports per-layer metrics only, and the profiler
    # slows the host, so its tails are never read
    for metric in () if trace else cell.end_to_end:
        m = re.fullmatch(r"(ttft|token_gap)_p(\d+)_ms", metric["name"])
        if m:
            values = red["ttft_ms"] if m.group(1) == "ttft" else red["gap_ms"]
            end_to_end[metric["name"]] = stats.percentile(
                values, float(m.group(2)))
    traced_calls = []
    if tw is not None and raw["trace_at"][0] is not None:
        lo, hi = raw["trace_at"]
        traced_calls = [c for c in loop.calls
                        if c["t"] >= lo and c["t"] + c["seconds"] <= hi]
    return {
        "end_to_end": end_to_end,
        "attempted": len(arrivals),
        "failed": red["failed"],
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "counters": {
            "occupancy_pct": red["occupancy_pct"],
            "kv_live_share_pct": red["kv_live_share_pct"],
            "traced_live_tokens": sum(c["live_tokens"]
                                      for c in traced_calls),
            "traced_decode_steps": sum(1 for c in traced_calls
                                       if c["decode_rows"]),
            "pool": pool,
        },
        "spans": {
            "ttft_ms": red["ttft_ms"],
            "queue_wait_ms": red["queue_wait_ms"],
            "decode_step_ms": red["decode_step_ms"],
            "prefill_share_pct": red["prefill_share_pct"],
            "wall_s": red["wall_s"],
        },
        "recorded": {} if trace else recorded,
        "trace": trace_summary,
        "family": family,
    }
