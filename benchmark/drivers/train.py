"""How a ``train`` cell is run and timed.

Through the program's own entry points, ``launch.detect_env`` and
``runner.run_training``. The runner owns its state and takes no
callback, so one cell makes three calls in one process, all on the SAME
compiled step (the compile cache's in-process memo; a call that did not
hit it fails ``correct``) and from the same seeded weights and feed:

1. one step — compiles or loads the program; its optimizer state gives
   the first gradient as the optimizer got it (``mu / (1 - beta1)``);
2. three steps, a log line each — the losses the reference follows, and
   the parameters' change;
3. the measured call — ``total_steps`` out of reach, ended by the
   runner's own drain channel: the monitor the runner polls once a step
   opens the window at a log boundary (where the runner has just waited
   for the device), closes it at the first boundary ``--seconds`` later,
   and asks for the drain.

The reference runs after the window, once the program's state is freed.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import statistics
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmark.harness import compare, device, refopt
from benchmark.harness.compiles import CompileCounter
from benchmark.harness.loader import Cell, load_part
from benchmark.harness.tracing import TraceWindow


class LossLines(logging.Handler):
    """Collects the runner's own ``step N loss=X`` log lines."""

    def __init__(self) -> None:
        super().__init__()
        self.losses: Dict[int, float] = {}

    def emit(self, record: logging.LogRecord) -> None:
        if str(record.msg).startswith("step %d loss="):
            self.losses[int(record.args[0])] = float(record.args[1])


def window_monitor(runner_module, log_every: int, warm_boundaries: int,
                   seconds: float, trace: Optional[TraceWindow]):
    """A ``DrainMonitor`` whose ``requested()`` — polled by the runner
    once after every step, after the log boundary's device sync — keeps
    the benchmark's clock. Built here so that the program's class is
    only needed once the program is imported."""

    class WindowMonitor(runner_module.DrainMonitor):
        def __init__(self) -> None:
            super().__init__()
            self.polls = 0
            self.boundaries: List[float] = []   # clock at each boundary
            self.open_at: Optional[int] = None  # index into boundaries
            self.close_at: Optional[int] = None
            self.compiles_in_window = (0, 0)

        def requested(self) -> bool:
            self.polls += 1
            if self.polls % log_every:
                return False
            now = time.perf_counter()
            self.boundaries.append(now)
            b = len(self.boundaries) - 1
            if self.open_at is None:
                if b + 1 >= warm_boundaries:
                    self.open_at = b
                    CompileCounter.get().mark()
                return False
            if trace is not None and not trace.done:
                # one whole log interval, between two device syncs
                if not trace.active and b == self.open_at + 1:
                    trace.start()
                elif trace.active:
                    trace.stop()
                return False
            if now - self.boundaries[self.open_at] >= seconds:
                self.close_at = b
                self.compiles_in_window = CompileCounter.get().mark()
                return True
            return False

    return WindowMonitor()


def _losses_of(lines: LossLines, steps: int) -> List[float]:
    missing = [s for s in range(1, steps + 1) if s not in lines.losses]
    if missing:
        raise RuntimeError("the runner logged no loss for steps %r" % missing)
    return [lines.losses[s] for s in range(1, steps + 1)]


def program_numbers(family, config, traffic, seed: int, params, job,
                    runner, env, lines: LossLines) -> Dict[str, Any]:
    """Calls 1 and 2: what the program computes in its first steps."""
    opt = family.optimizer_spec(traffic)
    lines.losses.clear()
    one = runner.run_training(
        dataclasses.replace(job, total_steps=1, log_every=1), env)
    first_loss_alone = lines.losses[1]
    first_grad = jax.tree_util.tree_map(
        lambda m: m / (1.0 - opt["beta1"]), one["state"]["opt"]["mu"])
    grad_norms = refopt.leaf_norms(first_grad)
    first_grad = refopt.on_the_host(first_grad)
    sources = list(one["compile_sources"])
    del one
    lines.losses.clear()
    steps = int(traffic["reference_steps"])
    few = runner.run_training(
        dataclasses.replace(job, total_steps=steps, log_every=1), env)
    losses = _losses_of(lines, steps)
    update_norms = refopt.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, few["state"]["params"], params))
    sources += list(few["compile_sources"])
    del few
    lines.losses.clear()
    return {"losses": losses, "first_loss_alone": first_loss_alone,
            "first_grad_norms": grad_norms, "first_grad": first_grad,
            "update_norms": update_norms, "sources": sources}


def reference_numbers(family, config, traffic, seed: int, params,
                      precision: str, chips: int) -> Dict[str, Any]:
    """The plain reference over the same weights and feed, in blocks of
    rows (spread over the chips where there are several)."""
    key = jax.random.PRNGKey(seed % (2 ** 31))
    batches = [family.make_batch(config, traffic,
                                 jax.random.fold_in(key, s), s)
               for s in range(int(traffic["reference_steps"]))]
    rows_block = int(traffic["reference_rows_block"])
    loss_sum = family.reference_loss_sum(config, precision)
    opt = family.optimizer_spec(traffic)
    if chips > 1:
        return refopt.train(loss_sum, params, batches, opt,
                            rows_block * chips,
                            refopt.spread_over(jax.devices()))
    return refopt.train(loss_sum, params, batches, opt, rows_block)


def gaps(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers a training cell is held to. The three the contract
    names are gaps of NORMS, and a norm averages zero-mean rounding out
    (at 65k tokens a step fp8 operands moved none of them by three times
    what bf16 does: my chip run, PR 23). So a fourth is read that sees
    precision at first order: the norm of the DIFFERENCE between the
    program's first gradient and the reference's, worst leaf, against
    the same floor."""
    free = compare.gradient_free(ref["first_grad_norms"])
    apart = refopt.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: jnp.asarray(a) - jnp.asarray(b),
        got["first_grad"], ref["first_grad"]))
    floor = statistics.median(ref["first_grad_norms"].values())
    return {
        "grad_apart": max(apart[k] / max(v, floor)
                          for k, v in ref["first_grad_norms"].items()),
        "loss_gap": max(abs(a - b)
                        for a, b in zip(got["losses"], ref["losses"])),
        "grad_norm_gap": compare.worst_leaf_gap(
            got["first_grad_norms"], ref["first_grad_norms"]),
        "update_norm_gap": compare.worst_leaf_gap(
            got["update_norms"], ref["update_norms"], skip=free),
    }


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        clock0: float, dev: Dict[str, Any], peaks: Dict[str, float],
        say) -> Dict[str, Any]:
    from paddle_operator_tpu import launch, runner

    family = load_part(cell, "families", cell.family)
    config, traffic = cell.config, cell.traffic
    chips = dev["count"]
    log_every = int(traffic["log_every"])
    tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
    steps = int(traffic["reference_steps"])

    t_start = time.perf_counter()
    params = jax.block_until_ready(family.make_params(config, seed))
    t_params = time.perf_counter()
    job = family.train_job(config, traffic, seed, params)
    env = launch.detect_env()
    lines = LossLines()
    rlog = logging.getLogger("tpujob.runner")
    level = rlog.level
    rlog.addHandler(lines)
    rlog.setLevel(logging.INFO)
    tw = TraceWindow() if trace else None
    try:
        got = program_numbers(family, config, traffic, seed, params, job,
                              runner, env, lines)
        t_proof = time.perf_counter()
        monitor = window_monitor(runner, log_every,
                                 int(traffic["warm_boundaries"]), seconds, tw)
        result = runner.run_training(
            dataclasses.replace(job, total_steps=10 ** 9,
                                drain_monitor=monitor), env)
    except BaseException:
        if tw is not None:
            tw.abandon()
        raise
    finally:
        rlog.removeHandler(lines)
        rlog.setLevel(level)

    if monitor.open_at is None or monitor.close_at is None:
        raise RuntimeError("the measured call ended before its window did")
    bounds = monitor.boundaries
    t_open, t_close = bounds[monitor.open_at], bounds[monitor.close_at]
    steps_in_window = (monitor.close_at - monitor.open_at) * log_every
    window_s = t_close - t_open
    memory_peak = device.memory_peak_bytes()
    window_losses = dict(lines.losses)
    hw = result["hardware"]
    memo = got["sources"][1:] + list(result["compile_sources"])
    mesh_history = list(result["mesh_history"])
    say("setup", before_driver_s=t_start - clock0,
        weights_s=t_params - t_start, first_steps_s=t_proof - t_params,
        to_window_s=t_open - t_proof)
    say("runner", banked_step_seconds=hw.get("step_seconds"),
        banked_steps=hw.get("steps"),
        own_step_seconds=window_s / steps_in_window,
        mesh=mesh_history, sources=got["sources"] + memo[-1:])
    # a window whose log intervals are not all alike reads low for a
    # reason the rate alone does not show: name the slow ones
    spans = [b - a for a, b in zip(bounds[monitor.open_at:],
                                   bounds[monitor.open_at + 1:
                                          monitor.close_at + 1])]
    typical = sorted(spans)[len(spans) // 2]
    say("intervals", log_every=log_every, median_s=typical, max_s=max(spans),
        slow=[(monitor.open_at + i + 1, round(g, 4))
              for i, g in enumerate(spans) if g > 1.05 * typical][:40])
    host_stages = result["host_stages"]
    # the program's state goes before the reference comes
    del result
    trace_summary = tw.summary() if tw is not None else None

    t_ref0 = time.perf_counter()
    ref = reference_numbers(family, config, traffic, seed, params, "f32",
                            chips)
    say("reference", seconds=time.perf_counter() - t_ref0, steps=steps,
        losses=ref["losses"], program_losses=got["losses"])
    limits, numbers = cell.extra["limits"], gaps(got, ref)
    nonfinite = sum(not math.isfinite(v) for v in window_losses.values())
    checks = [
        compare.at_most("loss_gap", numbers["loss_gap"], limits["loss_gap"],
                        "widest |loss - reference| over %d steps" % steps),
        compare.at_most("grad_norm_gap", numbers["grad_norm_gap"],
                        limits["grad_norm_gap"],
                        "first gradient, worst leaf"),
        compare.at_most("grad_apart", numbers["grad_apart"],
                        limits["grad_apart"],
                        "|first gradient - reference's|, worst leaf"),
        compare.at_most("update_norm_gap", numbers["update_norm_gap"],
                        limits["update_norm_gap"],
                        "parameters' change over %d steps, worst leaf"
                        % steps),
        compare.at_most("first_loss_vs_ln_vocab", abs(
            got["losses"][0] - math.log(config["vocab_size"])), 0.5),
        compare.exactly("first_loss_repeats", got["losses"][0],
                        got["first_loss_alone"],
                        "the same seed gives the same first step"),
        compare.exactly("mesh_not_as_asked", sum(
            m != ({"dp": chips} if chips > 1 else None)
            for m in mesh_history), 0, "mesh history %r" % mesh_history),
        compare.exactly("memo_misses", sum(s != "memo" for s in memo), 0,
                        "every later call ran the first call's step"),
        compare.exactly("nonfinite_losses", nonfinite, 0),
        compare.exactly("lowerings_in_window",
                        monitor.compiles_in_window[0], 0),
        compare.exactly("compiles_in_window",
                        monitor.compiles_in_window[1], 0),
    ]
    return {
        "end_to_end": {
            "train_tokens_per_s": tokens_per_step * steps_in_window / window_s,
            "setup_s": t_open - clock0,
        },
        "attempted": steps_in_window,
        "failed": nonfinite,
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "counters": {
            "tokens_per_step": tokens_per_step,
            "steps_in_window": steps_in_window,
            "log_every": log_every,
            "host_stages": host_stages,
        },
        "spans": {
            "boundary_s": [b - a for a, b in zip(bounds, bounds[1:])],
            "window_s": window_s,
        },
        "trace": trace_summary,
        "family": family,
    }
