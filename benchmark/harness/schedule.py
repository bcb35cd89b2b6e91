"""The general traffic generator — part of the yardstick.

One function turns a traffic file's parameters and a seed into an
open-loop schedule: when each request is due, how long its prompt is,
how many tokens it may produce, and its token ids. Every seed gets the
SAME set of lengths and the same set of gaps between arrivals (the
distribution's own quantiles: stratified, not drawn), so a seed never
changes how much work the run holds. A distribution or an arrival
process that no mix uses is not here: the mix that needs one adds it. Which request meets which still moves a tail: with the order
drawn from the run's seed, the 90th percentile of time to first token
read 398..494 ms over six seeds against 4% between two runs of one seed
(my chip runs, PR 23). A mix that judges tails therefore fixes the order
with ``order_seed``; the run's seed then draws the token ids (and the
weights), not the work.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List


@dataclass(frozen=True)
class Arrival:
    index: int
    due_s: float          # seconds after the window opens
    prompt: tuple
    max_new_tokens: int


def _midpoints(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def length_quantiles(spec: Dict, n: int) -> List[int]:
    """``n`` lengths at the distribution's evenly spaced quantiles."""
    if spec["dist"] != "lognormal":
        raise ValueError("unknown length distribution %r" % (spec["dist"],))
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    normal = NormalDist()
    raw = [math.exp(mu + sigma * normal.inv_cdf(u)) for u in _midpoints(n)]
    # a program that compiles per length wants few of them: ``step``
    # rounds every length to a multiple of itself
    step = int(spec.get("step", 1))
    return [int(min(spec["max"], max(spec["min"], step * round(x / step))))
            for x in raw]


def gap_quantiles(spec: Dict, rate_per_s: float, n: int) -> List[float]:
    """``n`` gaps between arrivals with mean ``1 / rate_per_s``: the
    exponential distribution's evenly spaced quantiles, which a Poisson
    process's gaps follow; the caller shuffles them."""
    if spec["process"] != "poisson":
        raise ValueError("unknown arrival process %r" % (spec["process"],))
    raw = [-math.log(1.0 - u) for u in _midpoints(n)]
    scale = n / (sum(raw) * rate_per_s)
    return [g * scale for g in raw]


def make_schedule(traffic: Dict, seed: int, seconds: float,
                  vocab_size: int, rate_per_s: float = 0.0
                  ) -> List[Arrival]:
    """The requests due inside a window of ``seconds``. ``rate_per_s``
    overrides the file's (the knee sweep does); otherwise the file's
    ``rate_per_s`` holds."""
    rate = rate_per_s or float(traffic["rate_per_s"])
    n = int(rate * seconds)
    if n < 1:
        raise ValueError("rate %g over %g s gives no request" % (rate, seconds))
    rnd = random.Random(seed)
    order = random.Random(traffic["order_seed"]) \
        if "order_seed" in traffic else rnd
    prompts = length_quantiles(traffic["prompt_len"], n)
    outputs = length_quantiles(traffic["output_len"], n)
    gaps = gap_quantiles(traffic["arrivals"], rate, n)
    order.shuffle(prompts)
    order.shuffle(outputs)
    order.shuffle(gaps)
    due, out = 0.0, []
    for i in range(n):
        # the first request is due half a gap in, so the last falls
        # inside the window whatever the order
        due += gaps[i] if i else gaps[i] / 2.0
        prompt = tuple(rnd.randrange(vocab_size) for _ in range(prompts[i]))
        out.append(Arrival(i, due, prompt, outputs[i]))
    return out


def lateness(due_s: List[float], sent_s: List[float]) -> Dict[str, float]:
    """How late the generator submitted: a starved generator must not
    read as a fast server."""
    late = sorted(max(0.0, s - d) for d, s in zip(due_s, sent_s))
    if not late:
        return {"median_ms": 0.0, "max_ms": 0.0}
    return {"median_ms": late[len(late) // 2] * 1e3, "max_ms": late[-1] * 1e3}
