"""How a ``serve_rows`` cell is run and timed: ``drivers/serve.py``, whole
— the same server, open loop, warm-up, window, stamps, checks and line —
for a cell whose reference cannot hold logits at EVERY position.

``drivers/serve.py::served_logit_gaps`` asks the family's reference for
``[requests, prompt max + output max, vocabulary]`` float32 logits in
one array. At 34,816 positions of 73,448 logits that is 10.2 GB a
request beside 10.1 GB of weights on a 16 GB chip, and the compiler
does not fuse the head into the comparisons that read it (tried here on
the v5e compiler: the array is allocated whole). The comparison reads
those logits at the positions that produced a served token and nowhere
else, so this driver asks for them there and nowhere else:
``family.reference_rows(config, precision)`` -> ``f(params, ids [S],
positions [N]) -> [N, V]``. Every position's hidden state is still
computed (the layers are causal over all of them); only the head is
applied to fewer rows. Same positions, same reference, same numbers
out: ``(best - served, best - the lower precision's first)`` a served
token, request by request.

Nothing of ``drivers/serve.py`` is copied: its ``run`` is called with
this module's ``served_logit_gaps`` in the place of its own for the
length of the call.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import serve as base


def served_logit_gaps(family, config, params, sample, precision="f32",
                      pad_to: int = 0):
    """``drivers/serve.py::served_logit_gaps``'s two lists, the
    reference's head applied at the served positions alone: one forward
    a request over prompt + served tokens padded to ``pad_to`` (causal,
    so padding on the right changes nothing before it), the same
    compiled layers for all of them."""
    width = pad_to or max(len(r.prompt) + len(r.generated) for r in sample)
    rows = max(len(r.generated) for r in sample)
    ref_rows = family.reference_rows(config, "f32")
    low_rows = family.reference_rows(config, precision)

    # NOT under one ``jit``: the reference compiles a layer at a time so
    # that 34,816 positions fit beside the weights, and an outer ``jit``
    # would hand the compiler the whole stack at once
    def gaps(p, ids, at):
        ref = ref_rows(p, ids, at)
        best = jnp.max(ref, axis=-1)
        served = jnp.take_along_axis(ref, ids[at + 1][:, None], axis=-1)[:, 0]
        if precision == "f32":
            return best - served, jnp.zeros_like(best)
        first = jnp.argmax(low_rows(p, ids, at), axis=-1)
        low = jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
        return best - served, best - low

    out_served, out_low = [], []
    for r in sample:
        seq = list(r.prompt) + list(r.generated)
        ids = np.zeros((width,), np.int32)
        ids[:len(seq)] = seq
        # the position whose logits chose generated[j] is prompt + j - 1;
        # rows past this request's answer repeat its last and are cut
        at = np.minimum(len(r.prompt) - 1 + np.arange(rows), len(seq) - 2)
        served, low = jax.device_get(gaps(params, jnp.asarray(ids),
                                          jnp.asarray(at, jnp.int32)))
        out_served.extend(served[:len(r.generated)].tolist())
        out_low.extend(low[:len(r.generated)].tolist())
    return out_served, out_low


def run(cell, seed: int, seconds: float, trace: bool, clock0: float,
        dev: Dict[str, Any], peaks: Dict[str, float], say) -> Dict[str, Any]:
    theirs = base.served_logit_gaps
    base.served_logit_gaps = served_logit_gaps
    try:
        return base.run(cell, seed, seconds, trace, clock0, dev, peaks, say)
    finally:
        base.served_logit_gaps = theirs
