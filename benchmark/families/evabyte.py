"""The ``evabyte`` family (EvaByte: EVA attention — an exact window of
the newest positions beside one pooled summary row for every chunk of
the windows before it — in a byte-level decoder): how a configuration
file becomes the program's server, the weights made from the seed, and
the functions that count the bytes and operations its decode step
requires.

Serving only. The configuration states one pipeline stage of a
deployment (some of the layers, each of them whole) with the embedding
and the head beside it; nothing here or in the program stands in for
the other stages.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

# what a served family's arrays are held in: read from them, one way
from benchmark.families.axk1 import storage_bits  # noqa: F401
from benchmark.harness.loader import load_sibling
# at import, so that a program without the model fails before any weight
# is made: the driver tries a new cell on the parent commit first
from paddle_operator_tpu.models import evabyte as program_model

REFERENCE = "evabyte"


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names ``models/evabyte`` reads."""
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        mlp_dim=config["intermediate_size"],
        window=config["window_size"], chunk=config["chunk_size"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_seq=config["max_position_embeddings"])


def make_params(config: Dict[str, Any], seed: int):
    """Every weight on the device in bfloat16, leaf by leaf, in the tree
    ``models/evabyte`` reads: normal(0, init_std) kernels and tables,
    zero norm offsets (``norm_add_unit_offset``: a unit gain), and, a
    head and layer, ``phi`` and ``mu`` normal(0, 1) so that the pooling
    weights ``softmax(s phi . k)`` are not flat and ``mu`` moves a
    summary's logit as much as its keys do (the configuration's
    ``assumed``)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    f, std = config["intermediate_size"], config["init_std"]
    # XLA's own bit generator, as the other served families'
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    count = [0]

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, scale):
        return (scale * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    def normal(*shape, scale=std):
        count[0] += 1
        return draw(jax.random.fold_in(key, count[0]), shape, scale)

    def zeros(*shape):
        return jnp.zeros(shape, jnp.bfloat16)

    def layer():
        return {"norm1": zeros(d), "norm2": zeros(d),
                "attn": {"q": normal(d, d), "k": normal(d, d),
                         "v": normal(d, d), "o": normal(d, d),
                         "phi": normal(h, d // h, scale=1.0),
                         "mu": normal(h, d // h, scale=1.0)},
                "mlp": {"gate": normal(d, f), "up": normal(d, f),
                        "down": normal(f, d)}}

    return {"embed": {"table": normal(config["vocab_size"], d)},
            "layers": [layer() for _ in range(config["num_hidden_layers"])],
            "final_norm": zeros(d),
            "lm_head": normal(d, config["vocab_size"])}


def reference_logits(config: Dict[str, Any], precision: str):
    ref = load_sibling(__file__, "reference", REFERENCE)

    return lambda p, ids: ref.logits(p, ids, config, precision)


def serving_engine(config: Dict[str, Any], traffic: Dict[str, Any], params):
    """The program's server at the sizes of the traffic file, told the
    model's module; bfloat16 weights as made, the model's own pools
    (bfloat16)."""
    from paddle_operator_tpu.serving.engine import ServingEngine

    eng = traffic["engine"]
    for key in ("param_dtype", "cache_dtype"):
        if eng.get(key, "bfloat16") != "bfloat16":
            raise ValueError("the evabyte server stores in bfloat16, the "
                             "traffic file asks %s=%s" % (key, eng[key]))
    return ServingEngine(
        params, program_config(config), max_batch=eng["max_batch"],
        prompt_pad=eng["prompt_pad"], num_blocks=eng["num_blocks"],
        block_size=eng["block_size"], attn=eng["attn"], eos_id=None,
        model=program_model)


# -- what the work requires: operations and bytes from shapes ---------------

def row_bytes(config: Dict[str, Any]) -> int:
    """One cached row of one layer and side: every head's key (or
    value), a window row and a summary row alike, in bfloat16."""
    return 2 * config["hidden_size"]


def eva_decode_floor(config: Dict[str, Any], rows_read: float,
                     peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time one chip could take for the attention of decode
    steps whose live rows attend over ``rows_read`` cached rows between
    them (window rows and summary rows, summed over the rows of the
    batch and over the steps): every layer reads each such row's key
    and value once and multiplies each into one head's score or
    context a lane. Two operations a byte: memory-bound by two orders,
    but the larger of the two times is taken."""
    layers = config["num_hidden_layers"]
    nbytes = float(layers) * rows_read * 2 * row_bytes(config)
    flops = float(layers) * rows_read * 2 * 2.0 * config["hidden_size"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes,
            "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def traced_rows_read(record: Dict[str, Any]):
    """The cached rows the traced decode steps attended over: the loop's
    own count of their live positions times the share of them the
    program says it read (``eva.rows_read`` / ``eva.tokens_live`` over
    the steps stamped inside the traced interval, which opens where the
    window closes and lasts the traffic file's ``trace_span_s``). None
    where the traced interval held no decode step or the program banks
    no such counters."""
    from benchmark.harness.program_spans import serve_window
    from benchmark.harness.step_counters import steps

    counters, window = record["counters"], serve_window(record)
    if window is None or not counters.get("traced_decode_steps"):
        return None
    until = window[1] + float(record["traffic"].get("trace_span_s", 0.0))
    read = sum(steps(record, "eva.rows_read", window[1], until))
    live = sum(steps(record, "eva.tokens_live", window[1], until))
    return counters["traced_live_tokens"] * read / live if live else None


def decode_weight_bytes(config: Dict[str, Any]) -> float:
    """Bytes of weights ONE decode step has to read, bfloat16: of every
    layer its four attention projections, ``phi`` and ``mu``, its gated
    MLP and its two norms; the final norm and the head. The embedding is
    a gather of a few rows and is left out."""
    d, f = config["hidden_size"], config["intermediate_size"]
    layer = 4 * d * d + 2 * d + 3 * d * f + 2 * d
    return 2.0 * (config["num_hidden_layers"] * layer
                  + d + d * config["vocab_size"])
