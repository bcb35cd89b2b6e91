"""The ``bert`` family: ``examples/train_bert.py``'s job with its
arguments repeated here (the example has no ``build_job`` and is not
edited), the weights and feed made from the seed, and the operations
its work requires.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.harness.loader import load_sibling

REFERENCE = "bert"


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    return dict(vocab_size=config["vocab_size"], hidden=config["hidden_size"],
                layers=config["num_hidden_layers"],
                heads=config["num_attention_heads"],
                mlp_dim=config["intermediate_size"],
                max_seq=config["max_position_embeddings"],
                type_vocab=config["type_vocab_size"],
                moe_experts=0, moe_every=2)


def make_params(config: Dict[str, Any], seed: int):
    """Every weight in one jitted call, float32, in the tree
    ``models/bert`` reads: normal(0, initializer_range) kernels and
    tables, zero biases, unit LayerNorm scales, as published."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    f, v, std = config["intermediate_size"], config["vocab_size"], \
        config["initializer_range"]
    dh = d // heads

    def build(key):
        count = [0]

        def normal(*shape):
            count[0] += 1
            return std * jax.random.normal(
                jax.random.fold_in(key, count[0]), shape, jnp.float32)

        def ln():
            return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}

        def dense(i, o):
            return {"kernel": normal(i, o), "bias": jnp.zeros((o,))}

        def proj():
            return {"kernel": normal(d, heads, dh),
                    "bias": jnp.zeros((heads, dh))}

        def layer():
            return {"attn": {"q": proj(), "k": proj(), "v": proj(),
                             "o": {"kernel": normal(heads, dh, d),
                                   "bias": jnp.zeros((d,))}},
                    "ln1": ln(), "ln2": ln(),
                    "mlp": {"fc1": dense(d, f), "fc2": dense(f, d)}}

        return {
            "embed": {"tok": {"table": normal(v, d)},
                      "pos": {"table": normal(
                          config["max_position_embeddings"], d)},
                      "type": {"table": normal(
                          config["type_vocab_size"], d)},
                      "ln": ln()},
            "layers": [layer() for _ in range(config["num_hidden_layers"])],
            "pooler": dense(d, d),
            "mlm": {"transform": dense(d, d), "ln": ln(),
                    "decoder": dense(d, v)},
        }

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def make_batch(config: Dict[str, Any], traffic: Dict[str, Any], rng, step):
    """Token ids, labels and a 15% loss mask, all rows different; every
    position attends (an attention mask of ones, which is also what
    sends the program down its einsum attention)."""
    del step
    shape = (traffic["global_batch"], traffic["seq_len"])
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "input_ids": jax.random.randint(k1, shape, 0, config["vocab_size"]),
        "labels": jax.random.randint(k2, shape, 0, config["vocab_size"]),
        "loss_mask": (jax.random.uniform(k3, shape)
                      < traffic["mask_rate"]).astype(jnp.float32),
        "attention_mask": jnp.ones(shape, jnp.int32),
    }


def optimizer_spec(traffic: Dict[str, Any]) -> Dict[str, float]:
    steps = int(traffic["schedule_steps"])
    return dict(learning_rate=1e-4, schedule_steps=steps,
                warmup_steps=steps // 10, weight_decay=0.01, beta1=0.9,
                beta2=0.999, eps=1e-8, grad_clip=1.0)


def train_job(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
              params):
    """``examples/train_bert.py``'s TrainJob: AdamW 1e-4 under a cosine
    with a tenth of warm-up, weight decay 0.01, remat, clip 1.0."""
    from paddle_operator_tpu.models import bert
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel.sharding import bert_rules
    from paddle_operator_tpu.runner import TrainJob

    opt = optimizer_spec(traffic)
    return TrainJob(
        init_params=lambda rng: params,
        loss_fn=lambda p, b: bert.loss_fn(p, b, remat=True),
        optimizer=optim.adamw(
            optim.cosine_schedule(opt["learning_rate"],
                                  opt["schedule_steps"],
                                  opt["warmup_steps"]),
            weight_decay=opt["weight_decay"]),
        make_batch=lambda rng, step: make_batch(config, traffic, rng, step),
        rules=bert_rules(), grad_clip=opt["grad_clip"],
        total_steps=opt["schedule_steps"],
        log_every=int(traffic["log_every"]), checkpoint_dir="",
        seed=seed % (2 ** 31))


def reference_loss_sum(config: Dict[str, Any], precision: str):
    ref = load_sibling(__file__, "reference", "bert")

    eps = config["layer_norm_eps"]
    return lambda p, b: ref.loss_sum(p, b, eps, precision)


# -- what the work requires -------------------------------------------------

def matmul_params(config: Dict[str, Any]) -> int:
    """Attention and MLP kernels of every layer, the masked-LM head's
    transform and its decoder; not the embeddings, the unused pooler,
    biases or LayerNorms."""
    d, f = config["hidden_size"], config["intermediate_size"]
    return config["num_hidden_layers"] * (4 * d * d + 2 * d * f) \
        + d * d + d * config["vocab_size"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 per matmul parameter plus full (not causal) attention: scores
    and context are 2 matmuls x 2 x d x S forward a layer, twice that
    backward. The decoder runs over every position (the loss masks
    afterwards), so it is counted for every token."""
    attn = config["num_hidden_layers"] * 3 * 2 * 2 \
        * config["hidden_size"] * seq
    return 6.0 * matmul_params(config) + attn
