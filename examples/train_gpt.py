"""GPT causal-LM training entry (long-context; deploy/examples/gpt.yaml).

Set TPUJOB_SP>1 to shard the sequence axis over `sp` with causal ring
attention (context length scales with chips); TPUJOB_MOE_EXPERTS>0 switches
every other FFN to an expert-parallel MoE block.
"""

import functools
import logging
import os

import jax

from paddle_operator_tpu.models import gpt
from paddle_operator_tpu.ops import optim
from paddle_operator_tpu.parallel import gpt_rules, moe_rules, ring_attention
from paddle_operator_tpu.runner import TrainJob, run_training

logging.basicConfig(level=logging.INFO)

BATCH = int(os.environ.get("TPUJOB_BATCH", "16"))
SEQ = int(os.environ.get("TPUJOB_SEQ", "1024"))
STEPS = int(os.environ.get("TPUJOB_STEPS", "100"))
SP = int(os.environ.get("TPUJOB_SP", "1"))
MOE = int(os.environ.get("TPUJOB_MOE_EXPERTS", "0"))

# Rows a trip of the chunked LM-head loss sends through the head, so that the
# [B,S,V] float32 logits (gigabytes at long context) never exist. Chosen on
# the chip by ``train_tokens_per_s`` of ``gpt2-small.train-1k`` (PR 42's sweep).
CE_CHUNK = 2048


def build_job(total_steps: int = STEPS, batch: int = BATCH, seq: int = SEQ,
              config: dict = gpt.BASE_CONFIG) -> TrainJob:
    """The TrainJob this example trains; ``chip_smoke.py`` runs the same
    one (its CPU rehearsal passes ``gpt.TINY_CONFIG``). ``total_steps``
    also sets the cosine schedule's horizon."""
    cfg = dict(config, max_seq=seq)
    for knob, key in (("TPUJOB_LAYERS", "layers"), ("TPUJOB_HIDDEN", "hidden"),
                      ("TPUJOB_HEADS", "heads"), ("TPUJOB_MLP_DIM", "mlp_dim"),
                      ("TPUJOB_VOCAB", "vocab_size")):
        if os.environ.get(knob):
            cfg[key] = int(os.environ[knob])
    if MOE:
        cfg.update(moe_experts=MOE, moe_every=2)

    def loss_fn(p, b, mesh=None):
        attn = "auto"
        if mesh is not None and SP > 1 and "sp" in mesh.shape:
            attn = functools.partial(
                ring_attention, mesh=mesh, axis="sp", causal=True)
        return gpt.loss_fn(p, b, remat=True, attn_impl=attn,
                           ce_chunk=CE_CHUNK, mesh=mesh)

    return TrainJob(
        init_params=lambda rng: gpt.init(rng, cfg),
        loss_fn=loss_fn,
        optimizer=optim.adamw(
            optim.cosine_schedule(3e-4, total_steps, total_steps // 10),
            weight_decay=0.1,
        ),
        make_batch=lambda rng, step: gpt.synthetic_batch(
            rng, batch, seq, cfg["vocab_size"]),
        rules=gpt_rules() + moe_rules(),
        mesh_axes={"dp": -1, "sp": SP} if SP > 1 else None,
        seq_axis="sp" if SP > 1 else None,
        grad_clip=1.0,
        total_steps=total_steps,
        steps_per_call=int(os.environ.get("TPUJOB_STEPS_PER_CALL", "1")),
        checkpoint_dir=os.environ.get("TPUJOB_CHECKPOINT_DIR", ""),
    )


def main():
    out = run_training(build_job())
    leaf = jax.tree_util.tree_leaves(out["state"]["params"])[0]
    print("mesh:", out["mesh_history"][-1], "first parameter on:",
          leaf.sharding)
    print("final loss:", out.get("loss"))


if __name__ == "__main__":
    main()
