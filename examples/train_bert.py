"""BERT-base MLM training entry (multi-host collective; deploy/examples/bert.yaml)."""

import logging
import os

from paddle_operator_tpu.models import bert
from paddle_operator_tpu.ops import optim
from paddle_operator_tpu.parallel.sharding import bert_rules
from paddle_operator_tpu.runner import TrainJob, run_training

logging.basicConfig(level=logging.INFO)

BATCH = int(os.environ.get("TPUJOB_BATCH", "64"))
SEQ = int(os.environ.get("TPUJOB_SEQ", "512"))
STEPS = int(os.environ.get("TPUJOB_STEPS", "100"))


def main():
    # run_training hands the live mesh to a loss that declares the
    # argument: the masked-LM head then packs and loops per dp shard
    def loss_fn(p, b, mesh=None):
        return bert.loss_fn(p, b, remat=True, mesh=mesh)

    job = TrainJob(
        init_params=lambda rng: bert.init(rng),
        loss_fn=loss_fn,
        optimizer=optim.adamw(
            optim.cosine_schedule(1e-4, STEPS, STEPS // 10), weight_decay=0.01,
        ),
        make_batch=lambda rng, step: bert.synthetic_batch(rng, BATCH, SEQ),
        rules=bert_rules(),
        grad_clip=1.0,
        total_steps=STEPS,
        steps_per_call=int(os.environ.get("TPUJOB_STEPS_PER_CALL", "1")),
        checkpoint_dir=os.environ.get("TPUJOB_CHECKPOINT_DIR", ""),
    )
    out = run_training(job)
    print("final loss:", out.get("loss"))


if __name__ == "__main__":
    main()
