"""Measure the chunked LM-head cross-entropy claim.

ops/nn.py's chunked_lm_xent claims to avoid materializing the [B, S, V]
logits and their backward residuals. Two measurements, same train step,
dense vs chunked:

* XLA's OWN memory analysis of the compiled executable
  (``compiled.memory_analysis().temp_size_in_bytes``) — the compiler's
  peak temp-buffer requirement, deterministic, no timing noise, valid on
  CPU and TPU alike.
* step wall time, by bench.py's ``_timed_windows`` (windows that end
  in ``block_until_ready``); a device time only when run on the chip.

Run:  JAX_PLATFORMS=cpu python scripts/perf_ce_chunk.py         (small cfg)
      PERF_CE_PRESET=base python scripts/perf_ce_chunk.py       (GPT-2 scale)
Emits one JSON line.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    if os.environ.get("JAX_PLATFORMS", "") != "tpu":
        jax.config.update("jax_platforms",
                          os.environ.get("JAX_PLATFORMS", "cpu"))
    from functools import partial

    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel import build_train_step

    if os.environ.get("PERF_CE_PRESET") == "base":
        cfg = dict(gpt.BASE_CONFIG)
        batch, seq = 8, 2048
    else:  # CPU-friendly: small transformer, REAL-scale vocab (the point)
        cfg = dict(gpt.TINY_CONFIG, vocab_size=32000, max_seq=512)
        batch, seq = 2, 512
    batch = int(os.environ.get("PERF_CE_BATCH", batch))
    seq = int(os.environ.get("PERF_CE_SEQ", seq))
    steps = int(os.environ.get("PERF_CE_STEPS", "3"))
    chunk = int(os.environ.get("PERF_CE_CHUNK", "1024"))

    params = jax.jit(lambda k: gpt.init(k, cfg))(jax.random.PRNGKey(0))
    batch_data = gpt.synthetic_batch(jax.random.PRNGKey(1), batch,
                                     seq_len=seq,
                                     vocab_size=cfg["vocab_size"])
    opt = optim.adamw(1e-4)

    # bench._timed_windows is the one home of the step-timing method
    import bench

    out = {"stage": "ce_chunk", "backend": jax.default_backend(),
           "batch": batch, "seq": seq, "vocab": cfg["vocab_size"],
           "chunk": chunk,
           "logits_bytes_dense": batch * seq * cfg["vocab_size"] * 4}
    for name, ce in (("chunked", chunk), ("dense", 0)):
        loss_fn = partial(gpt.loss_fn, ce_chunk=ce)
        step_fn, state = build_train_step(loss_fn, opt, params, batch_data)
        # the compiler's own accounting of peak temp buffers — a fresh
        # compile per config IS the measurement (2-config sweep, not a
        # step loop)
        lowered = jax.jit(lambda s, b: step_fn(s, b)).lower(  # opslint: disable=OPS501
            state, batch_data)
        mem = lowered.compile().memory_analysis()
        if mem is not None:
            out["%s_temp_bytes" % name] = int(mem.temp_size_in_bytes)
        best, _backend = bench._timed_windows(step_fn, state, batch_data,
                                              steps)
        out["%s_step_ms" % name] = round(best * 1000, 1)
        del state
    if "dense_temp_bytes" in out and "chunked_temp_bytes" in out:
        out["temp_bytes_saved"] = (out["dense_temp_bytes"]
                                   - out["chunked_temp_bytes"])
        out["temp_reduction"] = round(
            out["dense_temp_bytes"] / max(out["chunked_temp_bytes"], 1), 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
