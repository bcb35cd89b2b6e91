"""Model zoo covering the reference's acceptance workloads (BASELINE.json):

* ResNet-50 — collective-mode image classification (deploy/examples/resnet.yaml)
* BERT — multi-host collective transformer (v5e-32 config)
* GPT — decoder-only causal LM, the long-context flagship (RoPE + causal
  flash attention + ring/Ulysses sequence parallelism)
* wide_and_deep / deepfm — PS-mode CTR models (deploy/examples/*.yaml)
* axk1 — latent attention and sigmoid-routed experts beside a shared one,
  as one chip's share of a wide expert-parallel deployment (serving only)
* dsv32 — axk1's stack with a learned sparse attention (an indexer with a
  key cache of its own, decode over the selected rows) and group-limited
  bias-corrected routing (serving only)
* ouro — a stack of layers run several times over with the same weights,
  a key-value cache of its own for every loop step, an exit gate
  (serving only; imported where it is served, as evabyte is)
* minicpm_sala — layers of two kinds in a published order: lightning
  linear attention (one fixed-size state a sequence) beside block-sparse
  grouped-query attention (the top blocks of a paged cache read in place)
  (serving only; imported where it is served)

All models are (init, apply) pure functions over dict pytrees, bf16 compute,
built from `paddle_operator_tpu.ops.nn`.
"""

from . import resnet, bert, gpt, wide_deep, deepfm, axk1, dsv32  # noqa: F401
