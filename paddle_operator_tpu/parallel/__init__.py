"""SPMD parallelism over `jax.sharding.Mesh` — the TPU-native replacement for
the reference's NCCL/gloo/gRPC process-topology wiring (SURVEY.md §2.2-2.3).

The recipe (scaling-book style): pick a mesh (dp × tp [× sp]), annotate param
and batch shardings, let XLA/GSPMD insert the ICI collectives, profile,
iterate. Data parallel = batch on `dp` (gradient psum inserted by XLA);
tensor parallel = hidden dims on `tp`; sequence parallel = activation
constraints on `sp`.
"""

from .mesh import make_hybrid_mesh, make_mesh, mesh_from_env  # noqa: F401
from .sharding import (  # noqa: F401
    shard_tree, named, P, bert_rules, gpt_rules, resnet_rules, ctr_rules,
    moe_rules,
)
from .train import batch_shardings, build_train_step  # noqa: F401
from .pipeline import pipeline_apply, stack_stage_params  # noqa: F401
from .context import (  # noqa: F401
    ring_attention, ring_flash_attention, sharded_flash_attention,
    ulysses_attention,
)
