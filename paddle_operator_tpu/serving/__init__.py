"""TpuServe — the inference serving plane (ISSUE 17).

Training planes built so far (ledger, feedback, MFU, incidents, fleet
artifact store) all point here: PR 15 made replica scale-out compile-free
by construction (one lease-grant, N fleet fetches), so horizontal serving
is finally cheap enough to build. The plane has three layers:

* **control plane** (:mod:`.controller`) — a ``spec.serving`` section on
  TpuJob the reconciler scales as independent replica gangs on the
  existing membership machinery; the autoscaler's desired count flows
  through an annotation the reconciler applies to
  ``spec.worker.replicas`` (the same spec path elastic resize uses), so
  pods scale with zero new pod-lifecycle code;
* **data plane** (:mod:`.batching`, :mod:`.kv_cache`, :mod:`.engine`) —
  a continuous-batching engine over a model's module (:mod:`..models.gpt`,
  :mod:`..models.axk1`, :mod:`..models.dsv32`, :mod:`..models.evabyte`,
  :mod:`..models.ouro`, :mod:`..models.minicpm_sala`): a request queue
  with admission / load-shedding, iteration-level scheduling that admits
  new sequences into in-flight batches, and a paged KV-cache (block-table
  allocator + the ``paged_decode_attention`` Pallas kernel in
  :mod:`..ops.attention_pallas`; for a model with recurrent layers a
  state a sequence beside the pages, :class:`.kv_cache.StateKvCache`);
* **autoscaler** (:mod:`.autoscaler`) — replica count driven by queue
  depth and the ``ttft``/``tpot`` SLO burn rates
  (:func:`..obs.slo.serving_slos` on the stock burn-window evaluator),
  with the MFU plane distinguishing saturated replicas (scale out) from
  degraded ones (replace, don't multiply).

Per-request latency (queue / prefill / decode) flows into the goodput
ledger and the ``tpujob_serve_*`` metric family (:mod:`.metrics`); the
``serving_brownout`` chaos scenario (chaos/serving_faults.py) proves the
drain / shed / warm-rejoin story deterministically.
"""

from typing import Any

from .autoscaler import ScaleDecision, ServingAutoscaler  # noqa: F401
from .batching import (  # noqa: F401
    ContinuousBatcher, Request, RequestQueue, SHED_POLICIES,
)
from .controller import (  # noqa: F401
    ANNOT_DESIRED_REPLICAS, SERVING_DEFAULTS, apply_desired_replicas,
    serving_config, serving_replicas, sync_serving_spec,
)
from .kv_cache import (  # noqa: F401
    KvBlockAllocator, KvCacheFull, LatentKvCache, PagedKvCache,
    SlotBlockAllocator, StateKvCache, WindowKvCache,
)
from .metrics import ServeMetrics  # noqa: F401

__all__ = [
    "ANNOT_DESIRED_REPLICAS", "ContinuousBatcher", "KvBlockAllocator",
    "KvCacheFull", "LatentKvCache", "PagedKvCache", "WindowKvCache",
    "Request",
    "RequestQueue", "SERVING_DEFAULTS", "SHED_POLICIES", "ScaleDecision",
    "ServeMetrics", "ServingAutoscaler", "ServingEngine",
    "SlotBlockAllocator", "StateKvCache",
    "apply_desired_replicas", "serving_config", "serving_replicas",
    "sync_serving_spec",
]


def __getattr__(name: str) -> Any:
    # ServingEngine pulls in jax at import time; loading it lazily keeps
    # the operator's import chain (reconciler -> serving.controller)
    # model-free, matching how controllers/ never import models/ directly
    if name == "ServingEngine":
        from .engine import ServingEngine
        return ServingEngine
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
