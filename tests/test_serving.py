"""TpuServe serving plane: paged KV-cache, continuous batching,
SLO-driven autoscaling, and the serving control-plane glue.

Three layers, three test families:

* **data plane** — allocator conservation/fragmentation invariants, the
  paged decode kernel's interpret-mode equivalence against the gather-
  einsum reference, and the golden test: the engine's incremental
  prefill+decode token stream must be bit-identical to a full-context
  ``gpt.apply`` greedy generation, on BOTH attention paths;
* **scheduler** — FIFO admission, counted sheds under both policies,
  requeue-front overflow, drain-to-empty, preemption accounting;
* **control plane** — autoscaler decisions (backlog, burn, degraded-MFU
  replace, scale-down patience), the annotation->spec sync the
  reconciler applies, and the ``validate_serving`` admission checks.

Shared-state holders are wrapped with the declared guard specs so
``make race`` asserts the lock contracts on these exact paths.
"""

import functools

import pytest

from paddle_operator_tpu.analysis import guards
from paddle_operator_tpu.api import types as api
from paddle_operator_tpu.controllers.webhook import (
    validate_admission, validate_serving)
from paddle_operator_tpu.serving import (
    ANNOT_DESIRED_REPLICAS, ContinuousBatcher, KvBlockAllocator,
    KvCacheFull, Request, RequestQueue, ServeMetrics, ServingAutoscaler,
    apply_desired_replicas, serving_config, sync_serving_spec)


def _alloc(num_blocks=8, block_size=4):
    return guards.guard_declared(KvBlockAllocator(num_blocks, block_size))


# ---------------------------------------------------------------------------
# KV block allocator: conservation, fragmentation, all-or-nothing
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_conserves_blocks():
    a = _alloc()
    t1 = a.alloc_sequence("a", 10)      # 3 blocks
    t2 = a.alloc_sequence("b", 4)       # 1 block
    assert len(t1) == 3 and len(t2) == 1
    assert not set(t1) & set(t2)
    assert a.check() == []
    st = a.stats()
    assert st["blocks_used"] == 4 and st["blocks_free"] == 4
    # tail slack is the ONLY fragmentation: ceil(10/4)*4 - 10 = 2
    assert st["waste_slots"] == 2
    a.free_sequence("a")
    a.free_sequence("b")
    assert a.check() == []
    assert a.stats()["blocks_used"] == 0
    assert a.stats()["blocks_peak"] == 4


def test_allocator_exhaustion_is_all_or_nothing():
    a = _alloc(num_blocks=4, block_size=4)
    a.alloc_sequence("a", 12)           # 3 of 4 blocks
    with pytest.raises(KvCacheFull):
        a.alloc_sequence("b", 8)        # needs 2, only 1 free
    # the failed alloc left NOTHING allocated
    assert a.sequences() == ["a"]
    assert a.check() == []
    a.alloc_sequence("c", 4)            # the single free block still works
    assert a.stats()["blocks_free"] == 0


def test_allocator_reservation_advance_and_exhaustion():
    a = _alloc()
    a.alloc_sequence("s", 8, live_tokens=3)   # prompt 3, budget 8
    assert a.seq_len("s") == 3
    assert a.stats()["reserved_slack"] == 5
    for want in (3, 4, 5, 6, 7):
        assert a.advance("s") == want
    with pytest.raises(KvCacheFull):
        a.advance("s")                  # reservation spent
    assert a.check() == []


def test_allocator_append_token_grows_at_block_boundary():
    a = _alloc(num_blocks=4, block_size=4)
    a.alloc_sequence("s", 4)
    assert a.append_token("s") is not None      # 5th token: new block
    assert a.append_token("s") is None          # 6th: inside it
    assert len(a.block_table("s")) == 2
    assert a.seq_len("s") == 6
    assert a.check() == []


def test_allocator_free_unknown_is_noop_and_double_alloc_rejected():
    a = _alloc()
    assert a.free_sequence("ghost") == 0
    a.alloc_sequence("s", 4)
    with pytest.raises(ValueError):
        a.alloc_sequence("s", 4)


# ---------------------------------------------------------------------------
# request queue: bounded admission, counted sheds
# ---------------------------------------------------------------------------

def _queue(capacity=2, policy="reject_new", t=(0.0,)):
    clock = lambda: t[0]  # noqa: E731
    return guards.guard_declared(
        RequestQueue(capacity, shed_policy=policy, clock=clock))


def _req(i, prompt_len=4, budget=4):
    return Request("r%03d" % i, prompt=[1] * prompt_len,
                   max_new_tokens=budget)


def test_queue_fifo_and_reject_new_shed_is_counted():
    q = _queue(capacity=2)
    assert q.submit(_req(0)) == (True, None)
    assert q.submit(_req(1)) == (True, None)
    accepted, shed = q.submit(_req(2))
    assert accepted is False and shed is None
    c = q.counts()
    assert c["submitted"] == 3 and c["shed_reject_new"] == 1
    assert q.pop().request_id == "r000"     # FIFO
    assert q.pop().request_id == "r001"
    assert q.pop() is None
    assert q.counts()["admitted"] == 2


def test_queue_drop_oldest_sheds_the_stalest():
    q = _queue(capacity=2, policy="drop_oldest")
    q.submit(_req(0))
    q.submit(_req(1))
    accepted, shed = q.submit(_req(2))
    assert accepted is True and shed.request_id == "r000"
    assert q.counts()["shed_drop_oldest"] == 1
    assert [q.pop().request_id, q.pop().request_id] == ["r001", "r002"]


def test_queue_requeue_front_preserves_order_and_returns_overflow():
    q = _queue(capacity=3)
    q.submit(_req(5))
    inflight = [_req(0), _req(1), _req(2)]
    overflow = q.requeue_front(inflight)
    # capacity 3, one occupant: two fit back at the head; the OLDEST
    # in-flight request is the one returned to shed (freshness, matching
    # drop_oldest's posture) — and survivors keep FIFO order
    assert [r.request_id for r in overflow] == ["r000"]
    assert [q.pop().request_id for _ in range(3)] == \
        ["r001", "r002", "r005"]


def test_queue_rejects_bad_config():
    with pytest.raises(ValueError):
        RequestQueue(0)
    with pytest.raises(ValueError):
        RequestQueue(4, shed_policy="coin_flip")


# ---------------------------------------------------------------------------
# continuous batcher: iteration-level scheduling
# ---------------------------------------------------------------------------

def _batcher(capacity=8, max_batch=2, t=None, **kw):
    t = t if t is not None else [0.0]
    clock = lambda: t[0]  # noqa: E731
    q = guards.guard_declared(RequestQueue(capacity, clock=clock))
    b = guards.guard_declared(
        ContinuousBatcher(q, max_batch, clock=clock, **kw))
    return q, b, t


def _step_n(n):
    """Engine-step fake: every sequence emits token 7, finishing after
    its budget (the batcher enforces max_new_tokens)."""
    def step(active):
        return [(7, False)] * len(active)
    return step


def test_batcher_admits_fifo_up_to_max_batch():
    q, b, t = _batcher(max_batch=2)
    for i in range(4):
        q.submit(_req(i, budget=2))
    b.step(_step_n(1))
    assert b.active_ids() == ["r000", "r001"]   # admission order
    b.step(_step_n(1))                           # budget 2 -> both finish
    assert b.counts()["completed"] == 2
    b.step(_step_n(1))                           # freed slots refill FIFO
    assert b.active_ids() == ["r002", "r003"]


def test_batcher_defers_admission_when_kv_pool_full():
    admitted = []
    q, b, t = _batcher(max_batch=4,
                       on_admit=lambda r: len(admitted) < 1
                       and not admitted.append(r.request_id))
    for i in range(2):
        q.submit(_req(i, budget=1))
    b.step(_step_n(1))
    # r000 got the only slot; r001 deferred back to the queue FRONT
    assert admitted == ["r000"]
    assert q.depth() == 1
    assert b.counts()["admit_deferred"] == 1
    assert q.pop().request_id == "r001"


def test_batcher_completion_flows_into_metrics_and_retire():
    retired = []
    m = guards.guard_declared(ServeMetrics(job="default/unit"))
    q, b, t = _batcher(max_batch=2, metrics=m,
                       on_retire=lambda r: retired.append(r.request_id))
    q.submit(_req(0, budget=3))
    for _ in range(3):
        t[0] += 0.5
        b.step(_step_n(1))
    assert retired == ["r000"]
    c = m.counts()
    assert c["requests_ok"] == 1 and c["tokens"] == 3
    # ttft/tpot samples drained exactly once
    kinds = sorted(k for k, _ in m.slo_samples())
    assert kinds == ["tpot", "ttft"]
    assert m.slo_samples() == []


def test_batcher_preempt_returns_victims_reset():
    q, b, t = _batcher(max_batch=2)
    q.submit(_req(0, budget=8))
    b.step(_step_n(1))
    victims = b.preempt()
    assert [v.request_id for v in victims] == ["r000"]
    assert victims[0].generated == [] and victims[0].t_admitted == 0.0
    assert b.in_flight() == 0
    assert b.counts()["preempted"] == 1


def test_batcher_drain_runs_to_empty_without_admitting():
    q, b, t = _batcher(max_batch=2)
    q.submit(_req(0, budget=2))
    q.submit(_req(1, budget=2))
    q.submit(_req(2, budget=2))
    b.step(_step_n(1))                  # r000+r001 in flight, 1 token each
    iters = b.drain(_step_n(1))
    assert iters == 1                    # one more token finishes both
    assert b.in_flight() == 0
    assert q.depth() == 1                # r002 untouched by the drain
    assert b.max_batch == 2              # admission valve restored


def test_batcher_rejects_misaligned_engine_step():
    q, b, t = _batcher()
    q.submit(_req(0))
    with pytest.raises(RuntimeError):
        b.step(lambda active: [])


# ---------------------------------------------------------------------------
# serve metrics: exposition + ledger hookup
# ---------------------------------------------------------------------------

def test_serve_metrics_exposition_families():
    m = guards.guard_declared(ServeMetrics(job="default/serve"))
    r = _req(0)
    r.t_arrival, r.t_admitted = 0.0, 0.5
    r.t_first_token, r.t_done = 1.0, 2.0
    r.generated = [7, 7, 7]
    m.observe_request(r, outcome="ok")
    m.observe_request(_req(1), outcome="shed_reject_new")
    m.set_queue_depth(3)
    m.set_replicas(2)
    block = m.metrics_block()
    for family in ("tpujob_serve_requests_total",
                   "tpujob_serve_tokens_total",
                   "tpujob_serve_queue_depth",
                   "tpujob_serve_replicas",
                   "tpujob_serve_ttft_seconds_bucket",
                   "tpujob_serve_tpot_seconds_count"):
        assert family in block, family
    assert 'outcome="shed_reject_new"} 1' in block
    assert 'tpujob_serve_queue_depth{job="default/serve"} 3' in block
    with pytest.raises(ValueError):
        m.observe_request(_req(2), outcome="vanished")


def test_serve_metrics_charges_queue_wait_to_ledger():
    from paddle_operator_tpu.obs.ledger import GoodputLedger

    t = [0.0]
    ledger = GoodputLedger(clock=lambda: t[0])
    ledger.observe_phase("default", "serve", "Running")
    t[0] = 10.0
    m = ServeMetrics(job="default/serve", ledger=ledger,
                     namespace="default", name="serve")
    r = _req(0)
    r.t_arrival, r.t_admitted = 1.0, 3.0
    r.t_first_token, r.t_done = 3.5, 4.0
    r.generated = [7, 7]
    m.observe_request(r, outcome="ok")
    snap = ledger.snapshot("default", "serve")
    assert snap["badput"].get("sched_wait") == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# autoscaler: queue + burn + MFU decisions
# ---------------------------------------------------------------------------

def _burn(ttft_fast=0.0, ttft_slow=0.0, tpot_fast=0.0, tpot_slow=0.0):
    return {("ttft", "fast"): ttft_fast, ("ttft", "slow"): ttft_slow,
            ("tpot", "fast"): tpot_fast, ("tpot", "slow"): tpot_slow}


def test_autoscaler_scales_up_on_backlog():
    a = guards.guard_declared(ServingAutoscaler(max_replicas=4))
    d = a.decide(current=2, queue_depth=10)      # 5/replica > 4
    assert (d.action, d.desired) == ("scale_up", 3)


def test_autoscaler_burn_needs_both_windows():
    a = ServingAutoscaler()
    # fast window alone (transient spike): hold
    d = a.decide(1, 0, burn=_burn(ttft_fast=5.0, ttft_slow=0.1))
    assert d.action == "hold"
    # both windows burning with mfu saturated: scale out
    d = a.decide(1, 0, burn=_burn(ttft_fast=5.0, ttft_slow=3.0), mfu=0.5)
    assert (d.action, d.desired) == ("scale_up", 2)


def test_autoscaler_replaces_degraded_replicas():
    a = ServingAutoscaler()
    d = a.decide(2, 0, burn=_burn(tpot_fast=4.0, tpot_slow=4.0), mfu=0.05)
    assert d.action == "replace"
    assert d.desired == 2                        # recycle, don't multiply
    assert "degraded" in d.reason


def test_autoscaler_holds_at_max_and_scale_down_needs_patience():
    a = ServingAutoscaler(max_replicas=2, scale_down_patience=3)
    assert a.decide(2, 100).action == "hold"     # overloaded at max
    # idle: two calm decisions hold, the third steps down
    assert a.decide(2, 0).action == "hold"
    assert a.decide(2, 0).action == "hold"
    d = a.decide(2, 0)
    assert (d.action, d.desired) == ("scale_down", 1)
    # at min_replicas idle holds forever
    for _ in range(5):
        assert a.decide(1, 0).action == "hold"
    assert len(a.history()) == 9


def test_autoscaler_rejects_bad_bounds():
    with pytest.raises(ValueError):
        ServingAutoscaler(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        ServingAutoscaler(degraded_mfu=0.5, saturation_mfu=0.3)


# ---------------------------------------------------------------------------
# control plane: annotation -> spec sync, defaults
# ---------------------------------------------------------------------------

def _serving_job(serving=None, replicas=2, **spec_extra):
    spec = {"worker": {"replicas": replicas, "template": {"spec": {
        "containers": [{"name": "w", "image": "img"}]}}},
        "serving": {} if serving is None else serving}
    spec.update(spec_extra)
    return api.new_tpujob("serve", spec=spec)


def test_serving_config_defaults_and_training_none():
    cfg = serving_config(_serving_job({"maxBatch": 2}))
    assert cfg["maxBatch"] == 2
    assert cfg["queueCapacity"] == 64            # defaulted
    assert serving_config({"spec": {"worker": {}}}) is None


def test_desired_replica_annotation_round_trip():
    obj = _serving_job({"minReplicas": 1, "maxReplicas": 3})
    assert apply_desired_replicas(obj, 1) is True
    assert apply_desired_replicas(obj, 1) is False    # no-op write
    job = api.TpuJob(obj)
    assert sync_serving_spec(job) is True
    assert job.spec["worker"]["replicas"] == 1
    assert sync_serving_spec(job) is False            # already applied
    # desires clamp to the spec bounds, never reject
    apply_desired_replicas(obj, 99)
    assert sync_serving_spec(job) is True
    assert job.spec["worker"]["replicas"] == 3
    apply_desired_replicas(obj, 0)
    sync_serving_spec(job)
    assert job.spec["worker"]["replicas"] == 1


def test_sync_ignores_malformed_annotation_and_training_jobs():
    obj = _serving_job()
    obj["metadata"]["annotations"] = {ANNOT_DESIRED_REPLICAS: "lots"}
    assert sync_serving_spec(api.TpuJob(obj)) is False
    training = api.new_tpujob("train", spec={"worker": {"replicas": 2}})
    training["metadata"]["annotations"] = {ANNOT_DESIRED_REPLICAS: "4"}
    assert sync_serving_spec(api.TpuJob(training)) is False


def test_reconciler_applies_serving_annotation_end_to_end():
    from paddle_operator_tpu.testing import OperatorHarness

    h = OperatorHarness()
    h.create_job(_serving_job({"minReplicas": 1, "maxReplicas": 3}))
    h.converge()
    assert len(h.pods()) == 2

    def annotate(obj):
        apply_desired_replicas(obj, 5)            # autoscaler's write
    h.update_job_spec("serve", annotate)
    h.converge()
    job = h.get_job("serve")
    assert job.spec["worker"]["replicas"] == 3    # clamped to maxReplicas
    assert len(h.pods()) == 3


# ---------------------------------------------------------------------------
# webhook: validate_serving
# ---------------------------------------------------------------------------

def test_validate_serving_accepts_good_and_absent_specs():
    assert validate_serving(_serving_job(
        {"minReplicas": 1, "maxReplicas": 4,
         "shedPolicy": "drop_oldest"})) == []
    assert validate_serving(
        api.new_tpujob("train", spec={"worker": {"replicas": 1}})) == []
    review = {"apiVersion": "admission.k8s.io/v1", "kind":
              "AdmissionReview",
              "request": {"uid": "u", "operation": "CREATE",
                          "object": _serving_job({"maxBatch": 4})}}
    assert validate_admission(review)["response"]["allowed"] is True


def test_validate_serving_rejects_bad_counts_and_inversion():
    for field in ("minReplicas", "maxReplicas", "queueCapacity",
                  "maxBatch"):
        for bad in (0, -1, 1.5, True, "2"):
            errs = validate_serving(_serving_job({field: bad}))
            assert errs and field in errs[0], (field, bad)
    errs = validate_serving(
        _serving_job({"minReplicas": 4, "maxReplicas": 2}))
    assert errs and "minReplicas" in errs[0]


def test_validate_serving_rejects_unknown_shed_policy_and_elastic():
    errs = validate_serving(_serving_job({"shedPolicy": "coin_flip"}))
    assert errs and "shedPolicy" in errs[0]
    errs = validate_serving(
        _serving_job({}, elastic={"minReplicas": 1, "maxReplicas": 4}))
    assert errs and "spec.elastic" in errs[0]
    review = {"apiVersion": "admission.k8s.io/v1",
              "kind": "AdmissionReview",
              "request": {"uid": "u", "operation": "CREATE",
                          "object": _serving_job(
                              {"shedPolicy": "coin_flip"})}}
    out = validate_admission(review)
    assert out["response"]["allowed"] is False
    assert "shedPolicy" in out["response"]["status"]["message"]


# ---------------------------------------------------------------------------
# data plane (jax): kernel equivalence + the engine golden test
# ---------------------------------------------------------------------------

def test_supports_paged_names_the_kernels_shapes():
    from paddle_operator_tpu.ops.attention_pallas import supports_paged

    assert supports_paged((3, 2, 64), 8)
    assert supports_paged((3, 12, 48), 8)        # heads sit side by side
    assert not supports_paged((3, 129, 8), 8)    # a head a score lane
    assert not supports_paged((3, 2, 64), 6)     # sublane-hostile page
    assert not supports_paged((3, 1, 2, 64), 8)


_STACK = dict(b=4, h=2, d=64, bs=8, layers=3, pages=16, t=4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_decode_interpret_matches_reference(layer, dtype):
    """The kernel reads page ``[layer, table[b, t]]`` of the stacked
    pools in place: the first, a middle and the last layer, float32 and
    bfloat16 pages; a row of length 1, one that fills its last page to
    the last slot, a ragged one, and a pad row (length 0: zeros)."""
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.ops.attention_pallas import (
        _reference_paged_decode, paged_decode_attention)

    c = _STACK
    b, h, d, bs = c["b"], c["h"], c["d"], c["bs"]
    pool = (c["layers"], c["pages"], bs, h * d)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, h, d), jnp.float32)
    k_pages = jax.random.normal(keys[1], pool, jnp.float32).astype(dtype)
    v_pages = jax.random.normal(keys[2], pool, jnp.float32).astype(dtype)
    # ragged: each row its own depth, tables deliberately non-contiguous
    tables = jnp.asarray([[1, 5, 9, 13], [2, 6, 10, 14], [3, 7, 11, 0],
                          [0, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([1, 2 * bs, 23, 0], jnp.int32)
    scale = 1.0 / (d ** 0.5)
    ref = _reference_paged_decode(q, k_pages, v_pages, tables, lens, scale,
                                  layer)
    out = paged_decode_attention(q, k_pages, v_pages, tables, lens, layer,
                                 interpret=True)
    assert out.shape == (b, h, d) and out.dtype == q.dtype
    # float32 pages are multiplied at float32 precision; bfloat16 pages
    # are the MXU's operands as they lie, so the query is rounded to
    # bfloat16 too (2^-9 of each product; the sums stay float32) where
    # the reference keeps it float32: 4e-3 read here (since PR 36)
    assert jnp.max(jnp.abs(out - ref)) < (1e-5 if dtype == "float32"
                                          else 8e-3)
    assert not jnp.any(out[3])
    # the layer picks its pages: another layer's answer is another one
    other = _reference_paged_decode(q, k_pages, v_pages, tables, lens,
                                    scale, (layer + 1) % c["layers"])
    assert jnp.max(jnp.abs(out - other)) > 1e-2


@pytest.mark.parametrize("lens", [[5, 16, 9], [0, 5, 0], [0, 0, 0]],
                         ids=["ragged", "one-live-row", "no-live-row"])
def test_paged_decode_pads_rows_narrower_than_a_lane_tile(lens):
    """Heads whose rows do not fill whole 128-lane tiles (4 heads of 16:
    64 of 128 lanes): the cache pads the row, the kernel reads the
    padded pool and leaves the padding out. The kernel's grid ends at
    the last live row and the longest row's last page: an empty row
    inside it, the rows past it and a batch with no live row read
    zeros."""
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.ops.attention_pallas import (
        _reference_paged_decode, paged_decode_attention)

    b, h, d, bs = 3, 4, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (b, h, d), jnp.float32)
    # the padding lanes hold what must not be read
    k_pages = jax.random.normal(keys[1], (2, 9, bs, 128), jnp.float32)
    v_pages = jax.random.normal(keys[2], (2, 9, bs, 128), jnp.float32)
    tables = jnp.asarray([[1, 5], [2, 6], [3, 7]], jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    ref = _reference_paged_decode(q, k_pages, v_pages, tables, lens, 0.25,
                                  1)
    out = paged_decode_attention(q, k_pages, v_pages, tables, lens, 1,
                                 interpret=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5
    assert not jnp.any(jnp.where((lens == 0)[:, None, None], out, 0.0))


def test_paged_decode_refuses_pools_it_cannot_read_in_place():
    import jax.numpy as jnp

    from paddle_operator_tpu.ops.attention_pallas import (
        paged_decode_attention)

    q = jnp.zeros((3, 4, 16))
    pool = jnp.zeros((2, 9, 8, 128))
    tables, lens = jnp.zeros((3, 2), jnp.int32), jnp.ones((3,), jnp.int32)
    with pytest.raises(ValueError, match="stacked"):      # a layer's own
        paged_decode_attention(q, pool[0], pool[0], tables, lens, 0)
    with pytest.raises(ValueError, match="whole lanes"):  # half a tile
        paged_decode_attention(q, pool[..., :64], pool[..., :64], tables,
                               lens, 0)
    with pytest.raises(ValueError, match="cover batch"):
        paged_decode_attention(q, pool, pool, tables[:2], lens, 0)


def _greedy_full_forward(params, prompt, budget):
    """What greedy generation gives when every token is a full-context
    ``gpt.apply``: the engine tests' golden."""
    import jax.numpy as jnp

    from paddle_operator_tpu.models import gpt

    ids = list(prompt)
    for _ in range(budget):
        logits, _ = gpt.apply(params, jnp.asarray([ids], jnp.int32),
                              dtype=jnp.float32, attn_impl="einsum")
        ids.append(int(jnp.argmax(logits[0, -1])))
    return ids[len(prompt):]


def _engine_golden(attn):
    """Incremental serving (prefill + paged decode) must reproduce the
    full-context greedy generation token for token."""
    import jax

    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.serving.engine import ServingEngine

    cfg = dict(gpt.TINY_CONFIG)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 99, 7], [11, 3, 250, 42, 8], [1023]]
    budgets = [4, 3, 5]

    want = [_greedy_full_forward(params, p, n)
            for p, n in zip(prompts, budgets)]

    eng = ServingEngine(params, cfg, max_batch=4, prompt_pad=16,
                        num_blocks=64, block_size=8, attn=attn,
                        label="test-%s" % attn)
    reqs = [Request("g%d" % i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, budgets))]
    q = RequestQueue(capacity=8)
    b = ContinuousBatcher(q, max_batch=4, on_admit=eng.admit,
                          on_retire=eng.retire)
    for r in reqs:
        q.submit(r)
    for _ in range(32):
        if b.step(eng.step_fn) == 0 and q.depth() == 0:
            break
    assert [r.generated for r in reqs] == want
    assert eng.cache.allocator.check() == []
    assert eng.cache.allocator.stats()["blocks_used"] == 0


def test_engine_reference_attention_matches_full_forward():
    _engine_golden("reference")


@pytest.mark.slow
def test_engine_paged_kernel_matches_full_forward():
    # interpret-mode Pallas on CPU is slow; the reference-path twin above
    # covers the engine logic in tier-1, this one proves the kernel path
    _engine_golden("paged")


_WRITE_BS, _WRITE_PAD = 4, 16


@pytest.mark.parametrize(
    "n", [1, _WRITE_BS - 1, _WRITE_BS, _WRITE_BS + 1, _WRITE_PAD])
def test_paged_cache_write_rows_lands_whole_pages(n):
    """A prefill's padded rows land in the sequence's pages of every
    layer (slots < n hold the prompt's), and nothing else moves: not an
    earlier sequence's pages, not the sequence's reserved pages past the
    prompt's last live one, not a free page. The pools handed in are
    donated: the cache holds the new ones."""
    import jax
    import numpy as np

    from paddle_operator_tpu.serving.kv_cache import PagedKvCache

    bs, pad, layers, heads, dim = _WRITE_BS, _WRITE_PAD, 2, 2, 8
    cache = PagedKvCache(12, bs, layers, heads, dim)
    # rows of 16 stored as one whole lane tile
    assert [p.shape for p in cache.k_pages + cache.v_pages] \
        == [(layers, 13, bs, 128)] * 2 and cache.donate_pools

    def rows(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), 2)
        return tuple(jax.random.normal(k, (layers, pad, heads * dim))
                     for k in keys)

    # an earlier sequence whose table is not contiguous with the next's
    cache.allocator.alloc_sequence("hole", bs)
    first = cache.allocator.alloc_sequence("first", 2 * bs + 1,
                                           live_tokens=bs + 2)
    cache.allocator.free_sequence("hole")
    first_rows = rows(1)
    cache.write_rows("first", first_rows, bs + 2)
    # prompt n, with a generation budget that reserves pages past it
    table = cache.allocator.alloc_sequence("second", pad + 2 * bs,
                                           live_tokens=n)
    handed = cache.pools()
    # copies: a view of a buffer would keep it from being donated
    before = [np.array(pool) for pool in handed]
    second_rows = rows(2)
    cache.write_rows("second", second_rows, n)
    assert all(pool.is_deleted() for pool in handed)

    live = -(-n // bs)
    untouched = [b for b in range(cache.dummy_page)
                 if b not in table[:live]]
    assert set(first) | set(table[live:]) <= set(untouched)
    for pool, was, mine, theirs in zip(
            cache.pools(), before, second_rows, first_rows):
        now = np.asarray(pool)
        assert not now[..., heads * dim:].any()      # the lanes' padding
        now = now[..., :heads * dim]
        got = now[:, table[:live]].reshape(layers, live * bs, heads * dim)
        np.testing.assert_array_equal(got[:, :n], np.asarray(mine)[:, :n])
        np.testing.assert_array_equal(
            now[:, first[:2]].reshape(
                layers, 2 * bs, heads * dim)[:, :bs + 2],
            np.asarray(theirs)[:, :bs + 2])
        np.testing.assert_array_equal(
            now[:, untouched], was[:, untouched, :, :heads * dim])


def test_paged_cache_write_rows_is_one_program_a_padded_length(caplog):
    """After a first request, prefills of other prompt lengths trace and
    compile no further write: it is one program a padded length,
    whatever the prompt's length and page count."""
    import jax

    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.serving.engine import ServingEngine

    cfg = dict(gpt.TINY_CONFIG)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, max_batch=4, prompt_pad=16,
                        num_blocks=32, block_size=4, attn="reference",
                        label="test-one-write")

    def prefill(i, n):
        req = Request("w%d" % i, prompt=list(range(1, n + 1)),
                      max_new_tokens=2)
        assert eng.admit(req)
        eng.step_fn([req])
        eng.retire(req)

    prefill(0, 6)
    assert eng.cache._write._cache_size() == 1
    with jax.log_compiles(), caplog.at_level("WARNING", logger="jax"):
        for i, n in enumerate((1, 4, 16), 1):
            prefill(i, n)
    assert eng.cache._write._cache_size() == 1
    seen = [r.getMessage() for r in caplog.records]
    # (nor anything else: the engine builds the prompt in numpy)
    assert seen == []


def _tiny_engine(model, attn, label, prompt_pad=16):
    """An engine over ``model`` ("gpt": its decode step returns no
    counters; "axk1": it counts its expert pairs; "dsv32": those and its
    selected rows; "evabyte": its window's) at the model's tiny
    configuration."""
    import importlib

    import jax

    from paddle_operator_tpu.serving.engine import ServingEngine

    module = importlib.import_module("paddle_operator_tpu.models." + model)
    cfg = dict(module.TINY_CONFIG)
    return ServingEngine(module.init(jax.random.PRNGKey(0), cfg), cfg,
                         max_batch=4, prompt_pad=prompt_pad, num_blocks=32,
                         block_size=8, attn=attn, model=module,
                         label="%s-%s-%s" % (label, model, attn))


_BOUNDARY_PROMPTS = ([5, 99, 7], [11, 3, 250, 42, 8, 9, 9, 9, 9], [511])


def _decode_steps(eng, steps):
    """Prefill ``_BOUNDARY_PROMPTS`` in one step, then ``steps`` decode-
    only steps; each step's tokens as the engine returned them."""
    reqs = [Request("b%d" % i, prompt=list(p), max_new_tokens=steps + 1)
            for i, p in enumerate(_BOUNDARY_PROMPTS)]
    assert all(eng.admit(r) for r in reqs)
    out = []
    for _ in range(steps + 1):
        tokens = [t for t, _ in eng.step_fn(reqs)]
        for req, token in zip(reqs, tokens):
            req.generated.append(token)
        out.append(tokens)
    for req in reqs:
        eng.retire(req)
    return out[1:]


@pytest.mark.parametrize("attn", ["reference", "paged"])
def test_a_decode_step_updates_its_donated_pools_in_one_slot_a_row(attn):
    """The decode step takes the cache's two pools donated and hands the
    same two back: what was handed in is deleted afterwards, and of
    their contents one slot of every layer has changed for the row that
    decoded (and the dummy page, the pad rows' target); a prefilled
    sequence that sat the step out, every free page and every other
    slot of the row's own pages are bit-equal."""
    import numpy as np

    eng = _tiny_engine("gpt", attn, "slot")
    bs = eng.cache.allocator.block_size
    stepping = Request("a", prompt=list(range(1, 12)), max_new_tokens=4)
    waiting = Request("b", prompt=[7, 8, 9], max_new_tokens=4)
    assert eng.admit(stepping) and eng.admit(waiting)
    for req, (token, _) in zip((stepping, waiting),
                               eng.step_fn([stepping, waiting])):
        req.generated.append(token)
    handed = eng.cache.pools()
    assert [p.shape for p in handed] == [(2, 33, bs, 128)] * 2
    before = [np.array(p) for p in handed]          # copies, not views
    pos = eng.cache.allocator.seq_len("a")
    eng.step_fn([stepping])
    assert all(p.is_deleted() for p in handed)
    page = eng.cache.allocator.block_table("a")[pos // bs]
    assert page not in eng.cache.allocator.block_table("b")
    for was, pool in zip(before, eng.cache.pools()):
        changed = (np.asarray(pool) != was).any(-1)          # [L, P, bs]
        assert changed[:, page, pos % bs].all()
        changed[:, page, pos % bs] = False
        changed[:, eng.cache.dummy_page, 0] = False
        assert not changed.any()


class _PackedResult:
    """A decode step's one result beside the pools as the host may touch
    it: its copy home asked for, waited for and fetched whole
    (``__array__`` is what ``np.asarray`` calls), each logged; never
    indexed or converted element by element."""

    def __init__(self, array, log):
        self._array, self._log = array, log

    def copy_to_host_async(self):
        self._log.append("asked")
        self._array.copy_to_host_async()

    def block_until_ready(self):
        self._log.append("waited")
        self._array.block_until_ready()
        return self

    def __array__(self, *args, **kwargs):
        import numpy as np

        self._log.append("fetched")
        return np.asarray(self._array)

    def _touched(self, *args):
        raise AssertionError("a device array indexed on the host")

    __getitem__ = __int__ = __index__ = __iter__ = __len__ = _touched


@pytest.mark.parametrize("model", ["gpt", "axk1"])
def test_a_decode_step_crosses_back_in_one_device_get(model, monkeypatch):
    """Whatever the model counts beside its tokens, a decode step
    crosses the boundary as ONE array each way: the compiled step is
    handed one ``int32[max_batch, 4 + pages_per_seq]`` beside params and
    pools, sent by the step's one ``jax.device_put``, and hands ONE
    ``int32[max_batch + counters]`` back beside the pools, whose copy
    home is asked for before it is waited for and which is fetched
    once (never through ``jax.device_get``). The engine banks the
    hook's counters under their names (GPT: none), one sample a step,
    and hands on Python ints: the tokens of an engine on the reference
    attention path."""
    import jax
    import numpy as np

    steps = 3
    want = _decode_steps(_tiny_engine(model, "reference", "want"), steps)

    eng = _tiny_engine(model, "paged", "once")
    _decode_steps(eng, 1)                    # builds both steps
    eng.times.reset()
    names = {"gpt": set(),
             "axk1": {"moe.pairs_here", "moe.experts_hit"}}[model]
    decode_fn, touched = eng._decode_fn, []

    def guarded(params, pools, *handed):
        packed, = jax.tree_util.tree_leaves(handed)
        assert isinstance(packed, jax.Array)
        assert (packed.shape, packed.dtype) \
            == ((eng.max_batch, 4 + eng.pages_per_seq), np.int32)
        out, pools = decode_fn(params, pools, packed)
        assert (out.shape, out.dtype) \
            == ((eng.max_batch + len(names),), np.int32)
        touched.append([])
        return _PackedResult(out, touched[-1]), pools

    eng._decode_fn = guarded
    crossings = {"device_get": 0, "device_put": 0}
    for name in crossings:
        def counting(x, _name=name, _real=getattr(jax, name)):
            crossings[_name] += 1
            return _real(x)

        monkeypatch.setattr(jax, name, counting)
    got = _decode_steps(eng, steps)
    assert touched == [["asked", "waited", "fetched"]] * steps
    # (a put a prefill, in the first step, and one a decode step)
    assert crossings == {"device_get": 0,
                         "device_put": len(_BOUNDARY_PROMPTS) + steps}
    assert got == want
    assert all(type(t) is int for row in got for t in row)
    # the counters' samples, one a step, apart from the stages, which are
    # the spans and nothing else
    assert {k: v["steps"] for k, v in eng.times.counts().items()} \
        == {name: steps for name in names}
    assert all(k.startswith("serve.") for k in eng.times.summary())


@functools.lru_cache(maxsize=None)
def _packed_steps(model):
    """Three requests through a tiny engine of ``model`` with four rows,
    joining one a step and retiring at budgets of their own (the second
    passes position 32, where ``evabyte`` closes a window). Every decode
    step is also run as the MODEL'S HOOK on the five separate arrays,
    over a copy of the pools the step was handed. One record a decode
    step: the array handed, what the packed step and the hook returned
    (tokens, counters, pools), the pools before, what the engine handed
    on; and the engine, for what it banked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng = _tiny_engine(model, "reference", "packed", prompt_pad=64)
    hook = jax.jit(eng.model.serve_decode(
        eng.config, eng.attn, eng.cache.allocator.block_size,
        eng.cache.dummy_page))
    step, records = eng._build_decode(), []

    def host(tree):
        return [np.array(a) for a in jax.tree_util.tree_leaves(tree)]

    def spy(params, pools, handed):
        kept = jax.tree_util.tree_map(jnp.copy, pools)      # donated below
        before, packed = host(pools), np.asarray(handed)
        out, pools = step(params, pools, handed)
        tokens, hook_pools, counters = hook(
            params, kept, packed[:, 0], packed[:, 1], packed[:, 4:],
            packed[:, 2], packed[:, 3].astype(bool))
        records.append({
            "packed": packed, "out": np.asarray(out).tolist(),
            "hook_tokens": np.asarray(tokens).tolist(),
            "hook_counters": {k: int(v) for k, v in counters.items()},
            "before": before, "pools": host(pools),
            "hook_pools": host(hook_pools)})
        return out, pools

    eng._decode_fn = spy
    reqs = [Request("p%d" % i, prompt=[(7 * j + i) % 61 + 1
                                       for j in range(n)],
                    max_new_tokens=budget)
            for i, (n, budget) in enumerate(zip((3, 30, 41), (5, 6, 4)))]
    active = []
    while reqs or active:
        if reqs:
            active.append(reqs.pop(0))
            assert eng.admit(active[-1])
        decoding = [r.request_id for r in active if r.generated]
        seen = len(records)
        tokens = [t for t, _ in eng.step_fn(active)]
        for req, token in zip(active, tokens):
            req.generated.append(token)
        if decoding:
            record, = records[seen:]
            record["handed_on"] = [r.generated[-1] for r in active
                                   if r.request_id in decoding]
        for req in [r for r in active
                    if len(r.generated) == r.max_new_tokens]:
            active.remove(req)
            eng.retire(req)
    assert eng.cache.allocator.check() == []
    return eng, records


@pytest.mark.parametrize("check", ["tokens", "counters", "pad-rows"])
@pytest.mark.parametrize("model", ["gpt", "axk1", "dsv32", "evabyte"])
def test_the_packed_decode_step_is_the_models_hook(model, check):
    """The step the engine compiles around a model's ``serve_decode``
    (one array in, one out) against the hook itself on the five arrays,
    over steps of 1, 2, 3, 3, 2 and 1 live rows of 4. ``tokens``: the same
    token in every row of every step, and the live rows' are what the
    engine handed on. ``counters``: what follows the tokens is the
    hook's counters in the order of their sorted names, and what the
    engine banked is those values, one sample a step. ``pad-rows``: the
    rows past the live ones are zero (not live) in what the step is
    handed, the step left every page alone but the live rows' own and
    the dummy page, and the pools are bit for bit the hook's."""
    import numpy as np

    eng, records = _packed_steps(model)
    b = eng.max_batch
    live = [int(r["packed"][:, 3].sum()) for r in records]
    assert live == [1, 2, 3, 3, 2, 1]
    if check == "tokens":
        for n, r in zip(live, records):
            assert r["out"][:b] == r["hook_tokens"]
            assert r["handed_on"] == r["out"][:n]
    elif check == "counters":
        names = sorted(records[0]["hook_counters"])
        assert names == {
            "gpt": [], "axk1": ["moe.experts_hit", "moe.pairs_here"],
            "dsv32": ["dsa.rows_live", "dsa.rows_selected", "dsa.rung_rows",
                      "moe.experts_hit", "moe.pairs_here"],
            "evabyte": ["eva.rows_read", "eva.tokens_live",
                        "eva.windows_closed"]}[model]
        for r in records:
            assert r["out"][b:] == [r["hook_counters"][k] for k in names]
        assert set(eng.times.counts()) == set(names)
        assert all(k.startswith("serve.") for k in eng.times.summary())
        for k in names:
            assert [s.seconds for s in eng.times.samples(k)] \
                == [float(r["hook_counters"][k]) for r in records]
        if model == "evabyte":
            assert sum(r["hook_counters"]["eva.windows_closed"]
                       for r in records) == 1
    else:
        dummy = eng.cache.dummy_page
        for n, r in zip(live, records):
            packed = r["packed"]
            assert (packed[:n, 3] == 1).all() and not packed[n:].any()
            own = set(packed[:n, 4:].ravel().tolist()) | {dummy}
            for was, now, hooks in zip(r["before"], r["pools"],
                                       r["hook_pools"]):
                changed = (now != was).any(axis=(0, 2, 3))     # [pages]
                assert changed.any()
                assert set(np.nonzero(changed)[0].tolist()) <= own
                assert now.tobytes() == hooks.tobytes()


@pytest.mark.parametrize("model, pad", [("gpt", 48), ("axk1", 40)])
def test_a_buckets_prompts_are_one_numpy_fill_and_no_new_program(model, pad):
    """After one prefill of a bucket, prompts of other lengths in it
    lower and compile NOTHING (counted as the benchmark counts programs
    inside its window), and what the prefill step is handed is the
    padded prompt: ``[1, pad]`` int32, zero past the prompt. (A padded
    length of the case's own: no earlier test of the process can have
    compiled a program of these shapes.)"""
    import jax
    import numpy as np

    from benchmark.harness.compiles import CompileCounter

    eng = _tiny_engine(model, "reference", "fill", prompt_pad=pad)
    handed = []

    def prefill(i, n):
        req = Request("f%d" % i, prompt=list(range(1, n + 1)),
                      max_new_tokens=2)
        assert eng.admit(req)
        eng.step_fn([req])
        eng.retire(req)

    prefill(0, 6)
    step = eng._prefill_fns[pad]
    assert list(eng._prefill_fns) == [pad]

    def spy(params, ids, length):
        handed.append((ids, length))
        return step(params, ids, length)

    eng._prefill_fns[pad] = spy
    counter = CompileCounter.get()
    counter.mark()
    lengths = (1, pad // 2 + 3, pad)
    for i, n in enumerate(lengths, 1):
        prefill(i, n)
    assert counter.mark() == (0, 0)
    assert len(handed) == len(lengths)
    for n, (ids, length) in zip(lengths, handed):
        assert isinstance(ids, jax.Array) and isinstance(length, jax.Array)
        assert (ids.shape, ids.dtype) == ((1, pad), np.int32)
        assert (length.shape, length.dtype) == ((), np.int32)
        assert int(length) == n
        assert np.asarray(ids)[0].tolist() \
            == list(range(1, n + 1)) + [0] * (pad - n)


def test_engine_reuses_pages_with_stale_rows_past_a_shorter_prompt():
    """A sequence retires and a SHORTER prompt takes its pages: the last
    live page keeps the old sequence's rows (and the new prompt's
    padding) in the slots past ``n``; ``seq_lens`` masks them, so the
    generated tokens are the full forward's."""
    import jax
    import numpy as np

    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.serving.engine import ServingEngine

    cfg = dict(gpt.TINY_CONFIG)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, max_batch=2, prompt_pad=16,
                        num_blocks=4, block_size=8, attn="reference",
                        label="test-stale-pages")
    long_req = Request("long", prompt=[7, 300, 12, 9, 41, 5, 88, 1000, 3,
                                       64, 2], max_new_tokens=3)
    short_req = Request("short", prompt=[11, 3, 250], max_new_tokens=6)
    q = RequestQueue(capacity=4)
    b = ContinuousBatcher(q, max_batch=2, on_admit=eng.admit,
                          on_retire=eng.retire)
    tables = {}
    for req in (long_req, short_req):
        q.submit(req)
        for _ in range(16):
            live = b.step(eng.step_fn)
            if req.request_id in eng.cache.allocator.sequences():
                tables[req.request_id] = eng.cache.allocator.block_table(
                    req.request_id)
            if live == 0 and q.depth() == 0:
                break
    # LIFO free list: the short prompt's first page is one the long
    # sequence filled, and its slots past the prompt's are not zeros
    assert tables["short"][0] in tables["long"]
    assert np.asarray(eng.cache.k_pages[0])[
        0, tables["short"][0], 3:].any()
    for req in (long_req, short_req):
        assert req.generated == _greedy_full_forward(
            params, req.prompt, req.max_new_tokens)
    assert eng.cache.allocator.check() == []


# ---------------------------------------------------------------------------
# chaos: serving brownout (1 seed here; make chaos sweeps 20)
# ---------------------------------------------------------------------------

def test_serving_brownout_single_seed_and_deterministic():
    from paddle_operator_tpu.chaos import run_scenario

    report = run_scenario("serving_brownout", 3, quick=True)
    assert report.converged, report.violations
    assert report.violations == []
    assert report.extra["completed"] + report.extra["shed"] == \
        report.extra["submitted"]
    assert report.extra["cold_compiles"] == 1
    replay = run_scenario("serving_brownout", 3, quick=True)
    assert replay.fingerprint() == report.fingerprint()


# ---------------------------------------------------------------------------
# exception-path conservation: the OPS10xx-found leaks stay fixed
# ---------------------------------------------------------------------------

def test_batcher_admit_hook_raise_conserves_the_popped_request():
    """A raising on_admit must not vanish the popped queue slot: the
    request is retired as an engine error (conservation holds) and the
    failure still surfaces."""
    from paddle_operator_tpu.serving.metrics import ServeMetrics

    m = ServeMetrics(job="t/conserve")

    def exploding_admit(req):
        raise RuntimeError("kv accounting broke mid-admit")

    q, b, _ = _batcher(metrics=m, on_admit=exploding_admit)
    q.submit(_req(0))
    with pytest.raises(RuntimeError):
        b.step(_step_n(1))
    assert b.counts()["admit_error"] == 1
    assert m.counts()["requests_error"] == 1
    assert 'outcome="error"' in m.metrics_block()
    # not half-admitted anywhere: neither active nor back in the queue
    assert b.counts()["completed"] == 0 and q.depth() == 0


def test_engine_admit_validates_prompt_before_reserving_kv():
    """An invalid prompt must be rejected BEFORE alloc_sequence: a
    post-alloc reject would leak the reservation (the request never
    reaches retire)."""
    import jax

    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.serving.engine import ServingEngine

    cfg = dict(gpt.TINY_CONFIG)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, max_batch=2, prompt_pad=8,
                        num_blocks=16, block_size=4, attn="reference",
                        label="test-admit-validate")
    for bad_prompt in ([], [1] * 9):
        with pytest.raises(ValueError):
            eng.admit(Request("bad", prompt=bad_prompt, max_new_tokens=2))
    assert eng.cache.allocator.stats()["blocks_used"] == 0
    ok = Request("ok", prompt=[1, 2, 3], max_new_tokens=2)
    assert eng.admit(ok)
    assert eng.cache.allocator.stats()["blocks_used"] > 0
    eng.retire(ok)
    assert eng.cache.allocator.stats()["blocks_used"] == 0
