"""models/dsv32 on the CPU at a tiny size (hidden 64, 4 heads, ranks
32/16, an indexer of 2 heads x 16 that selects 16 positions, 8 experts
in 2 groups of which a token takes 2 inside 1 group and this chip holds
4, one dense + two expert layers), contexts of 40-100 so that the
selection is live, against the benchmark's plain reference
(``benchmark/reference/dsv32.py``: float32, not absorbed, dense scores
masked to the selection, no cache; it imports nothing of the program)."""

import functools
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

from cellbench_tiny_dsv32 import TINY_DSV32 as TINY
from test_axk1 import _expert_layer as _plain_expert_layer
from test_axk1 import _share

from benchmark.families import dsv32 as family
from benchmark.reference import axk1 as axk1_reference
from benchmark.reference import dsv32 as reference
from paddle_operator_tpu import compile_cache
from paddle_operator_tpu.models import axk1, dsv32
from paddle_operator_tpu.ops import attention_pallas as ap
from paddle_operator_tpu.ops import moe
from paddle_operator_tpu.serving.batching import Request
from paddle_operator_tpu.serving.engine import ServingEngine
from paddle_operator_tpu.serving.kv_cache import LatentKvCache


@pytest.fixture(scope="module")
def params():
    return family.make_params(TINY, 30)


@pytest.fixture
def chunks_of_16(monkeypatch):
    """Prefill walks a prompt in several chunks, as the real size does."""
    monkeypatch.setattr(dsv32, "PREFILL_CHUNK", 16)


def test_the_tiny_preset_is_the_tiny_file():
    cfg = family.program_config(TINY)
    assert cfg == dsv32.TINY_CONFIG
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), family.make_params(TINY, 1)) \
        == jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype),
            dsv32.init(jax.random.PRNGKey(1), cfg))


def test_the_published_preset_is_the_catalogs_row():
    cfg = dsv32.BASE_CONFIG
    assert (cfg["layers"], cfg["dense_layers"], cfg["hidden"], cfg["heads"],
            cfg["router_experts"], cfg["n_group"], cfg["topk_group"],
            cfg["index_heads"], cfg["index_head_dim"], cfg["index_topk"],
            cfg["rope_factor"], cfg["vocab_size"], cfg["max_seq"]) \
        == (61, 3, 7168, 128, 256, 8, 4, 64, 128, 2048, 40.0, 129280, 163840)
    # the widths it shares with A.X-K1 are that preset's
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "mlp_dim", "moe_mlp_dim"):
        assert cfg[key] == axk1.BASE_CONFIG[key]
    with pytest.raises(ValueError, match="one leading dense layer"):
        dsv32.init(jax.random.PRNGKey(0), dict(dsv32.TINY_CONFIG,
                                               dense_layers=3, layers=5))


# -- prefill, then decode, against one forward ------------------------------

@pytest.mark.parametrize("attn", ["paged", "reference"])
def test_prefill_then_decode_through_both_caches_gives_the_references_logits(
        params, attn, chunks_of_16):
    """Three prompts (one shorter than the selection, two longer, each
    walked in chunks of 16) prefilled, their rows and index keys written
    into pages, then five decode steps of the whole batch: at every step
    each row's logits against the reference's full forward over
    everything the row has seen."""
    cfg = family.program_config(TINY)
    bs, blocks, batch = 8, 48, 4
    cache = dsv32.serve_cache(cfg, blocks, bs)
    rnd = np.random.RandomState(0)
    prompts = [list(rnd.randint(0, 512, size=n)) for n in (5, 40, 61)]
    seqs = []
    for i, prompt in enumerate(prompts):
        cache.allocator.alloc_sequence("s%d" % i, len(prompt) + 6,
                                       live_tokens=len(prompt))
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(prompt)] = prompt
        token, rows = jax.jit(dsv32.serve_prefill(cfg, 64))(
            params, jnp.asarray(ids), jnp.asarray(len(prompt), jnp.int32))
        cache.write_rows("s%d" % i, rows, len(prompt))
        seqs.append(prompt + [int(token)])
    decode = jax.jit(lambda *a: dsv32.decode(
        cfg, *a, attn_impl=attn, block_size=bs, dummy_page=blocks,
        with_logits=True))
    pools = cache.pools()
    apart = []
    for step in range(5):
        tokens, positions, lens = [0] * batch, [0] * batch, [0] * batch
        tables = np.zeros((batch, 128 // bs), np.int32)
        for i, seq in enumerate(seqs):
            sid = "s%d" % i
            tokens[i], lens[i] = seq[-1], cache.allocator.seq_len(sid)
            positions[i] = cache.allocator.advance(sid)
            table = cache.allocator.block_table(sid)
            tables[i, :len(table)] = table
        out, pools, counters, logits = decode(
            params, pools, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32),
            jnp.asarray([True, True, True, False]))
        for i, seq in enumerate(seqs):
            want = reference.logits(params, jnp.asarray([seq], jnp.int32),
                                    TINY, "f32")[0, -1]
            apart.append(float(jnp.max(jnp.abs(logits[i] - want))))
            seq.append(int(out[i]))
        # the rows hold 6 + 41 + 62 tokens at the first step and one more
        # each at every further one; a layer reads min(n, 16) of each
        assert int(counters["dsa.rows_live"]) == 109 + 3 * step
        assert int(counters["dsa.rows_selected"]) == 6 + step + 16 + 16
        assert 0 < int(counters["moe.pairs_here"]) <= 12
        assert 0 < int(counters["moe.experts_hit"]) <= 8
    # bfloat16 activations against float32, logits of spread 1.5. Where
    # rounding flips a router's second choice or the selection's
    # sixteenth, part of a layer's output moves, which is no rounding
    # error: such rows are few and bounded
    assert sorted(apart)[len(apart) // 2] < 0.15, apart
    assert sum(a > 0.3 for a in apart) <= 3 and max(apart) < 1.5, apart
    # the prefill's first token is the reference's too
    for prompt, seq in zip(prompts, seqs):
        want = reference.logits(params, jnp.asarray([prompt], jnp.int32),
                                TINY, "f32")[0, -1]
        assert float(jnp.max(want) - want[seq[len(prompt)]]) < 0.2


def test_the_engine_serves_it_token_for_token_on_both_attention_paths(
        params, chunks_of_16):
    """``attn="paged"`` (the two kernels, interpreted) against
    ``attn="reference"`` (gather and einsum) through ``step_fn``: the
    same tokens; and the reference's logit of every served token lies
    near its best."""
    cfg = family.program_config(TINY)
    served = {}
    for attn in ("paged", "reference"):
        engine = ServingEngine(params, cfg, max_batch=4, prompt_pad=64,
                               num_blocks=40, block_size=8, attn=attn,
                               model=dsv32, label="serve-dsv32-" + attn)
        assert engine.buckets == (64,)
        rnd = np.random.RandomState(1)
        reqs = [Request("r%d" % i,
                        [int(t) for t in rnd.randint(0, 512, size=n)],
                        max_new_tokens=8) for i, n in enumerate((9, 40, 64))]
        assert all(engine.admit(r) for r in reqs)
        for _ in range(8):
            for req, (token, _) in zip(reqs, engine.step_fn(reqs)):
                req.generated.append(token)
        served[attn] = [list(r.generated) for r in reqs]
        if attn == "paged":
            for req in reqs:
                ids = jnp.asarray([list(req.prompt) + req.generated],
                                  jnp.int32)
                logits = reference.logits(params, ids, TINY, "f32")[0]
                lo = len(req.prompt) - 1
                gaps = [float(jnp.max(logits[lo + j]) - logits[lo + j, t])
                        for j, t in enumerate(req.generated)]
                assert max(gaps) < 1.0 and sorted(gaps)[4] < 0.2, gaps
            counts = engine.times.counts()
            # seven decode steps banked their counters beside the spans
            for name in ("dsa.rows_live", "dsa.rows_selected",
                         "moe.pairs_here", "moe.experts_hit"):
                assert counts[name]["steps"] == 7, name
            build = engine.times.samples("serve.prefill.build")
            assert sorted(s.attrs["prompt_len"] for s in build) \
                == [9, 40, 64]
        for req in reqs:
            engine.retire(req)
        assert engine.cache.allocator.check() == []
        assert engine.cache.allocator.stats()["blocks_used"] == 0
    assert served["paged"] == served["reference"]


# -- the rung: the sparse attention runs over the rows up to the last live --

RUNG_BATCH = 16


def _rung_cases():
    """(rows live, the rung they take) for every rung of the ladder at
    1, r - 1, r, r + 1 and all rows live, packed to the front; then live
    rows that are NOT at the front."""
    ladder = dsv32._decode_rungs(RUNG_BATCH)
    cases = []
    for r in ladder[:-1]:
        for n in (1, r - 1, r, r + 1, RUNG_BATCH):
            cases.append((tuple(range(n)), next(x for x in ladder if x >= n)))
    cases.append(((0, 9), RUNG_BATCH))
    cases.append(((2,), ladder[0]))
    return cases


@pytest.fixture(scope="module")
def rung_steps(params):
    """One decode step of 16 rows over pools of random rows (contexts of
    20-60 tokens, so every row's selection of 16 is live), as three
    programs of the same arguments: the paged step with its ladder, the
    paged step with no rung below the batch (the parent's program), and
    ``attn="reference"``."""
    cfg = family.program_config(TINY)
    bs, per_seq, b = 8, 8, RUNG_BATCH
    pages = b * per_seq
    rnd = np.random.RandomState(47)
    ks = jax.random.split(jax.random.PRNGKey(47), 2)
    pools = tuple(
        jax.random.normal(k, (cfg["layers"], pages + 1, bs, 128),
                          jnp.float32).astype(jnp.bfloat16) for k in ks)
    lens = rnd.randint(20, 60, size=b).astype(np.int32)
    tables = rnd.permutation(pages).reshape(b, per_seq).astype(np.int32)
    tokens = rnd.randint(0, 512, size=b).astype(np.int32)

    # name -> (attn, the ladder the step is traced under, at its first call)
    kinds = {"ladder": ("paged", dsv32.DECODE_RUNGS), "whole": ("paged", ()),
             "reference": ("reference", dsv32.DECODE_RUNGS)}
    programs = {
        name: jax.jit(functools.partial(
            dsv32.decode, cfg, attn_impl=attn, block_size=bs,
            dummy_page=pages, with_logits=True))
        for name, (attn, _) in kinds.items()}

    def step(name, rows):
        live = np.zeros((b,), bool)
        live[list(rows)] = True
        # a row that is not live is handed zeros, as the engine packs them
        keep = lambda a: jnp.asarray(np.where(live, a, 0))
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(dsv32, "DECODE_RUNGS", kinds[name][1])
            return programs[name](
                params, pools, keep(tokens), keep(lens),
                jnp.asarray(np.where(live[:, None], tables, 0)), keep(lens),
                jnp.asarray(live))
    return step


@pytest.mark.parametrize("rows,rung", _rung_cases())
def test_the_sparse_attention_runs_over_the_rung_that_covers_the_live_rows(
        rung_steps, rows, rung):
    """Whatever rows are live, a live row's token is the reference
    step's, its logits are bit for bit those of the step that runs all
    16 rows, both pools' live pages and the counters of the live rows
    are equal in all three, and ``dsa.rung_rows`` reads the smallest rung that
    reaches the last live row."""
    rows = list(rows)
    tokens, pools, counters, logits = rung_steps("ladder", rows)
    whole_tokens, whole_pools, whole_counters, whole_logits = rung_steps(
        "whole", rows)
    ref_tokens, ref_pools, ref_counters, _ = rung_steps("reference", rows)
    assert np.asarray(tokens)[rows].tolist() \
        == np.asarray(ref_tokens)[rows].tolist() \
        == np.asarray(whole_tokens)[rows].tolist()
    np.testing.assert_array_equal(np.asarray(logits)[rows],
                                  np.asarray(whole_logits)[rows])
    # every page a table names (the dummy page, the last, takes the rows
    # that are not live: what such a row computes is nobody's business)
    for got, whole, ref in zip(pools, whole_pools, ref_pools):
        np.testing.assert_array_equal(np.asarray(got[:, :-1], np.float32),
                                      np.asarray(whole[:, :-1], np.float32))
        np.testing.assert_array_equal(np.asarray(got[:, :-1], np.float32),
                                      np.asarray(ref[:, :-1], np.float32))
    for name in ("dsa.rows_live", "dsa.rows_selected", "moe.pairs_here",
                 "moe.experts_hit"):
        assert int(counters[name]) == int(whole_counters[name]) \
            == int(ref_counters[name]), name
    assert int(counters["dsa.rows_selected"]) == 16 * len(rows)
    assert int(counters["dsa.rung_rows"]) == rung
    # the yardsticks run the whole batch
    assert int(whole_counters["dsa.rung_rows"]) \
        == int(ref_counters["dsa.rung_rows"]) == RUNG_BATCH


def _conditionals(jaxpr):
    """The branch counts of every ``cond`` in a jaxpr, loops and calls
    looked into, kernels' bodies (their ``pl.when``) not."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(len(eqn.params["branches"]))
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _conditionals(sub)
    return found


@pytest.mark.parametrize("batch", [16, 8, 4, 2])
def test_a_decode_step_holds_one_conditional_a_site_with_a_branch_a_rung(
        params, batch, monkeypatch):
    """Two sites call the sparse attention (the dense layer, the scan's
    body). A batch with rungs below it holds one conditional at each,
    with as many branches as ``_decode_rungs`` says; a batch no larger
    than the smallest rung holds none, and ``attn="reference"`` never
    does. Lowered for the chip (the kernels as Mosaic calls: interpreted,
    their ``pl.when`` would be conditionals of the text too)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = family.program_config(TINY)
    rungs = dsv32._decode_rungs(batch)
    assert rungs == tuple(r for r in dsv32.DECODE_RUNGS if r < batch) \
        + (batch,)
    row = jax.ShapeDtypeStruct((batch,), jnp.int32)
    pool = jax.ShapeDtypeStruct((cfg["layers"], 9, 8, 128), jnp.bfloat16)
    args = (params, (pool, pool), row, row,
            jax.ShapeDtypeStruct((batch, 4), jnp.int32), row,
            jax.ShapeDtypeStruct((batch,), bool))
    for attn in ("paged", "reference"):
        traced = jax.jit(dsv32.serve_decode(cfg, attn, 8, 8)).trace(*args)
        want = [len(rungs)] * 2 if attn == "paged" and len(rungs) > 1 else []
        assert _conditionals(traced.jaxpr.jaxpr) == want, attn
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("stablehlo.case") == len(want), attn
        # a rung is traced and lowered once, whichever site calls it
        assert len(set(re.findall(r"func\.func private @(_sparse_rows\w*)",
                                  text))) \
            == (len(rungs) if attn == "paged" else 0), attn


def test_an_engine_builds_one_decode_program_whatever_rows_are_live(
        params, chunks_of_16):
    """An engine of 16 rows stepped with 1, 3, 5, 9 and 16 of them live
    (every rung of the ladder): ONE program is lowered and compiled, in
    the first decode step, and the later steps lower and compile nothing
    (counted as the benchmark counts a window's); the rung is among the
    step's counters."""
    from benchmark.harness.compiles import CompileCounter

    cfg = family.program_config(TINY)
    engine = ServingEngine(params, cfg, max_batch=16, prompt_pad=64,
                           num_blocks=16 * 9, block_size=8, attn="paged",
                           model=dsv32, label="serve-dsv32-rungs")
    rnd = np.random.RandomState(3)
    reqs = [Request("r%d" % i, [int(t) for t in rnd.randint(0, 512, size=n)],
                    max_new_tokens=8)
            for i, n in enumerate(rnd.randint(20, 60, size=16))]
    assert all(engine.admit(r) for r in reqs)

    def step(n):
        for req, (token, _) in zip(reqs[:n], engine.step_fn(reqs[:n])):
            req.generated.append(token)

    step(16)                    # the prefills, no decode row yet
    counter = CompileCounter.get()
    counter.mark()
    rung = []
    for n in (1, 3, 5, 9, 16):
        step(n)
        # (programs lowered, programs compiled): the step, once
        assert counter.mark() == ((1, 1) if n == 1 else (0, 0)), n
        rung.append(int(engine.times.samples("dsa.rung_rows")[-1].seconds))
    ladder = dsv32._decode_rungs(16)
    assert rung == [next(x for x in ladder if x >= n)
                    for n in (1, 3, 5, 9, 16)]
    assert "dsa.rung_rows" in engine._counters


def test_buckets_are_eighths_of_the_prompt_pad_in_whole_chunks():
    assert dsv32.serve_buckets({}, 32768) == tuple(
        4096 * i for i in range(1, 9))
    assert dsv32.serve_buckets({}, 8192) == tuple(
        1024 * i for i in range(1, 9))
    # an eighth that is no whole chunk: the pad alone
    assert dsv32.serve_buckets({}, 4096) == (4096,)
    assert dsv32.serve_buckets({}, 64) == (64,)
    with pytest.raises(ValueError, match="no multiple"):
        jax.eval_shape(dsv32.serve_prefill(dsv32.TINY_CONFIG, 1536),
                       dsv32.init(jax.random.PRNGKey(0), dsv32.TINY_CONFIG),
                       jnp.zeros((1, 1536), jnp.int32), jnp.int32(5))


# -- the shared code: with the selection off it IS dense latent attention ---

def test_with_a_selection_no_smaller_than_the_context_it_is_axk1s_attention(
        params, chunks_of_16):
    """``index_topk`` >= every context: prefill's rows and first token
    and decode's logits are those of ``models.axk1`` on the same weights
    (its stack, its dense attention, plain top-k over one group with no
    bias), whatever the indexer scores."""
    cfg = dict(family.program_config(TINY), index_topk=128, n_group=1,
               topk_group=1)
    plain = jax.tree_util.tree_map(lambda a: a, params)
    plain["experts"]["moe"] = {k: v for k, v in params["experts"]["moe"].items()
                               if k != "bias"}
    flat = dict(params, experts=dict(params["experts"], moe=dict(
        params["experts"]["moe"],
        bias=jnp.zeros_like(params["experts"]["moe"]["bias"]))))
    rnd = np.random.RandomState(2)
    prompt = list(rnd.randint(0, 512, size=45))
    ids = np.zeros((1, 64), np.int32)
    ids[0, :45] = prompt
    args = (jnp.asarray(ids), jnp.asarray(45, jnp.int32))
    token, (rows, keys) = jax.jit(dsv32.serve_prefill(cfg, 64))(flat, *args)
    want_token, (want_rows,) = jax.jit(axk1.serve_prefill(cfg, 64))(plain, *args)
    assert int(token) == int(want_token)
    apart = np.abs(np.asarray(rows[:, :45], np.float32)
                   - np.asarray(want_rows[:, :45], np.float32))
    # the first layer's rows see the same input: equal to the bit. Further
    # up, the two attentions round differently (a running softmax a key
    # block at a time against one softmax over the prompt), and where
    # that flips a router's choice a token's row moves: few, bounded
    assert not apart[0].any()
    assert np.mean(apart > 0.06) < 0.02 and apart.max() < 1.0
    # decode: one row of 45 cached tokens, both models' own caches
    bs, blocks = 8, 16
    logits = {}
    for name, model, weights, cached in (
            ("dsv32", dsv32, flat, (rows, keys)),
            ("axk1", axk1, plain, (want_rows,))):
        cache = model.serve_cache(cfg, blocks, bs)
        cache.allocator.alloc_sequence("s", 50, live_tokens=45)
        cache.write_rows("s", cached, 45)
        table = cache.allocator.block_table("s")
        tables = np.zeros((2, 128 // bs), np.int32)
        tables[0, :len(table)] = table
        _, _, _, logits[name] = jax.jit(lambda *a: model.decode(
            cfg, *a, attn_impl="paged", block_size=bs, dummy_page=blocks,
            with_logits=True))(
                weights, cache.pools(), jnp.asarray([int(token), 0]),
                jnp.asarray([45, 0]), jnp.asarray(tables),
                jnp.asarray([45, 0]), jnp.asarray([True, False]))
    apart = np.abs(np.asarray(logits["dsv32"][0] - logits["axk1"][0]))
    assert np.median(apart) < 0.02 and apart.max() < 0.3, apart.max()


def test_the_indexers_rotation_and_scores_are_the_references():
    """One layer's index scores I[t, s] as the program computes them
    against the reference's: both on queries and keys computed in
    float32 and rounded to bfloat16 as stored, so both select the same
    keys in every row."""
    cfg = dsv32.TINY_CONFIG
    p = dsv32.init(jax.random.PRNGKey(3), cfg, jnp.float32)["dense"]["attn"]
    z = jax.random.normal(jax.random.PRNGKey(4), (40, 64), jnp.float32)
    inv_freq, _ = axk1._rotary(cfg)
    (_, _, q_idx, w), (_, key) = dsv32._dsa_inputs(
        cfg, p, z, jnp.arange(40), inv_freq, lambda dtype: z.astype(dtype))
    assert q_idx.dtype == key.dtype == jnp.bfloat16 and w.dtype == jnp.float32
    got = dsv32._index_scores(q_idx, w, key)
    config = dict(TINY, index_topk=40)
    c_q = axk1_reference.rms(p["q_norm"], z @ p["q_a"], 1e-6)
    ref_inv = axk1_reference.yarn_inv_freq(8, 1e4, TINY["rope_scaling"])
    np.testing.assert_allclose(inv_freq, ref_inv, rtol=1e-6)
    # the reference's selection at a top of 3: its three largest scores
    # of each row are the program's three largest
    chosen = reference.selection(p["indexer"], c_q, z,
                                 dict(config, index_topk=3), "f32", ref_inv)
    causal = np.tril(np.ones((40, 40), bool))
    mine = np.where(causal, np.asarray(got), -np.inf)
    agree = 0
    for t in range(3, 40):
        agree += set(np.argsort(-mine[t], kind="stable")[:3]) \
            == set(np.flatnonzero(np.asarray(chosen[t])))
    assert np.asarray(chosen).sum(axis=1).tolist() \
        == [min(t + 1, 3) for t in range(40)]
    assert agree == 37


# -- selection ----------------------------------------------------------------

@pytest.mark.parametrize("top", [1, 5, 16, 40, 64])
def test_prefills_mask_selects_what_decodes_top_k_selects(top):
    """``_selection_mask`` (prefill: 32 counting passes, no sort) and
    ``select_rows`` (decode: ``lax.top_k``) take the same set, ties and
    unseen keys included; the reference's ``largest`` agrees."""
    rnd = np.random.RandomState(top)
    scores = rnd.randn(7, 40).astype(np.float32)
    scores[1, 5:30] = 0.25          # many equal scores across the cut
    scores[2] = -1.5                # all equal
    scores[3, :] = np.round(scores[3], 1)      # some ties
    scores[4, 3] = 0.0
    scores[4, 9] = -0.0
    lens = np.asarray([40, 40, 40, 40, 40, 12, 1], np.int32)
    scores = np.where(np.arange(40)[None] < lens[:, None], scores, -np.inf)
    mask = np.asarray(dsv32._selection_mask(jnp.asarray(scores), top))
    chosen, count = ap.select_rows(jnp.asarray(scores), jnp.asarray(lens),
                                   top)
    chosen, count = np.asarray(chosen), np.asarray(count)
    assert count.tolist() == np.minimum(lens, top).tolist()
    for b in range(7):
        picked = chosen[b, :count[b]]
        assert picked.tolist() == sorted(picked.tolist())
        if b != 4:       # -0.0 and 0.0 are one score to a sort only
            assert set(picked) == set(np.flatnonzero(mask[b])), b
        assert mask[b].sum() == count[b]
        # the stable order's first ``top``: ties to the lower position
        want = np.argsort(-scores[b, :lens[b]], kind="stable")[:top]
        assert set(picked) == set(want), b
    np.testing.assert_array_equal(
        np.delete(np.asarray(reference.largest(jnp.asarray(scores), top)),
                  4, 0), np.delete(mask, 4, 0))


def test_a_row_no_longer_than_the_selection_takes_the_dense_kernels_result():
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    layers, pages, bs, b, t = 2, 12, 8, 3, 5
    keys = jax.random.normal(ks[0], (layers, pages, bs, 128), jnp.bfloat16)
    latent = jax.random.normal(ks[1], (layers, pages, bs, 128), jnp.bfloat16)
    q_idx = jax.random.normal(ks[2], (b, 2, 16), jnp.bfloat16)
    w = jax.random.normal(ks[3], (b, 2), jnp.float32)
    q_lat = jax.random.normal(ks[4], (b, 4, 16), jnp.bfloat16)
    q_rope = jax.random.normal(ks[5], (b, 4, 8), jnp.bfloat16)
    tables = jnp.arange(b * t).reshape(b, t) % pages
    lens = jnp.asarray([37, 1, 16])
    scores = ap.dsa_index_scores(q_idx, w, keys, tables, lens, layer=1,
                                 interpret=True)
    chosen, count = ap.select_rows(scores, lens, 16)
    got = ap.mla_selected_decode(q_lat, q_rope, latent, tables, chosen,
                                 count, 0.3, layer=1, interpret=True)
    dense = ap.mla_paged_decode(q_lat, q_rope, latent, tables, lens, 0.3,
                                layer=1, interpret=True)
    # rows of 1 and 16 tokens: bit for bit; the row of 37 is another sum
    np.testing.assert_array_equal(np.asarray(got[1:], np.float32),
                                  np.asarray(dense[1:], np.float32))
    assert float(jnp.max(jnp.abs(got[0].astype(jnp.float32)
                                 - dense[0].astype(jnp.float32)))) > 0.05


# -- the kernels ----------------------------------------------------------------

def _index_case(seed=0, b=3, j=4, di=16, bs=8, pages=12, per_seq=5,
                layers=2, width=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, j, di), jnp.float32),
            jax.random.normal(ks[1], (b, j), jnp.float32),
            jax.random.normal(ks[2], (layers, pages, bs, width),
                              jnp.float32).at[..., di:].set(0.0),
            jax.random.randint(ks[3], (b, per_seq), 0, pages),
            jnp.asarray([1, 17, 40], jnp.int32))


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("per_cell", [2, 8])
def test_index_scores_match_their_reference_interpreted(layer, per_cell,
                                                        monkeypatch):
    """Cells of 2 pages (5 pages a row: the last cell is half past the
    table) and of 8 (one cell a row)."""
    monkeypatch.setattr(ap, "INDEX_PAGES_PER_CELL", per_cell)
    q_idx, w, pool, tables, lens = _index_case()
    got = ap.dsa_index_scores(q_idx, w, pool, tables, lens, layer=layer,
                              interpret=True)
    want = ap._reference_index_scores(q_idx, w, pool[layer], tables, lens)
    assert got.shape == (3, 40)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.isneginf(np.asarray(got)[0, 1:]).all()
    # one layer's pool handed over alone is the same call
    np.testing.assert_allclose(
        ap.dsa_index_scores(q_idx, w, pool[layer], tables, lens,
                            interpret=True), got, atol=1e-7)


def test_index_scores_refuse_what_does_not_fit():
    q_idx, w, pool, tables, lens = _index_case()
    with pytest.raises(ValueError, match="say which"):
        ap.dsa_index_scores(q_idx, w, pool, tables, lens)
    with pytest.raises(ValueError, match="do not match"):
        ap.dsa_index_scores(q_idx, w, pool[0, :, :, :8], tables, lens)
    with pytest.raises(ValueError, match="do not match"):
        ap.dsa_index_scores(q_idx, w[:, :2], pool[0], tables, lens)
    with pytest.raises(ValueError, match="do not cover"):
        ap.dsa_index_scores(q_idx, w, pool[0], tables[:2], lens)


@pytest.mark.parametrize("top", [8, 12, 40])
def test_selected_decode_matches_its_reference_interpreted(top):
    """A selection of 8 (one page of its own), of 12 (padded to two) and
    of the whole table."""
    ks = jax.random.split(jax.random.PRNGKey(top), 4)
    q_idx, w, keys, tables, lens = _index_case(seed=top)
    latent = jax.random.normal(ks[0], keys.shape, jnp.float32)
    q_lat = jax.random.normal(ks[1], (3, 4, 16), jnp.float32)
    q_rope = jax.random.normal(ks[2], (3, 4, 8), jnp.float32)
    scores = ap._reference_index_scores(q_idx, w, keys[1], tables, lens)
    chosen, count = ap.select_rows(scores, lens, top)
    got = ap.mla_selected_decode(q_lat, q_rope, latent, tables, chosen,
                                 count, 0.3, layer=1, interpret=True)
    want = ap._reference_mla_selected_decode(q_lat, q_rope, latent, tables,
                                             chosen, count, 0.3, layer=1)
    np.testing.assert_allclose(got, want, atol=3e-6)
    # the softmax is over the chosen rows alone: by hand for the longest
    rows = jnp.take(latent[1], tables[2], axis=0).reshape(40, -1)[
        chosen[2, :count[2]]]
    q = jnp.concatenate([q_lat[2], q_rope[2]], axis=-1)
    p = jax.nn.softmax(q @ rows[:, :24].T * 0.3, axis=-1)
    np.testing.assert_allclose(got[2], p @ rows[:, :16], atol=3e-5)


# -- the cache: two pools, one allocator ----------------------------------------

def test_two_pools_share_one_allocator_and_one_block_table():
    cache = LatentKvCache(6, 4, layers=2, widths=(24, 16))
    assert [p.shape for p in cache.k_pages] == [(2, 7, 4, 128)] * 2
    assert cache.v_pages == [] and cache.donate_pools
    assert all(p.dtype == jnp.bfloat16 for p in cache.k_pages)
    cache.allocator.alloc_sequence("a", 9, live_tokens=6)
    cache.allocator.alloc_sequence("b", 4, live_tokens=3)
    rows = (jnp.arange(2 * 8 * 24, dtype=jnp.float32).reshape(2, 8, 24) / 64,
            -jnp.arange(2 * 8 * 16, dtype=jnp.float32).reshape(2, 8, 16) / 64)
    cache.write_rows("a", rows, 6)
    latent, keys = cache.pools()
    table = cache.allocator.block_table("a")
    assert len(table) == 3
    for pool, written, width in ((latent, rows[0], 24), (keys, rows[1], 16)):
        got = jnp.take(pool, jnp.asarray(table[:2]), axis=1).reshape(
            2, 8, 128)
        np.testing.assert_array_equal(
            np.asarray(got[:, :6, :width], np.float32),
            np.asarray(written[:, :6].astype(jnp.bfloat16), np.float32))
        assert not np.asarray(got[..., width:], np.float32).any()
        # b's page and the third page of a (the budget) are untouched
        for page in cache.allocator.block_table("b") + table[2:]:
            assert not np.asarray(pool[:, page], np.float32).any()
    # the pools go to the decode step as a pair and come back as one
    cache.set_pools((latent + 1, keys))
    assert float(cache.k_pages[0][0, 0, 0, 0]) != float(latent[0, 0, 0, 0])
    assert cache.allocator.check() == []
    cache.allocator.free_sequence("a")
    cache.allocator.free_sequence("b")
    assert cache.allocator.stats()["blocks_used"] == 0
    # one width is the cache ``models.axk1`` has: a tuple of one pool
    pool, = LatentKvCache(6, 4, layers=2, widths=(24,)).pools()
    assert pool.shape == (2, 7, 4, 128)


# -- routing inside groups, with a bias that chooses only -----------------------

def _expert_layer(seed=5, routed=16, std=0.3):
    """``tests/test_axk1``'s random expert layer and a routing bias."""
    return dict(_plain_expert_layer(seed=seed, routed=routed, std=std),
                bias=std * jax.random.normal(jax.random.PRNGKey(seed + 100),
                                             (routed,), jnp.float32))


def test_the_eight_shares_add_up_to_the_uncut_layer_under_grouped_routing():
    """What each of eight chips computes of one expert layer (its two of
    the 16 routed experts, routed over all 16 in 4 groups of which 2 are
    kept, on biased scores), the shared expert counted once, is the
    reference's layer with every expert held."""
    layer = _expert_layer()
    z = jax.random.normal(jax.random.PRNGKey(6), (24, 64), jnp.float32)
    config = dict(TINY, held_experts=list(range(16)), n_group=4,
                  topk_group=2, num_experts_per_tok=4)
    whole = reference.expert_ffn(layer, z, config, "f32")
    shared = axk1_reference.gated_mlp(layer["shared"], z, "f32")
    total, pairs = shared, 0
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        out, counters = moe.moe_share_apply(
            _share(layer, held), z, held, top_k=4, scale=2.5,
            dtype=jnp.float32, block=8, n_group=4, topk_group=2,
            bias=layer["bias"])
        np.testing.assert_allclose(
            out, reference.expert_ffn(_share(layer, held), z, config, "f32",
                                      held=held), atol=2e-5)
        total = total + (out - shared)
        pairs += int(counters["pairs_here"])
    np.testing.assert_allclose(total, whole, atol=5e-5)
    # every pair (token, expert) was computed on exactly one chip
    assert pairs == 24 * 4


def test_the_bias_chooses_and_the_gates_stay_the_scores():
    layer = _expert_layer(seed=11)
    z = jax.random.normal(jax.random.PRNGKey(12), (10, 64), jnp.float32)
    config = dict(TINY, held_experts=list(range(16)), n_group=4,
                  topk_group=2, num_experts_per_tok=4)
    scores = jax.nn.sigmoid(z @ layer["router"])
    gate = np.asarray(reference.gates(layer, z, config, "f32"))
    for t in range(10):
        picked = np.flatnonzero(gate[t])
        assert len(picked) == 4
        # inside at most two groups of four
        assert len({e // 4 for e in picked}) <= 2
        # gated by the unbiased scores, normalised and scaled
        np.testing.assert_allclose(
            gate[t, picked], 2.5 * scores[t, picked]
            / jnp.sum(scores[t, picked]), rtol=1e-5)
    # a bias that lifts one group above all: every token goes inside it
    lifted = dict(layer, bias=jnp.zeros(16).at[8:12].set(5.0))
    gate = np.asarray(reference.gates(
        lifted, z, dict(config, topk_group=1), "f32"))
    assert (np.flatnonzero(gate.sum(0)) // 4 == 2).all()
    out, counters = moe.moe_share_apply(
        _share(lifted, (8, 9, 10, 11)), z, (8, 9, 10, 11), top_k=4,
        scale=2.5, dtype=jnp.float32, n_group=4, topk_group=1,
        bias=lifted["bias"])
    assert int(counters["pairs_here"]) == 40
    np.testing.assert_allclose(out, reference.expert_ffn(
        lifted, z, dict(config, topk_group=1), "f32"), atol=5e-5)


def test_one_group_and_no_bias_is_plain_top_k():
    layer = _expert_layer(seed=13)
    z = jax.random.normal(jax.random.PRNGKey(14), (9, 64), jnp.float32)
    held = (0, 5, 9, 12)
    plain, _ = moe.moe_share_apply(_share(layer, held), z, held, 4, 2.5,
                                   dtype=jnp.float32)
    told, _ = moe.moe_share_apply(_share(layer, held), z, held, 4, 2.5,
                                  dtype=jnp.float32, n_group=1, topk_group=1,
                                  bias=jnp.zeros(16))
    np.testing.assert_allclose(told, plain, atol=1e-6)


# -- the other latent model the engine serves ----------------------------------

#: sha256 of the lowered text of ``models.axk1``'s serving programs at
#: ``axk1.TINY_CONFIG`` with ``max_seq`` 64 (max_batch 2, prompt_pad 16,
#: 8 pages of 8), less the names of ``main``'s results, AS PR 51 LEFT
#: THEM (until then the prefill's as PR 29 left it, d0b961a, and the
#: decode steps' as PR 38 left them). PR 38
#: meant to change the two decode steps and re-pinned them: the engine
#: hands a step ONE ``int32[max_batch, 4 + pages_per_seq]`` where it
#: handed five arrays, and takes ONE ``int32[max_batch + counters]``
#: back beside the pool where it took the tokens and two scalars. With
#: the names of the values normalised, the parent's text and this differ
#: in ``main``'s signature, 13 lines at its head (five slices, four
#: reshapes, the ``!= 0`` of the live column) and 3 at its end (the two
#: counters broadcast to ``[1]`` and one concatenate); the stack, the
#: expert layer and the kernel are the parent's line for line.
#: PR 51 meant to change the expert layer of all four and re-pinned
#: them: ``moe_share_apply`` walks ONE loop over the row blocks that
#: exist where it walked a loop over the held experts around a loop
#: over each one's blocks. With the names of the values normalised, as
#: multisets of lines a decode step loses 31 lines and gains 36 (1901
#: -> 1906; the prefill 34 and 35, 1022 -> 1023), every one of them
#: integer bookkeeping of that loop: the outer ``while``, the function
#: that was its body and the scalar ``ceil(counts[e] / rows)`` go; the
#: blocks a held expert (``ceil`` over ``[4]``), their running sum and
#: its last entry before the one ``while``, and in its body the expert
#: looked up from the block's index (``sum(b >= block_ends)``) come. The
#: private functions are emitted in another order, so a line-by-line
#: diff is long. Nothing in the stack, the attention, the routing, the
#: gather of a block's rows, the three products or the scatter-add.
#: jax 0.9.0.
PARENT_AXK1_PROGRAMS = {
    ("paged", "serve-prefill"):
        "b6dbd5edb673c5f201d8b39a9b499f1f2fa0bab16cbc737ca4216e7e34d6430c",
    ("paged", "serve-decode"):
        "f418f2c4685c4f2bcf1842d6b3a68a4b23067c21863b010a206e52b39629504e",
    ("reference", "serve-prefill"):
        "b6dbd5edb673c5f201d8b39a9b499f1f2fa0bab16cbc737ca4216e7e34d6430c",
    ("reference", "serve-decode"):
        "a0a28380f2fbf605c6604d5c6813da0e7538aebe675031387d4b08002fdb120b",
}


@pytest.mark.parametrize("attn", ["paged", "reference"])
def test_axk1s_serve_programs_lower_to_the_parents_text(attn, monkeypatch):
    """What ``models.dsv32`` shares with ``models.axk1`` (the stack, the
    attention's inputs, where a decode step writes, the expert layer's
    routing) was made shareable without changing one operation of
    ``axk1``'s prefill or decode: ``axk1-share16.serve-decode-1k`` cannot
    move."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the parent's text was lowered by jax 0.9.0")
    lowered = {}

    def capture(fn, example_args, config=None, label="", donate_argnums=(),
                **kw):
        jitted = jax.jit(fn, donate_argnums=donate_argnums)
        text = jitted.lower(*example_args).as_text()
        lowered[label] = re.sub(r' \{jax\.result_info = "[^"]*"\}', "", text)
        return jitted

    monkeypatch.setattr(compile_cache, "cached_jit", capture)
    cfg = dict(axk1.TINY_CONFIG, max_seq=64)
    engine = ServingEngine(axk1.init(jax.random.PRNGKey(0), cfg), cfg,
                           max_batch=2, prompt_pad=16, num_blocks=8,
                           block_size=8, attn=attn, label="serve",
                           model=axk1)
    req = Request("a", [1, 2, 3], max_new_tokens=3)
    assert engine.admit(req)
    for _ in range(2):
        (token, _), = engine.step_fn([req])
        req.generated.append(token)
    for label in ("serve-prefill", "serve-decode"):
        assert hashlib.sha256(lowered[label].encode()).hexdigest() \
            == PARENT_AXK1_PROGRAMS[attn, label], label
