"""models/axk1 on the CPU at a tiny size (hidden 64, 4 heads, ranks
32/16, 16 experts of which a token takes 4 and this chip holds 4, one
dense + two expert layers), against the benchmark's plain reference
(``benchmark/reference/axk1.py``: float32, not absorbed, no cache, no
sorting; it imports nothing of the program)."""

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

from cellbench_tiny_axk1 import TINY_AXK1

from benchmark.families import axk1 as family
from benchmark.reference import axk1 as reference
from paddle_operator_tpu import compile_cache
from paddle_operator_tpu.models import axk1, gpt
from paddle_operator_tpu.ops import attention_pallas as ap
from paddle_operator_tpu.ops import moe, nn
from paddle_operator_tpu.serving.batching import Request
from paddle_operator_tpu.serving.engine import ServingEngine
from paddle_operator_tpu.serving.kv_cache import LatentKvCache

#: the configuration FILE of the tiny model, under the published names
#: (the one the tiny benchmark cell runs)
TINY = TINY_AXK1

@pytest.fixture(scope="module")
def params():
    return family.make_params(TINY, 26)


def test_the_tiny_preset_is_the_tiny_file():
    cfg = family.program_config(TINY)
    assert cfg == dict(axk1.TINY_CONFIG, max_seq=64)
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), family.make_params(TINY, 1)) \
        == jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype),
            axk1.init(jax.random.PRNGKey(1), cfg))


def test_yarn_frequencies_and_scale_are_the_references():
    cfg = family.program_config(TINY)
    inv_freq, scale = axk1._rotary(cfg)
    np.testing.assert_allclose(
        inv_freq, reference.yarn_inv_freq(8, 1e4, TINY["rope_scaling"]),
        rtol=1e-6)
    assert scale == pytest.approx(reference.score_scale(TINY))
    # the published scaling: high pairs keep their frequency, low ones
    # have it divided by the factor
    full = nn.yarn_inv_freq(64, 1e4, 32.0, 4096, 32.0, 1.0)
    plain = 1e4 ** (-np.arange(32) / 32.0)
    assert full[0] == pytest.approx(plain[0])
    assert full[-1] == pytest.approx(plain[-1] / 32.0)
    assert nn.yarn_mscale(32.0) == pytest.approx(0.1 * np.log(32.0) + 1.0)


@pytest.mark.parametrize("attn", ["paged", "reference"])
def test_prefill_then_decode_through_the_latent_cache_gives_the_references_logits(
        params, attn):
    """Three prompts of different lengths prefilled (each in its bucket),
    their rows scattered into pages, then five decode steps of the whole
    batch: at every step the logits of each row against the reference's
    full forward over everything the row has seen."""
    cfg = family.program_config(TINY)
    bs, blocks, batch = 8, 24, 4
    cache = LatentKvCache(blocks, bs, layers=3, widths=(24,))
    rnd = np.random.RandomState(0)
    prompts = [list(rnd.randint(0, 512, size=n)) for n in (5, 16, 23)]
    seqs = []
    for i, prompt in enumerate(prompts):
        cache.allocator.alloc_sequence("s%d" % i, len(prompt) + 6,
                                       live_tokens=len(prompt))
        pad = 16 if len(prompt) <= 16 else 32
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(prompt)] = prompt
        token, rows = jax.jit(axk1.serve_prefill(cfg, pad))(
            params, jnp.asarray(ids), jnp.asarray(len(prompt), jnp.int32))
        cache.write_rows("s%d" % i, rows, len(prompt))
        seqs.append(prompt + [int(token)])
    decode = jax.jit(lambda *a: axk1.decode(
        cfg, *a, attn_impl=attn, block_size=bs, dummy_page=blocks,
        with_logits=True))
    pool = cache.pools()
    apart = []
    for _ in range(5):
        tokens, positions, lens = [0] * batch, [0] * batch, [0] * batch
        tables = np.zeros((batch, 64 // bs), np.int32)
        for i, seq in enumerate(seqs):
            sid = "s%d" % i
            tokens[i], lens[i] = seq[-1], cache.allocator.seq_len(sid)
            positions[i] = cache.allocator.advance(sid)
            table = cache.allocator.block_table(sid)
            tables[i, :len(table)] = table
        out, pool, counters, logits = decode(
            params, pool, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32),
            jnp.asarray([True, True, True, False]))
        for i, seq in enumerate(seqs):
            want = reference.logits(params, jnp.asarray([seq], jnp.int32),
                                    TINY, "f32")[0, -1]
            apart.append(float(jnp.max(jnp.abs(logits[i] - want))))
            seq.append(int(out[i]))
        # three live rows x 4 experts a token x 2 expert layers, of
        # which this chip holds a quarter of the experts
        assert 0 < int(counters["moe.pairs_here"]) <= 24
        assert 0 < int(counters["moe.experts_hit"]) <= 8
    # bfloat16 activations against float32, logits of spread 1.5: the
    # reference itself computed with bfloat16 operands lies 0.07 from
    # its float32 self. Where rounding flips a router's fourth choice a
    # whole expert's gate (2.5 x 1/4) moves, which is no rounding error:
    # such rows are few and bounded
    assert sorted(apart)[len(apart) // 2] < 0.1, apart
    assert sum(a > 0.2 for a in apart) <= 3 and max(apart) < 1.0, apart
    # the prefill's first token is the reference's too
    for prompt, seq in zip(prompts, seqs):
        want = reference.logits(params, jnp.asarray([prompt], jnp.int32),
                                TINY, "f32")[0, -1]
        assert float(jnp.max(want) - want[seq[len(prompt)]]) < 0.2


def test_the_engine_serves_it_through_step_fn(params):
    cfg = family.program_config(TINY)
    engine = ServingEngine(params, cfg, max_batch=4, prompt_pad=32,
                           num_blocks=16, block_size=8, model=axk1,
                           label="serve-axk1-test")
    assert engine.buckets == (32,)
    rnd = np.random.RandomState(1)
    reqs = [Request("r%d" % i, [int(t) for t in rnd.randint(0, 512, size=n)],
                    max_new_tokens=6) for i, n in enumerate((5, 17, 32))]
    assert all(engine.admit(r) for r in reqs)
    for _ in range(6):
        for req, (token, _) in zip(reqs, engine.step_fn(reqs)):
            req.generated.append(token)
    for req in reqs:
        ids = jnp.asarray([list(req.prompt) + req.generated], jnp.int32)
        logits = reference.logits(params, ids, TINY, "f32")[0]
        lo = len(req.prompt) - 1
        for j, token in enumerate(req.generated):
            assert float(jnp.max(logits[lo + j]) - logits[lo + j, token]) \
                < 0.3
        engine.retire(req)
    assert engine.cache.allocator.check() == []
    counts = engine.times.counts()
    # five decode steps banked their counters beside the spans
    assert counts["moe.pairs_here"]["steps"] == 5
    assert counts["moe.experts_hit"]["steps"] == 5
    assert not set(counts) & set(engine.times.summary())
    bucket = engine.times.samples("serve.prefill.dispatch")[0].attrs["bucket"]
    assert bucket == 32


def test_buckets_are_at_most_four_halvings_of_the_prompt_pad():
    assert axk1.serve_buckets({}, 4096) == (512, 1024, 2048, 4096)
    assert axk1.serve_buckets({}, 1024) == (512, 1024)
    assert axk1.serve_buckets({}, 48) == (48,)
    assert gpt.serve_buckets({}, 512) == (512,)


# -- the kernel -------------------------------------------------------------

def _mla_case(seed=0, b=3, h=4, c=16, r=8, bs=8, pages=9, per_seq=4,
              layers=2, width=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, h, c), jnp.float32),
            jax.random.normal(ks[1], (b, h, r), jnp.float32),
            jax.random.normal(ks[2], (layers, pages, bs, width),
                              jnp.float32),
            jax.random.randint(ks[3], (b, per_seq), 0, pages),
            jnp.asarray([1, 17, 32], jnp.int32))


@pytest.mark.parametrize("layer", [0, 1])
def test_mla_paged_decode_matches_its_reference_interpreted(layer):
    q_lat, q_rope, pool, tables, lens = _mla_case()
    got = ap.mla_paged_decode(q_lat, q_rope, pool, tables, lens, 0.3,
                              layer=layer, interpret=True)
    want = ap._reference_mla_paged_decode(q_lat, q_rope, pool[layer],
                                          tables, lens, 0.3)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # one layer's pool handed over alone is the same call
    np.testing.assert_allclose(
        ap.mla_paged_decode(q_lat, q_rope, pool[layer], tables, lens, 0.3,
                            interpret=True), got, atol=1e-7)


def test_mla_paged_decode_refuses_what_does_not_fit():
    q_lat, q_rope, pool, tables, lens = _mla_case()
    with pytest.raises(ValueError, match="say which"):
        ap.mla_paged_decode(q_lat, q_rope, pool, tables, lens, 0.3)
    with pytest.raises(ValueError, match="do not match"):
        ap.mla_paged_decode(q_lat, q_rope, pool[0, :, :, :16], tables, lens,
                            0.3)
    with pytest.raises(ValueError, match="do not cover"):
        ap.mla_paged_decode(q_lat, q_rope, pool[0], tables[:2], lens, 0.3)


def test_absorbed_attention_is_the_non_absorbed_one():
    """Scoring the cached rows with the query pushed through the key
    up-projection, and up-projecting the weighted sum of rows, is the
    same attention as rebuilding every head's keys and values."""
    h, n, r, c, v, s = 4, 16, 8, 16, 16, 19
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    q_nope = jax.random.normal(ks[0], (1, h, n))
    q_rope = jax.random.normal(ks[1], (1, h, r))
    rows = jax.random.normal(ks[2], (s, c + r))
    k_up = jax.random.normal(ks[3], (h, n, c))
    v_up = jax.random.normal(ks[4], (h, c, v))
    # non-absorbed: keys and values of every head
    k_nope = jnp.einsum("sc,hnc->shn", rows[:, :c], k_up)
    value = jnp.einsum("sc,hcv->shv", rows[:, :c], v_up)
    scores = (jnp.einsum("bhn,shn->bhs", q_nope, k_nope)
              + jnp.einsum("bhr,sr->bhs", q_rope, rows[:, c:])) * 0.2
    want = jnp.einsum("bhs,shv->bhv", jax.nn.softmax(scores, -1), value)
    # absorbed, through pages of 8 rows
    pages = jnp.zeros((4, 8, 128)).at[:3].set(
        jnp.pad(rows, ((0, 5), (0, 128 - c - r))).reshape(3, 8, 128))
    q_lat = jnp.einsum("bhn,hnc->bhc", q_nope, k_up)
    ctx = ap.mla_paged_decode(q_lat, q_rope, pages,
                              jnp.asarray([[0, 1, 2]]), jnp.asarray([s]),
                              0.2, interpret=True)
    got = jnp.einsum("bhc,hcv->bhv", ctx, v_up)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the expert layer -------------------------------------------------------

def _expert_layer(seed=5, d=64, f=32, routed=16, std=0.3):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def normal(*shape):
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    return {"router": normal(d, routed), "gate": normal(routed, d, f),
            "up": normal(routed, d, f), "down": normal(routed, f, d),
            "shared": {"gate": normal(d, f), "up": normal(d, f),
                       "down": normal(f, d)}}


def _share(layer, held):
    idx = jnp.asarray(held)
    return dict(layer, gate=layer["gate"][idx], up=layer["up"][idx],
                down=layer["down"][idx])


def _stacked(share):
    """The share's expert kernels as layer 1 of two, layer 0 zeros."""
    return dict(share, **{k: jnp.stack([jnp.zeros_like(share[k]), share[k]])
                          for k in ("gate", "up", "down")})


def test_the_four_shares_add_up_to_the_uncut_layer():
    """What each of four chips computes of one expert layer (its quarter
    of the 16 routed experts, routed over all 16, plus the shared expert
    every chip holds), the shared expert counted once, is the reference's
    layer with every expert held."""
    layer = _expert_layer()
    z = jax.random.normal(jax.random.PRNGKey(6), (24, 64), jnp.float32)
    config = dict(TINY, held_experts=list(range(16)))
    whole = reference.expert_ffn(layer, z, config, "f32")
    shared = reference.gated_mlp(layer["shared"], z, "f32")
    total, pairs = shared, 0
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        out, counters = moe.moe_share_apply(
            _share(layer, held), z, held, top_k=4, scale=2.5,
            dtype=jnp.float32, block=8)
        np.testing.assert_allclose(
            out, reference.expert_ffn(_share(layer, held), z, config, "f32",
                                      held=held), atol=2e-5)
        total = total + (out - shared)
        pairs += int(counters["pairs_here"])
    np.testing.assert_allclose(total, whole, atol=5e-5)
    # every pair (token, expert) was computed on exactly one chip
    assert pairs == 24 * 4


def test_no_token_is_dropped_when_all_route_to_one_expert():
    """A router that sends every token to expert 2 first: the one held
    expert computes all 40 tokens, in several row blocks; no capacity."""
    layer = _expert_layer(seed=7)
    z = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (40, 64))) + 0.1
    layer["router"] = layer["router"].at[:, 2].set(4.0)   # sigmoid -> 1
    out, counters = moe.moe_share_apply(
        _share(layer, (2,)), z, (2,), top_k=4, scale=2.5,
        dtype=jnp.float32, block=16)
    assert int(counters["pairs_here"]) == 40
    assert int(counters["experts_hit"]) == 1
    config = dict(TINY, held_experts=[2])
    np.testing.assert_allclose(
        out, reference.expert_ffn(_share(layer, (2,)), z, config, "f32"),
        atol=2e-4)
    gate = reference.gates(layer["router"], z, config, "f32")[:, 2]
    assert float(jnp.min(gate)) > 0          # every token is in it


def test_padding_rows_route_nowhere_and_layers_index_stacked_kernels():
    layer = _expert_layer(seed=9)
    held = (0, 5, 9, 12)
    z = jax.random.normal(jax.random.PRNGKey(10), (12, 64), jnp.float32)
    live = jnp.arange(12) < 7
    share = _share(layer, held)
    out, counters = moe.moe_share_apply(share, z, held, 4, 2.5, live=live,
                                        dtype=jnp.float32)
    alone, alone_counters = moe.moe_share_apply(share, z[:7], held, 4, 2.5,
                                                dtype=jnp.float32)
    np.testing.assert_allclose(out[:7], alone, atol=1e-5)
    assert int(counters["pairs_here"]) == int(alone_counters["pairs_here"])
    # padding gets the shared expert only
    np.testing.assert_allclose(
        out[7:], nn.gated_mlp(layer["shared"], z[7:], jnp.float32),
        atol=1e-5)
    indexed, _ = moe.moe_share_apply(_stacked(share), z, held, 4, 2.5,
                                     live=live, layer=jnp.asarray(1),
                                     dtype=jnp.float32)
    np.testing.assert_allclose(indexed, out, atol=1e-6)


#: which of the held experts (0, 5, 9, 12) a router sends its tokens to,
#: and which rows are tokens: the cases an expert without pairs decides
#: (its kernels are not read: ``moe_share_apply``)
HIT_CASES = {
    "every-row-dead": dict(forced=(0, 5, 9, 12), live=0, hit=()),
    "all-routed-elsewhere": dict(forced=(1, 2, 3, 4), live=12, hit=()),
    "elsewhere-and-padding": dict(forced=(1, 2, 3, 4), live=7, hit=()),
    "first-held-only": dict(forced=(0, 1, 2, 3), live=12, hit=(0,)),
    "last-held-only": dict(forced=(12, 1, 2, 3), live=7, hit=(12,)),
    "first-and-last": dict(forced=(0, 12, 2, 3), live=12, hit=(0, 12)),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["flat", "layer"])
@pytest.mark.parametrize("case", sorted(HIT_CASES))
def test_an_expert_without_pairs_adds_nothing_and_the_hit_ones_all(
        case, stacked):
    """A router forced onto four experts for every token (their columns
    score 1, so they ARE the top 4). Held experts that nobody is routed
    to add nothing — with none hit the result is the shared expert's
    alone, bit for bit — and the ones that are hit add what a chip
    holding them alone adds, bit for bit: the same rows in the same
    blocks. Against the reference on the live rows; with and without the
    layers as the kernels' leading axis."""
    spec = HIT_CASES[case]
    layer = _expert_layer(seed=11)
    held = (0, 5, 9, 12)
    z = jnp.abs(jax.random.normal(jax.random.PRNGKey(12), (12, 64))) + 0.1
    for column in spec["forced"]:
        layer["router"] = layer["router"].at[:, column].set(4.0)
    n = spec["live"]
    live = jnp.arange(12) < n

    def apply(held):
        share = _share(layer, held)
        if stacked:
            return moe.moe_share_apply(
                _stacked(share), z, held, 4, 2.5, live=live,
                layer=jnp.asarray(1), dtype=jnp.float32, block=8)
        return moe.moe_share_apply(share, z, held, 4, 2.5, live=live,
                                   dtype=jnp.float32, block=8)

    out, counters = apply(held)
    assert int(counters["experts_hit"]) == len(spec["hit"])
    assert int(counters["pairs_here"]) == n * len(spec["hit"])
    shared = nn.gated_mlp(layer["shared"], z, jnp.float32)
    np.testing.assert_array_equal(out[n:], shared[n:])
    if not spec["hit"]:
        np.testing.assert_array_equal(out, shared)
        return
    alone, _ = apply(spec["hit"])
    np.testing.assert_array_equal(out, alone)
    config = dict(TINY, held_experts=list(held))
    np.testing.assert_allclose(
        out[:n], reference.expert_ffn(_share(layer, held), z, config,
                                      "f32")[:n], atol=2e-4)


# -- the other model the engine serves ------------------------------------

#: sha256 of the lowered text of GPT's serving programs at
#: gpt.TINY_CONFIG (max_batch 2, prompt_pad 16, 8 pages of 8) AS PR 33
#: LEFT THEM, less the names of ``main``'s results
#: (``jax.result_info``). Until PR 33 these were the texts of PR 25
#: (dcb9952), where the engine held the programs inline; PR 33 meant to
#: change them and re-pinned all four: the decode step takes the cache's
#: two stacked pools donated, writes a token's rows with one scatter a
#: pool and layer and reads them through ``paged_decode`` (or its
#: reference) by layer index; the prefill hands its rows as the cache
#: stores them, ``[layers, pad, heads * head_dim]`` a side. PR 36
#: re-pinned ONE, the paged decode step: ``paged_decode_attention`` took
#: a second user (``models.evabyte``: bfloat16 pages of 32 heads x 128)
#: and one form for both — the query spread a head a row and two MXU
#: products a page in the pages' own type, where PR 33's multiplied on
#: the VPU and summed through two 0/1 matmuls — so the kernel's text
#: inside GPT's step changed; the prefill and the reference step did
#: not. PR 38 re-pinned the TWO decode steps: the engine hands a step ONE
#: ``int32[max_batch, 4 + pages_per_seq]`` where it handed five arrays,
#: and takes ONE ``int32[max_batch + counters]`` back beside the pools.
#: With the names of the values normalised, the parent's text and this
#: differ in ``main``'s signature and in 13 lines at its head (five
#: slices, four reshapes, the ``!= 0`` of the live column); the blocks,
#: the scatters and the kernel are the parent's line for line. The
#: prefill is untouched. jax 0.9.0.
PARENT_GPT_PROGRAMS = {
    ("paged", "serve-prefill"):
        "9b5d75b9d22cb9a64417fc244e230ae91394b477f005c1e66e258ffee6d72de8",
    ("paged", "serve-decode"):
        "e2f7bd616772ef818c2a29c981353f2ac4c76d0b7c8d7a526c605251f4688325",
    ("reference", "serve-prefill"):
        "9b5d75b9d22cb9a64417fc244e230ae91394b477f005c1e66e258ffee6d72de8",
    ("reference", "serve-decode"):
        "cd26debe61137f9108707be0153a218f350769e71ea65a3e98163faff80e51e4",
}


@pytest.mark.parametrize("attn", ["paged", "reference"])
def test_gpts_serve_programs_lower_to_the_parents_text(attn, monkeypatch):
    """GPT's prefill and decode lower to the pinned texts: a PR that does
    not mean to change them sees here that ``gpt2-small.serve-steady``
    runs its parent's programs (as ``axk1``'s and ``dsv32``'s pins say
    of their cells), and one that means to re-pins them and says why.
    The decode step's two pools, and nothing else, are donated: the
    sign, on the CPU, that the step updates the cache where it lies."""
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
    cfg = dict(gpt.TINY_CONFIG)
    engine = ServingEngine(gpt.init(jax.random.PRNGKey(0), cfg), cfg,
                           max_batch=2, prompt_pad=16, num_blocks=8,
                           block_size=8, attn=attn, label="serve")
    req = Request("a", [1, 2, 3], max_new_tokens=3)
    assert engine.admit(req)
    for _ in range(2):
        (token, _), = engine.step_fn([req])
        req.generated.append(token)
    donated = {label: len(re.findall(r"jax\.buffer_donor|tf\.aliasing_output",
                                     text))
               for label, text in lowered.items()}
    assert donated == {"serve-prefill": 0, "serve-decode": 2}
    for label in ("serve-prefill", "serve-decode"):
        assert hashlib.sha256(lowered[label].encode()).hexdigest() \
            == PARENT_GPT_PROGRAMS[attn, label], label


def test_the_engine_refuses_gpts_expert_configurations():
    cfg = dict(gpt.TINY_MOE_CONFIG)
    with pytest.raises(ValueError, match="no expert configuration"):
        ServingEngine(gpt.init(jax.random.PRNGKey(0), cfg), cfg)
