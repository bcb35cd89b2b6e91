"""models/ouro on the CPU at a tiny size (hidden 128, 4 heads of 32, two
layers run three times over: six cache layers behind two layers of
weights, pages of 8 rows) against the benchmark's plain reference
(``benchmark/reference/ouro.py``: float32, no cache; it imports nothing
of the program)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

from cellbench_tiny_ouro import TINY_OURO as TINY

from benchmark.families import ouro as family
from benchmark.reference import ouro as reference
from paddle_operator_tpu.models import ouro
from paddle_operator_tpu.serving.batching import Request
from paddle_operator_tpu.serving.engine import ServingEngine

#: |program's logits - the float32 reference's|, widest over a vocabulary
#: of 64 whose logits span about 6 at init_std 0.1. The program
#: multiplies bfloat16 operands and stores bfloat16 rows: it reads
#: 0.057 here, the reference itself in bfloat16 0.05 against its
#: float32 self and in fp8 0.85; rows stored in fp8 read 0.52, loop
#: steps that share one cache layer 5.5
LOGIT_TOL = 0.15


@pytest.fixture(scope="module")
def params():
    return family.make_params(TINY, 41)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)


def test_the_tiny_preset_is_the_tiny_file():
    cfg = family.program_config(TINY)
    assert cfg == ouro.TINY_CONFIG
    assert _shapes(family.make_params(TINY, 1)) \
        == _shapes(ouro.init(jax.random.PRNGKey(1), cfg))


def test_the_published_preset_is_the_catalogs_row():
    cfg = ouro.BASE_CONFIG
    assert (cfg["layers"], cfg["hidden"], cfg["heads"], cfg["head_dim"],
            cfg["mlp_dim"], cfg["loop_steps"], cfg["exit_threshold"],
            cfg["vocab_size"], cfg["max_seq"], cfg["rope_theta"],
            cfg["rms_norm_eps"]) \
        == (48, 2048, 16, 128, 5632, 4, 1.0, 49152, 65536, 1e6, 1e-6)
    with pytest.raises(ValueError, match="whole number of tiles"):
        ouro.serve_cache(dict(ouro.TINY_CONFIG, heads=3), 8, 8)
    assert ouro.serve_buckets(cfg, 512) == (128, 256, 512)
    assert ouro.serve_buckets(cfg, 384) == (192, 384)
    assert ouro.serve_buckets(ouro.TINY_CONFIG, 32) == (32,)


def test_the_cache_has_a_layer_for_every_loop_step_of_every_layer():
    """``PagedKvCache(layers=)`` is handed the CACHE's layers: loop steps
    x the weights' layers, loop step ``t`` of layer ``l`` at ``t L + l``."""
    cache = ouro.serve_cache(ouro.TINY_CONFIG, 5, 8)
    assert cache.layers == 3 * 2
    assert cache.pools()[0].shape == (6, 5 + 1, 8, 128)
    assert cache.pools()[0].dtype == jnp.bfloat16
    assert [ouro.cache_layer(t, l, 2) for t in range(3) for l in range(2)] \
        == list(range(6))
    big = jax.eval_shape(lambda: ouro.serve_cache(
        ouro.BASE_CONFIG, 40, 128).pools())
    assert big[0].shape == (192, 41, 128, 2048)


# -- prefill, then decode through the cache, against one forward ----------

def _serve(params, attn, prompts, steps, tiny=TINY):
    """Prompts prefilled and written into the cache as the engine does
    it, then ``steps`` decode steps of the whole batch: the widest
    distance of any row's logits, at the prefill and at every step,
    from the reference's full forward over everything the row has
    seen; the counters of every step; what the rows hold."""
    cfg = family.program_config(tiny)
    bs, blocks, batch = 8, 40, 4
    cache = ouro.serve_cache(cfg, blocks, bs)
    seqs, apart, counted = [], [], []

    ref = jax.jit(lambda p, ids: reference.logits(p, ids, tiny, "f32"))

    def want(seq):
        return ref(params, _padded(seq))[0, len(seq) - 1]

    for i, prompt in enumerate(prompts):
        n = len(prompt)
        cache.allocator.alloc_sequence(
            "s%d" % i, n + steps + 1, live_tokens=n)
        ids = np.zeros((1, 32), np.int32)
        ids[0, :n] = prompt
        token, rows, logits = jax.jit(
            lambda p, i, l: ouro.prefill(cfg, p, i, l, with_logits=True)
        )(params, jnp.asarray(ids), jnp.asarray(n, jnp.int32))
        assert rows[0].shape == (cache.layers, 32, 128)
        apart.append(float(jnp.max(jnp.abs(logits - want(prompt)))))
        cache.write_rows("s%d" % i, rows, n)
        seqs.append(list(prompt) + [int(token)])
    decode = jax.jit(lambda *a: ouro.decode(
        cfg, *a, attn_impl=attn, block_size=bs, dummy_page=blocks,
        with_logits=True))
    pools = cache.pools()
    width = cache.table_width(cfg["max_seq"])
    for _ in range(steps):
        tokens, positions, lens = np.zeros((3, batch), np.int32)
        tables = np.zeros((batch, width), np.int32)
        for i, seq in enumerate(seqs):
            tokens[i] = seq[-1]
            positions[i], table, lens[i] = cache.decode_row("s%d" % i)
            tables[i, :len(table)] = table
        out, pools, counters, logits = decode(
            params, pools, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(lens),
            jnp.arange(batch) < len(seqs))
        counted.append({k: int(v) for k, v in counters.items()})
        for i, seq in enumerate(seqs):
            apart.append(float(jnp.max(jnp.abs(logits[i] - want(seq)))))
            seq.append(int(out[i]))
    assert cache.allocator.check() == []
    return max(apart), counted, seqs


def _padded(seq, width=40):
    """One shape for every length: causal, so padding on the right
    changes nothing before it."""
    ids = np.zeros((1, width), np.int32)
    ids[0, :len(seq)] = seq
    return jnp.asarray(ids)


def _prompts(*lengths):
    rnd = np.random.RandomState(0)
    return [list(rnd.randint(0, TINY["vocab_size"], size=n))
            for n in lengths]


@pytest.mark.parametrize("attn", ["paged", "reference"])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        params, attn):
    """Three prompts (one inside its first page, one that ends on a
    page's end, one over three pages) prefilled, every loop step's rows
    written into that step's cache layers, then 10 decode steps of the
    batch: logits at the prefill and at every step."""
    worst, counted, _ = _serve(params, attn, _prompts(5, 16, 23), 10)
    assert worst < LOGIT_TOL
    # three live rows, three loop steps, two layers; the first step's
    # rows stand at positions 5, 16, 23 and attend over 6 + 17 + 24 rows
    # in each of the three loop steps
    assert counted[0] == {"loop.layer_passes": 3 * 3 * 2,
                          "loop.rows_live": 3,
                          "loop.rows_read": 3 * (6 + 17 + 24),
                          "loop.exit_steps": 3 * 3}
    assert counted[-1]["loop.rows_read"] == 3 * (15 + 26 + 33)


def test_one_loop_step_is_one_pass_of_a_plain_stack(params):
    """``total_ut_steps`` 1: the stack once, the final norm, the head —
    and as many cache layers as weight layers."""
    once = dict(TINY, total_ut_steps=1)
    assert ouro.serve_cache(family.program_config(once), 4, 8).layers == 2
    worst, counted, _ = _serve(params, "reference", _prompts(7, 20), 6,
                               tiny=once)
    assert worst < LOGIT_TOL
    assert counted[0]["loop.layer_passes"] == 2 * 1 * 2
    assert counted[0]["loop.exit_steps"] == 2
    # and it is NOT the looped model: its logits lie far from those
    ids = jnp.asarray([_prompts(20)[0]], jnp.int32)
    assert float(jnp.max(jnp.abs(
        reference.logits(params, ids, once, "f32")
        - reference.logits(params, ids, TINY, "f32")))) > 4 * LOGIT_TOL


def test_the_loop_counters_count_what_ran_not_what_the_config_says(params):
    """``loop.layer_passes`` and ``loop.rows_read`` are carried through
    the scan and added to where a layer is applied: a step that walks
    ONE of the two layers counts half the passes, whatever ``layers``
    the config states, so ``loop_steps_per_token`` falls with the work
    left out."""
    cfg = family.program_config(TINY)
    cache = ouro.serve_cache(cfg, 8, 8)
    cache.allocator.alloc_sequence("s", 12, live_tokens=5)
    position, table, length = cache.decode_row("s")
    tables = np.zeros((2, cache.table_width(cfg["max_seq"])), np.int32)
    tables[0, :len(table)] = table

    def counted(weights):
        out = ouro.decode(
            cfg, weights, cache.pools(), jnp.zeros((2,), jnp.int32),
            jnp.asarray([position, 0], jnp.int32), jnp.asarray(tables),
            jnp.asarray([length, 0], jnp.int32),
            jnp.asarray([True, False]), attn_impl="reference",
            block_size=8, dummy_page=8)
        return {k: int(v) for k, v in out[2].items()}

    whole = counted(params)
    assert whole["loop.layer_passes"] == 1 * 3 * 2
    assert whole["loop.rows_read"] == 3 * (length + 1)
    half = counted(dict(params, layers=params["layers"][:1]))
    assert half["loop.layer_passes"] == 1 * 3 * 1
    assert half["loop.rows_live"] == whole["loop.rows_live"] == 1


def _one_shared_cache_layer(monkeypatch):
    """Planted fault: every loop step reads and writes the first loop
    step's cache layers."""
    monkeypatch.setattr(ouro, "cache_layer",
                        lambda step, layer, layers: layer)


def _rows_stored_in_fp8(monkeypatch):
    """Planted fault: keys and values rounded to float8 (e4m3) as
    stored, the step below what the configuration states."""
    qkv = ouro._qkv

    def rounded(*args):
        q, k, v = qkv(*args)
        return q, *(a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
                    for a in (k, v))

    monkeypatch.setattr(ouro, "_qkv", rounded)


@pytest.mark.parametrize("plant", [_one_shared_cache_layer,
                                   _rows_stored_in_fp8],
                         ids=["loop-steps-share-a-cache-layer",
                              "rows-stored-in-fp8"])
def test_a_planted_fault_fails_the_comparison(params, plant, monkeypatch):
    """The (t, l) index of the cache is tested, not assumed: with one
    cache layer for all loop steps, step t + 1 writes over step t's row
    and steps 0 .. T - 2 attend over the last step's rows."""
    plant(monkeypatch)
    worst, _, _ = _serve(params, "reference", _prompts(5, 16, 23), 10)
    assert worst > 2 * LOGIT_TOL


# -- the exit gate and its rule --------------------------------------------

def test_the_exit_distribution_sums_to_one(params):
    ids = jnp.asarray(_prompts(24, 24), jnp.int32)
    p = reference.exit_distribution(params, ids, TINY)
    assert p.shape == (2, 3, 24)
    np.testing.assert_allclose(jnp.sum(p, axis=1), 1.0, atol=1e-6)
    assert float(jnp.min(p)) > 0.0
    # the program's gate on the reference's loop outputs: the same
    # distribution, and the rule's exit step at three thresholds
    u = reference.loop_outputs(params, ids[0], TINY, "f32")
    for q in (0.3, 0.6, 1.0):
        e, mine = ouro._exit(dict(ouro.TINY_CONFIG, exit_threshold=q),
                             params, u)
        np.testing.assert_allclose(mine, p[0], atol=1e-6)
        np.testing.assert_array_equal(e, reference.exit_steps(p[0], q))
    assert set(np.asarray(e)) == {3}          # q = 1: every row at T


def test_at_a_threshold_below_one_rows_leave_at_different_steps(params):
    """``early_exit_threshold`` 0.6: the head reads each row's own
    ``u_e``. The gate's weight drawn ten times wider so that lambda is
    not 0.5 everywhere."""
    wide = dict(params, exit={"w": params["exit"]["w"] * 10,
                              "b": params["exit"]["b"]})
    early = dict(TINY, early_exit_threshold=0.6)
    worst, counted, seqs = _serve(wide, "reference", _prompts(5, 16, 23), 8,
                                  tiny=early)
    assert worst < LOGIT_TOL
    leave = jax.jit(lambda ids: reference.exit_distribution(wide, ids,
                                                            early))
    want = []
    for step in range(8):
        e = []
        for seq in seqs:
            held = len(seq) - 8 + step
            p = leave(_padded(seq[:held]))[0, :, held - 1]
            # a row whose probability of having left lies within 0.02 of
            # the threshold may fall either side in bfloat16
            assert float(jnp.min(jnp.abs(jnp.cumsum(p)[:-1] - 0.6))) > 0.02
            e.append(int(reference.exit_steps(p[:, None], 0.6)[0]))
        want.append(e)
    assert [c["loop.exit_steps"] for c in counted] \
        == [sum(e) for e in want]
    assert len({e for row in want for e in row}) > 1
    # every loop step is still computed for every row
    assert {c["loop.layer_passes"] for c in counted} == {3 * 3 * 2}


# -- through the engine ---------------------------------------------------

def _engine(params, attn, **kw):
    return ServingEngine(params, family.program_config(TINY), max_batch=3,
                         prompt_pad=32, num_blocks=12, block_size=8,
                         attn=attn, model=ouro, **kw)


def _generate(engine, requests):
    for r in requests:
        assert engine.admit(r)
    while any(len(r.generated) < r.max_new_tokens for r in requests):
        active = [r for r in requests
                  if len(r.generated) < r.max_new_tokens]
        for r, (token, _) in zip(active, engine.step_fn(active)):
            r.generated.append(token)
    return [list(r.generated) for r in requests]


def test_the_paged_kernel_and_the_gather_serve_the_same_tokens(params):
    """A mixed batch through ``ServingEngine`` (``admit``, ``step_fn``,
    ``retire``) with the kernel interpreted and with the gather-einsum:
    token for token; the allocator's audit while the batch is live and
    after it has gone; the ``loop.*`` counters among the engine's
    counts."""
    served = {}
    for attn in ("paged", "reference"):
        engine = _engine(params, attn)
        assert engine.cache.layers == 6 and engine.pages_per_seq == 16
        requests = [Request("a", _prompts(5)[0], max_new_tokens=14),
                    Request("b", _prompts(23)[0], max_new_tokens=9),
                    Request("c", _prompts(16)[0], max_new_tokens=3)]
        served[attn] = _generate(engine, requests)
        alloc = engine.cache.allocator
        # 19, 32 and 19 tokens reserved: 3 + 4 + 3 pages of 8 rows
        assert alloc.stats()["blocks_used"] == 10
        assert alloc.check() == []
        for r in requests:
            engine.retire(r)
        assert alloc.stats()["blocks_used"] == 0
        assert alloc.stats()["sequences"] == 0 and alloc.check() == []
        counts = engine.times.counts()
        assert set(counts) >= {"loop.layer_passes", "loop.rows_live",
                               "loop.rows_read", "loop.exit_steps"}
        # 13 + 8 + 2 decoded tokens, each three loop steps of two layers
        assert counts["loop.rows_live"]["total"] == 13 + 8 + 2
        assert counts["loop.layer_passes"]["total"] == (13 + 8 + 2) * 6
        assert counts["loop.exit_steps"]["total"] == (13 + 8 + 2) * 3
    assert served["paged"] == served["reference"]
    assert [len(s) for s in served["paged"]] == [14, 9, 3]


def test_a_full_pool_defers_a_request_until_pages_come_back(params):
    """A pool that holds fewer rows than every slot's longest request:
    admission says no, nothing is reserved, and the request is taken
    once a sequence has gone (the cell's 40 pages beside 8 slots)."""
    engine = _engine(params, "reference")
    long = [Request("r%d" % i, [1] * 24, max_new_tokens=24)
            for i in range(3)]                       # 6 pages each
    assert engine.admit(long[0]) and engine.admit(long[1])
    assert not engine.admit(long[2])
    assert engine.cache.allocator.stats()["blocks_used"] == 12
    assert engine.cache.allocator.check() == []
    engine.retire(long[0])
    assert engine.admit(long[2])
    with pytest.raises(ValueError, match="max_seq"):
        engine.admit(Request("x", [1] * 32, max_new_tokens=97))
