"""models/minicpm_sala on the CPU at a tiny size (hidden 64; four of six
"published" layers, one block-sparse grouped-query layer of 4 query
heads over 2 key/value heads of 16 among three lightning layers of 2
heads of 16; blocks of 8 tokens, the top 4 of them, dense below 48
tokens; pages of 16 rows) against the benchmark's plain reference
(``benchmark/reference/minicpm_sala.py``: float32, the quadratic form,
the selection by its definition; it imports nothing of the program)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

from cellbench_tiny_minicpm_sala import TINY_SALA as TINY

from benchmark.families import minicpm_sala as family
from benchmark.reference import minicpm_sala as reference
from paddle_operator_tpu.models import minicpm_sala as sala
from paddle_operator_tpu.ops import attention_pallas as kernels
from paddle_operator_tpu.ops import linear_attention
from paddle_operator_tpu.serving.batching import (
    ContinuousBatcher, Request, RequestQueue)
from paddle_operator_tpu.serving.engine import ServingEngine
from paddle_operator_tpu.serving.kv_cache import (
    KvCacheFull, SlotBlockAllocator, StateKvCache)

#: |program's logits - the float32 reference's|, widest over a vocabulary
#: of 96 whose logits span about 10 (a spread of 2.0) at initializer_range
#: 0.5. The program multiplies bfloat16 operands and stores bfloat16
#: rows: it reads 0.081 here; the newest blocks in the selection's place
#: read 0.93, the decay left out of the state 0.23. (The state kept in
#: bfloat16 reads 0.072 over these 12 steps: its rounding adds up over
#: the thousands of steps of a served answer, and is read on the chip.)
LOGIT_TOL = 0.12
PAGE, BLOCKS, BATCH, PAD = 16, 40, 4, 96


@pytest.fixture(scope="module")
def params():
    return family.make_params(TINY, 49)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)


def _cfg(tiny=TINY):
    return family.program_config(tiny)


def test_the_tiny_preset_is_the_tiny_file():
    cfg = family.program_config(TINY)
    assert cfg == sala.TINY_CONFIG
    assert _shapes(family.make_params(TINY, 1)) \
        == _shapes(sala.init(jax.random.PRNGKey(1), cfg))


def test_a_sparse_layers_seeded_gains_are_the_configurations():
    """``seeded_weights.sparse_qk_gain`` lands on the sparse layers' q
    and k gains and nowhere else; a configuration that names none (the
    tiny one) has unit gains."""
    made = family.make_params(
        dict(TINY, seeded_weights={"sparse_qk_gain": 2.0}), 1)
    for layer, plain, kind in zip(made["layers"],
                                  family.make_params(TINY, 1)["layers"],
                                  TINY["mixer_types"]):
        want = 2.0 if kind == sala.SPARSE else 1.0
        for name in ("q_norm", "k_norm"):
            assert np.all(np.asarray(layer["attn"][name], np.float32)
                          == want)
            assert np.all(np.asarray(plain["attn"][name], np.float32) == 1.0)
        assert np.array_equal(np.asarray(layer["attn"]["q"], np.float32),
                              np.asarray(plain["attn"]["q"], np.float32))


def test_the_published_preset_is_the_catalogs_row():
    cfg = sala.BASE_CONFIG
    assert (cfg["layers"], cfg["hidden"], cfg["heads"], cfg["kv_heads"],
            cfg["head_dim"], cfg["lightning_heads"],
            cfg["lightning_head_dim"], cfg["mlp_dim"], cfg["vocab_size"],
            cfg["max_seq"], cfg["scale_emb"], cfg["scale_depth"],
            cfg["dim_model_base"]) \
        == (32, 4096, 32, 2, 128, 32, 128, 16384, 73448, 524288, 12.0, 1.4,
            256)
    kinds = list(cfg["mixer_types"])
    assert [i for i, k in enumerate(kinds) if k == sala.SPARSE] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        import json
        with open(catalog) as fh:
            row = next(json.loads(ln) for ln in fh
                       if '"name": "MiniCPM-SALA"' in ln)
        assert kinds == row["config"]["mixer_types"]
    # a bucket is whole chunks; the three the cell's prompt_pad gives
    assert sala.serve_buckets(cfg, 32768) == (16384, 24576, 32768)
    assert sala.serve_buckets(cfg, 96) == (96,)
    with pytest.raises(ValueError, match="mixer_types"):
        sala.serve_cache(dict(cfg, layers=3), 8, 128, 2)
    with pytest.raises(ValueError, match="whole blocks"):
        sala.serve_cache(cfg, 8, 32, 2)


def test_the_decay_follows_the_published_layer_index():
    """lambda_h = exp(-s_h (1 - l / (L - 1) + 1e-5)), l the PUBLISHED
    index: the same layer held at another offset decays otherwise, and
    the reference agrees."""
    cfg = _cfg()
    lam = np.asarray(sala.decay(cfg, 2))
    slopes = 2.0 ** (-8.0 * np.arange(1, 3) / 2)
    np.testing.assert_allclose(
        lam, np.exp(-slopes * (1 - (1 + 2) / 5 + 1e-5)), rtol=1e-6)
    assert not np.allclose(lam, sala.decay(dict(cfg, layer_offset=0), 2))
    np.testing.assert_allclose(lam, reference.decay(TINY, 2), rtol=1e-6)


def test_the_cache_holds_pages_and_a_state_a_sequence():
    cache = sala.serve_cache(_cfg(), 5, PAGE, 3)
    assert isinstance(cache, StateKvCache)
    k, v, c, s = cache.pools()
    assert k.shape == v.shape == (1, 5 + 1, PAGE, 128)
    assert c.shape == (1, 5 + 1, PAGE // 4, 128)
    assert s.shape == (3, 3 + 1, 2, 16, 16)
    assert (k.dtype, c.dtype, s.dtype) \
        == (jnp.bfloat16, jnp.bfloat16, jnp.float32)
    # the names the other caches answer to hold every array
    assert len(cache.k_pages) == 3 and len(cache.v_pages) == 1
    assert cache.table_width(256) == 1 + 16
    big = jax.eval_shape(lambda: sala.serve_cache(
        dict(sala.BASE_CONFIG, layers=16,
             mixer_types=sala.BASE_CONFIG["mixer_types"][8:24],
             layer_offset=8, max_seq=34816),
        4352, 128, 16).pools())
    assert big[0].shape == (4, 4353, 128, 256)
    assert big[2].shape == (4, 4353, 8, 256)
    assert big[3].shape == (12, 17, 32, 128, 128)
    # the engine tells the state pool its size: a slot a row of the batch
    engine = ServingEngine(
        family.make_params(TINY, 1), _cfg(), max_batch=3, prompt_pad=PAD,
        num_blocks=BLOCKS, block_size=PAGE, attn="reference", model=sala)
    assert engine.cache.slots == 3


# -- the allocator: pages and a slot, both or neither ----------------------

def test_a_sequence_takes_pages_and_a_slot_and_hands_both_back():
    alloc = SlotBlockAllocator(8, PAGE, slots=2)
    alloc.alloc_sequence("a", 40, live_tokens=20)
    alloc.alloc_sequence("b", 16)
    assert {alloc.slot("a"), alloc.slot("b")} == {0, 1}
    stats = alloc.stats()
    assert (stats["slots_used"], stats["slots_total"],
            stats["blocks_used"]) == (2, 2, 4)
    # pages are there and no slot is: nothing is taken
    with pytest.raises(KvCacheFull, match="slot"):
        alloc.alloc_sequence("c", 16)
    assert alloc.stats()["blocks_used"] == 4 and alloc.check() == []
    # a slot would be there and no pages are: the slot goes back
    freed = alloc.slot("b")
    alloc.free_sequence("b")
    with pytest.raises(KvCacheFull, match="block"):
        alloc.alloc_sequence("c", 8 * PAGE)
    assert alloc.stats()["slots_used"] == 1 and alloc.check() == []
    alloc.alloc_sequence("c", 16)
    assert alloc.slot("c") == freed          # a just-freed slot first
    for seq in ("a", "c", "never-there"):
        alloc.free_sequence(seq)
    assert alloc.stats()["slots_used"] == 0 and alloc.check() == []


def test_the_decode_table_starts_with_the_state_slot():
    cache = sala.serve_cache(_cfg(), BLOCKS, PAGE, 2)
    cache.allocator.alloc_sequence("a", 40, live_tokens=20)
    cache.allocator.alloc_sequence("b", 40, live_tokens=33)
    position, table, live = cache.decode_row("b")
    assert (position, live) == (33, 33)
    assert table == [cache.allocator.slot("b")] \
        + cache.allocator.block_table("b")
    assert cache.scatter_attrs("b") == {"state_slot": table[0]}


# -- prefill, then decode through the cache, against one forward ----------

def _padded(seq, width=PAD + 32):
    ids = np.zeros((1, width), np.int32)
    ids[0, :len(seq)] = seq
    return jnp.asarray(ids)


def _prompts(*lengths):
    rnd = np.random.RandomState(0)
    return [list(rnd.randint(0, TINY["vocab_size"], size=n))
            for n in lengths]


def _serve(params, attn, prompts, steps, tiny=TINY):
    """Prompts prefilled and written into the cache as the engine does
    it, then ``steps`` decode steps of the whole batch: the widest
    distance of any row's logits, at the prefill and at every step,
    from the reference's full forward over everything the row has
    seen; the counters of every step; what the rows hold."""
    cfg = _cfg(tiny)
    cache = sala.serve_cache(cfg, BLOCKS, PAGE, BATCH)
    seqs, apart, counted = [], [], []
    ref = jax.jit(lambda p, ids: reference.logits(p, ids, tiny, "f32"))

    def want(seq):
        return ref(params, _padded(seq))[0, len(seq) - 1]

    fill = jax.jit(lambda p, i, n: sala.prefill(cfg, p, i, n,
                                                with_logits=True))
    for i, prompt in enumerate(prompts):
        n = len(prompt)
        cache.allocator.alloc_sequence(
            "s%d" % i, n + steps + 1, live_tokens=n)
        ids = np.zeros((1, PAD), np.int32)
        ids[0, :n] = prompt
        token, rows, logits = fill(params, jnp.asarray(ids),
                                   jnp.asarray(n, jnp.int32))
        assert rows[0].shape == rows[1].shape == (1, PAD, 32)
        assert rows[2].shape == (1, PAD // 4, 32)
        assert rows[3].shape == (cache.state_layers, 2, 16, 16)
        apart.append(float(jnp.max(jnp.abs(logits - want(prompt)))))
        cache.write_rows("s%d" % i, rows, n)
        seqs.append(list(prompt) + [int(token)])
    decode = jax.jit(lambda *a: sala.decode(
        cfg, *a, attn_impl=attn, block_size=PAGE, dummy_page=BLOCKS,
        with_logits=True))
    pools = cache.pools()
    width = cache.table_width(cfg["max_seq"])
    for _ in range(steps):
        tokens, positions, lens = np.zeros((3, BATCH), np.int32)
        tables = np.zeros((BATCH, width), np.int32)
        for i, seq in enumerate(seqs):
            tokens[i] = seq[-1]
            positions[i], table, lens[i] = cache.decode_row("s%d" % i)
            tables[i, :len(table)] = table
        out, pools, counters, logits = decode(
            params, pools, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(lens),
            jnp.arange(BATCH) < len(seqs))
        counted.append({k: int(v) for k, v in counters.items()})
        for i, seq in enumerate(seqs):
            apart.append(float(jnp.max(jnp.abs(logits[i] - want(seq)))))
            seq.append(int(out[i]))
    assert cache.allocator.check() == []
    return max(apart), counted, seqs


@pytest.mark.parametrize("attn", ["paged", "reference"])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        params, attn):
    """Three prompts — one dense all the way (20 + 12 < 48), one that
    crosses ``dense_len`` while it decodes (41 .. 53), one sparse from
    its prefill on (77: 10 blocks, the top 4 read) — prefilled, their
    pages, compressed keys and states written, then 12 decode steps of
    the batch, in which windows close and the selection moves: logits
    at the prefill and at every step."""
    worst, counted, _ = _serve(params, attn, _prompts(20, 41, 77), 12)
    assert worst < LOGIT_TOL
    first = counted[0]
    # rows of 21, 42 and 78 tokens: 3, 6 and 10 blocks a key/value head;
    # the third reads its top 4 and scores 78 // 4 - 1 compressed rows;
    # the kernel's lists are 6 wide, one cell a row and head
    assert first == {"sala.blocks_live": 2 * (3 + 6 + 10),
                     "sala.blocks_read": 2 * (3 + 6 + 4),
                     "sala.kernel_cells": 3 * 2 if attn == "paged" else 0,
                     "sala.ckeys_read": 18,
                     "lin.state_updates": 3 * 3, "lin.rows_live": 3}
    # the second row is past dense_len by the 7th step (48 tokens)
    assert counted[6]["sala.blocks_read"] == 2 * (4 + 4 + 4)
    assert counted[6]["sala.ckeys_read"] == (48 // 4 - 1) + (84 // 4 - 1)


def _the_newest_blocks(monkeypatch):
    """Planted fault: the selection replaced by the newest blocks (every
    block scored alike, so the stable top-k takes the forced ones and
    then the lowest, the decode side; prefill is left sound)."""
    real = kernels.select_blocks

    def newest(scores, lens, block, topk, init_blocks, local_blocks,
               dense_len):
        return real(scores, lens, block, topk, 0, topk, dense_len)

    monkeypatch.setattr(kernels, "select_blocks", newest)


def _no_decay(monkeypatch):
    """Planted fault: the decay left out of the state's update."""
    real = linear_attention.step
    monkeypatch.setattr(
        linear_attention, "step",
        lambda q, k, v, decay, state: real(q, k, v, jnp.ones_like(decay),
                                           state))


@pytest.mark.parametrize("plant,least", [
    (_the_newest_blocks, 4.0), (_no_decay, 1.5)],
    ids=["the-newest-blocks-selected", "no-decay"])
def test_a_planted_fault_fails_the_comparison(params, plant, least,
                                              monkeypatch):
    plant(monkeypatch)
    worst, _, _ = _serve(params, "reference", _prompts(20, 41, 77), 12)
    assert worst > least * LOGIT_TOL


def test_the_counters_follow_what_ran(params):
    """The selection bypassed (``dense_len`` past every context) reads
    every block: ``sparse_blocks_read_pct`` 100. A stack that holds one
    lightning layer fewer advances one state fewer a row."""
    dense = dict(TINY, sparse_config=dict(TINY["sparse_config"],
                                          dense_len=10 ** 6))
    worst, counted, _ = _serve(params, "reference", _prompts(20, 77), 3,
                               tiny=dense)
    assert worst < LOGIT_TOL
    assert all(c["sala.blocks_read"] == c["sala.blocks_live"]
               and c["sala.ckeys_read"] == 0 for c in counted)
    fewer = dict(TINY, num_hidden_layers=3,
                 mixer_types=TINY["mixer_types"][:3])
    cut = dict(params, layers=params["layers"][:3])
    worst, counted, _ = _serve(cut, "reference", _prompts(20, 77), 3,
                               tiny=fewer)
    assert worst < LOGIT_TOL
    assert all(c["lin.state_updates"] == 2 * c["lin.rows_live"] == 4
               for c in counted)


# -- the selection's rule, by hand -----------------------------------------

def test_the_forced_blocks_count_among_the_top_and_ties_go_to_the_lower():
    """One row of 100 tokens (13 blocks of 8, the newest token in block
    12), the top 5 with one block forced first and two last: blocks 0,
    11 and 12 are in whatever they score, and two more by score; of
    equal scores the lower block."""
    scores = jnp.zeros((1, 1, 16)).at[0, 0, jnp.asarray([3, 7, 9, 11])].set(
        jnp.asarray([0.5, 0.9, 0.5, 0.0]))
    chosen, count = kernels.select_blocks(
        scores, jnp.asarray([100]), block=8, topk=5, init_blocks=1,
        local_blocks=2, dense_len=48)
    assert int(count[0, 0]) == 5
    # the three forced (lowest first), then 0.9, then the LOWER of the
    # two 0.5s; block 9 is left out and blocks 13.. are never seen
    assert chosen[0, 0, :5].tolist() == [0, 11, 12, 7, 3]
    # under dense_len every block the row has, in order
    chosen, count = kernels.select_blocks(
        scores, jnp.asarray([41]), block=8, topk=5, init_blocks=1,
        local_blocks=2, dense_len=48)
    assert int(count[0, 0]) == 6
    assert chosen[0, 0, :6].tolist() == [0, 1, 2, 3, 4, 5]
    assert chosen.shape[-1] == 6             # ceil(48 / 8): a dense row's
    # a pad row reads nothing
    _, count = kernels.select_blocks(
        scores, jnp.asarray([0]), block=8, topk=5, init_blocks=1,
        local_blocks=2, dense_len=48)
    assert int(count[0, 0]) == 0


def test_a_blocks_score_is_the_largest_over_the_windows_that_overlap_it():
    """Compressed row r is the window of strides r - 1 and r. Block m
    (2 strides here) is overlapped by the windows that end with strides
    2 m .. 2 m + 2, and only complete windows are scored."""
    d = 8
    ckeys = jnp.zeros((1, 8, 1, d)).at[0, 5, 0, 0].set(8.0)
    q = jnp.zeros((1, 1, 1, d)).at[0, 0, 0, 0].set(8.0)
    scores = kernels.gqa_block_scores(q, ckeys, jnp.asarray([32]), 2, 4, 1.0)
    assert scores.shape == (1, 1, 4)
    # row 5 takes all the mass: it overlaps block 2 (rows 4, 5, 6) and
    # no other (block 1 takes rows 2, 3, 4; block 3 rows 6, 7, 8)
    np.testing.assert_allclose(scores[0, 0], [0, 0, 1, 0], atol=1e-6)
    # a row of 20 tokens has complete windows up to row 4 only: row 5
    # is not scored, the mass is spread over rows 1 .. 4
    scores = kernels.gqa_block_scores(q, ckeys, jnp.asarray([20]), 2, 4, 1.0)
    np.testing.assert_allclose(scores[0, 0], [0.25, 0.25, 0.25, 0],
                               atol=1e-6)


def _lists(*rows):
    """(lens, chosen, count) from a ``(tokens, [a head's list, ...])`` a
    row; a list is padded to the longest with block 0, as the selection
    pads it."""
    width = max(len(ids) for _, heads in rows for ids in heads)
    return (jnp.asarray([n for n, _ in rows], jnp.int32),
            jnp.asarray([[list(ids) + [0] * (width - len(ids))
                          for ids in heads] for _, heads in rows], jnp.int32),
            jnp.asarray([[len(ids) for ids in heads] for _, heads in rows],
                        jnp.int32))


#: what a grid of cells of several blocks can get wrong, at 4 blocks a
#: cell unless a case says otherwise: rows of (tokens, a list a head)
#: over block tables of 6 pages of 2 blocks of 8 tokens
KERNEL_CASES = {
    # PR 49's: lists of different lengths, a selected block that ends
    # past the row's tokens (43 = 5 blocks and 3 tokens), a pad row
    "lists-of-4-2-1-4-and-a-pad-row": (4, [
        (43, [[0, 5, 3, 1], [5, 4]]), (0, [[], []]),
        (30, [[3], [0, 1, 2, 3]])]),
    "a-list-shorter-than-one-cell": (4, [
        (70, [[8, 2, 5], [1]]), (61, [[7, 0], [3, 6, 4]])]),
    "a-list-that-ends-in-the-middle-of-a-cell": (4, [
        (90, [[0, 11, 4, 9, 2, 7], [3, 1, 10, 6, 8]])]),
    "a-list-exactly-width-long": (4, [
        (96, [list(range(12)), [11, 3, 7, 0, 9, 1, 5, 10, 2, 8, 4, 6]]),
        (41, [[0, 1, 2, 3, 4, 5], [5, 0]])]),
    "two-heads-of-one-row-a-cell-apart": (4, [
        (85, [[10, 2], [0, 1, 2, 3, 4, 5, 6, 7, 8, 9]])]),
    "a-pad-row-between-live-rows": (4, [
        (50, [[6, 1, 4, 0, 2], [3, 6]]), (0, [[], []]), (0, [[], []]),
        (77, [[9, 0, 5, 3, 8, 1, 7], [2, 9, 4, 6, 0]])]),
    # 59 tokens: block 7 holds 3 of them, in a cell whose others are whole
    "a-block-past-the-tokens-among-whole-ones": (4, [
        (59, [[1, 7, 4, 2], [0, 3, 7, 5, 6]])]),
    "the-last-live-row-reads-one-block": (4, [
        (33, [[4, 0, 1, 2, 3], [2, 4, 0]]), (3, [[0], [0]])]),
    "width-smaller-than-the-constant": (8, [
        (47, [[5, 0, 2], [1, 4, 3, 0, 5]]), (0, [[], []]),
        (20, [[2], [0, 1, 2]])]),
    "one-block-a-cell": (1, [
        (43, [[0, 5, 3, 1], [5, 4]]), (30, [[3], [0, 1, 2, 3]])]),
    "a-cell-of-two-and-an-odd-list": (2, [
        (66, [[8, 0, 3], [1, 7, 2, 5, 4]])]),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernel_reads_the_selected_blocks_in_place(case, monkeypatch):
    """``gqa_block_decode`` in interpret mode against its gather-einsum
    reference: pages in block tables in another order than the pool's,
    ``KERNEL_CASES``' lists, both layers of the pool."""
    per_cell, rows = KERNEL_CASES[case]
    monkeypatch.setattr(kernels, "SPARSE_BLOCKS_PER_CELL", per_cell)
    g, r, d, block, page = 2, 2, 16, 8, 16
    keys = jax.random.split(jax.random.key(5), 3)
    k_pages = jax.random.normal(keys[0], (2, 25, page, 128), jnp.bfloat16)
    v_pages = jax.random.normal(keys[1], (2, 25, page, 128), jnp.bfloat16)
    q = jax.random.normal(keys[2], (len(rows), g, r, d), jnp.float32)
    tables = jnp.asarray(np.random.RandomState(3).permutation(24).reshape(
        4, 6)[:len(rows)], jnp.int32)
    lens, chosen, count = _lists(*rows)
    (n_rows, heads, cells), per = kernels.gqa_block_grid(
        count, lens, chosen.shape[-1])
    assert per == min(per_cell, chosen.shape[-1])
    assert (int(n_rows), heads, int(cells)) == (
        max(i + 1 for i, (n, _) in enumerate(rows) if n), g,
        -(-int(count.max()) // per))
    for layer in (0, 1):
        got = kernels.gqa_block_decode(q, k_pages, v_pages, tables, chosen,
                                       count, lens, layer, block,
                                       interpret=True)
        want = kernels._reference_gqa_block_decode(
            q.astype(jnp.bfloat16), k_pages, v_pages, tables, chosen, count,
            lens, layer, block, d ** -0.5)
        assert got.shape == (len(rows), g, r, d)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for i, (n, _) in enumerate(rows):
            assert (float(jnp.max(jnp.abs(got[i]))) > 0.1) == (n > 0)


# -- through the engine and the batcher ---------------------------------

def _engine(params, attn="reference", max_batch=BATCH, blocks=BLOCKS,
            slots=None):
    """``slots``: a state pool SMALLER than the batch, which the engine
    never builds (it hands the hook its ``max_batch``), put in the
    engine's place before its first step."""
    engine = ServingEngine(params, _cfg(), max_batch=max_batch,
                           prompt_pad=PAD, num_blocks=blocks,
                           block_size=PAGE, attn=attn, model=sala)
    assert engine.cache.slots == max_batch
    if slots is not None:
        engine.cache = sala.serve_cache(engine.config, blocks, PAGE, slots)
    return engine


def _run(engine, requests, steps=200):
    queue = RequestQueue(64, "reject_new")
    batcher = ContinuousBatcher(queue, engine.max_batch,
                                on_admit=engine.admit,
                                on_retire=engine.retire)
    for req in requests:
        queue.submit(req)
    for _ in range(steps):
        batcher.step(engine.step_fn)
        if not batcher.in_flight() and not queue.depth():
            break
    return batcher


def _requests(lengths, new):
    return [Request("r%d" % i, p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts(*lengths), new))]


def test_the_paged_kernel_and_the_gather_serve_the_same_tokens(params):
    served = {}
    for attn in ("paged", "reference"):
        requests = _requests((20, 41, 77, 60), (9, 14, 6, 11))
        _run(_engine(params, attn), requests)
        assert all(len(r.generated) == r.max_new_tokens for r in requests)
        served[attn] = [r.generated for r in requests]
    assert served["paged"] == served["reference"]


def _kernel_calls(jaxpr):
    """The names of a jaxpr's Pallas calls, calls and loops looked into."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _kernel_calls(sub)
    return found


def test_the_decode_step_calls_the_kernel_once_a_sparse_layer_in_cells(
        params, monkeypatch):
    """Three rows through the model's hook at 4 blocks a cell: lists of
    3, 6 and 4 blocks a head (the second row dense, all 6 of its blocks)
    make a grid of 3 rows x 2 heads x 2 cells; once the second row is
    past ``dense_len`` every list is 4 long and a cell a (row, head) is
    left. The tokens and every other counter are the gather's."""
    from paddle_operator_tpu.utils import trace

    monkeypatch.setattr(kernels, "SPARSE_BLOCKS_PER_CELL", 4)
    monkeypatch.setattr(trace, "_global", trace.Tracer(enabled=True))
    monkeypatch.setattr(sala, "_plans_seen", set())
    served = {attn: _serve(params, attn, _prompts(20, 41, 77), 8)
              for attn in ("paged", "reference")}
    assert served["paged"][2] == served["reference"][2]
    cells = [c.pop("sala.kernel_cells") for c in served["paged"][1]]
    assert {c.pop("sala.kernel_cells") for c in served["reference"][1]} == {0}
    assert served["paged"][1] == served["reference"][1]
    assert [c["sala.blocks_read"] for c in served["paged"][1][:7:6]] \
        == [2 * (3 + 6 + 4), 2 * (4 + 4 + 4)]
    assert cells == [3 * 2 * 2] * 6 + [3 * 2 * 1] * 2
    assert sorted(e["attrs"]["blocks_per_cell"]
                  for e in trace.tracer().events
                  if e["name"] == "sala.plan" and e["attrs"]["chunk"] == 1
                  ) == [0, 4]
    cfg = _cfg()
    cache = sala.serve_cache(cfg, BLOCKS, PAGE, BATCH)
    row = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    step = jax.make_jaxpr(lambda *a: sala.decode(
        cfg, *a, attn_impl="paged", block_size=PAGE, dummy_page=BLOCKS))(
        params, cache.pools(), row, row,
        jax.ShapeDtypeStruct((BATCH, cache.table_width(cfg["max_seq"])),
                             jnp.int32),
        row, jax.ShapeDtypeStruct((BATCH,), jnp.bool_))
    assert _kernel_calls(step.jaxpr) == ["gqa_block_decode"] \
        * TINY["mixer_types"].count("minicpm4")


def test_a_request_that_finds_pages_and_no_slot_is_deferred(params):
    """Two slots behind a pool with room for all three: the third waits
    for a slot (``admit`` says no, nothing is held), takes the one the
    first to finish hands back, and is served what a server with room
    for all serves it."""
    engine = _engine(params, slots=2)
    requests = _requests((20, 41, 30), (4, 12, 5))
    assert engine.admit(requests[0]) and engine.admit(requests[1])
    assert engine.admit(requests[2]) is False
    assert engine.cache.allocator.stats()["sequences"] == 2
    for req in requests[:2]:
        engine.retire(req)
    batcher = _run(engine, requests)
    assert batcher.counts()["admit_deferred"] >= 1
    assert all(len(r.generated) == r.max_new_tokens for r in requests)
    stats = engine.cache.allocator.stats()
    assert (stats["slots_used"], stats["blocks_used"]) == (0, 0)
    assert engine.cache.allocator.check() == []
    roomy = _requests((20, 41, 30), (4, 12, 5))
    _run(_engine(params), roomy)
    assert [r.generated for r in requests] == [r.generated for r in roomy]


def test_a_freed_slot_is_reused_with_its_new_sequences_state(params):
    """One slot: the second request takes the slot the first left its
    state in. A prefill writes the slot whole, so what the second is
    served does not depend on who held the slot before."""
    engine = _engine(params, max_batch=1)
    first, second = _requests((77, 41), (8, 10))
    _run(engine, [first])
    left = np.asarray(engine.cache.pools()[3][:, 0])
    assert float(np.abs(left).max()) > 0.0
    _run(engine, [second])
    alone = _requests((77, 41), (8, 10))[1]
    _run(_engine(params, max_batch=1), [alone])
    assert second.generated == alone.generated
    # the pad rows' slot is never advanced
    assert float(jnp.max(jnp.abs(engine.cache.pools()[3][:, 1]))) == 0.0
    spans = engine.times.samples("serve.prefill.scatter")
    assert [s.attrs["state_slot"] for s in spans] == [0, 0]


def test_the_engine_banks_the_counters_and_says_its_plan_once(
        params, monkeypatch):
    from paddle_operator_tpu.utils import trace

    monkeypatch.setattr(trace, "_global", trace.Tracer(enabled=True))
    monkeypatch.setattr(sala, "_plans_seen", set())
    engine = _engine(params)
    _run(engine, _requests((20, 77), (5, 7)))
    counts = engine.times.counts()
    assert {"sala.blocks_read", "sala.blocks_live", "sala.ckeys_read",
            "sala.kernel_cells", "lin.state_updates",
            "lin.rows_live"} <= set(counts)
    assert counts["lin.state_updates"]["total"] \
        == 3 * counts["lin.rows_live"]["total"]
    assert counts["sala.blocks_read"]["total"] \
        < counts["sala.blocks_live"]["total"]
    # the gather reference ran no kernel cell; an operator reads so
    from paddle_operator_tpu.serving import ServeMetrics
    assert ('tpujob_serve_step_counter_total{job="default/serve",'
            'counter="sala.kernel_cells"} 0') in ServeMetrics(
        job="default/serve", stages=(engine.times,)).metrics_block()
    # tracing a program says its plan, once however often it is traced
    # (the engine may take a program from the compile ladder untraced)
    ids = jax.ShapeDtypeStruct((1, PAD), jnp.int32)
    for _ in range(2):
        jax.eval_shape(lambda p, i, n: sala.prefill(_cfg(), p, i, n),
                       params, ids, jax.ShapeDtypeStruct((), jnp.int32))
    plans = [e["attrs"] for e in trace.tracer().events
             if e["name"] == "sala.plan"]
    assert sorted(plans, key=lambda a: a["chunk"]) == [
        dict(layers_sparse=1, layers_lightning=3, block=8, topk=4, chunk=c,
             blocks_per_cell=0)
        for c in (1, 96)]
