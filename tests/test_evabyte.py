"""models/evabyte on the CPU at a tiny size (hidden 128, 4 heads of 32,
windows of 32 positions in chunks of 4, pages of 8 rows: a closed
window's 8 summaries are one page, as 2048 / 16 = 128 are at the
published sizes) against the benchmark's plain reference
(``benchmark/reference/evabyte.py``: float32, no cache; it imports
nothing of the program), and ``serving.kv_cache.WindowKvCache``'s page
arithmetic at the published sizes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

from cellbench_tiny_evabyte import TINY_EVABYTE as TINY

from benchmark.families import evabyte as family
from benchmark.reference import evabyte as reference
from paddle_operator_tpu.models import evabyte
from paddle_operator_tpu.ops import attention_pallas as ap
from paddle_operator_tpu.serving.batching import Request
from paddle_operator_tpu.serving.engine import ServingEngine
from paddle_operator_tpu.serving.kv_cache import (
    KvCacheFull, PagedKvCache, WindowKvCache)

#: |program's logits - the float32 reference's|, widest over a vocabulary
#: of 64 whose logits span about 10 at init_std 0.2. The program
#: multiplies bfloat16 operands and stores bfloat16 rows: it reads
#: 0.05-0.25 here, the reference itself in bfloat16 0.45 against its
#: float32 self and in fp8 4.6; summaries left out read 1.5 and more, a
#: plain mean in place of ``softmax(s phi . k)`` 0.8 and more
LOGIT_TOL = 0.4


@pytest.fixture(scope="module")
def params():
    return family.make_params(TINY, 36)


def test_the_tiny_preset_is_the_tiny_file():
    cfg = family.program_config(TINY)
    assert cfg == evabyte.TINY_CONFIG
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), family.make_params(TINY, 1)) \
        == jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype),
            evabyte.init(jax.random.PRNGKey(1), cfg))


def test_the_published_preset_is_the_catalogs_row():
    cfg = evabyte.BASE_CONFIG
    assert (cfg["layers"], cfg["hidden"], cfg["heads"], cfg["mlp_dim"],
            cfg["window"], cfg["chunk"], cfg["vocab_size"], cfg["max_seq"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) \
        == (32, 4096, 32, 11008, 2048, 16, 320, 32768, 100000.0, 1e-5)
    with pytest.raises(ValueError, match="whole number of tiles"):
        evabyte.serve_cache(dict(evabyte.TINY_CONFIG, hidden=64), 8, 8)
    with pytest.raises(ValueError, match="whole windows"):
        evabyte.serve_buckets(evabyte.TINY_CONFIG, 48)
    assert evabyte.serve_buckets(evabyte.TINY_CONFIG, 96) == (32, 64, 96)


def test_the_norm_adds_one_to_its_learned_vector():
    from paddle_operator_tpu.ops import nn

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16), jnp.float32)
    g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,), jnp.float32)
    got = nn.rmsnorm(g, x, 1e-5, jnp.float32, unit_offset=True)
    np.testing.assert_allclose(got, reference.norm(g, x, 1e-5), atol=1e-6)
    np.testing.assert_allclose(
        got, nn.rmsnorm(1.0 + g, x, 1e-5, jnp.float32), atol=1e-6)


# -- prefill, then decode through the cache, against one forward ----------

def _serve(params, attn, prompts, steps):
    """Prompts prefilled and written into the cache as the engine does
    it, then ``steps`` decode steps of the whole batch: the widest
    distance of any row's logits, at the prefill and at every step,
    from the reference's full forward over everything the row has
    seen; and the counters of every step."""
    cfg = family.program_config(TINY)
    bs, blocks, batch = 8, 40, 4
    cache = evabyte.serve_cache(cfg, blocks, bs)
    seqs, apart, counted = [], [], []

    def want(seq):
        return reference.logits(params, jnp.asarray([seq], jnp.int32),
                                TINY, "f32")[0, -1]

    for i, prompt in enumerate(prompts):
        n = len(prompt)
        cache.allocator.alloc_sequence(
            "s%d" % i, n + steps + 1, live_tokens=n)
        pad = next(b for b in evabyte.serve_buckets(cfg, 96) if b >= n)
        ids = np.zeros((1, pad), np.int32)
        ids[0, :n] = prompt
        token, rows, logits = jax.jit(
            lambda p, i, l: evabyte.prefill(cfg, p, i, l, with_logits=True)
        )(params, jnp.asarray(ids), jnp.asarray(n, jnp.int32))
        apart.append(float(jnp.max(jnp.abs(logits - want(prompt)))))
        cache.write_rows("s%d" % i, rows, n)
        seqs.append(list(prompt) + [int(token)])
    decode = jax.jit(lambda *a: evabyte.decode(
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


def _prompts(*lengths):
    rnd = np.random.RandomState(0)
    return [list(rnd.randint(0, TINY["vocab_size"], size=n))
            for n in lengths]


@pytest.mark.parametrize("attn", ["paged", "reference"])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        params, attn):
    """Three prompts — one that ends inside a chunk and inside its first
    window (30), one inside a later window (45), one exactly on a
    window's end (64) — prefilled, their open windows' rows and their
    summaries written into pages, then 12 decode steps of the batch,
    which close the first prompt's window: logits at the prefill and at
    every step."""
    worst, counted, _ = _serve(params, attn, _prompts(30, 45, 64), 12)
    assert worst < LOGIT_TOL
    # at the first step the rows stand at positions 30, 45, 64: they read
    # 31, 8 + 14 and 2 x 8 + 1 rows where exact attention reads 31 + 46
    # + 65; the second step closes the first row's window
    assert counted[0] == {"eva.rows_read": 31 + 22 + 17,
                          "eva.tokens_live": 31 + 46 + 65,
                          "eva.windows_closed": 0}
    assert [c["eva.windows_closed"] for c in counted[:3]] == [0, 1, 0]
    # ... after which it reads its 8 summaries and its new window's rows
    assert counted[2]["eva.rows_read"] == (8 + 1) + 24 + 19


def test_a_generation_that_closes_two_windows_keeps_the_references_logits(
        params):
    """One prompt of 20 positions and 70 decode steps: the windows at 32
    and 64 close while it decodes, and from then on its attention reads
    summaries pooled by decode steps alone."""
    worst, counted, (seq,) = _serve(params, "reference", _prompts(20), 70)
    assert worst < LOGIT_TOL
    assert sum(c["eva.windows_closed"] for c in counted) == 2
    assert len(seq) == 20 + 1 + 70
    # position 89 is the 70th: 2 x 8 summaries and 89 % 32 + 1 rows
    assert counted[-1] == {"eva.rows_read": 16 + 26, "eva.tokens_live": 90,
                           "eva.windows_closed": 0}


def _no_summaries(monkeypatch):
    """Planted fault (a): a row sees its own window only."""
    attend = evabyte._attend_window
    monkeypatch.setattr(
        evabyte, "_attend_window",
        lambda q, k, v, sum_k, sum_v, visible: attend(q, k, v, sum_k, sum_v,
                                                      0))
    row = WindowKvCache.decode_row

    def decode_row(self, seq_id):
        position, table, _ = row(self, seq_id)
        closed = position // self.window
        return (position, table[:1] + table[1 + closed:],
                position % self.window)

    monkeypatch.setattr(WindowKvCache, "decode_row", decode_row)


def _mean_pool(monkeypatch):
    """Planted fault (b): a chunk pooled by a plain mean."""
    def pool(attn, k, v):
        mu = attn["mu"].astype(jnp.float32)
        return ((jnp.mean(k.astype(jnp.float32), axis=-3) + mu
                 ).astype(jnp.bfloat16),
                jnp.mean(v.astype(jnp.float32), axis=-3
                         ).astype(jnp.bfloat16))

    monkeypatch.setattr(evabyte, "_pool_chunks", pool)


@pytest.mark.parametrize("plant", [_no_summaries, _mean_pool],
                         ids=["summaries-left-out", "chunks-pooled-by-mean"])
def test_a_planted_fault_fails_the_comparison(params, plant, monkeypatch):
    plant(monkeypatch)
    worst, _, _ = _serve(params, "reference", _prompts(30, 45, 64), 12)
    assert worst > 2 * LOGIT_TOL


# -- through the engine ---------------------------------------------------

def _engine(params, attn, **kw):
    return ServingEngine(params, family.program_config(TINY), max_batch=2,
                         prompt_pad=96, num_blocks=20, block_size=8,
                         attn=attn, model=evabyte, **kw)


def _generate(engine, requests):
    for r in requests:
        assert engine.admit(r)
    while any(len(r.generated) < r.max_new_tokens for r in requests):
        active = [r for r in requests
                  if len(r.generated) < r.max_new_tokens]
        for r, (token, _) in zip(active, engine.step_fn(active)):
            r.generated.append(token)
    return [list(r.generated) for r in requests]


def test_the_paged_kernel_and_the_gather_serve_the_same_bytes(params):
    """Two requests through ``ServingEngine`` (queue-less: ``admit``,
    ``step_fn``, ``retire``), one of which closes a window while it
    decodes, with the kernel interpreted and with the gather-einsum:
    token for token; window pages written over in place, every page
    back in the pool after ``retire``."""
    served = {}
    for attn in ("paged", "reference"):
        engine = _engine(params, attn)
        requests = [Request("a", _prompts(45)[0], max_new_tokens=30),
                    Request("b", _prompts(70)[0], max_new_tokens=9)]
        served[attn] = _generate(engine, requests)
        alloc = engine.cache.allocator
        # a's 75 positions hold 4 window pages and 2 summary pages, b's
        # 79 as many: 12 pages where a row a token would take 10 + 10
        assert alloc.stats()["blocks_used"] == 12
        # a has written 74 positions (its last byte is not fed back): 10
        # rows of its window and 18 whole chunks, of which its two
        # summary pages hold 16; b 78: 14 rows, 16 of 19 chunks
        assert alloc.stats()["waste_slots"] \
            == 12 * 8 - (74 % 32 + 16) - (78 % 32 + 16)
        assert alloc.check() == []
        for r in requests:
            engine.retire(r)
        assert alloc.stats()["blocks_used"] == 0
        assert alloc.stats()["sequences"] == 0 and alloc.check() == []
        counts = engine.times.counts()
        assert counts["eva.windows_closed"]["steps"] == 29
    assert served["paged"] == served["reference"]
    assert len(served["paged"][0]) == 30 and len(served["paged"][1]) == 9


def test_admission_reserves_by_the_caches_arithmetic(params):
    engine = _engine(params, "reference")
    # 96 + 32 positions: 4 window pages and 3 summary pages each
    long = [Request("r%d" % i, [1] * 96, max_new_tokens=32)
            for i in range(3)]
    assert engine.admit(long[0]) and engine.admit(long[1])
    assert not engine.admit(long[2])                 # 6 pages left of 20
    short = Request("s", [1] * 20, max_new_tokens=12)   # one window: 4
    assert engine.admit(short)
    assert engine.cache.allocator.stats()["blocks_free"] == 2
    assert engine.pages_per_seq == 1 + 3 + 4
    with pytest.raises(ValueError, match="max_seq"):
        engine.admit(Request("x", [1] * 96, max_new_tokens=33))


# -- the cache's answers --------------------------------------------------

@pytest.fixture(scope="module")
def published():
    """The arithmetic of the published sizes over a pool that holds
    nothing (one layer, one head)."""
    return WindowKvCache(64, 128, 1, 1, 128, window=2048, chunk=16)


@pytest.mark.parametrize("tokens,pages", [
    (1, 1), (128, 1), (129, 2), (1920, 15), (2048, 16), (2049, 17),
    (4096, 17), (4097, 18), (16384, 23), (18432, 24), (32768, 31)])
def test_the_pages_a_budget_reserves(published, tokens, pages):
    """min(16, ceil(T / 128)) window pages and (T - 1) // 2048 summary
    pages: 18,432 positions are 24 pages where a row a token needs 144."""
    assert published.pages_for(tokens) == pages
    assert published.allocator.pages_for(tokens) == pages
    assert pages <= -(-tokens // 128)


@pytest.mark.parametrize("cache", ["paged", "latent"])
def test_a_row_a_token_cache_answers_as_the_engine_used_to_compute(cache):
    from paddle_operator_tpu.serving.kv_cache import LatentKvCache

    c = PagedKvCache(8, 16, 1, 1, 128) if cache == "paged" \
        else LatentKvCache(8, 16, 1, (128,))
    assert [c.pages_for(t) for t in (1, 16, 17, 100)] == [1, 1, 2, 7]
    assert c.table_width(100) == 7
    table = c.allocator.alloc_sequence("s", 40, live_tokens=20)
    assert c.decode_row("s") == (20, table, 20)
    assert c.decode_row("s") == (21, table, 21)
    assert c.allocator.stats()["waste_slots"] == 3 * 16 - 22


def test_a_decode_rows_table_through_a_sequences_windows(published):
    alloc = published.allocator
    table = alloc.alloc_sequence("s", 6000, live_tokens=2047)
    assert len(table) == 16 + 2 and published.table_width(18432) == 25
    pages, summaries = table[:16], table[16:]
    dummy = published.dummy_page
    # the last position of the first window: nothing closed yet, column 0
    # is the first window's summary page
    assert published.decode_row("s") == (2047, summaries[:1] + pages, 2047)
    # the window has closed: its page is attended first, the window's
    # pages start again
    assert published.decode_row("s") == (
        2048, summaries[1:] + summaries[:1] + pages, 128)
    assert alloc.stats()["waste_slots"] == 18 * 128 - (1 + 128)
    for _ in range(2049, 4096):
        position, row, live = published.decode_row("s")
    assert (position, live) == (4095, 128 + 2047)
    # the budget ends inside the third window: no page for its summaries
    assert published.decode_row("s") == (
        4096, [dummy] + summaries + pages, 256)
    assert alloc.stats()["waste_slots"] == 18 * 128 - (1 + 256)
    assert alloc.check() == []
    # a budget within one window has no summary page and fewer pages
    short = alloc.alloc_sequence("t", 300, live_tokens=200)
    assert len(short) == 3
    assert published.decode_row("t") == (200, [dummy] + short, 200)
    assert len(alloc.alloc_sequence("u", 18432)) == 24      # 43 were free
    with pytest.raises(KvCacheFull):
        alloc.alloc_sequence("v", 18432)
    for seq in "stu":
        alloc.free_sequence(seq)
    assert alloc.stats()["blocks_used"] == 0 and alloc.check() == []


def test_a_prefills_rows_go_to_the_pages_that_hold_something():
    """``write_rows``: the summary pages that hold a whole chunk of the
    prompt, the window pages that hold a live row, nothing else."""
    cache = WindowKvCache(20, 8, 1, 1, 128, window=32, chunk=4)
    table = cache.allocator.alloc_sequence("s", 100, live_tokens=45)
    pages, summaries = table[:4], table[4:]
    assert len(summaries) == 3
    # 64 // 4 summary rows, then the open window's 32: rows that say
    # where they came from
    rows = jnp.arange(16 + 32, dtype=jnp.float32)[None, :, None] \
        * jnp.ones((1, 1, 128))
    cache.write_rows("s", (rows.astype(jnp.bfloat16),) * 2, 45)
    k = np.asarray(cache.k_pages[0][0, :, :, 0], np.float32)
    # 11 whole chunks: the first summary page whole, 3 rows of the second
    np.testing.assert_array_equal(k[summaries[0]], np.arange(8))
    np.testing.assert_array_equal(k[summaries[1]][:3], np.arange(8, 11))
    assert not k[summaries[2]].any()
    # 45 % 32 = 13 live rows: two window pages
    np.testing.assert_array_equal(k[pages[0]], 16 + np.arange(8))
    np.testing.assert_array_equal(k[pages[1]][:5], 24 + np.arange(5))
    assert not k[pages[2]].any() and not k[pages[3]].any()
    others = [p for p in range(20) if p not in table]
    assert not k[others].any()


# -- one kernel, two users ---------------------------------------------------

@pytest.mark.parametrize("dtype,heads,head_dim,tol", [
    ("float32", 12, 64, 1e-5), ("bfloat16", 32, 128, 2e-2)],
    ids=["gpt2-small-f32-768", "evabyte-bf16-4096"])
def test_paged_decode_at_both_users_page_shapes(dtype, heads, head_dim, tol):
    """Pages of 128 rows at the two widths the cells run, interpreted:
    float32 ``[128, 768]`` at float32 precision; bfloat16 ``[128, 4096]``
    with the query rounded to bfloat16 as the MXU's operand (2^-9 a
    product) where the reference keeps it float32. A table that lists
    pages out of order, a row that ends inside a page, a pad row."""
    bs, pages, layers = 128, 7, 2
    keys = jax.random.split(jax.random.PRNGKey(36), 3)
    pool = (layers, pages, bs, heads * head_dim)
    q = jax.random.normal(keys[0], (3, heads, head_dim), jnp.float32)
    k_pages = jax.random.normal(keys[1], pool, jnp.float32).astype(dtype)
    v_pages = jax.random.normal(keys[2], pool, jnp.float32).astype(dtype)
    tables = jnp.asarray([[5, 2, 6], [1, 0, 0], [0, 0, 0]], jnp.int32)
    lens = jnp.asarray([2 * bs + 77, 3, 0], jnp.int32)
    out = ap.paged_decode_attention(q, k_pages, v_pages, tables, lens, 1,
                                    interpret=True)
    with jax.default_matmul_precision("highest"):
        ref = ap._reference_paged_decode(q, k_pages, v_pages, tables, lens,
                                         head_dim ** -0.5, 1)
    assert float(jnp.max(jnp.abs(out - ref))) < tol
    assert not jnp.any(out[2])
