"""EvaByte family (``model_type: evabyte``, ``attention_class: eva``): a
byte-level decoder whose attention keeps an exact WINDOW of the newest
positions beside one pooled SUMMARY row for every chunk of the windows
before it — the serving path.

Block (pre-norm, no biases, the residual stream float32)::

    h = x + W_o EVA(norm(x))     y = h + W_down(silu(W_gate n) * W_up n)
    n = norm(h)                  norm(x) = x / rms(x) * (1 + g)

EVA, a head (``H`` heads of ``Dh``, ``s = Dh^-1/2``), positions 0-based,
``W = window``, ``C = chunk``, ``win(i) = i // W``::

    q_i = rope_i(W_q n_i)    k_i = rope_i(W_k n_i)    v_i = W_v n_i
    chunk c = positions C c .. C c + C - 1, two learned vectors a head,
    phi and mu:
        a_j = softmax_j(s phi . k_j)    over the chunk's C positions
        K_c = sum_j a_j k_j + mu        V_c = sum_j a_j v_j
    o_i = ONE softmax over  s q_i . k_j  for j <= i in i's own window
          and  s q_i . K_c  for every chunk c of an EARLIER window,
          weighing v_j and V_c

A window's chunks are never seen as summaries by the window's own
positions: they appear when its last position has been written. (The
pooling — phi, mu, the scale on its logit, the one softmax over both
kinds of row — is Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542, in the deterministic form of EvaByte's
published modelling code; ``config.json`` pins C, W, the heads, theta,
eps, the unit offset and the float32 residual and logits.)

What a sequence leaves in the cache (``serving.kv_cache.WindowKvCache``,
the pools and the decode kernel GPT's cache has) is therefore its open
window's rows and one K and one V row a chunk: keys and values are
rounded to bfloat16 AS STORED and both prefill and decode attend over
the stored values; a summary is pooled in float32 from stored rows and
stored in bfloat16 itself. Decode pools a chunk when its last position
lands and writes the row to the open window's summary page, which the
cache lists among the attended pages once the window has closed: the
window closes inside the compiled step and costs a step in which none
closes nothing more. Prefill walks the prompt a window at a time through
the whole stack inside one program (a window's queries against its own
causal square and the earlier windows' summaries: linear in the prompt)
and hands the cache the open window's rows and the summaries only.

Parameter tree (``init``; kernels in the dtype handed in)::

    embed.table [V, D]    final_norm [D]    lm_head [D, V]
    layers[i]: norm1, norm2 [D]; attn {q, k, v [D, H Dh], o [H Dh, D],
      phi, mu [H, Dh]}; mlp {gate, up [D, F], down [F, D]}

The module is also the serving engine's view of the model (``serve_*``
below; ``models.gpt`` and ``models.axk1`` have the same).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import nn

BASE_CONFIG = dict(      # EvaByte/EvaByte config.json
    vocab_size=320, hidden=4096, layers=32, heads=32, mlp_dim=11008,
    window=2048, chunk=16, rope_theta=100000.0, rms_norm_eps=1e-5,
    max_seq=32768,
)

TINY_CONFIG = dict(
    BASE_CONFIG, vocab_size=64, hidden=128, layers=2, heads=4, mlp_dim=256,
    window=32, chunk=4, max_seq=128,
)

#: queries a block of prefill attention holds (no W x W x H tensor)
PREFILL_QUERY_BLOCK = 512


def _config(config: Optional[dict]) -> Dict[str, Any]:
    return dict(BASE_CONFIG, **(config or {}))


def init(key, config: Optional[dict] = None, dtype=jnp.bfloat16,
         std: float = 0.02) -> Dict:
    """normal(0, std) kernels, zero norm offsets (a unit gain), phi and
    mu normal(0, 1) so that the pooling weights are not flat."""
    cfg = _config(config)
    d, h, f = cfg["hidden"], cfg["heads"], cfg["mlp_dim"]
    count = [0]

    def normal(*shape, scale=std):
        count[0] += 1
        return (scale * jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, jnp.float32)
        ).astype(dtype)

    def layer():
        return {"norm1": jnp.zeros((d,), dtype),
                "norm2": jnp.zeros((d,), dtype),
                "attn": {"q": normal(d, d), "k": normal(d, d),
                         "v": normal(d, d), "o": normal(d, d),
                         "phi": normal(h, d // h, scale=1.0),
                         "mu": normal(h, d // h, scale=1.0)},
                "mlp": {"gate": normal(d, f), "up": normal(d, f),
                        "down": normal(f, d)}}

    return {"embed": {"table": normal(cfg["vocab_size"], d)},
            "layers": [layer() for _ in range(cfg["layers"])],
            "final_norm": jnp.zeros((d,), dtype),
            "lm_head": normal(d, cfg["vocab_size"])}


# -- a layer's parts ---------------------------------------------------------

def _mm(a, w):
    """bfloat16 operands, the sum float32."""
    return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _norm(cfg, gain, x):
    return nn.rmsnorm(gain, x, cfg["rms_norm_eps"], unit_offset=True)


def _qkv(cfg, attn, z, positions):
    """z [T, D] (normed), positions [T] -> the query [T, H, Dh] (float32,
    rotated) and the key and value AS STORED [T, H, Dh] (bfloat16, the
    key rotated)."""
    h = cfg["heads"]
    half = cfg["hidden"] // h // 2
    inv_freq = cfg["rope_theta"] ** (
        -jnp.arange(half, dtype=jnp.float32) / half)

    def heads(w):
        return _mm(z, w).reshape(z.shape[0], h, -1)

    q = nn.rope_rows(heads(attn["q"]), positions, inv_freq)
    k = nn.rope_rows(heads(attn["k"]), positions, inv_freq)
    return q, k.astype(jnp.bfloat16), heads(attn["v"]).astype(jnp.bfloat16)


def _pool_chunks(attn, k, v):
    """Stored keys and values ``[..., C, H, Dh]`` of whole chunks -> their
    summaries ``K_c``, ``V_c`` ``[..., H, Dh]`` as stored (bfloat16); the
    pooling weights, the sums and ``mu`` in float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    logit = jnp.sum(kf * attn["phi"].astype(jnp.float32), axis=-1) \
        * k.shape[-1] ** -0.5                              # [..., C, H]
    a = jax.nn.softmax(logit, axis=-2)[..., None]
    return ((jnp.sum(a * kf, axis=-3) + attn["mu"].astype(jnp.float32)
             ).astype(jnp.bfloat16),
            jnp.sum(a * vf, axis=-3).astype(jnp.bfloat16))


def _ffn(cfg, layer, h):
    return h + nn.gated_mlp(layer["mlp"], _norm(cfg, layer["norm2"], h))


def _logits(cfg, params, x):
    """x [B, D] -> float32 logits [B, V]."""
    return _mm(_norm(cfg, params["final_norm"], x), params["lm_head"])


# -- prefill -------------------------------------------------------------

def _attend_window(q, k, v, sum_k, sum_v, visible):
    """One window of a prompt: q [W, H, Dh] float32, its stored keys and
    values [W, H, Dh], every chunk summary computed so far [N, H, Dh]
    of which the first ``visible`` (those of the windows before this
    one) are seen -> the context [W, H, Dh] (bfloat16). One softmax
    over a query's summaries and its own window's positions up to
    itself, ``PREFILL_QUERY_BLOCK`` queries at a time."""
    w, _, dh = q.shape
    keys = jnp.concatenate([sum_k, k], axis=0)
    values = jnp.concatenate([sum_v, v], axis=0)
    n = sum_k.shape[0]
    kind = jnp.arange(n + w)
    qb = min(PREFILL_QUERY_BLOCK, w)
    if w % qb:
        raise ValueError("a window of %d is no multiple of %d" % (w, qb))

    def block(args):
        start, qs = args
        scores = jnp.einsum("qhd,khd->hqk", qs.astype(jnp.bfloat16), keys,
                            preferred_element_type=jnp.float32) * dh ** -0.5
        rows = (start + jnp.arange(qb))[:, None]
        seen = jnp.where(kind[None, :] < n, kind[None, :] < visible,
                         kind[None, :] - n <= rows)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        return jnp.einsum("hqk,khd->qhd", probs.astype(jnp.bfloat16),
                          values, preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)

    ctx = jax.lax.map(block, (jnp.arange(w // qb) * qb,
                              q.reshape(w // qb, qb, *q.shape[1:])))
    return ctx.reshape(q.shape)


def prefill(config: Optional[dict], params: Dict, ids: jnp.ndarray,
            length: jnp.ndarray, with_logits: bool = False):
    """ids [1, S] zero-padded (S whole windows), length [] -> (the first
    sampled token [], the rows to cache (K, V), each ``[L, S // C + W,
    H Dh]``: one summary row for every chunk of S, then the rows of the
    window that is open after ``length`` positions). The prompt goes
    through the whole stack a window at a time (``lax.scan``), carrying
    the summaries, the open window's rows and the last position's
    hidden row: nothing is S x S and no layer's rows of the whole
    prompt are held."""
    cfg = _config(config)
    win, chunk, d = cfg["window"], cfg["chunk"], cfg["hidden"]
    s, per = ids.shape[1], cfg["window"] // cfg["chunk"]
    if s % win:
        raise ValueError("prompt bucket %d is not whole windows of %d"
                         % (s, win))
    layers = params["layers"]

    def window(carry, xs):
        sum_k, sum_v, open_k, open_v, last = carry
        index, tokens = xs
        positions = index * win + jnp.arange(win)
        x = jnp.take(params["embed"]["table"], tokens, axis=0
                     ).astype(jnp.float32)
        is_open = index == length // win
        for li, layer in enumerate(layers):
            attn = layer["attn"]
            q, k, v = _qkv(cfg, attn, _norm(cfg, layer["norm1"], x),
                           positions)
            ctx = _attend_window(
                q, k, v, sum_k[li].reshape(-1, *k.shape[1:]),
                sum_v[li].reshape(-1, *k.shape[1:]), index * per)
            pooled = _pool_chunks(attn, k.reshape(per, chunk, *k.shape[1:]),
                                  v.reshape(per, chunk, *v.shape[1:]))
            sum_k, sum_v = (
                jax.lax.dynamic_update_slice(
                    pool, rows.reshape(1, per, d), (li, index * per, 0))
                for pool, rows in zip((sum_k, sum_v), pooled))
            open_k = open_k.at[li].set(
                jnp.where(is_open, k.reshape(win, d), open_k[li]))
            open_v = open_v.at[li].set(
                jnp.where(is_open, v.reshape(win, d), open_v[li]))
            x = _ffn(cfg, layer, x + _mm(ctx.reshape(win, d), attn["o"]))
        at = length - 1
        last = jnp.where(index == at // win, x[at % win], last)
        return (sum_k, sum_v, open_k, open_v, last), None

    depth = len(layers)
    start = (jnp.zeros((depth, s // chunk, d), jnp.bfloat16),) * 2 \
        + (jnp.zeros((depth, win, d), jnp.bfloat16),) * 2 \
        + (jnp.zeros((d,), jnp.float32),)
    (sum_k, sum_v, open_k, open_v, last), _ = jax.lax.scan(
        window, start, (jnp.arange(s // win), ids[0].reshape(-1, win)))
    logits = _logits(cfg, params, last[None])[0]
    out = (jnp.argmax(logits).astype(jnp.int32),
           (jnp.concatenate([sum_k, open_k], axis=1),
            jnp.concatenate([sum_v, open_v], axis=1)))
    return out + (logits,) if with_logits else out


# -- decode --------------------------------------------------------------

def decode(config: Optional[dict], params: Dict, pools: Tuple,
           tokens: jnp.ndarray, positions: jnp.ndarray,
           tables: jnp.ndarray, lens: jnp.ndarray, live: jnp.ndarray,
           attn_impl: str = "paged", block_size: int = 128,
           dummy_page: int = 0, with_logits: bool = False):
    """One byte for every row of the batch: ``pools`` = (K, V), the
    cache's two stacked pools ``[L, P, bs, H Dh]``, donated; tokens /
    positions / lens [B], tables [B, T], live [B] as
    ``WindowKvCache.decode_row`` answers: a row's column 0 names the
    summary page of its OPEN window, its other columns the pages its
    attention reads in order (closed windows' summaries, then the
    window's), ``lens`` the rows live in them. Every layer writes the
    new key and value at row ``lens`` of those pages, pools the chunk
    the position lies in (rows of ONE page) and, where the position is
    the chunk's last, writes the summary at the chunk's slot of the open
    window's summary page (else into the dummy page), then attends over
    ``lens + 1`` rows. -> (next bytes [B], (K, V), counters
    ``eva.rows_read`` / ``eva.tokens_live`` / ``eva.windows_closed``)."""
    from ..ops.attention_pallas import (
        _reference_paged_decode, paged_decode_attention)

    cfg = _config(config)
    win, chunk, d = cfg["window"], cfg["chunk"], cfg["hidden"]
    heads, bs = cfg["heads"], block_size
    k_pages, v_pages = pools
    reads = tables[:, 1:]
    page = jnp.take_along_axis(reads, (lens // bs)[:, None], axis=1)[:, 0]
    # pad rows write into the dummy page, which no table names
    page = jnp.where(live, page, dummy_page)
    slot = jnp.where(live, lens % bs, 0)
    new_lens = jnp.where(live, lens + 1, 0)
    chunk_slots = (slot // chunk * chunk)[:, None] + jnp.arange(chunk)
    pooled_now = live & (positions % chunk == chunk - 1)
    sum_page = jnp.where(pooled_now, tables[:, 0], dummy_page)
    sum_slot = jnp.where(pooled_now, positions % win // chunk, 0)

    @jax.jit
    def block(layer, li, x, k_pages, v_pages):
        """One layer of the step, ``li`` its index in the pools: jitted so
        that the step traces and lowers it once for all its layers, as
        ``models.gpt``'s."""
        attn = layer["attn"]
        q, k, v = _qkv(cfg, attn, _norm(cfg, layer["norm1"], x), positions)
        # the operand the MXU is fed, whichever path multiplies it
        q = q.astype(jnp.bfloat16)
        k_pages = k_pages.at[li, page, slot].set(k.reshape(-1, d))
        v_pages = v_pages.at[li, page, slot].set(v.reshape(-1, d))
        sum_k, sum_v = _pool_chunks(attn, *(
            pool[li, page[:, None], chunk_slots].reshape(
                -1, chunk, heads, d // heads)
            for pool in (k_pages, v_pages)))
        k_pages = k_pages.at[li, sum_page, sum_slot].set(
            sum_k.reshape(-1, d))
        v_pages = v_pages.at[li, sum_page, sum_slot].set(
            sum_v.reshape(-1, d))
        if attn_impl == "paged":
            ctx = paged_decode_attention(
                q, k_pages, v_pages, reads, new_lens, li,
                interpret=jax.default_backend() != "tpu")
        else:
            ctx = _reference_paged_decode(
                q, k_pages, v_pages, reads, new_lens,
                (d // heads) ** -0.5, li)
        x = _ffn(cfg, layer, x + _mm(ctx.reshape(-1, d), attn["o"]))
        return x, k_pages, v_pages

    x = jnp.take(params["embed"]["table"], tokens, axis=0
                 ).astype(jnp.float32)
    for li, layer in enumerate(params["layers"]):
        x, k_pages, v_pages = block(layer, jnp.int32(li), x, k_pages,
                                    v_pages)
    logits = _logits(cfg, params, x)
    counters = {
        "eva.rows_read": jnp.sum(new_lens),
        "eva.tokens_live": jnp.sum(jnp.where(live, positions + 1, 0)),
        "eva.windows_closed": jnp.sum(live & (positions % win == win - 1)),
    }
    out = (jnp.argmax(logits, axis=-1).astype(jnp.int32),
           (k_pages, v_pages), counters)
    return out + (logits,) if with_logits else out


# -- what the serving engine asks of a model's module -----------------------

def serve_buckets(config: dict, prompt_pad: int) -> Tuple[int, ...]:
    """The padded prompt lengths prefill compiles for: whole windows up
    to ``prompt_pad``."""
    win = _config(config)["window"]
    if prompt_pad % win:
        raise ValueError("prompt_pad %d is not whole windows of %d"
                         % (prompt_pad, win))
    return tuple(range(win, prompt_pad + 1, win))


def serve_cache(config: dict, num_blocks: int, block_size: int):
    """Window rows and summary rows in one K and one V pool, bfloat16."""
    from ..serving.kv_cache import WindowKvCache

    cfg = _config(config)
    if cfg["hidden"] % 128:
        raise ValueError("a row of %d lanes is no whole number of tiles: "
                         "the steps write the pools' rows unpadded"
                         % cfg["hidden"])
    return WindowKvCache(
        num_blocks, block_size, layers=cfg["layers"], heads=cfg["heads"],
        head_dim=cfg["hidden"] // cfg["heads"], window=cfg["window"],
        chunk=cfg["chunk"], dtype=jnp.bfloat16)


def serve_prefill(config: dict, pad: int) -> Callable:
    del pad          # the shape of ``ids`` says it
    return functools.partial(prefill, config)


def serve_decode(config: dict, attn: str, block_size: int,
                 dummy_page: int) -> Callable:
    return functools.partial(decode, config, attn_impl=attn,
                             block_size=block_size, dummy_page=dummy_page)
