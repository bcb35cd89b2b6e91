"""Decoder-only causal LM (GPT family) for TPU: the long-context flagship.

The reference operator launches user containers and never sees a model
(SURVEY.md §0); this framework ships the training runtime, and the GPT family
is where the long-context machinery earns its keep: rotary embeddings (no
learned position table to gather under sequence sharding), causal flash
attention fused in Pallas (diagonal tiles skipped, ~2x FLOP saving), and
drop-in ring/Ulysses sequence parallelism over the ``sp`` mesh axis — pass
``attn_impl=partial(parallel.ring_attention, mesh=mesh, causal=True)``.

Pre-LN blocks, bf16 compute, optional switch-MoE FFNs (expert axis over
``ep``), per-layer remat. Sharding rules: :func:`parallel.sharding.gpt_rules`.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..ops import nn

BASE_CONFIG = dict(      # GPT-2 small scale
    vocab_size=50304, hidden=768, layers=12, heads=12, mlp_dim=3072,
    max_seq=1024, moe_experts=0, moe_every=2,
)

TINY_CONFIG = dict(
    vocab_size=1024, hidden=128, layers=2, heads=4, mlp_dim=256,
    max_seq=256, moe_experts=0, moe_every=2,
)

TINY_MOE_CONFIG = dict(TINY_CONFIG, moe_experts=4, moe_every=1)


def init(key, config: Optional[dict] = None) -> Dict:
    cfg = dict(BASE_CONFIG, **(config or {}))
    h, mlp = cfg["hidden"], cfg["mlp_dim"]
    keys = iter(jax.random.split(key, 8 + 8 * cfg["layers"]))
    from ..ops.moe import moe_init

    params: Dict = {
        "embed": {"tok": nn.embedding_init(next(keys), cfg["vocab_size"], h)},
        "layers": [],
        "final_ln": nn.layernorm_init(h),
        "lm_head": nn.dense_init(next(keys), h, cfg["vocab_size"],
                                 use_bias=False),
    }
    for li in range(cfg["layers"]):
        layer = {
            "ln1": nn.layernorm_init(h),
            "attn": nn.mha_init(next(keys), h, cfg["heads"]),
            "ln2": nn.layernorm_init(h),
        }
        if cfg["moe_experts"] and li % cfg["moe_every"] == 0:
            layer["moe"] = moe_init(next(keys), h, mlp, cfg["moe_experts"])
        else:
            layer["mlp"] = {
                "fc1": nn.dense_init(next(keys), h, mlp),
                "fc2": nn.dense_init(next(keys), mlp, h),
            }
        params["layers"].append(layer)
    return params


def _block(layer, x, dtype, attn_impl, positions):
    """Pre-LN decoder block: x + attn(ln1 x); x + ffn(ln2 x)."""
    from ..ops.moe import moe_apply

    causal = not callable(attn_impl)  # callables (ring/ulysses) own masking
    y = nn.mha(layer["attn"], nn.layernorm(layer["ln1"], x, dtype=dtype),
               dtype=dtype, impl=attn_impl, causal=causal, use_rope=True,
               positions=positions)
    x = x + y
    z = nn.layernorm(layer["ln2"], x, dtype=dtype)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in layer:
        z, moe_aux = moe_apply(layer["moe"], z, dtype=dtype)
        aux = aux + moe_aux["moe_aux_loss"]
    else:
        z = nn.dense(layer["mlp"]["fc1"], z, dtype=dtype)
        z = nn.gelu(z)
        z = nn.dense(layer["mlp"]["fc2"], z, dtype=dtype)
    return x + z, aux


def apply(params, input_ids, dtype=jnp.bfloat16, remat: bool = False,
          attn_impl="auto", positions: Optional[jnp.ndarray] = None):
    """input_ids: [B, S] -> (logits [B, S, V] fp32, moe aux loss scalar)."""
    x, aux = encode(params, input_ids, dtype=dtype, remat=remat,
                    attn_impl=attn_impl, positions=positions)
    logits = nn.dense(params["lm_head"], x, dtype=jnp.float32)
    return logits, aux


def encode(params, input_ids, dtype=jnp.bfloat16, remat: bool = False,
           attn_impl="auto", positions: Optional[jnp.ndarray] = None):
    """Backbone up to (but excluding) the LM head: [B, S] -> ([B, S, D]
    final-LN hidden states, moe aux loss). Split out so the chunked
    cross-entropy path can consume hidden states without ever
    materializing the [B, S, V] logits."""
    x = nn.embedding(params["embed"]["tok"], input_ids, dtype)

    layer_fn = _block
    if remat:
        layer_fn = jax.checkpoint(_block, static_argnums=(2, 3))
    aux = jnp.zeros((), jnp.float32)
    for layer in params["layers"]:
        x, layer_aux = layer_fn(layer, x, dtype, attn_impl, positions)
        aux = aux + layer_aux
    x = nn.layernorm(params["final_ln"], x, dtype=dtype)
    return x, aux


def loss_fn(params, batch, train=True, dtype=jnp.bfloat16, remat: bool = False,
            attn_impl="auto", moe_aux_weight: float = 0.01,
            ce_chunk: int = 0, mesh=None):
    """Next-token LM loss. batch = {"input_ids" [B,S], optional "loss_mask"}.

    ``mesh`` is the mesh the step is jitted over (``run_training`` hands it
    to any loss function that declares the argument). On the TPU backend
    ``attn_impl="auto"`` then runs the flash kernel per device shard
    (:func:`parallel.sharded_flash_attention`): called bare under a
    mesh-wide jit the kernel does not lower at all. The chunked loss
    runs per ``dp`` shard for the same reason in kind: the partitioner
    cannot split its scan (:func:`ops.nn.chunked_lm_xent`).

    Labels are input_ids shifted left; the final position is dropped. A
    ``loss_mask`` (e.g. padding) applies to the *label* position.

    ``ce_chunk > 0`` routes the LM head through
    :func:`ops.nn.chunked_lm_xent`: tokens stream through the head in
    chunks, so the ``[B, S, V]`` fp32 logits (gigabytes at S=2k, V=50k —
    the dominant HBM cost of this loss) are never materialized, and each
    chunk's logits are computed ONCE: under ``jax.grad`` the one loop also
    takes the head's and the hidden states' gradients, which wait as
    float32 residuals (rows x D and D x V) for the backward to scale
    them. Same loss/accuracy as the dense path up to fp32 summation
    order.
    """
    if mesh is not None and attn_impl == "auto" \
            and jax.default_backend() == "tpu":
        from ..parallel import sharded_flash_attention

        attn_impl = functools.partial(
            sharded_flash_attention, mesh=mesh, causal=True)
    ids = batch["input_ids"]
    labels = ids[:, 1:]
    mask = batch.get("loss_mask")
    mask = (jnp.ones_like(labels, jnp.float32) if mask is None
            else mask[:, 1:].astype(jnp.float32))

    if ce_chunk:
        hidden, moe_aux = encode(params, ids, dtype=dtype, remat=remat,
                                 attn_impl=attn_impl)
        loss, acc = nn.chunked_lm_xent(
            params["lm_head"], hidden[:, :-1], labels, mask=mask,
            chunk=ce_chunk, dtype=dtype, mesh=mesh)
        loss = loss + moe_aux_weight * moe_aux
        return loss, {"accuracy": acc, "moe_aux": moe_aux}

    logits, moe_aux = apply(params, ids, dtype=dtype, remat=remat,
                            attn_impl=attn_impl)
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = -jnp.sum(picked * mask) / denom
    loss = loss + moe_aux_weight * moe_aux
    acc = jnp.sum(
        (jnp.argmax(logits, -1) == labels).astype(jnp.float32) * mask) / denom
    return loss, {"accuracy": acc, "moe_aux": moe_aux}


def synthetic_batch(key, batch_size: int, seq_len: int = 256,
                    vocab_size: int = 50304):
    ids = jax.random.randint(key, (batch_size, seq_len), 0, vocab_size)
    return {"input_ids": ids}


# -- what the serving engine asks of a model's module -----------------------
#
# ``serving.engine.ServingEngine`` keeps the scheduling, the slots, the
# block tables, page memory and the spans; the layer stack, the cache's
# layout and the two step functions come from the model's module
# (``models.axk1`` has the same five). These are the bodies the engine
# held inline before it took a second model: float32 throughout, one
# padded prompt length, one K and one V page pool for all layers, a
# token's heads side by side in a row (``serving.kv_cache.PagedKvCache``).

def _rope_rows(x: jnp.ndarray, positions: jnp.ndarray,
               base: float = 10000.0) -> jnp.ndarray:
    """Rotary embedding with PER-ROW positions: x [B, S, H, D],
    positions [B, S]. Training's shared ``arange`` (ops.nn.rope) does not
    apply to a mixed decode batch where every sequence sits at its own
    depth."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _qkv(layer: Dict, h: jnp.ndarray):
    """The mha projections with the head axis explicit (ops.nn.mha_init
    layout: kernels are [dim, heads, head_dim])."""
    def proj(p: Dict) -> jnp.ndarray:
        return jnp.einsum("bsd,dhk->bshk", h, p["kernel"]) + p["bias"]

    attn = layer["attn"]
    return proj(attn["q"]), proj(attn["k"]), proj(attn["v"])


def _ffn(layer: Dict, x: jnp.ndarray) -> jnp.ndarray:
    z = nn.layernorm(layer["ln2"], x, dtype=jnp.float32)
    z = nn.dense(layer["mlp"]["fc1"], z, dtype=jnp.float32)
    z = nn.gelu(z)
    z = nn.dense(layer["mlp"]["fc2"], z, dtype=jnp.float32)
    return x + z


def serve_buckets(config: dict, prompt_pad: int):
    """The padded prompt lengths prefill compiles for: one."""
    del config
    return (prompt_pad,)


def serve_cache(config: dict, num_blocks: int, block_size: int):
    """One K and one V pool ``[layers, num_blocks + 1, block_size,
    heads * head_dim]``, float32, which the decode step updates in
    place."""
    from ..serving.kv_cache import PagedKvCache

    if config.get("moe_experts"):
        raise ValueError(
            "models.gpt serves no expert configuration: its Switch layer "
            "drops tokens over capacity and has no decode path; an expert "
            "layer that is served is models.axk1's (ops.moe.moe_share_apply)")
    heads = config["heads"]
    return PagedKvCache(num_blocks, block_size, layers=config["layers"],
                        heads=heads, head_dim=config["hidden"] // heads,
                        dtype=jnp.float32)


def serve_prefill(config: dict, pad: int):
    del config
    import math

    def prefill(params, ids: jnp.ndarray, length: jnp.ndarray):
        """ids [1, pad] zero-padded, length [] int32 -> (first
        sampled token [] int32, (k, v)) with k/v shaped [layers, pad,
        H * Dh], a token's row as the cache stores it (its
        ``write_rows`` takes the first ``length``). Plain causal
        attention — prefill sees the whole prompt, so the
        training-style full-sequence path is exactly right."""
        x = nn.embedding(params["embed"]["tok"], ids, jnp.float32)
        positions = jnp.arange(pad)[None, :]
        cmask = jnp.tril(jnp.ones((pad, pad), bool))[None, None]
        ks, vs = [], []
        for layer in params["layers"]:
            h = nn.layernorm(layer["ln1"], x, dtype=jnp.float32)
            q, k, v = _qkv(layer, h)
            q = _rope_rows(q, positions)
            k = _rope_rows(k, positions)
            ks.append(k[0].reshape(pad, -1))
            vs.append(v[0].reshape(pad, -1))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
                / math.sqrt(q.shape[-1])
            scores = jnp.where(cmask, scores, -1e30)
            probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            y = jnp.einsum("bqhd,hdo->bqo", ctx,
                           layer["attn"]["o"]["kernel"]) \
                + layer["attn"]["o"]["bias"]
            x = _ffn(layer, x + y)
        x = nn.layernorm(params["final_ln"], x, dtype=jnp.float32)
        last = x[0, length - 1]
        logits = nn.dense(params["lm_head"], last[None],
                          dtype=jnp.float32)[0]
        return (jnp.argmax(logits).astype(jnp.int32),
                (jnp.stack(ks), jnp.stack(vs)))

    return prefill


def serve_decode(config: dict, attn: str, block_size: int, dummy_page: int):
    del config
    import math

    from ..ops.attention_pallas import (
        _reference_paged_decode, paged_decode_attention,
    )

    bs, dummy = block_size, dummy_page

    @jax.jit
    def block(layer, li, x, k_pages, v_pages, pos2, blocks, slots, tables,
              new_lens):
        """One layer of the step, ``li`` its index in the pools. Jitted
        so that the step traces and lowers it ONCE for all its layers
        (they differ in nothing but their weights and ``li``): a
        donating step is traced in every process, the compile ladder's
        AOT rung refusing it, and twelve lowerings of the layer and its
        kernel were 1.8 s of the benchmark's set-up (chip runs,
        PR 33)."""
        def stored(rows):               # [B, 1, H, D] -> the cache's row
            rows = rows.reshape(rows.shape[0], -1).astype(k_pages.dtype)
            return jnp.pad(rows, ((0, 0), (0, k_pages.shape[-1]
                                           - rows.shape[1])))

        h = nn.layernorm(layer["ln1"], x, dtype=jnp.float32)
        q, k, v = _qkv(layer, h)
        q = _rope_rows(q, pos2)
        k = _rope_rows(k, pos2)
        k_pages = k_pages.at[li, blocks, slots].set(stored(k))
        v_pages = v_pages.at[li, blocks, slots].set(stored(v))
        if attn == "paged":
            ctx = paged_decode_attention(
                q[:, 0], k_pages, v_pages, tables, new_lens, li,
                interpret=jax.default_backend() != "tpu")
        else:
            ctx = _reference_paged_decode(
                q[:, 0], k_pages, v_pages, tables, new_lens,
                1.0 / math.sqrt(q.shape[-1]), li)
        y = jnp.einsum("bhd,hdo->bo", ctx.astype(jnp.float32),
                       layer["attn"]["o"]["kernel"]) \
            + layer["attn"]["o"]["bias"]
        return _ffn(layer, x + y[:, None]), k_pages, v_pages

    def decode(params, pools, tokens: jnp.ndarray, positions: jnp.ndarray,
               tables: jnp.ndarray, lens: jnp.ndarray, live: jnp.ndarray):
        """One token for every row: pools = (K, V), the cache's two
        stacked pools ``[layers, pages, bs, W]``, which the engine
        donates; tokens [B] int32 (each row's last sampled token),
        positions [B] (its 0-based index), tables [B, T], lens [B]
        (live cache tokens BEFORE this step), live [B] bool (False =
        pad row). Every layer writes its new rows into the two arrays
        where they lie and attends over them there; the SAME two come
        back: (next tokens [B], (K, V), no counters)."""
        k_pages, v_pages = pools
        x = nn.embedding(params["embed"]["tok"], tokens[:, None],
                         jnp.float32)                       # [B,1,D]
        pos2 = positions[:, None]
        gathered = jnp.take_along_axis(
            tables, (positions // bs)[:, None], axis=1)[:, 0]
        # pad rows scatter into the reserved dummy page: every pad
        # row writes the same value there (identical inert inputs),
        # and no live block table can reference it
        blocks = jnp.where(live, gathered, dummy)
        slots = jnp.where(live, positions % bs, 0)
        # a pad row attends to nothing: the kernel skips its pages
        new_lens = jnp.where(live, lens + 1, 0)
        for li, layer in enumerate(params["layers"]):
            x, k_pages, v_pages = block(
                layer, jnp.int32(li), x, k_pages, v_pages, pos2, blocks,
                slots, tables, new_lens)
        x = nn.layernorm(params["final_ln"], x, dtype=jnp.float32)
        logits = nn.dense(params["lm_head"], x[:, 0],
                          dtype=jnp.float32)               # [B,V]
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                (k_pages, v_pages), {})

    return decode
