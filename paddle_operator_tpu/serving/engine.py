"""ServingEngine — prefill + paged incremental decode over models/gpt.

Training runs the full sequence through the model every step; serving
must not: after the prompt is processed once (**prefill**), each new
token needs only its OWN query row against the cached K/V of everything
before it (**decode**). The engine owns that split:

* **prefill** — one fixed-shape jitted forward over the padded prompt
  that returns the per-layer K/V *and* the first sampled token; K/V land
  in the paged cache (:class:`.kv_cache.PagedKvCache`);
* **decode** — one fixed-shape jitted step over the whole active batch:
  project q/k/v for the single new position (per-sequence rotary
  positions), scatter k/v into each sequence's current page slot, and
  attend via :func:`..ops.attention_pallas.paged_decode_attention` (or
  the reference gather-einsum path — ``attn="reference"`` — which the
  perf gate compares token-for-token).

Both steps compile through :func:`..compile_cache.cached_jit`, so a
serving replica warms from the fleet artifact store exactly like a
training worker does: replica N+1 serves its first token with
``cache="fleet"`` and zero compile seconds (scripts/perf_serving.py
proves it; the serving_brownout chaos scenario models it).

Shapes are FIXED by construction — prompts pad to ``prompt_pad``, the
decode batch pads to ``max_batch`` with inert dummy rows aimed at the
cache's reserved dummy page — so each step function compiles exactly
once per engine config (one fingerprint, one fleet bundle). Sampling is
greedy argmax: serving replicas must be deterministic so the paged-vs-
reference bit-identity gate and the chaos replays can compare token ids
exactly.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..utils.trace import StageTimes, export_stage_times
from .batching import Request
from .kv_cache import KvCacheFull, PagedKvCache


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


def _qkv(layer: Dict[str, Any], h: jnp.ndarray
         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The mha projections with the head axis explicit (ops.nn.mha_init
    layout: kernels are [dim, heads, head_dim])."""
    def proj(p: Dict[str, Any]) -> jnp.ndarray:
        return jnp.einsum("bsd,dhk->bshk", h, p["kernel"]) + p["bias"]

    attn = layer["attn"]
    return proj(attn["q"]), proj(attn["k"]), proj(attn["v"])


def _ffn(layer: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    from ..ops import nn

    z = nn.layernorm(layer["ln2"], x, dtype=jnp.float32)
    z = nn.dense(layer["mlp"]["fc1"], z, dtype=jnp.float32)
    z = nn.gelu(z)
    z = nn.dense(layer["mlp"]["fc2"], z, dtype=jnp.float32)
    return x + z


class ServingEngine:
    """One replica's model: gpt params + paged KV cache + step functions.

    ``attn="paged"`` uses the Pallas decode kernel (interpret-mode off
    TPU); ``attn="reference"`` uses the gather-einsum path. MoE configs
    are rejected up front — serving the switch-FFN needs its own routing
    cache and is out of scope for this engine.
    """

    def __init__(self, params: Any, config: Dict, max_batch: int = 8,
                 prompt_pad: int = 32, num_blocks: int = 256,
                 block_size: int = 16, attn: str = "paged",
                 eos_id: Optional[int] = None, label: str = "serve"
                 ) -> None:
        if attn not in ("paged", "reference"):
            raise ValueError("attn must be paged|reference, got %r" % attn)
        if config.get("moe_experts"):
            raise ValueError("ServingEngine does not serve MoE configs")
        heads = config["heads"]
        head_dim = config["hidden"] // heads
        self.params = params
        self.config = dict(config)
        self.max_batch = max_batch
        self.prompt_pad = prompt_pad
        self.attn = attn
        self.eos_id = eos_id
        self.label = label
        #: pages one sequence may span — the decode block-table width
        self.pages_per_seq = -(-config["max_seq"] // block_size)
        self.cache = PagedKvCache(num_blocks, block_size,
                                  layers=config["layers"], heads=heads,
                                  head_dim=head_dim, dtype=jnp.float32)
        self._prefilled: Dict[str, bool] = {}
        self._prefill_fn = None
        self._decode_fn = None
        #: this engine's spans (utils.trace): one ``serve.step`` per
        #: step_fn call with its phases inside, ``serve.admit`` per
        #: reservation; always on, bounded. Exported under the engine's
        #: label for who reads in the same process; hand it to
        #: ``ServeMetrics(stages=...)`` for the ``tpujob_serve_stage_*``
        #: families
        self.times = export_stage_times(label, StageTimes())

    # -- admission hooks (wired into ContinuousBatcher) ------------------

    def admit(self, req: Request) -> bool:
        """Reserve KV pages for the prompt plus the WHOLE token budget up
        front (a mid-generation KvCacheFull would strand a half-generated
        sequence); only the prompt is live until decode advances. False =
        pool exhausted, the batcher defers the request."""
        need = len(req.prompt) + req.max_new_tokens
        if need > self.config["max_seq"]:
            raise ValueError(
                "request %s needs %d tokens > max_seq %d"
                % (req.request_id, need, self.config["max_seq"]))
        # validate the prompt BEFORE reserving: _prefill rejecting an
        # oversized/empty prompt after alloc_sequence succeeded would
        # leak the reservation (the request never reaches retire)
        if not 0 < len(req.prompt) <= self.prompt_pad:
            raise ValueError(
                "request %s prompt length %d outside (0, %d]"
                % (req.request_id, len(req.prompt), self.prompt_pad))
        with self.times.timed("serve.admit", request_id=req.request_id):
            try:
                self.cache.allocator.alloc_sequence(
                    req.request_id, need, live_tokens=len(req.prompt))
            except KvCacheFull:
                return False
            return True

    def retire(self, req: Request) -> None:
        self.cache.allocator.free_sequence(req.request_id)
        self._prefilled.pop(req.request_id, None)

    # -- step builders ---------------------------------------------------

    def _build_prefill(self) -> Callable[..., Any]:
        from .. import compile_cache

        pad = self.prompt_pad

        def prefill(params: Any, ids: jnp.ndarray,
                    length: jnp.ndarray) -> Any:
            """ids [1, pad] zero-padded, length [] int32 -> (first
            sampled token [] int32, [k per layer], [v per layer]) with
            k/v shaped [pad, H, Dh] (callers slice to the real length).
            Plain causal attention — prefill sees the whole prompt, so
            the training-style full-sequence path is exactly right."""
            from ..ops import nn

            x = nn.embedding(params["embed"]["tok"], ids, jnp.float32)
            positions = jnp.arange(pad)[None, :]
            cmask = jnp.tril(jnp.ones((pad, pad), bool))[None, None]
            ks, vs = [], []
            for layer in params["layers"]:
                h = nn.layernorm(layer["ln1"], x, dtype=jnp.float32)
                q, k, v = _qkv(layer, h)
                q = _rope_rows(q, positions)
                k = _rope_rows(k, positions)
                ks.append(k[0])
                vs.append(v[0])
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
            return jnp.argmax(logits).astype(jnp.int32), ks, vs

        def serve_prefill(*args: Any) -> Any:
            # the name XProf's ``XLA Modules`` line shows
            # (``jit_serve_prefill``) and the scope of its operations
            with jax.named_scope("serve_prefill"):
                return prefill(*args)

        ex = (self.params, jnp.zeros((1, pad), jnp.int32),
              jnp.zeros((), jnp.int32))
        return compile_cache.cached_jit(
            serve_prefill, ex, config=dict(self.config, prompt_pad=pad),
            label="%s-prefill" % self.label)

    def _build_decode(self) -> Callable[..., Any]:
        from .. import compile_cache

        attn = self.attn
        bs = self.cache.allocator.block_size
        dummy = self.cache.dummy_page

        def decode(params: Any, k_pages: Any, v_pages: Any,
                   tokens: jnp.ndarray, positions: jnp.ndarray,
                   tables: jnp.ndarray, lens: jnp.ndarray,
                   live: jnp.ndarray) -> Any:
            """One token for every row: tokens [B] int32 (each row's
            last sampled token), positions [B] (its 0-based index),
            tables [B, T], lens [B] (live cache tokens BEFORE this
            step), live [B] bool (False = pad row). Returns (next tokens
            [B], new k_pages, v_pages)."""
            from ..ops import nn
            from ..ops.attention_pallas import (
                _reference_paged_decode, paged_decode_attention,
            )

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
            new_lens = lens + 1
            new_k, new_v = [], []
            for li, layer in enumerate(params["layers"]):
                h = nn.layernorm(layer["ln1"], x, dtype=jnp.float32)
                q, k, v = _qkv(layer, h)
                q = _rope_rows(q, pos2)
                k = _rope_rows(k, pos2)
                kp = k_pages[li].at[blocks, slots].set(k[:, 0])
                vp = v_pages[li].at[blocks, slots].set(v[:, 0])
                new_k.append(kp)
                new_v.append(vp)
                if attn == "paged":
                    ctx = paged_decode_attention(
                        q[:, 0], kp, vp, tables, new_lens,
                        interpret=jax.default_backend() != "tpu")
                else:
                    ctx = _reference_paged_decode(
                        q[:, 0], kp, vp, tables, new_lens,
                        1.0 / math.sqrt(q.shape[-1]))
                y = jnp.einsum("bhd,hdo->bo", ctx.astype(jnp.float32),
                               layer["attn"]["o"]["kernel"]) \
                    + layer["attn"]["o"]["bias"]
                x = _ffn(layer, x + y[:, None])
            x = nn.layernorm(params["final_ln"], x, dtype=jnp.float32)
            logits = nn.dense(params["lm_head"], x[:, 0],
                              dtype=jnp.float32)               # [B,V]
            return (jnp.argmax(logits, -1).astype(jnp.int32),
                    new_k, new_v)

        def serve_decode(*args: Any) -> Any:
            # ``jit_serve_decode`` on ``XLA Modules``: what the
            # benchmark's ``decode_device_ms`` looks for
            with jax.named_scope("serve_decode"):
                return decode(*args)

        b = self.max_batch
        layers = self.config["layers"]
        pshape = self.cache.k_pages[0].shape
        pages0 = [jnp.zeros(pshape, jnp.float32)] * layers
        ex = (self.params, pages0, pages0,
              jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
              jnp.zeros((b, self.pages_per_seq), jnp.int32),
              jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool))
        return compile_cache.cached_jit(
            serve_decode, ex,
            config=dict(self.config, attn=attn, max_batch=b,
                        block_size=bs, num_blocks=pshape[0] - 1),
            label="%s-decode" % self.label)

    # -- the batcher-facing step ----------------------------------------

    def step_fn(self, active: List[Request]) -> List[Tuple[int, bool]]:
        """One engine iteration for the batcher's active set: prefill
        newly admitted sequences (their first token comes from the
        prefill logits), then one batched decode step for the rest.
        Banked as one ``serve.step`` span with the phases of
        ``_prefill`` and ``_decode`` inside it."""
        if len(active) > self.max_batch:
            raise RuntimeError("active set %d exceeds max_batch %d"
                               % (len(active), self.max_batch))
        results: Dict[str, Tuple[int, bool]] = {}
        new = [r for r in active if not self._prefilled.get(r.request_id)]
        decode_rows = [r for r in active
                       if self._prefilled.get(r.request_id)]
        with self.times.timed(
                "serve.step", new=len(new), decode_rows=len(decode_rows),
                prefill_tokens=sum(len(r.prompt) for r in new)):
            for req in new:
                token = self._prefill(req)
                results[req.request_id] = (token, token == self.eos_id)
                self._prefilled[req.request_id] = True
            if decode_rows:
                for req, token in zip(decode_rows,
                                      self._decode(decode_rows)):
                    results[req.request_id] = (token, token == self.eos_id)
        return [results[r.request_id] for r in active]

    def _prefill(self, req: Request) -> int:
        if not 0 < len(req.prompt) <= self.prompt_pad:
            raise ValueError("prompt length %d outside (0, %d]"
                             % (len(req.prompt), self.prompt_pad))
        if self._prefill_fn is None:
            self._prefill_fn = self._build_prefill()
        timed, n = self.times.timed, len(req.prompt)
        rid = req.request_id
        with timed("serve.prefill.build", request_id=rid, prompt_len=n):
            ids = jnp.zeros((1, self.prompt_pad), jnp.int32).at[
                0, :n].set(jnp.asarray(req.prompt, jnp.int32))
            length = jnp.asarray(n, jnp.int32)
        with timed("serve.prefill.dispatch", request_id=rid):
            token, ks, vs = self._prefill_fn(self.params, ids, length)
        with timed("serve.prefill.scatter", request_id=rid,
                   pages=-(-n // self.cache.allocator.block_size)):
            for li in range(self.config["layers"]):
                self.cache.write_prefill(rid, li, ks[li][:n], vs[li][:n])
        with timed("serve.prefill.wait", request_id=rid):
            return int(token)

    def _decode(self, rows: List[Request]) -> List[int]:
        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        timed = self.times.timed
        with timed("serve.decode.tables"):
            alloc = self.cache.allocator
            b = self.max_batch
            tokens = [0] * b
            positions = [0] * b
            tables = [[0] * self.pages_per_seq for _ in range(b)]
            lens = [0] * b
            live = [False] * b
            for i, req in enumerate(rows):
                sid = req.request_id
                tokens[i] = req.generated[-1]
                lens[i] = alloc.seq_len(sid)
                positions[i] = alloc.advance(sid)  # == lens[i], slot reserved
                table = alloc.block_table(sid)
                tables[i][:len(table)] = table
                live[i] = True
        with timed("serve.decode.put"):
            args = (jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(positions, jnp.int32),
                    jnp.asarray(tables, jnp.int32),
                    jnp.asarray(lens, jnp.int32),
                    jnp.asarray(live, bool))
        with timed("serve.decode.dispatch"):
            out, kp, vp = self._decode_fn(
                self.params, list(self.cache.k_pages),
                list(self.cache.v_pages), *args)
            self.cache.k_pages = list(kp)
            self.cache.v_pages = list(vp)
        with timed("serve.decode.wait"):
            # the first int() below waited here before: no wait is added
            jax.block_until_ready(out)
        with timed("serve.decode.readback"):
            return [int(out[i]) for i in range(len(rows))]
