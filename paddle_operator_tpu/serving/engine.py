"""ServingEngine — prefill + paged incremental decode over a model.

Training runs the full sequence through the model every step; serving
must not: after the prompt is processed once (**prefill**), each new
token needs only its OWN query row against the cache of everything
before it (**decode**). The engine owns that split and everything around
it that is not the model: admission, slots, the two compiled steps and
their spans. The layer stack, the cache and the two step functions come
from the MODEL'S MODULE (``model=``, default :mod:`..models.gpt`), which
offers:

* ``serve_cache(config, num_blocks, block_size[, max_batch])`` — the
  cache (``max_batch`` is handed to a hook that names it): page
  memory behind a :class:`.kv_cache.KvBlockAllocator`
  (:class:`.kv_cache.PagedKvCache`: one K and one V pool for all
  layers, a token's heads side by side in a row;
  :class:`.kv_cache.LatentKvCache`: a tuple of pools behind the one
  block table, one compressed row a token in each;
  :class:`.kv_cache.WindowKvCache`: the paged pools holding an exact
  window's rows beside one summary row a chunk of the windows before
  it; :class:`.kv_cache.StateKvCache`: pages of keys, values and
  compressed keys beside one fixed-size state a SEQUENCE, whose slot
  the allocator reserves with the pages and the cache hands over as
  column 0 of a decode row's table). Each hands the decode step its
  pools donated: the step updates
  them where they lie. WHAT A SEQUENCE KEEPS FOR ITS TOKENS IS THE
  CACHE'S TO SAY, and the engine asks it instead of computing:
  ``pages_for(tokens)`` (the pages a budget reserves, which is what
  ``allocator.alloc_sequence`` takes), ``table_width(max_seq)`` (the
  decode table's columns) and, for every row of a decode step,
  ``decode_row(seq_id)`` -> (the token's position, the pages its
  attention reads in order, the rows live in them: the new row's
  slot). A row a token, the first pages and the tokens so far for the
  paged and the latent cache; window arithmetic for the third. Nothing
  here branches on which cache or which model it holds;
* ``serve_buckets(config, prompt_pad)`` — the padded prompt lengths
  prefill compiles for (a prompt takes the shortest that holds it);
* ``serve_prefill(config, pad)`` -> ``f(params, ids [1, pad], length) ->
  (first sampled token, the rows to cache)``; the cache's
  ``write_rows`` stores them;
* ``serve_decode(config, attn, block_size, dummy_page)`` -> ``f(params,
  pools, tokens, positions, tables, lens, live) -> (next tokens, pools,
  counters)``: one fixed-shape step over the whole batch, the new
  token's rows written into each sequence's current page slot,
  attention through the model's paged kernel (``attn="paged"``) or its
  gather-einsum reference (``attn="reference"``, which the tests
  compare token for token). ``counters`` are int32 scalars the engine
  banks as counts (``StageTimes.count``) under their names
  (``moe.pairs_here``, ``moe.experts_hit``, ``dsa.rows_live``,
  ``dsa.rows_selected``, ``eva.rows_read``, ``eva.tokens_live``,
  ``eva.windows_closed``, ``loop.layer_passes``, ``loop.rows_live``,
  ``loop.rows_read``, ``loop.exit_steps``, ``sala.blocks_read``,
  ``sala.kernel_cells``, ``sala.blocks_live``, ``sala.ckeys_read``,
  ``lin.state_updates``, ``lin.rows_live``).

Models served: :mod:`..models.gpt` (float32; no expert configuration:
its Switch layer drops tokens over capacity and has no decode path),
:mod:`..models.axk1` (bfloat16; latent attention through a latent page
cache, an expert layer that computes the experts this chip holds) and
:mod:`..models.dsv32` (that stack with an indexer whose keys have a pool
of their own, decode over the selected rows only, a prefill that walks
its prompt in chunks inside one program a bucket) and
:mod:`..models.evabyte` (bfloat16; a byte-level decoder whose attention
reads an exact window and pooled summaries of every earlier chunk out
of GPT's pools through GPT's decode kernel; a window closes inside the
compiled decode step; a prefill that walks its prompt a window at a time
and caches the open window and the summaries only) and
:mod:`..models.ouro` (bfloat16; a stack of layers run several times over
with the same weights, every loop step's keys and values in cache
layers of its own — the paged cache is handed loop steps x layers and
the model indexes it —, GPT's decode kernel once a layer and loop step,
an exit gate that chooses which loop step's output the head reads) and
:mod:`..models.minicpm_sala` (bfloat16; layers of two kinds: lightning
linear attention, whose memory of a sequence is one float32 state in a
pool of slots that the decode step advances in place, and block-sparse
grouped-query attention, which scores compressed keys, takes the top
blocks and reads them in place from the paged cache; a prefill that
walks its prompt in chunks carrying the states; its ``serve_cache``
takes ``max_batch`` besides, a slot of state for every row).

Both steps compile through :func:`..compile_cache.cached_jit`, as a
training worker's step does, so a replica takes them from whichever rung
of the compile ladder holds them.

Shapes are FIXED by construction — prompts pad to a bucket, the decode
batch pads to ``max_batch`` with inert dummy rows aimed at the cache's
reserved dummy page — so the decode step compiles once and prefill once
a bucket per engine config. Sampling is greedy argmax: serving replicas
must be deterministic so the paged-vs-reference tests and the chaos
replays can compare token ids exactly.

A decode step crosses the host-device boundary as ONE array each way.
In: one numpy ``int32[max_batch, 4 + pages_per_seq]`` (a row's token,
its position, the rows live, whether the row is live, then its block
table), sent by ONE ``jax.device_put``; the compiled step slices the
columns back out for the model's hook.
Out: one ``int32[max_batch + counters]``, every row's token and then the
hook's counters in the order of their sorted names (read once when the
step is built), whose copy home is asked for AT DISPATCH
(``copy_to_host_async``), so it follows the program on the device's
queue and the read-back finds the bytes here. A prefill's padded prompt
and its length go by one ``jax.device_put`` of the pair. No eager
``jnp`` program runs in ``step_fn``: only the compiled steps and the
cache's page write.
"""

from __future__ import annotations

import inspect
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.trace import StageTimes, export_stage_times
from .batching import Request
from .kv_cache import KvCacheFull


class ServingEngine:
    """One replica's model: params + paged cache + step functions.

    ``model`` is the module the layer stack, the cache's layout and the
    two steps come from (see the module docstring; default
    :mod:`..models.gpt`). ``attn="paged"`` uses the model's Pallas decode
    kernel (interpret mode off the TPU); ``attn="reference"`` uses its
    gather-einsum path.
    """

    def __init__(self, params: Any, config: Dict, max_batch: int = 8,
                 prompt_pad: int = 32, num_blocks: int = 256,
                 block_size: int = 16, attn: str = "paged",
                 eos_id: Optional[int] = None, label: str = "serve",
                 model: Any = None) -> None:
        if attn not in ("paged", "reference"):
            raise ValueError("attn must be paged|reference, got %r" % attn)
        if model is None:
            from ..models import gpt as model
        self.model = model
        self.params = params
        self.config = dict(config)
        self.max_batch = max_batch
        self.prompt_pad = prompt_pad
        self.attn = attn
        self.eos_id = eos_id
        self.label = label
        # a cache with a pool whose unit is a SEQUENCE asks how many
        # there can be: its hook takes ``max_batch`` besides
        sized = "max_batch" in inspect.signature(
            model.serve_cache).parameters
        self.cache = model.serve_cache(
            self.config, num_blocks, block_size,
            **({"max_batch": max_batch} if sized else {}))
        #: the decode block-table's width: the pages one sequence's
        #: attention may read, which its cache knows
        self.pages_per_seq = self.cache.table_width(config["max_seq"])
        #: the padded prompt lengths, ascending, and the program of each
        self.buckets = tuple(model.serve_buckets(self.config, prompt_pad))
        self._prefilled: Dict[str, bool] = {}
        self._prefill_fns: Dict[int, Callable[..., Any]] = {}
        self._decode_fn = None
        #: the names of the decode hook's counters, sorted: the order
        #: they follow the tokens in, in what a decode step hands back
        self._counters: Tuple[str, ...] = ()
        #: this engine's spans (utils.trace): one ``serve.step`` per
        #: step_fn call with its phases inside, ``serve.admit`` per
        #: reservation, and what a decode step counted as counters;
        #: always on, bounded. Exported under the engine's label for
        #: who reads in the same process; hand it to
        #: ``ServeMetrics(stages=...)``, beside the batcher's, for the
        #: ``tpujob_serve_stage_*`` and ``tpujob_serve_step_count_*``
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

    def _build_prefill(self, pad: int) -> Callable[..., Any]:
        from .. import compile_cache

        prefill = self.model.serve_prefill(self.config, pad)

        def serve_prefill(*args: Any) -> Any:
            # the name XProf's ``XLA Modules`` line shows
            # (``jit_serve_prefill``) and the scope of its operations
            with jax.named_scope("serve_prefill"):
                return prefill(*args)

        ex = (self.params, jax.ShapeDtypeStruct((1, pad), np.int32),
              jax.ShapeDtypeStruct((), np.int32))
        return compile_cache.cached_jit(
            serve_prefill, ex, config=dict(self.config, prompt_pad=pad),
            label="%s-prefill" % self.label)

    def _build_decode(self) -> Callable[..., Any]:
        from .. import compile_cache

        attn = self.attn
        bs = self.cache.allocator.block_size
        decode = self.model.serve_decode(self.config, attn, bs,
                                         self.cache.dummy_page)

        b = self.max_batch
        pools = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.cache.pools())
        row = jax.ShapeDtypeStruct((b,), np.int32)
        # what the hook counts is static for a model and a config: asked
        # of its shapes here, not learnt from tracing (the compile
        # cache's memo can hand this engine a step it never traced)
        _, _, counted = jax.eval_shape(
            decode, self.params, pools, row, row,
            jax.ShapeDtypeStruct((b, self.pages_per_seq), np.int32),
            row, jax.ShapeDtypeStruct((b,), np.bool_))
        names = self._counters = tuple(sorted(counted))

        def serve_decode(params: Any, pools: Any, packed: Any) -> Any:
            # ``jit_serve_decode`` on ``XLA Modules``: what the
            # benchmark's ``decode_device_ms`` looks for
            with jax.named_scope("serve_decode"):
                tokens, pools, counters = decode(
                    params, pools, packed[:, 0], packed[:, 1],
                    packed[:, 4:], packed[:, 2], packed[:, 3] != 0)
                return jnp.concatenate(
                    [tokens] + [counters[n][None] for n in names]), pools

        ex = (self.params, pools,
              jax.ShapeDtypeStruct((b, 4 + self.pages_per_seq), np.int32))
        return compile_cache.cached_jit(
            serve_decode, ex,
            config=dict(self.config, attn=attn, max_batch=b,
                        block_size=bs,
                        num_blocks=self.cache.allocator.num_blocks),
            donate_argnums=(1,) if self.cache.donate_pools else (),
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
        timed, n = self.times.timed, len(req.prompt)
        pad = next(b for b in self.buckets if b >= n)
        if pad not in self._prefill_fns:
            self._prefill_fns[pad] = self._build_prefill(pad)
        rid = req.request_id
        with timed("serve.prefill.build", request_id=rid, prompt_len=n):
            ids = np.zeros((1, pad), np.int32)
            ids[0, :n] = req.prompt
            ids, length = jax.device_put((ids, np.int32(n)))
        with timed("serve.prefill.dispatch", request_id=rid, bucket=pad):
            token, rows = self._prefill_fns[pad](self.params, ids, length)
        # a cache that keeps more than pages says where (a state slot)
        where = getattr(self.cache, "scatter_attrs", None)
        with timed("serve.prefill.scatter", request_id=rid,
                   pages=self.cache.pages_for(n),
                   **(where(rid) if where else {})):
            self.cache.write_rows(rid, rows, n)
        with timed("serve.prefill.wait", request_id=rid):
            return int(token)

    def _decode(self, rows: List[Request]) -> List[int]:
        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        timed = self.times.timed
        with timed("serve.decode.tables"):
            # one array for the whole step, filled on the host: a row's
            # token, position, rows live, live flag, then its table;
            # pad rows stay zero and not live
            packed = np.zeros((self.max_batch, 4 + self.pages_per_seq),
                              np.int32)
            for i, req in enumerate(rows):
                # the cache's answer: where the token stands, the pages
                # its attention reads in order, the rows live in them
                # (the new row's slot, which this call reserves)
                position, table, lens = self.cache.decode_row(
                    req.request_id)
                packed[i, :4] = req.generated[-1], position, lens, 1
                packed[i, 4:4 + len(table)] = table
        with timed("serve.decode.put"):
            # host -> device, once a step: one transfer of one array
            packed = jax.device_put(packed)
        with timed("serve.decode.dispatch"):
            out, pools = self._decode_fn(self.params, self.cache.pools(),
                                         packed)
            self.cache.set_pools(pools)
            # device -> host, once a step, asked for now: the copy
            # follows the program on the device's queue
            out.copy_to_host_async()
        with timed("serve.decode.wait"):
            # the device's part of the step
            jax.block_until_ready(out)
        with timed("serve.decode.readback"):
            # every row's token, then the model's counters (a model that
            # counts nothing hands none): the bytes are here already
            out = np.asarray(out).tolist()
            # banked as counts, apart from the stages: one sample a
            # step, stamped where its tokens were read back
            now = time.perf_counter()
            for name, value in zip(self._counters, out[self.max_batch:]):
                self.times.count(name, value, start=now)
            return out[:len(rows)]
