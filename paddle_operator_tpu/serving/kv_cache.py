"""Paged KV-cache: a block-table allocator over fixed-size token pages.

Contiguous per-sequence KV buffers waste HBM quadratically under
continuous batching: every admitted sequence would reserve ``max_seq``
slots up front, and a mid-batch finish leaves an unusable hole. Paging
(the vLLM design) fixes both: the cache is a pool of fixed-size blocks
(``block_size`` token slots each), a sequence owns a *block table* — an
ordered list of block ids — and grows one block at a time, so the only
internal fragmentation is the unfilled tail of each sequence's last
block.

:class:`KvBlockAllocator` is the bookkeeping half (pure Python, no
arrays): alloc/append/free with conservation invariants the chaos
scenario and ``make race`` exercise. :class:`PagedKvCache` is the array
half: one K and one V pool for every layer of the CACHE (a model's
layers, or loop steps x layers where a stack is run several times
over), ``[layers, num_blocks + 1, block_size, heads * head_dim]``, the
layout
:func:`..ops.attention_pallas.paged_decode_attention` reads in place.
:class:`LatentKvCache` is the array half for latent attention: ONE
compressed row a token and layer for all heads (a tuple of pools, one a
width it is told), behind the same allocator.
:class:`WindowKvCache` is :class:`PagedKvCache` for a model whose
sequences keep an exact WINDOW of their newest positions beside one
pooled SUMMARY row for every chunk of the windows before it
(``models.evabyte``): the same two pools hold both kinds of row, and a
sequence's pages are its window's, written over each time a window
closes, and one summary page a closed window.

What a sequence keeps for its tokens is the cache's to say, and the
engine asks instead of computing it: ``pages_for(tokens)`` (the pages a
budget reserves: the allocator's arithmetic), ``table_width(max_seq)``
(the decode step's table columns) and ``decode_row(seq_id)`` (for a
row of the next decode step: its position, the pages its attention
reads IN ORDER, and how many rows of them are live; the new row is
written at that row of the table). One row a token, the first pages,
the tokens so far for the paged and the latent cache (:class:`_RowAToken`);
window arithmetic for :class:`WindowKvCache`. Beside them ``pools()`` /
``set_pools()`` (what the decode step takes and hands back; the decode
step itself writes each new token's rows), ``write_rows()`` (a
prefill's rows: one jitted program a padded prompt length that takes
the pools donated and writes whole pages in place) and ``donate_pools``
(whether the decode step may overwrite the pools it is handed: all say
yes).

Thread safety: every allocator field is owned by ``_lock``, the slots'
two by ``_slot_lock`` (declared in analysis/guards.py — the static
OPS9xx passes and the runtime race detector both enforce it).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple


def _prompt_pages(cache: Any, seq_id: str, n: int, padded: int) -> Any:
    """Where the whole pages of a prefill's ``padded`` rows go in either
    cache: the first ``ceil(n / block_size)`` entries of the sequence's
    table, then the dummy page for the pages that hold padding alone."""
    import numpy as np

    size = cache.allocator.block_size
    blocks = np.full((-(-padded // size),), cache.dummy_page, np.int32)
    live = -(-n // size)
    blocks[:live] = cache.allocator.block_table(seq_id)[:live]
    return blocks


LANES = 128


def _stored(width: int) -> int:
    """A row's width rounded up to whole 128-lane tiles: the chip's
    tiled layout of a row-major page holds that many lanes anyway, and a
    minor axis that is no multiple of 128 makes XLA lay the pool out
    token-minor and copy it whole before every kernel call."""
    return -(-width // LANES) * LANES


def _write_pages(pool: Any, rows: Any, blocks: Any) -> Any:
    """``rows`` ``[layers, pad, width]`` as whole pages ``blocks`` of
    every layer of ``pool`` ``[layers, pages, size, stored]`` at once.
    (A scatter row by row makes XLA copy the whole pool into a layout
    of its own first: 3 GB of temporaries for a pool that fills the
    chip.)"""
    import jax.numpy as jnp

    layers, _, size, stored = pool.shape
    rows = jnp.pad(rows, ((0, 0),
                          (0, blocks.shape[0] * size - rows.shape[1]),
                          (0, stored - rows.shape[2])))
    return pool.at[:, blocks].set(rows.astype(pool.dtype).reshape(
        layers, blocks.shape[0], size, stored))


class KvCacheFull(Exception):
    """No free block — the admission layer must shed, not crash."""


class KvBlockAllocator:
    """Block-table bookkeeping for a pool of ``num_blocks`` KV pages.

    Invariants (asserted by :meth:`check`):

    * every block is either in the free list or in exactly one
      sequence's table — no leak, no double-own;
    * ``len(table) == pages_for(reserved tokens)`` — tables are exactly
      as long as the budget needs, never longer;
    * fragmentation is only ever slack:
      ``waste == Σ (len(table) * block_size - rows_for(seq_len))``.

    ``pages_for(tokens)`` and ``rows_for(tokens, budget)`` are the
    cache's answers (how many pages a budget of ``tokens`` reserves;
    how many ROWS of them are filled once ``tokens`` positions of a
    sequence with that budget are written). Left out, a token is a row:
    ``ceil(tokens / block_size)`` pages, ``tokens`` rows.
    :class:`WindowKvCache` hands its own: its pages are written over
    and hold fewer rows than the sequence has tokens.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 pages_for: Optional[Callable[[int], int]] = None,
                 rows_for: Optional[Callable[[int, int], int]] = None
                 ) -> None:
        if num_blocks <= 0 or block_size <= 0:
            raise ValueError("num_blocks and block_size must be positive")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.pages_for = pages_for or (
            lambda tokens: -(-tokens // block_size))
        self.rows_for = rows_for or (lambda tokens, budget: tokens)
        self._lock = threading.Lock()
        # LIFO free list: a just-freed (hot) block is reused first
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._tables: Dict[str, List[int]] = {}
        self._lens: Dict[str, int] = {}
        self._reserved: Dict[str, int] = {}
        self._peak_used = 0

    # -- allocation ------------------------------------------------------

    def alloc_sequence(self, seq_id: str, num_tokens: int,
                       live_tokens: Optional[int] = None) -> List[int]:
        """Reserve blocks for ``num_tokens`` token slots. All-or-nothing:
        on pool exhaustion nothing is allocated and :class:`KvCacheFull`
        is raised (the batcher sheds or defers).

        ``live_tokens`` (default ``num_tokens``) is the FILLED length the
        sequence starts at — the serving engine reserves the prompt plus
        the whole generation budget up front (a mid-generation
        KvCacheFull would strand a half-generated sequence) but only the
        prompt is live after prefill; :meth:`advance` grows the live
        length one decode step at a time."""
        if num_tokens <= 0:
            raise ValueError("num_tokens must be positive")
        live = num_tokens if live_tokens is None else live_tokens
        if not 0 < live <= num_tokens:
            raise ValueError("live_tokens %r outside (0, %d]"
                             % (live_tokens, num_tokens))
        need = self.pages_for(num_tokens)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError("sequence %r already allocated" % seq_id)
            if need > len(self._free):
                raise KvCacheFull(
                    "need %d block(s) for %d token(s), %d free"
                    % (need, num_tokens, len(self._free)))
            table = [self._free.pop() for _ in range(need)]
            self._tables[seq_id] = table
            self._lens[seq_id] = live
            self._reserved[seq_id] = num_tokens
            self._peak_used = max(self._peak_used,
                                  self.num_blocks - len(self._free))
            return list(table)

    def advance(self, seq_id: str) -> int:
        """Grow the live length into the pre-reserved slots by one token
        (the decode-step path); returns the new token's 0-based position.
        Raises when the reservation is exhausted — the batcher's token
        budget should have retired the sequence first."""
        with self._lock:
            if seq_id not in self._tables:
                raise KeyError("unknown sequence %r" % seq_id)
            if self._lens[seq_id] >= self._reserved[seq_id]:
                raise KvCacheFull(
                    "sequence %r exhausted its %d reserved slot(s)"
                    % (seq_id, self._reserved[seq_id]))
            pos = self._lens[seq_id]
            self._lens[seq_id] = pos + 1
            return pos

    def append_token(self, seq_id: str) -> Optional[int]:
        """Grow ``seq_id`` by one token slot, extending the reservation.
        Returns the newly allocated block id when the token crossed a
        block boundary, else None. Raises :class:`KvCacheFull` (sequence
        unchanged) on exhaustion. The incremental-growth counterpart of
        the up-front reservation: callers pick one style per sequence
        (a token a row: not for a cache that hands its own arithmetic)."""
        with self._lock:
            if seq_id not in self._tables:
                raise KeyError("unknown sequence %r" % seq_id)
            if self._lens[seq_id] < self._reserved[seq_id]:
                # still inside the reservation: no new block needed
                self._lens[seq_id] += 1
                return None
            if self._reserved[seq_id] % self.block_size == 0:
                # table exactly full: the next token needs a fresh block
                if not self._free:
                    raise KvCacheFull("no free block for %r" % seq_id)
                block = self._free.pop()
                self._tables[seq_id].append(block)
                self._lens[seq_id] += 1
                self._reserved[seq_id] += 1
                self._peak_used = max(self._peak_used,
                                      self.num_blocks - len(self._free))
                return block
            self._lens[seq_id] += 1
            self._reserved[seq_id] += 1
            return None

    def free_sequence(self, seq_id: str) -> int:
        """Return all of ``seq_id``'s blocks to the pool; returns how
        many. Unknown ids are a no-op (drain paths free defensively)."""
        with self._lock:
            table = self._tables.pop(seq_id, None)
            if table is None:
                return 0
            self._lens.pop(seq_id, None)
            self._reserved.pop(seq_id, None)
            self._free.extend(reversed(table))
            return len(table)

    # -- introspection ---------------------------------------------------

    def block_table(self, seq_id: str) -> List[int]:
        with self._lock:
            return list(self._tables[seq_id])

    def seq_len(self, seq_id: str) -> int:
        with self._lock:
            return self._lens[seq_id]

    def sequences(self) -> List[str]:
        with self._lock:
            return sorted(self._tables)

    def stats(self) -> Dict[str, int]:
        """Pool occupancy + fragmentation, in ROWS of cache:
        ``waste_slots`` is the slack (allocated-but-unfilled rows), the
        ONLY internal fragmentation paging permits."""
        with self._lock:
            used = self.num_blocks - len(self._free)
            waste = sum(len(t) * self.block_size
                        - self.rows_for(self._lens[s], self._reserved[s])
                        for s, t in self._tables.items())
            reserved_slack = sum(self._reserved[s] - self._lens[s]
                                 for s in self._tables)
            return {
                "blocks_total": self.num_blocks,
                "blocks_used": used,
                "blocks_free": len(self._free),
                "blocks_peak": self._peak_used,
                "sequences": len(self._tables),
                "waste_slots": waste,
                "reserved_slack": reserved_slack,
            }

    def check(self) -> List[str]:
        """Conservation audit (chaos + unit tests): returns violations."""
        errs: List[str] = []
        with self._lock:
            owned: List[int] = []
            for seq, table in self._tables.items():
                owned.extend(table)
                need = self.pages_for(self._reserved[seq])
                if len(table) != need:
                    errs.append(
                        "seq %r: %d block(s) for %d reserved slot(s), "
                        "expected %d"
                        % (seq, len(table), self._reserved[seq], need))
                if not 0 < self._lens[seq] <= self._reserved[seq]:
                    errs.append(
                        "seq %r: live length %d outside its reservation "
                        "%d" % (seq, self._lens[seq], self._reserved[seq]))
            everything = sorted(owned + self._free)
            if everything != list(range(self.num_blocks)):
                errs.append(
                    "block conservation broken: %d owned + %d free != "
                    "%d pool" % (len(owned), len(self._free),
                                 self.num_blocks))
            if len(set(owned)) != len(owned):
                errs.append("a block is owned by two sequences")
        return errs


class _RowAToken:
    """What the engine asks of a cache whose sequences keep one row a
    token: ``ceil(tokens / block_size)`` pages, attended in the order
    they were reserved, as many rows live as tokens written."""

    allocator: KvBlockAllocator

    def pages_for(self, tokens: int) -> int:
        return self.allocator.pages_for(tokens)

    def table_width(self, max_seq: int) -> int:
        return self.pages_for(max_seq)

    def decode_row(self, seq_id: str) -> Tuple[int, List[int], int]:
        """(the next token's position — its slot is reserved by this
        call —, the sequence's pages, the rows live before it)."""
        alloc = self.allocator
        live = alloc.seq_len(seq_id)
        return alloc.advance(seq_id), alloc.block_table(seq_id), live


class PagedKvCache(_RowAToken):
    """The array half: ONE key pool and ONE value pool for all layers,
    each ``[layers, num_blocks + 1, block_size, W]`` plus an allocator.
    A token's row is its heads side by side, ``W`` = ``heads *
    head_dim`` rounded up to whole 128-lane tiles (:func:`_stored`):
    row-major and lane-dense, which is both how XLA holds the array and
    what the decode kernel reads, so no step copies or transposes a
    pool. ``k_pages`` / ``v_pages`` are lists of the one array each
    (the names and the list :class:`LatentKvCache` answers to).

    ``layers`` counts the CACHE's layers, which need not be the
    weights': a model that runs its stack several times over and keeps
    every loop step's rows apart (``models.ouro``) hands loop steps x
    layers and indexes the pools by ``step * layers + layer`` itself.
    Nothing here or in the engine reads a model's layer count.

    The arrays live wherever JAX puts them (HBM on TPU) and are updated
    where they lie: a prefill's rows land through one jitted program
    that takes the pools donated and hands back the same buffers with
    the prompt's pages written (:meth:`write_rows`); the decode step
    takes them donated too (``donate_pools``), writes its new token's
    rows and hands the same two buffers back, so whoever held the
    arrays before a step holds deleted ones after it.
    Single-engine-thread by design — the batcher serializes model steps —
    so only the ALLOCATOR is locked.
    """

    donate_pools = True

    def __init__(self, num_blocks: int, block_size: int, layers: int,
                 heads: int, head_dim: int, dtype: Any = None) -> None:
        import jax.numpy as jnp

        self.allocator = KvBlockAllocator(num_blocks, block_size)
        self.layers = layers
        # +1: the LAST page is the decode batch's dummy-row target. The
        # engine pads its batch to a fixed shape; pad rows must scatter
        # their (garbage) k/v SOMEWHERE, and it must be a page no live
        # sequence can own or a pad row's write could race a real one.
        self.dummy_page = num_blocks
        shape = (layers, num_blocks + 1, block_size,
                 _stored(heads * head_dim))
        dtype = dtype or jnp.float32
        self.k_pages = [jnp.zeros(shape, dtype)]
        self.v_pages = [jnp.zeros(shape, dtype)]
        self._write: Optional[Any] = None

    def pools(self) -> Tuple[Any, Any]:
        return self.k_pages[0], self.v_pages[0]

    def set_pools(self, pools: Tuple[Any, Any]) -> None:
        self.k_pages, self.v_pages = [pools[0]], [pools[1]]

    def write_rows(self, seq_id: str, rows: Tuple[Any, Any], n: int) -> None:
        """A prefill's keys and values (each ``[layers, pad, heads *
        head_dim]``, the first ``n`` rows the prompt's) into the
        sequence's pages: one program a padded length, whole pages of
        every layer's K and V at once into the donated pools. Pages past
        the prompt's last go to the dummy page; the last page's slots
        past ``n`` take padding, which ``seq_lens`` masks until decode
        overwrites it."""
        self._write_blocks(rows, _prompt_pages(self, seq_id, n,
                                               rows[0].shape[1]))

    def _write_blocks(self, rows: Tuple[Any, Any], blocks: Any) -> None:
        import jax
        import jax.numpy as jnp

        if self._write is None:
            def write(pools, rows, blocks):
                return tuple(_write_pages(p, r, blocks)
                             for p, r in zip(pools, rows))

            self._write = jax.jit(write, donate_argnums=(0,))
        self.set_pools(self._write(self.pools(), tuple(rows),
                                   jnp.asarray(blocks)))


class WindowKvCache(PagedKvCache):
    """:class:`PagedKvCache` for attention that keeps an exact window
    beside pooled summaries (``models.evabyte``): of a sequence's
    positions ``0 .. i`` the pools hold the rows of ``i``'s own window
    (``window`` positions, the last of them ``i``) and, for every window
    before it, ONE row for each of its chunks of ``chunk`` positions.
    ``window // chunk == block_size``: a closed window's summaries are
    exactly one page.

    A budget of T tokens reserves ``min(window // block_size, ceil(T /
    block_size))`` WINDOW pages, written over each time a window closes,
    and ``(T - 1) // window`` SUMMARY pages, one for every window that
    closes before the budget's last position: the first and the last
    entries of the allocator's table. A decode row's table names in
    column 0 the summary page of its OPEN window (the dummy page where
    the budget ends inside that window), to which the decode step writes
    each chunk's row as the chunk's last position lands, and from column
    1 on the pages its attention reads, in this order: the summary pages
    of the closed windows, then the window pages. When a window closes
    its summary page moves from column 0 among the attended ones and the
    window's pages start again at row 0: nothing is copied.
    """

    def __init__(self, num_blocks: int, block_size: int, layers: int,
                 heads: int, head_dim: int, window: int, chunk: int,
                 dtype: Any = None) -> None:
        if window % block_size or window // chunk != block_size:
            raise ValueError(
                "a window of %d in chunks of %d does not fill pages of %d "
                "rows with rows and one page with summaries"
                % (window, chunk, block_size))
        self.window, self.chunk = window, chunk
        super().__init__(num_blocks, block_size, layers, heads, head_dim,
                         dtype)
        self.allocator = KvBlockAllocator(num_blocks, block_size,
                                          self.pages_for, self.rows_for)

    def pages_for(self, tokens: int) -> int:
        size = self.allocator.block_size
        return min(self.window // size, -(-tokens // size)) \
            + (tokens - 1) // self.window

    def rows_for(self, tokens: int, budget: int) -> int:
        """Rows filled once ``tokens`` positions are written: the open
        window's, and a summary for every whole chunk that has a page."""
        kept = (budget - 1) // self.window * self.allocator.block_size
        return tokens % self.window + min(tokens // self.chunk, kept)

    def table_width(self, max_seq: int) -> int:
        return 1 + (max_seq - 1) // self.window \
            + self.window // self.allocator.block_size

    def _split(self, seq_id: str) -> Tuple[List[int], List[int]]:
        """(the sequence's window pages, its summary pages): a budget
        that reserves a summary page reserves a whole window first."""
        table = self.allocator.block_table(seq_id)
        pages = min(len(table), self.window // self.allocator.block_size)
        return table[:pages], table[pages:]

    def decode_row(self, seq_id: str) -> Tuple[int, List[int], int]:
        """(the next token's position i, the table of the class's
        docstring, the rows live in its attended pages before it:
        ``block_size`` a closed window and ``i % window`` of its own)."""
        position = self.allocator.advance(seq_id)
        pages, summaries = self._split(seq_id)
        closed = position // self.window
        return (position,
                (summaries[closed:closed + 1] or [self.dummy_page])
                + summaries[:closed] + pages,
                closed * self.allocator.block_size + position % self.window)

    def write_rows(self, seq_id: str, rows: Tuple[Any, Any], n: int) -> None:
        """A prefill's keys and values, each ``[layers, pad // chunk +
        window, heads * head_dim]``: one summary row for every chunk of
        the padded prompt, then the rows of the window that is open
        after ``n`` positions (``n % window`` of them live). Whole
        pages into the donated pools, one program a padded length:
        summary pages that hold a whole chunk of the prompt and have a
        page reserved, window pages that hold a live row; every other
        page of ``rows`` goes to the dummy page."""
        import numpy as np

        size = self.allocator.block_size
        pages, summaries = self._split(seq_id)
        first = (rows[0].shape[1] - self.window) // size
        blocks = np.full((first + self.window // size,), self.dummy_page,
                         np.int32)
        keep = min(-(-(n // self.chunk) // size), len(summaries), first)
        blocks[:keep] = summaries[:keep]
        keep = min(-(-(n % self.window) // size), len(pages))
        blocks[first:first + keep] = pages[:keep]
        self._write_blocks(rows, blocks)


class LatentKvCache(_RowAToken):
    """The array half for latent attention (``models.axk1``,
    ``models.dsv32``): what a token leaves behind in a layer is one row
    ``[c_kv | k_rope]`` shared by every head, so there are no separate
    values; ``models.dsv32`` leaves its indexer's key beside it.

    ``widths`` names the rows a token leaves, and there is one pool a
    width behind the one allocator and block table (a token's page and
    slot are the same in each): ``pools()`` is their tuple and a
    prefill's rows one array a pool. A pool is ONE array ``[layers,
    num_blocks + 1, block_size, W]`` (the expert layers are one scan,
    which indexes it by layer; the last page is the pad rows' target as
    in :class:`PagedKvCache`), ``W`` the row's width rounded up to whole
    128-lane tiles (:func:`_stored`). It answers to the names the paged
    cache has:
    ``k_pages`` is the list of the pools, ``v_pages`` an empty list. A
    prefill lands through one jitted, donating scatter
    (:meth:`write_rows`), and the decode step updates the pools in
    place (``donate_pools``): an undonated copy of a pool sized to fill
    the chip does not fit beside it.
    """

    donate_pools = True

    def __init__(self, num_blocks: int, block_size: int, layers: int,
                 widths: Tuple[int, ...], dtype: Any = None) -> None:
        import jax.numpy as jnp

        self.allocator = KvBlockAllocator(num_blocks, block_size)
        self.layers = layers
        self.widths = tuple(widths)
        self.dummy_page = num_blocks
        self.k_pages = [jnp.zeros(
            (layers, num_blocks + 1, block_size, _stored(w)),
            dtype or jnp.bfloat16) for w in self.widths]
        self.v_pages: List[Any] = []
        self._scatter: Optional[Any] = None

    def pools(self) -> Tuple[Any, ...]:
        return tuple(self.k_pages)

    def set_pools(self, pools: Tuple[Any, ...]) -> None:
        self.k_pages = list(pools)

    def write_rows(self, seq_id: str, rows: Tuple[Any, ...], n: int) -> None:
        """A prefill's rows, one array ``[layers, pad, width]`` a pool
        (the first ``n`` the prompt's), into the sequence's pages: one
        program a padded length, whole pages of every layer and pool at
        once. Pages past the prompt's last go to the dummy page; the
        last page's slots past ``n`` take padding, which ``seq_lens``
        masks until decode overwrites it."""
        import jax
        import jax.numpy as jnp

        blocks = _prompt_pages(self, seq_id, n, rows[0].shape[1])
        if self._scatter is None:
            def scatter(pools, rows, blocks):
                return [_write_pages(p, r, blocks)
                        for p, r in zip(pools, rows)]

            self._scatter = jax.jit(scatter, donate_argnums=(0,))
        self.k_pages = self._scatter(self.k_pages, list(rows),
                                     jnp.asarray(blocks))


class SlotBlockAllocator(KvBlockAllocator):
    """:class:`KvBlockAllocator` that reserves, with a sequence's pages,
    ONE of ``slots`` state slots: both or neither (a sequence that finds
    pages and no slot raises :class:`KvCacheFull` and holds nothing, so
    the admission layer defers it as it defers one that finds no pages),
    and ``free_sequence`` hands both back. ``stats()`` keeps its meaning
    (rows of cache) and says how many slots are taken beside it. The
    slots' two fields are owned by ``_slot_lock`` (analysis/guards.py);
    it is never held across a call into the pages' half."""

    def __init__(self, num_blocks: int, block_size: int, slots: int) -> None:
        if slots <= 0:
            raise ValueError("slots must be positive")
        super().__init__(num_blocks, block_size)
        self.slots = slots
        self._slot_lock = threading.Lock()
        # LIFO as the pages are: a just-freed slot is taken first
        self._slots_free: List[int] = list(range(slots - 1, -1, -1))
        self._slot_of: Dict[str, int] = {}

    def alloc_sequence(self, seq_id: str, num_tokens: int,
                       live_tokens: Optional[int] = None) -> List[int]:
        with self._slot_lock:
            if not self._slots_free:
                raise KvCacheFull("no free state slot of %d for %r"
                                  % (self.slots, seq_id))
            slot = self._slots_free.pop()
        try:
            table = super().alloc_sequence(seq_id, num_tokens, live_tokens)
        except BaseException:
            with self._slot_lock:
                self._slots_free.append(slot)
            raise
        with self._slot_lock:
            self._slot_of[seq_id] = slot
        return table

    def free_sequence(self, seq_id: str) -> int:
        freed = super().free_sequence(seq_id)
        with self._slot_lock:
            slot = self._slot_of.pop(seq_id, None)
            if slot is not None:
                self._slots_free.append(slot)
        return freed

    def slot(self, seq_id: str) -> int:
        with self._slot_lock:
            return self._slot_of[seq_id]

    def stats(self) -> Dict[str, int]:
        out = super().stats()
        with self._slot_lock:
            out.update(slots_total=self.slots,
                       slots_used=self.slots - len(self._slots_free))
        return out

    def check(self) -> List[str]:
        errs = super().check()
        paged = self.sequences()
        with self._slot_lock:
            held = sorted(self._slot_of.values())
            if sorted(held + self._slots_free) != list(range(self.slots)):
                errs.append("slot conservation broken: %d held + %d free "
                            "!= %d" % (len(held), len(self._slots_free),
                                       self.slots))
            if sorted(self._slot_of) != paged:
                errs.append("the sequences with a slot are not the "
                            "sequences with pages")
        return errs


class StateKvCache(_RowAToken):
    """The array half for a model of two kinds of layer
    (``models.minicpm_sala``): ``layers`` attention layers that keep a
    key and a value row a token (``kv_heads`` heads of ``head_dim`` side
    by side, as :class:`PagedKvCache`'s) and ONE compressed key for
    every ``stride`` tokens, and ``state_layers`` recurrent layers that
    keep one state ``state_shape`` a SEQUENCE whatever its length.

    Four pools, all taken donated by the decode step and handed back
    (``pools()`` = (K, V, compressed keys, states)):

    * K and V ``[layers, num_blocks + 1, block_size, W]`` behind the
      allocator's block table, a token a row;
    * the compressed keys ``[layers, num_blocks + 1, block_size //
      stride, W]`` behind the SAME table: row ``r`` of a sequence (page
      ``r // (block_size // stride)``) holds the window that ENDS with
      stride ``r``, so a window's row lies in the page of its last
      token, which is reserved when the decode step writes it;
    * the states ``[state_layers, slots + 1, *state_shape]`` float32:
      slot ``slots`` is the pad rows' target, as the last page is.

    The cache owns the table's meaning: ``decode_row`` hands the
    sequence's state slot as column 0 of its table and its pages after
    it (``table_width`` counts the column), so the engine's one-array
    step carries the slot without knowing of it. ``write_rows`` lands a
    prefill's pages AND its final states in one donating program: a
    slot's state is whatever its sequence's prefill left, never its
    last owner's. ``k_pages`` / ``v_pages`` are lists of the arrays
    (the names the other caches answer to): K, the compressed keys and
    the states; V.
    """

    donate_pools = True

    def __init__(self, num_blocks: int, block_size: int, layers: int,
                 kv_heads: int, head_dim: int, stride: int,
                 state_layers: int, slots: int,
                 state_shape: Tuple[int, ...], dtype: Any = None) -> None:
        import jax.numpy as jnp

        if block_size % stride:
            raise ValueError("pages of %d rows hold no whole strides of %d"
                             % (block_size, stride))
        self.allocator = SlotBlockAllocator(num_blocks, block_size, slots)
        self.layers, self.state_layers = layers, state_layers
        self.slots = slots
        self.dummy_page = num_blocks
        dtype = dtype or jnp.bfloat16
        width = _stored(kv_heads * head_dim)
        rows = (layers, num_blocks + 1, block_size, width)
        self.k_pages = [
            jnp.zeros(rows, dtype),
            jnp.zeros(rows[:2] + (block_size // stride, width), dtype),
            jnp.zeros((state_layers, slots + 1) + tuple(state_shape),
                      jnp.float32)]
        self.v_pages = [jnp.zeros(rows, dtype)]
        self._write: Optional[Any] = None

    def pools(self) -> Tuple[Any, Any, Any, Any]:
        return (self.k_pages[0], self.v_pages[0], self.k_pages[1],
                self.k_pages[2])

    def set_pools(self, pools: Tuple[Any, Any, Any, Any]) -> None:
        self.k_pages = [pools[0], pools[2], pools[3]]
        self.v_pages = [pools[1]]

    def table_width(self, max_seq: int) -> int:
        return 1 + self.pages_for(max_seq)

    def decode_row(self, seq_id: str) -> Tuple[int, List[int], int]:
        """(the next token's position, [the state slot] + the pages, the
        rows live before it)."""
        position, pages, live = super().decode_row(seq_id)
        return position, [self.allocator.slot(seq_id)] + pages, live

    def scatter_attrs(self, seq_id: str) -> Dict[str, int]:
        """What ``serve.prefill.scatter`` says beside its pages."""
        return {"state_slot": self.allocator.slot(seq_id)}

    def write_rows(self, seq_id: str, rows: Tuple[Any, Any, Any, Any],
                   n: int) -> None:
        """A prefill's keys and values (each ``[layers, pad, W]``, the
        first ``n`` rows the prompt's), its compressed keys ``[layers,
        pad // stride, W]`` and its final states ``[state_layers,
        *state_shape]``: whole pages of the three paged pools and the
        sequence's slot of the fourth, one program a padded length, all
        four donated."""
        import jax
        import jax.numpy as jnp

        blocks = _prompt_pages(self, seq_id, n, rows[0].shape[1])
        if self._write is None:
            def write(pools, rows, blocks, slot):
                paged = [_write_pages(p, r, blocks)
                         for p, r in zip(pools[:3], rows[:3])]
                return tuple(paged) + (
                    pools[3].at[:, slot].set(rows[3].astype(pools[3].dtype)),)

            self._write = jax.jit(write, donate_argnums=(0,))
        self.set_pools(self._write(
            self.pools(), tuple(rows), jnp.asarray(blocks),
            jnp.asarray(self.allocator.slot(seq_id), jnp.int32)))
