"""Paged KV cache: a preallocated block pool plus per-sequence block tables.

vLLM's PagedAttention memory model, sized for the engine at startup and
never reallocated: the pools are ``[L, num_blocks, block_size, R]`` device
arrays, one row of all heads' values per position (compute dtype — the
exact values ``mha`` would see, which is what makes paged decode
token-identical to the uncached forward), and each admitted sequence owns a list of block ids covering
``ceil((prompt_len + max_new_tokens) / block_size)`` slots. The
:class:`BlockAllocator` is plain host-side bookkeeping — per-block
refcounts over a free list — because block assignment happens at
admission time, outside jit; the device side only ever sees dense int32
block tables.

Allocation is all-upfront per sequence (reservation = worst case decode
length) rather than on-demand per step: simpler, and it converts pool
exhaustion into *admission-time* backpressure (ServerOverloaded → client
retry/backoff) instead of a mid-decode eviction story.

Prefix sharing (docs/serving.md) rides on the refcounts: the
:class:`PrefixCache` content-hashes the prompt's blocks and lets a new
sequence alias already-resident block ids through its block table, so
the "millions of users, one system prompt" workload stores each prefix
once and skips its prefill entirely. A shared block is immutable from
the allocator's point of view; the engine copy-on-write forks the one
block a new owner would ever need to write (see docs for the proof that
full shared blocks are never written).
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


_LANES = 128  # a TPU tile's minor dimension


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    num_blocks: int
    block_size: int

    def __post_init__(self) -> None:
        if self.num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {self.num_blocks}")
        if self.block_size < 1 or self.block_size & (self.block_size - 1):
            raise ValueError(
                f"block_size must be a power of two, got {self.block_size}")

    def blocks_needed(self, total_len: int) -> int:
        return max(1, math.ceil(total_len / self.block_size))

    def pool_bytes(self, n_layers: int, n_heads: int, head_dim: int,
                   dtype_bytes: int = 2) -> int:
        """K + V pool footprint, for docs/serving.md-style sizing: the
        bytes :func:`init_kv_pools` allocates, row padding included."""
        return (2 * n_layers * self.num_blocks * self.block_size
                * kv_row_width(n_heads, head_dim) * dtype_bytes)


def kv_row_width(n_heads: int, head_dim: int) -> int:
    """Width R of one pool row: a position's ``H * head_dim`` K (or V)
    values, rounded up to a whole number of the TPU's 128-lane tiles
    (1024 for 16 x 64; 1664 for 25 x 64, 4 % padding)."""
    return -(-n_heads * head_dim // _LANES) * _LANES


def init_kv_pools(cfg: Any, cache: KVCacheConfig) -> Tuple[jnp.ndarray,
                                                           jnp.ndarray]:
    """Zero K/V pools [L, N, block, R] in the model's compute dtype,
    ``R = kv_row_width(H, hd)``.

    One row per position, all heads side by side, because that is the
    layout the paged forward scatters into and gathers from: with a
    128-multiple minor dimension the TPU keeps the array in HBM row-major
    and a donated pool is updated in place. (A ``[..., H, 64]`` pool was
    kept block-minor-most and copied to row-major and back, whole, by
    every program that touched it.) Columns ``H*hd..R`` are padding:
    never read by attention, always zero.

    Zeros (not garbage) so never-written slots contribute exactly
    0-probability * 0-value under the attention mask — see
    models/gpt.py:forward_paged's parity contract.
    """
    shape = (cfg.n_layers, cache.num_blocks, cache.block_size,
             kv_row_width(cfg.n_heads, cfg.head_dim))
    return (jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros(shape, cfg.compute_dtype))


class CacheLayout:
    """How one sequence's cache lies in pool blocks: which kinds of block
    it owns, how many of each a request reserves, and where each stands in
    the row of block ids the paged forward is given. This one is the
    uniform cache (one exact K/V row per position, block ``w`` of the
    table backing positions ``[w * block, (w + 1) * block)``); a model
    family whose cache has other kinds brings a subclass
    (``models/paged.py``). All kinds are blocks of the one pool and come
    from the one :class:`BlockAllocator`.
    """

    kinds: Tuple[str, ...] = ("kv",)
    # entries of a kind whose count a sequence is fixed (a recurrent
    # state's slot): reserved beside the blocks, from ids of their own
    # (``BlockAllocator(slots=)``), and counted last in ``blocks_by_kind``
    state_slots: int = 0

    def __init__(self, cache: KVCacheConfig, max_seq_len: int) -> None:
        self.cache = cache
        self.max_seq_len = int(max_seq_len)

    @property
    def row_args(self) -> Tuple[str, ...]:
        """Names of what ``step_rows`` counts: the args a decode step's
        span carries. The uniform cache: the rows the step's queries
        attend (``kv_rows``) and the rows its block tables span
        (``table_rows``), which is what a read of whole tables moves."""
        return tuple(f"{kind}_rows" for kind in self.kinds) \
            if len(self.kinds) > 1 else ("kv_rows", "table_rows")

    @property
    def table_width(self) -> int:
        """Entries in a row of the block table (fixed: no retrace)."""
        return self.cache.blocks_needed(self.max_seq_len)

    def blocks_by_kind(self, total_len: int) -> Tuple[int, ...]:
        """Blocks of each kind a request of ``total_len`` positions
        (prompt + new tokens) reserves at admission."""
        return (self.cache.blocks_needed(total_len),)

    def blocks_needed(self, total_len: int) -> int:
        """Pool blocks of all kinds (state slots are no pool blocks)."""
        return sum(self.blocks_by_kind(total_len)) - self.state_slots

    def lay_table(self, row: np.ndarray, blocks: Sequence[int]) -> None:
        """Write a sequence's blocks (as ``blocks_needed`` reserved them,
        kind after kind) into its row of the table."""
        row[:len(blocks)] = blocks

    def attended_rows(self, length: int) -> Tuple[int, ...]:
        """Cache rows of each kind that the query at position
        ``length - 1`` attends."""
        return (length,)

    def step_rows(self, lengths: Sequence[int], batch: int
                  ) -> Tuple[int, ...]:
        """One decode step's ``row_args``: ``attended_rows`` summed over
        its rows' context ``lengths`` and, for the uniform cache, the
        whole tables of the ``batch`` rows (padding among them) the step's
        batch bucket holds."""
        sums = tuple(map(sum, zip(*map(self.attended_rows, lengths))))
        if len(self.kinds) == 1:
            sums += (batch * self.table_width * self.cache.block_size,)
        return sums

    def check_prefill(self, max_prefill_len: int,
                      chunk_prefill_len: int) -> None:
        """Raise ValueError for prefill slice lengths this cache cannot
        take. The uniform cache takes any."""


class WindowSummaryLayout(CacheLayout):
    """A cache of two kinds (EVA attention, ``models/evabyte.py``): exact
    K/V rows of the current ``window`` positions, and one summary row per
    ``chunk`` positions of every finished window.

    The row of the table is two tables side by side. Entries
    ``[0, window / block)`` are a ring of window blocks: position ``p``
    lives in entry ``(p % window) // block``, and a new window overwrites
    the last one's rows in place. The entries after them are summary
    blocks: chunk ``c`` lives in entry ``c // block``, row ``c % block``.
    Entries a request did not reserve (the summaries of its last,
    unfinished window; the rest of the ring of a request shorter than a
    window) hold -1, which the forward never writes through. So a
    sequence holds, and a step reads, ``window + T / chunk`` rows, not
    ``T``. The chunk is the block (one gather of a block is a chunk).
    """

    kinds = ("window", "summary")

    def __init__(self, cache: KVCacheConfig, max_seq_len: int, *,
                 window: int, chunk: int) -> None:
        super().__init__(cache, max_seq_len)
        if chunk != cache.block_size:
            raise ValueError(
                f"the cache block ({cache.block_size}) must be the model's "
                f"chunk ({chunk}): a summary is pooled from one block")
        if window % chunk:
            raise ValueError(f"window {window} is not whole chunks of {chunk}")
        self.window, self.chunk = int(window), int(chunk)
        self.window_blocks = window // chunk
        # summaries of every window that can finish inside max_seq_len
        self.summary_blocks = -(-(self.max_seq_len // window)
                                * self.window_blocks // chunk)

    @property
    def table_width(self) -> int:
        return self.window_blocks + self.summary_blocks

    def blocks_by_kind(self, total_len: int) -> Tuple[int, int]:
        finished = total_len // self.window
        return (min(self.cache.blocks_needed(total_len), self.window_blocks),
                -(-finished * self.window_blocks // self.chunk))

    def lay_table(self, row: np.ndarray, blocks: Sequence[int]) -> None:
        # summaries are reserved only once the ring is whole; -1 = none
        n_window = min(len(blocks), self.window_blocks)
        row[:] = -1
        row[:n_window] = blocks[:n_window]
        row[self.window_blocks:self.window_blocks + len(blocks) - n_window] \
            = blocks[n_window:]

    def attended_rows(self, length: int) -> Tuple[int, int]:
        t = length - 1
        return (t % self.window + 1,
                t // self.window * self.window_blocks)

    def check_prefill(self, max_prefill_len: int,
                      chunk_prefill_len: int) -> None:
        if max_prefill_len > self.window:
            raise ValueError(
                f"max_prefill_len {max_prefill_len} exceeds the attention "
                f"window {self.window}: a prefill slice lies in one window")
        if chunk_prefill_len and (self.window % chunk_prefill_len
                                  or chunk_prefill_len % self.chunk):
            raise ValueError(
                f"chunk_prefill_len {chunk_prefill_len} must divide the "
                f"attention window {self.window} and be whole chunks of "
                f"{self.chunk}, so that no slice straddles a window")


class StateSlotLayout(CacheLayout):
    """Blocks that grow and one slot that does not: a cache whose contents
    differ by layer kind. Some layers keep rows a position, block ``w`` of
    the table backing positions ``[w * block, (w + 1) * block)`` as in the
    uniform cache; the others keep what a sequence holds once, whatever
    its length (a recurrent state, the tail of a short convolution), in
    pools of their own that one slot id names.

    Kinds ``("kv", "state")``: ``kv`` grows by ``ceil(len / block)``,
    ``state`` is ``state_slots`` = 1 slot. The slot is the last entry of
    the table's row, after ``blocks_needed(max_seq_len)`` block entries.
    This one reads every cached row (``models/kimi_linear.py``: latent
    attention with no selection beside delta-rule states); a family whose
    growing layers read a chosen few brings a subclass.
    """

    kinds = ("kv", "state")
    state_slots = 1

    @property
    def table_width(self) -> int:
        return self.cache.blocks_needed(self.max_seq_len) + self.state_slots

    @property
    def row_args(self) -> Tuple[str, ...]:
        return ("kv_rows", "selected_rows", "state_slots")

    def blocks_by_kind(self, total_len: int) -> Tuple[int, int]:
        return (self.cache.blocks_needed(total_len), self.state_slots)

    def lay_table(self, row: np.ndarray, blocks: Sequence[int]) -> None:
        n_kv = len(blocks) - self.state_slots
        row[:n_kv] = blocks[:n_kv]
        row[-1] = blocks[-1] - self.cache.num_blocks   # id -> slot

    def attended_rows(self, length: int) -> Tuple[int, int, int]:
        """(rows cached in a growing layer, rows of them the query at
        ``length - 1`` attends, state slots read)."""
        return (length, length, self.state_slots)

    def check_prefill(self, max_prefill_len: int,
                      chunk_prefill_len: int) -> None:
        if chunk_prefill_len % self.cache.block_size:
            raise ValueError(
                f"chunk_prefill_len {chunk_prefill_len} must be whole "
                f"cache blocks of {self.cache.block_size}: a prefill slice "
                f"starts on a block boundary")


class SparseStateLayout(StateSlotLayout):
    """:class:`StateSlotLayout` where the growing layers are block-sparse
    (``models/minicpm_sala.py``): the block-sparse attention layers keep
    exact K/V rows and beside every block the compressed keys that start
    in it, found by the same block id; the linear-attention layers keep one
    recurrent state a sequence. Past ``dense_len`` positions a query
    attends the rows of at most ``topk`` chosen blocks, the block it lies
    in among them.
    """

    def __init__(self, cache: KVCacheConfig, max_seq_len: int, *,
                 topk: int, dense_len: int) -> None:
        super().__init__(cache, max_seq_len)
        self.topk, self.dense_len = int(topk), int(dense_len)

    def attended_rows(self, length: int) -> Tuple[int, int, int]:
        bs = self.cache.block_size
        chosen = min(self.topk, self.cache.blocks_needed(length))
        selected = length if length <= self.dense_len \
            else (chosen - 1) * bs + (length - 1) % bs + 1
        return (length, selected, self.state_slots)


class WindowSlotLayout(StateSlotLayout):
    """:class:`StateSlotLayout` where the slot is a ring of K/V rows
    (``models/afmoe.py``): the full-attention layers keep one exact row a
    position in blocks that grow, and every sliding-window layer keeps the
    last ``ring = window + slice_len`` positions of a sequence in the
    sequence's slot of a pool of its own, position ``p`` in row ``p %
    ring``. A prefill slice of at most ``slice_len`` tokens is written
    first and attended after, so the ring still holds the ``window - 1``
    positions before the slice's first when its last row is in; what a
    sequence holds in those layers stops growing at ``ring`` positions. A
    query at position ``length - 1`` attends ``min(length, window)`` rows
    of a sliding layer and all ``length`` of a full one.
    """

    def __init__(self, cache: KVCacheConfig, max_seq_len: int, *,
                 window: int, slice_len: int) -> None:
        super().__init__(cache, max_seq_len)
        self.window, self.slice_len = int(window), int(slice_len)
        self.ring = self.window + self.slice_len
        if self.slice_len < cache.block_size or self.ring % cache.block_size:
            raise ValueError(
                f"the ring of {self.window} + {self.slice_len} positions is "
                f"not whole cache blocks of {cache.block_size} with a block "
                f"at least to spare")

    @property
    def row_args(self) -> Tuple[str, ...]:
        return ("kv_rows", "window_rows")

    def attended_rows(self, length: int) -> Tuple[int, int]:
        """(rows cached and read in a full layer, rows read in one sliding
        layer)."""
        return (length, min(length, self.window))

    def check_prefill(self, max_prefill_len: int,
                      chunk_prefill_len: int) -> None:
        super().check_prefill(max_prefill_len, chunk_prefill_len)
        if max(max_prefill_len, chunk_prefill_len) > self.slice_len:
            raise ValueError(
                f"a prefill slice of {max(max_prefill_len, chunk_prefill_len)}"
                f" tokens exceeds the {self.slice_len} positions the ring "
                f"keeps beside its window of {self.window}")


class LatentIndexLayout(CacheLayout):
    """A cache of one latent row a position and layer, with the keys of a
    learned index beside it in some layers (``models/glm_moe_dsa.py``).
    One growing kind, laid as the uniform cache is: block ``w`` of the
    table backs positions ``[w * block, (w + 1) * block)`` in every pool,
    so one block id finds a block's latents and its index keys. A query at
    position ``length - 1`` is scored against all ``length`` cached index
    keys and attends the ``min(length, topk)`` positions chosen.
    """

    def __init__(self, cache: KVCacheConfig, max_seq_len: int, *,
                 topk: int) -> None:
        super().__init__(cache, max_seq_len)
        self.topk = int(topk)

    @property
    def row_args(self) -> Tuple[str, ...]:
        return ("kv_rows", "selected_rows")

    def attended_rows(self, length: int) -> Tuple[int, int]:
        """(positions cached and scored by the index, positions of them
        the query at ``length - 1`` attends)."""
        return (length, min(length, self.topk))

    def step_rows(self, lengths: Sequence[int], batch: int
                  ) -> Tuple[int, ...]:
        return tuple(map(sum, zip(*map(self.attended_rows, lengths))))

    def check_prefill(self, max_prefill_len: int,
                      chunk_prefill_len: int) -> None:
        if chunk_prefill_len % self.cache.block_size:
            raise ValueError(
                f"chunk_prefill_len {chunk_prefill_len} must be whole "
                f"cache blocks of {self.cache.block_size}: a prefill slice "
                f"starts on a block boundary")


class BlockAllocator:
    """Thread-safe per-block refcounts over the pool's block ids.

    The engine's scheduler thread allocates at admission and frees at
    retirement; the HTTP threads only observe :meth:`free_blocks` for
    backpressure headroom, hence the lock. A block is free iff its
    refcount is zero; :meth:`allocate` hands out blocks at refcount 1,
    prefix sharing adds owners via :meth:`retain`, and :meth:`release`
    decrements — the block returns to the free list only when the last
    owner (sequence or prefix-cache entry) lets go, which is the
    never-freed-while-referenced invariant the COW protocol leans on.
    """

    def __init__(self, cache: KVCacheConfig, slots: int = 0) -> None:
        self._cache = cache
        self._lock = threading.Lock()
        self._free: List[int] = list(range(cache.num_blocks - 1, -1, -1))
        # state slots (``CacheLayout.state_slots``) are the ids past the
        # blocks': one discipline, one balance, a free list of their own
        self._n_ids = cache.num_blocks + int(slots)
        self._free_slots: List[int] = list(
            range(self._n_ids - 1, cache.num_blocks - 1, -1))
        self._ref: List[int] = [0] * self._n_ids

    @property
    def num_blocks(self) -> int:
        return self._cache.num_blocks

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._ref[block]

    def can_allocate(self, total_len: int) -> bool:
        return self.free_blocks() >= self._cache.blocks_needed(total_len)

    def allocate(self, total_len: int) -> List[int]:
        """Reserve blocks covering ``total_len`` positions; raises
        MemoryError when the pool can't — the engine maps that to
        ServerOverloaded (admission backpressure)."""
        return self.allocate_blocks(self._cache.blocks_needed(total_len))

    def allocate_blocks(self, need: int) -> List[int]:
        with self._lock:
            if need > len(self._free):
                raise MemoryError(
                    f"KV pool exhausted: need {need} blocks, "
                    f"{len(self._free)}/{self._cache.num_blocks} free")
            got = [self._free.pop() for _ in range(need)]
            for b in got:
                self._ref[b] = 1
        return got

    def free_slots(self) -> int:
        with self._lock:
            return len(self._free_slots)

    def allocate_slots(self, need: int) -> List[int]:
        """Reserve ``need`` state slots: ids ``num_blocks + slot``, held
        and released like blocks."""
        with self._lock:
            if need > len(self._free_slots):
                raise MemoryError(
                    f"state slots exhausted: need {need}, "
                    f"{len(self._free_slots)} free")
            got = [self._free_slots.pop() for _ in range(need)]
            for b in got:
                self._ref[b] = 1
        return got

    def retain(self, blocks: Sequence[int]) -> None:
        """Add one owner to each block; only live blocks can be shared."""
        with self._lock:
            for b in blocks:
                if not 0 <= b < self._n_ids or self._ref[b] < 1:
                    raise ValueError(f"retain of free/bogus block {b}")
                self._ref[b] += 1

    def release(self, blocks: Sequence[int]) -> None:
        with self._lock:
            for b in blocks:
                if not 0 <= b < self._n_ids or self._ref[b] < 1:
                    raise ValueError(f"double/bogus free of block {b}")
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    (self._free if b < self._cache.num_blocks
                     else self._free_slots).append(b)

    def outstanding(self) -> int:
        """Blocks and state slots currently owned by someone (refcount >
        0)."""
        with self._lock:
            return self._n_ids - len(self._free) - len(self._free_slots)

    def assert_balanced(self, expected_outstanding: int = 0) -> None:
        """Audit hook: every block not on the free list must be accounted
        for by ``expected_outstanding`` live owners' worth of blocks.

        Used by tests and the chaos invariant audit after a fleet drains:
        with no active sequences and no prefix cache, a nonzero balance
        is a leak (a crash path that dropped refs on the floor)."""
        with self._lock:
            held = self._n_ids - len(self._free) - len(self._free_slots)
            if held != expected_outstanding:
                owners = [i for i, r in enumerate(self._ref) if r > 0]
                raise AssertionError(
                    f"KV block balance: {held} blocks outstanding, "
                    f"expected {expected_outstanding} "
                    f"(held block ids: {owners[:16]}"
                    f"{'...' if len(owners) > 16 else ''})")


@dataclasses.dataclass
class PrefixMatch:
    """What :meth:`PrefixCache.match` found for one prompt.

    ``blocks`` are resident block ids covering prompt positions
    ``[0, shared_len)`` in order, already retained on behalf of the
    caller (who must release them, or hand them to a sequence that
    will). ``shared_len`` counts whole shared *positions*; it is a
    multiple of the block size except when the final entry was an exact
    partial-tail hit, in which case ``shared_len == len(prompt)``.
    """
    blocks: List[int]
    shared_len: int


class PrefixCache:
    """Content-addressed index of resident prompt blocks.

    Keys are chained hashes — ``h_i = sha256(h_{i-1} || tokens of block
    i)`` with ``h_{-1}`` empty — so a key identifies both a block's
    tokens *and* its absolute position, which is what lets a block table
    alias it verbatim (paged attention positions are absolute). Full
    prompt blocks are keyed by their chain hash; the prompt's partial
    tail block (when ``prompt_len % block_size != 0``) is keyed by the
    chain hash of the full prefix plus the exact tail tokens, so only a
    byte-identical prompt can alias it.

    The cache holds one allocator reference per indexed block; sequences
    sharing a block add their own. Eviction (LRU, deepest-first so a
    chain never strands unreachable descendants) merely drops the
    cache's reference — blocks stay alive until their last sequence
    retires, which is the never-freed-while-referenced invariant.

    The optional ``spill`` callback turns eviction into *demotion*: it
    fires for every evicted full-block entry, while the cache still
    holds its reference (so the block's contents are intact), letting
    the engine capture the exact K/V into the host/CAS tiers of
    serving/kv_store.py instead of dropping them. Tail-keyed entries
    never spill — they are private to one exact prompt. The callback
    must not raise (the engine's closure swallows its own failures; a
    failed spill just means the block is gone, like before).

    Single-writer: all mutation happens on the engine's scheduler
    thread; the lock only guards the counters HTTP threads read.
    """

    def __init__(self, cache: KVCacheConfig,
                 allocator: BlockAllocator, *,
                 spill: Optional[Any] = None) -> None:
        self._cfg = cache
        self._alloc = allocator
        self._spill = spill
        # key -> (block id, depth, last-used tick, tail?); depth = block
        # index within the prompt, used to evict leaves before their
        # parents; tail entries are salted keys that never spill.
        self._entries: Dict[bytes, Tuple[int, int, int, bool]] = {}
        self._tick = 0

    # -- hashing -----------------------------------------------------------

    @staticmethod
    def _chain(prev: bytes, tokens: Sequence[int]) -> bytes:
        h = hashlib.sha256(prev)
        h.update(b"|" + ",".join(str(int(t)) for t in tokens).encode())
        return h.digest()

    @staticmethod
    def _tail_key(prev: bytes, tokens: Sequence[int]) -> bytes:
        return PrefixCache._chain(prev + b"#tail", tokens)

    # -- lookup / registration --------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def match(self, prompt: Sequence[int]) -> PrefixMatch:
        """Longest resident prefix of ``prompt``, caller-retained."""
        bs = self._cfg.block_size
        blocks: List[int] = []
        shared = 0
        prev = b""
        self._tick += 1
        n_full = len(prompt) // bs
        for i in range(n_full):
            key = self._chain(prev, prompt[i * bs:(i + 1) * bs])
            ent = self._entries.get(key)
            if ent is None:
                break
            self._entries[key] = (ent[0], ent[1], self._tick, ent[3])
            blocks.append(ent[0])
            shared += bs
            prev = key
        else:
            tail = prompt[n_full * bs:]
            if tail:
                key = self._tail_key(prev, tail)
                ent = self._entries.get(key)
                if ent is not None:
                    self._entries[key] = (ent[0], ent[1], self._tick, ent[3])
                    blocks.append(ent[0])
                    shared += len(tail)
        if blocks:
            self._alloc.retain(blocks)
        return PrefixMatch(blocks, shared)

    def register(self, prompt: Sequence[int], blocks: Sequence[int]) -> None:
        """Index a just-prefilled prompt's blocks. ``blocks`` is the
        sequence's block table prefix (one id per prompt block, in
        order). Already-indexed keys are left alone — first writer wins,
        and colliding later sequences simply hold private copies."""
        bs = self._cfg.block_size
        self._tick += 1
        prev = b""
        n_full = len(prompt) // bs
        for i in range(n_full):
            key = self._chain(prev, prompt[i * bs:(i + 1) * bs])
            if key not in self._entries:
                self._alloc.retain([blocks[i]])
                self._entries[key] = (blocks[i], i, self._tick, False)
            prev = key
        tail = prompt[n_full * bs:]
        if tail:
            key = self._tail_key(prev, tail)
            if key not in self._entries:
                self._alloc.retain([blocks[n_full]])
                self._entries[key] = (blocks[n_full], n_full, self._tick,
                                      True)

    # -- tier promotion / inventory (serving/kv_store.py) ------------------

    def has_key(self, key: bytes) -> bool:
        return key in self._entries

    def adopt(self, key: bytes, block: int, depth: int) -> None:
        """Index a block promoted from a lower tier. The cache takes
        over the caller's allocator reference — the caller allocated
        the block (refcount 1) and must NOT release it. Only full
        blocks are ever promoted, so adopted entries are never
        tail-keyed."""
        if key in self._entries:
            raise ValueError("adopt of an already-indexed prefix key")
        self._tick += 1
        self._entries[key] = (block, depth, self._tick, False)

    def entries(self) -> List[Tuple[bytes, int, int]]:
        """``(key, block, depth)`` of every full-block entry, for the
        engine's flush-to-tier path and the prefix-inventory digest.
        Tail-keyed entries are omitted — they are private to one exact
        prompt and never spill or advertise."""
        return [(k, e[0], e[1]) for k, e in self._entries.items()
                if not e[3]]

    # -- pressure ----------------------------------------------------------

    def evict(self, want_free: int) -> int:
        """Drop LRU entries until the allocator has ``want_free`` free
        blocks or the cache is empty. Oldest tick first, deepest block
        first on ties, so a chain's leaves go before its root and no
        entry is ever left unreachable. Full-block entries are offered
        to the ``spill`` callback (tier demotion) before their
        reference is released. Returns entries dropped."""
        dropped = 0
        while (self._entries
               and self._alloc.free_blocks() < want_free):
            key = min(self._entries,
                      key=lambda k: (self._entries[k][2],
                                     -self._entries[k][1]))
            block, depth, _, tail = self._entries.pop(key)
            if self._spill is not None and not tail:
                self._spill(key, block, depth)
            self._alloc.release([block])
            dropped += 1
        return dropped

    def flush(self) -> int:
        """Drop everything — cached KV is a function of the params, so
        hot-swap invalidates the whole index. No spill: a deliberate
        same-params flush-to-tier goes through the engine's
        ``flush_kv_to_tier()``, which snapshots entries() first."""
        n = len(self._entries)
        for block, _, _, _ in self._entries.values():
            self._alloc.release([block])
        self._entries.clear()
        return n
