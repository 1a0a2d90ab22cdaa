"""Iteration-level continuous batching over the paged KV cache.

The Orca-style scheduler loop at the heart of ``dct serve``: requests
enter a bounded thread-safe queue; every scheduler iteration first
admits queued requests into the running batch (one bucketed prefill call
for the newcomers), then runs ONE decode step for every active sequence
(one bucketed T=1 call), retiring finished sequences immediately so
their pool blocks and batch slots free up for the next iteration. No
sequence ever waits for a stranger's completion — the property that
makes continuous batching beat run-to-completion batching on tokens/sec
under load. The decode step is pipelined one deep: a step's sampled
tokens stay on the device, the next step takes them from there and is
dispatched before the host reads them back, so the host's turn (prepare,
dispatch, commit, this loop) runs while the device computes
(docs/serving.md "One step ahead").

The model is whatever family the given model config belongs to: the
engine imports none and asks the config for its
:class:`~determined_clone_tpu.models.paged.PagedModel` (the paged forward,
the layout of a sequence's cache in pool blocks, the features that
family's cache cannot serve yet, which are refused at construction).

Compile discipline: all device work funnels through ONE jitted
``forward_paged`` whose shapes are padded to :class:`BucketSpec` buckets,
so the XLA program count is bounded by ``buckets.program_budget`` for
the lifetime of the engine — asserted by the tier-1 compile-discipline
test via :meth:`InferenceEngine.programs_compiled` (the PR 2 retrace
probe).

Backpressure: a full queue raises :class:`ServerOverloaded`;
:meth:`InferenceEngine.submit_with_backoff` wraps admission in the
repo-standard ``RetryPolicy`` (utils/retry.py) so clients back off with
full jitter instead of hammering. KV-pool exhaustion is *deferred*
admission (requests wait in queue until blocks free), never mid-decode
eviction.

Three raw-speed optimisations ride on the same loop, each individually
optional and all preserving the bit-identical-greedy-parity pin
(docs/serving.md has the full protocols):

- **copy-on-write prefix sharing** (``prefix_cache=True``): admission
  content-hashes the prompt's blocks against the
  :class:`~determined_clone_tpu.serving.kv_cache.PrefixCache` and
  aliases resident blocks through the block table, so prefill skips the
  shared prefix entirely; the one block a new owner could ever write (the
  block holding the re-scored last prompt token) is COW-forked first.
- **draft-model speculative decoding** (``speculative_k=k`` plus a tiny
  draft GPT): the draft proposes k tokens per iteration with T=1 calls,
  the target scores all of them in ONE k+1-token verify call
  (``forward_paged_logits``), and the accepted-prefix rule emits exactly
  the tokens one-at-a-time greedy decode would — a disagreeing draft
  costs speed, never correctness.
- **chunked prefill** (``chunk_prefill_len=n``): long prompts prefill n
  tokens per scheduler iteration, interleaved with decode steps, so one
  huge prompt can't head-of-line-block every running sequence's next
  token (and prompts longer than the largest prefill bucket become
  servable at all).
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from determined_clone_tpu import faults
from determined_clone_tpu.serving.bucketing import BucketSpec, bucket_for
from determined_clone_tpu.serving.kv_cache import (
    BlockAllocator,
    KVCacheConfig,
    PrefixCache,
)
from determined_clone_tpu.serving.kv_store import (
    PrefixInventory,
    params_fingerprint,
)
from determined_clone_tpu.telemetry import MetricsRegistry
from determined_clone_tpu.telemetry.spans import null_span
from determined_clone_tpu.utils.retry import RetryPolicy, retry_call


class ServerOverloaded(RuntimeError):
    """Admission rejected: queue full. Retryable — clients should back
    off (see :meth:`InferenceEngine.submit_with_backoff`)."""


class ReplicaFailed(RuntimeError):
    """The engine serving this request died (scheduler crash) or was
    condemned by the fleet supervisor. The fleet front door treats this
    as "requeue to a surviving replica"; ``active`` distinguishes
    requests that were *running* on the dead engine (they count toward
    the poison-pill strike budget — one of them may be what killed it)
    from ones that merely sat in its queue (innocent orphans, requeued
    without a strike)."""

    def __init__(self, msg: str, *, active: bool = False) -> None:
        super().__init__(msg)
        self.active = active


ADMISSION_RETRY = RetryPolicy(
    name="serving_admission", max_attempts=6, base_delay_s=0.05,
    multiplier=2.0, max_delay_s=2.0, retryable=(ServerOverloaded,))


def serving_form(params: Any, model_cfg: Any, span: Any = null_span) -> Any:
    """``params`` as the family's paged forward reads them
    (``PagedModel.serving_params``) and on the device: what an engine, and
    a fleet for all its replicas, holds and serves from, so that no
    serving program converts a weight and no call uploads one. Made once
    when a tree is handed over, on the caller's thread; a device array
    that already has its type comes back as the same buffer. The span
    records the bytes that were cast and the bytes kept in their type."""
    with span("serving_params_prepare") as sp:
        served = jax.block_until_ready(jax.device_put(
            model_cfg.paged_model().serving_params(params, model_cfg)))
        sizes = [(was.nbytes, now.dtype == was.dtype) for was, now in zip(
            jax.tree.leaves(params), jax.tree.leaves(served))]
        sp.set(cast_bytes=sum(n for n, same in sizes if not same),
               kept_bytes=sum(n for n, same in sizes if same))
    return served


def forward_paged(params: Any, cfg: Any, rows: jax.Array, tables: jax.Array,
                  last_tokens: jax.Array, *pools: jax.Array) -> Any:
    """A prefill slice or a decode step of ``cfg``'s family
    (``models/gpt.py:forward_paged`` states the family's contract), sampled
    greedily in the same program: ``(tokens, *pools, *extras)``, ``tokens``
    the int32 ``argmax`` of every row's logits, padded to ``last_tokens``'
    length (the engine's largest batch bucket) so that one call's return
    is the next one's ``last_tokens`` whatever their batch buckets.

    ``rows`` is ``[B, T + 3]`` int32, everything the host knows of a row
    in one transfer: its ``T`` tokens, the position of the first, how many
    of them are real (0: a padding row), and ``src``. In a decode step
    (``T == 1``, told from the shape) row ``i`` takes its token from the
    device, ``last_tokens[src[i]]``, where ``src[i] >= 0`` (it was row
    ``src[i]`` of the call that returned ``last_tokens``, whose tokens the
    host may not have read yet), else from the host's column."""
    T = rows.shape[1] - 3
    tokens, first, count, src = (rows[:, :T], rows[:, T], rows[:, T + 1],
                                 rows[:, T + 2])
    if T == 1:
        tokens = jnp.where(src >= 0, last_tokens[jnp.maximum(src, 0)],
                           tokens[:, 0])[:, None]
    steps = jnp.arange(T, dtype=jnp.int32)
    token_mask = steps < count[:, None]
    positions = jnp.where(token_mask, first[:, None] + steps, 0)
    logits, *rest = cfg.paged_model().forward_paged(
        params, cfg, tokens, positions, token_mask,
        jnp.maximum(count - 1, 0), *pools, tables)
    sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (jnp.zeros_like(last_tokens).at[:sampled.shape[0]].set(sampled),
            *rest)


def forward_paged_logits(params: Any, cfg: Any, tokens: jax.Array,
                         positions: jax.Array, token_mask: jax.Array,
                         *pools_and_tables: jax.Array) -> Any:
    """The family's paged forward with logits at every position."""
    return cfg.paged_model().forward_paged_logits(
        params, cfg, tokens, positions, token_mask, *pools_and_tables)


def make_paged_forward(n_pools: int = 2) -> Any:
    """The jitted, sampling paged forward an engine runs every prefill
    slice and decode step through, for whichever family the (static) model
    config it is called with belongs to; ``n_pools``
    (``len(PagedModel.pool_names)``) is how many pools the family hands
    over, each donated.
    Replica fleets pass ONE of these to every engine (``fwd=``) so the
    whole fleet shares a single XLA program cache: replica N>1 warms up
    for free, and scale-up never pays a compile (all replicas serve the
    same model config and bucket ladder, so the shapes are identical)."""
    return jax.jit(forward_paged, static_argnums=(1,),
                   donate_argnums=tuple(range(5, 5 + n_pools)))


def make_paged_verify(n_pools: int = 2) -> Any:
    """The jitted multi-logit forward the speculative verify step runs
    through: one [B, k+1] call scores the last committed token plus all
    k drafts; compiles one program per batch bucket."""
    return jax.jit(forward_paged_logits, static_argnums=(1,),
                   donate_argnums=tuple(range(5, 5 + n_pools)))


def _block_copy(k_pool: jax.Array, v_pool: jax.Array,
                src: jax.Array, dst: jax.Array):
    """COW fork: duplicate one pool block (all layers) into another. The
    uniform cache's two pools: only a family whose cache the prefix cache
    can serve gets here."""
    return (k_pool.at[:, dst].set(k_pool[:, src]),
            v_pool.at[:, dst].set(v_pool[:, src]))


def make_block_copy() -> Any:
    """Jitted :func:`_block_copy` — src/dst are dynamic scalars, so the
    whole COW protocol costs exactly one XLA program per pool pair."""
    return jax.jit(_block_copy, donate_argnums=(0, 1))


def _block_write(k_pool: jax.Array, v_pool: jax.Array, dst: jax.Array,
                 k_blk: jax.Array, v_blk: jax.Array):
    """KV-tier promotion: scatter one host-gathered block payload (all
    layers) into a pool slot — the exact inverse of the spill gather."""
    return (k_pool.at[:, dst].set(k_blk), v_pool.at[:, dst].set(v_blk))


def make_block_write() -> Any:
    """Jitted :func:`_block_write` — dst is a dynamic scalar, so tier
    promotion costs exactly one XLA program per pool pair."""
    return jax.jit(_block_write, donate_argnums=(0, 1))


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. Greedy decoding (argmax) — the serving
    contract that keeps paged output token-identical to the uncached
    forward, which the tier-1 parity test pins."""
    prompt: Tuple[int, ...]
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    request_id: str = ""
    # cross-process trace identity minted at the front door; rides every
    # per-request span so the stitched trace shows one request end to end
    trace_id: Optional[str] = None
    # absolute monotonic deadline (time.monotonic() clock). Expired work
    # is retired with finish_reason "expired" at the next iteration
    # boundary — never decoded into the void — and its blocks freed
    deadline_t: Optional[float] = None


@dataclasses.dataclass
class RequestResult:
    request_id: str
    prompt_len: int
    tokens: List[int]
    finish_reason: str          # "length" | "eos" | "aborted" | "expired"
    queue_wait_s: float
    prefill_s: float            # total prefill device time it rode
    decode_s: float             # prefill-done → last token
    total_s: float              # submit → last token
    prefix_hit_blocks: int = 0   # prompt blocks aliased from the cache
    prefix_miss_blocks: int = 0  # prompt blocks prefilled from scratch
    spec_proposed: int = 0       # draft tokens offered for this request
    spec_accepted: int = 0       # draft tokens the target agreed with
    trace_id: Optional[str] = None  # front-door trace identity, if minted
    # what the family's programs noted of every token run for this request
    # (PagedModel.token_records), in the order of its positions
    token_records: Optional[np.ndarray] = None

    @property
    def spec_acceptance(self) -> Optional[float]:
        if self.spec_proposed <= 0:
            return None
        return self.spec_accepted / self.spec_proposed


@dataclasses.dataclass
class EngineStats:
    submitted: int
    rejected: int
    completed: int
    tokens_generated: int
    peak_active: int
    queue_depth: int
    free_blocks: int
    programs_compiled: int
    program_budget: int
    prefix_hit_blocks: int = 0
    prefix_miss_blocks: int = 0
    prefix_cached_entries: int = 0
    spec_tokens_proposed: int = 0
    spec_tokens_accepted: int = 0
    spec_acceptance_rate: Optional[float] = None
    kv_host_hit_blocks: int = 0
    kv_cas_hit_blocks: int = 0
    kv_miss_blocks: int = 0
    kv_promoted_blocks: int = 0
    kv_spilled_blocks: int = 0


class _Handle:
    """Future for one in-flight request.

    Settlement is first-write-wins: once either `_finish` or `_fail`
    lands, later calls are no-ops. The fleet supervisor can fail a
    wedged replica's handles (so waiters requeue immediately) while the
    wedged scheduler thread is still alive — when that thread finally
    wakes and tears down, it must not clobber the verdict the client
    already acted on.
    """

    def __init__(self, req: Request) -> None:
        self.req = req
        self._done = threading.Event()
        self._lk = threading.Lock()  # leaf: guards the settle race only
        self._result: Optional[RequestResult] = None
        self._error: Optional[BaseException] = None
        # timestamps stamped by the engine (monotonic)
        self.submit_t = 0.0
        self.admit_t = 0.0
        self.prefill_s = 0.0
        self.prefill_done_t = 0.0
        self.cancelled = False  # set by InferenceEngine.abort

    def _finish(self, result: RequestResult) -> bool:
        with self._lk:
            if self._done.is_set():
                return False
            self._result = result
            self._done.set()
        return True

    def _fail(self, exc: BaseException) -> bool:
        with self._lk:
            if self._done.is_set():
                return False
            self._error = exc
            self._done.set()
        return True

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> RequestResult:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.req.request_id!r} not done in {timeout}s")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


class _Active:
    """Scheduler-private state of one running sequence."""

    __slots__ = ("handle", "blocks", "table", "by_kind", "prompt_len", "out",
                 "last_token", "prefill_pos", "pending_copy", "hit_blocks",
                 "miss_blocks", "spec_proposed", "spec_accepted", "records",
                 "flight", "slot", "retired")

    def __init__(self, handle: _Handle, blocks: List[int],
                 table: np.ndarray, prompt_len: int) -> None:
        self.handle = handle
        self.blocks = blocks
        # the row's line of every call's block table: its blocks as the
        # layout lays them, fixed from admission on
        self.table = table
        # blocks per kind of the cache layout (gauged while it runs)
        self.by_kind: Tuple[int, ...] = ()
        self.prompt_len = prompt_len
        self.out: List[int] = []
        self.last_token = -1
        # next un-prefilled prompt position: 0 for a cold prompt, the
        # shared-prefix length after a cache hit, prompt_len once done
        self.prefill_pos = 0
        # (src, dst) COW fork to execute before this row's first device
        # call; the src block keeps a caller reference until then
        self.pending_copy: Optional[Tuple[int, int]] = None
        self.hit_blocks = 0
        self.miss_blocks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # the programs' records of this row's tokens, a piece a call
        self.records: List[np.ndarray] = []
        # the decode step this row is part of whose tokens the host has not
        # read yet, and the row's index in it (the next step's ``src``)
        self.flight: Optional["_Flight"] = None
        self.slot = -1
        self.retired = False  # set by _retire: a step's token is dropped


class _Flight(NamedTuple):
    """One dispatched decode step, until its tokens are read back."""
    rows: List[_Active]
    size: Dict[str, int]        # the args of the step's spans
    attended: Tuple[int, ...]   # cache rows read (the layout's row_args)
    tokens: Any                 # [max_batch] int32, on the device
    extras: Tuple[Any, ...]     # what the program returned after its pools
    dispatch_s: float           # the host's seconds in the dispatch


class _PrefillCall(NamedTuple):
    """One dispatched prefill call, until its first tokens are read back
    (in the same scheduler turn, after the turn's decode step went out)."""
    rows: List[_Active]
    counts: List[int]           # positions each row advances
    batch: int                  # the call's buckets
    length: int
    tokens: Any                 # [max_batch] int32, on the device
    extras: Tuple[Any, ...]
    mirrored: Any               # the draft's mirror of the slice, if any
    t0: float                   # monotonic / perf_counter at the dispatch
    pt0: float
    reckoned: Dict[str, int]    # PagedModel.prefill_counts of the call


class InferenceEngine:
    """Continuous-batching decoder server over a paged KV cache.

    One scheduler thread (named ``serving-engine`` — the conftest
    thread-leak fixture knows it) owns all device work; request threads
    only touch the queue and their handle. Use as a context manager or
    call :meth:`close` — the thread must be joined.
    """

    def __init__(self, params: Any, model_cfg: Any, *,
                 buckets: Optional[BucketSpec] = None,
                 cache: Optional[KVCacheConfig] = None,
                 max_queue_depth: int = 64,
                 telemetry: Any = None,
                 fwd: Any = None,
                 iteration_floor_s: float = 0.0,
                 prefix_cache: bool = False,
                 chunk_prefill_len: int = 0,
                 speculative_k: int = 0,
                 draft_params: Any = None,
                 draft_cfg: Any = None,
                 kv_store: Any = None,
                 fault_scope: str = "") -> None:
        self.model_cfg = model_cfg
        # the family: its forward, its cache's layout, what it cannot serve
        self._model = model_cfg.paged_model()
        asked = {"prefix_cache": prefix_cache,
                 "kv_store": kv_store is not None,
                 "speculative": bool(speculative_k)}
        refused = [f for f in self._model.unsupported if asked[f]]
        if refused:
            raise ValueError(
                f"the {self._model.family} family's cache cannot serve "
                f"{', '.join(refused)} yet (models/paged.py)")
        # chaos targeting: with a scope (the fleet passes the replica
        # id) the scheduler also hits "engine.step.<scope>" /
        # "engine.admit.<request_id>" so a seeded FaultPlan can kill ONE
        # replica or poison ONE request by fnmatch pattern. Built by
        # concatenation on purpose: scoped names stay out of the static
        # CONTRACT001 catalog, which lists the constant base points.
        self._fault_scope = str(fault_scope)
        self.buckets = buckets or BucketSpec.build(
            8, min(128, model_cfg.max_seq_len))
        if self.buckets.max_prefill_len > model_cfg.max_seq_len:
            raise ValueError(
                f"prefill bucket {self.buckets.max_prefill_len} exceeds "
                f"model max_seq_len {model_cfg.max_seq_len}")
        if cache is None:
            cache = KVCacheConfig(
                num_blocks=self.buckets.max_batch
                * self.blocks_per_sequence(model_cfg, 16), block_size=16)
        self.cache = cache
        self._layout = self._model.cache_layout(model_cfg, cache)
        self.max_queue_depth = int(max_queue_depth)

        registry = getattr(telemetry, "registry", telemetry)
        self.registry: MetricsRegistry = (
            registry if isinstance(registry, MetricsRegistry)
            else MetricsRegistry())
        tracer = getattr(telemetry, "tracer", None)
        self._span = tracer.span if tracer is not None else null_span
        # per-request event recording (queue admission, prefill chunks,
        # speculative rounds, COW forks, retirement): None when telemetry
        # is off, so the disabled path pays one `is not None` per step and
        # nothing per request
        self._tracer = (tracer if tracer is not None
                        and getattr(tracer, "enabled", False) else None)

        # the engine holds the serving form and not the caller's tree
        self._params = serving_form(params, model_cfg, self._span)
        self._note_weight_bytes()
        self._pending_params: Any = None
        # a state slot per batch row beside the blocks, where the layout
        # has a kind of fixed count (none: an allocator of blocks alone)
        self._allocator = BlockAllocator(
            cache, slots=self.buckets.max_batch * self._layout.state_slots)
        # the family's pools, as one tuple: handed to every forward
        # donated, replaced by what it returns, never looked into
        self._pools: Tuple[Any, ...] = tuple(
            self._model.init_pools(model_cfg, cache,
                                   self.buckets.max_batch))
        # fixed block-table width: every call sees the same W, so table
        # shape never causes a retrace
        self._table_width = self._layout.table_width
        self._fwd = fwd if fwd is not None else make_paged_forward(
            len(self._pools))
        # the decode step whose tokens are still on the device: the next
        # step is dispatched before it is read (docs/serving.md "One step
        # ahead"); None after a drain
        self._flight: Optional[_Flight] = None
        # ``last_tokens`` of a call that takes no token from the device
        self._no_tokens = jnp.zeros((self.buckets.max_batch,), jnp.int32)

        # -- optional raw-speed features (module docstring) --------------
        self.chunk_prefill_len = int(chunk_prefill_len)
        if self.chunk_prefill_len:
            self.buckets.validate_chunk_len(self.chunk_prefill_len)
        self._layout.check_prefill(self.buckets.max_prefill_len,
                                   self.chunk_prefill_len)
        self._spec_k = int(speculative_k)
        if self._spec_k < 0:
            raise ValueError(f"speculative_k must be >= 0, got {speculative_k}")
        if self._spec_k:
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "speculative_k > 0 needs draft_params and draft_cfg")
            if draft_cfg.vocab_size != model_cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{model_cfg.vocab_size} (the tokenizer is shared)")
            self._draft_params = serving_form(draft_params, draft_cfg,
                                              self._span)
            self.draft_cfg = draft_cfg
            # the draft's pools share block ids (and hence block tables
            # and the allocator) with the target's — only the per-block
            # payload shape differs — so prefix sharing and COW cover
            # the draft KV with zero extra bookkeeping
            self._draft_pools: Tuple[Any, ...] = tuple(
                draft_cfg.paged_model().init_pools(
                    draft_cfg, cache, self.buckets.max_batch))
            self._draft_fwd = make_paged_forward(len(self._draft_pools))
            self._verify_fwd = make_paged_verify(len(self._pools))
        else:
            self._draft_pools = ()
            self._draft_params = None
            self.draft_cfg = None
            self._draft_fwd = None
            self._verify_fwd = None
        # -- KV memory hierarchy (serving/kv_store.py) -------------------
        # the host/CAS tiers below the prefix cache: eviction demotes
        # blocks into the store, admission promotes tier hits back into
        # pool blocks before prefilling only the uncovered tail
        if kv_store is not None and not prefix_cache:
            raise ValueError("kv_store requires prefix_cache=True — the "
                             "tier is keyed by the prefix cache's chain "
                             "hashes")
        self._kv_store = kv_store
        self._prefix = PrefixCache(
            cache, self._allocator,
            spill=(self._spill_block if kv_store is not None else None)) \
            if prefix_cache else None
        self._copy = make_block_copy() if prefix_cache else None
        self._write = make_block_write() if kv_store is not None else None
        # tier-key scope: cached K/V is a function of the params, so a
        # weight change (hot_swap/rollout) switches fingerprints and can
        # never be served another set of weights' blocks
        self._params_fp = (params_fingerprint(self._params)
                           if kv_store is not None else "")
        # host→pool promotion writes queued at admission; each dst block
        # carries an extra allocator reference until the write lands in
        # _do_writes (so no eviction/teardown race can free it first)
        self._pending_writes: List[Tuple[int, Dict[str, Any]]] = []

        # simulated device-step floor: pad every scheduler iteration that
        # did device work up to this many seconds. 0.0 (the default) is a
        # no-op. Fleet tests on a single host set it so per-replica
        # capacity is bounded by the floor rather than by the one CPU the
        # replicas share — the same stand-in-for-hardware idiom as
        # loadgen's simulated agents (see docs/serving.md).
        self.iteration_floor_s = float(iteration_floor_s)

        m = self.registry
        self._h_queue_wait = m.histogram(
            "serving_queue_wait_seconds", "submit → admitted into the batch")
        self._h_prefill = m.histogram(
            "serving_prefill_seconds", "one bucketed prefill call")
        self._h_decode = m.histogram(
            "serving_decode_step_seconds", "one bucketed decode step")
        self._h_total = m.histogram(
            "serving_request_total_seconds", "submit → last token")
        self._c_admitted = m.counter(
            "serving_requests_admitted_total", "requests accepted into queue")
        self._c_rejected = m.counter(
            "serving_requests_rejected_total",
            "admission rejections (queue full → ServerOverloaded)")
        self._c_completed = m.counter(
            "serving_requests_completed_total", "requests fully generated")
        self._c_tokens = m.counter(
            "serving_tokens_generated_total", "decoded tokens (all requests)")
        self._g_active = m.gauge(
            "serving_active_sequences", "sequences in the running batch")
        self._g_queue = m.gauge(
            "serving_queue_depth", "requests waiting for admission")
        self._g_free_blocks = m.gauge(
            "serving_free_kv_blocks", "unallocated KV pool blocks")
        self._g_free_blocks.set(self._allocator.free_blocks())
        # a cache of several kinds: blocks in use, by kind
        kinds = self._layout.kinds if len(self._layout.kinds) > 1 else ()
        self._g_kind_blocks = [
            m.gauge("serving_kv_blocks_in_use",
                    "KV pool blocks held by running sequences, by kind",
                    labels={"kind": kind}) for kind in kinds]
        self._kind_blocks = [0] * len(kinds)
        self._row_args = self._layout.row_args
        self._c_rows = [
            m.counter(name, f"{arg} of the cache attended by decode steps "
                            f"(at the rows' real lengths)")
            for name, arg in zip(self._model.row_counters, self._row_args)]
        # what only the device knows of a call (PagedModel.step_counters)
        self._step_counters = tuple(self._model.step_counters)
        self._c_steps = [
            m.counter(f"serving_{name}_total",
                      f"{name}, as the programs counted them")
            for name in self._step_counters]
        self._c_prefix_hit = m.counter(
            "prefix_cache_hit_blocks_total",
            "prompt blocks aliased from the prefix cache (prefill skipped)")
        self._c_prefix_miss = m.counter(
            "prefix_cache_miss_blocks_total",
            "prompt blocks prefilled from scratch")
        self._c_spec_proposed = m.counter(
            "serving_spec_tokens_proposed_total",
            "draft tokens offered to the verify step")
        self._c_spec_accepted = m.counter(
            "serving_spec_tokens_accepted_total",
            "draft tokens the target model agreed with")
        self._g_spec_rate = m.gauge(
            "spec_acceptance_rate",
            "cumulative accepted/proposed draft-token ratio")
        self._h_spec_accept = m.histogram(
            "serving_spec_request_acceptance_rate",
            "per-request draft acceptance rate at retirement")
        self._c_overlapped = m.counter(
            "serving_decode_steps_overlapped_total",
            "decode steps dispatched before the last one was read back")
        self._c_overrun = m.counter(
            "serving_decode_overrun_rows_total",
            "rows of a decode step that had retired by its read-back (an "
            "eos seen a step late, an abort, a deadline): token dropped")
        self._c_expired = m.counter(
            "serving_requests_expired_total",
            "requests retired at their deadline (blocks freed, not decoded)")
        self._c_kv_host_hit = m.counter(
            "kv_tier_host_hit_blocks_total",
            "prompt blocks promoted from the host KV tier")
        self._c_kv_cas_hit = m.counter(
            "kv_tier_cas_hit_blocks_total",
            "prompt blocks promoted from the CAS KV tier")
        self._c_kv_miss = m.counter(
            "kv_tier_miss_blocks_total",
            "prompt blocks absent from every KV tier (prefilled fresh)")
        self._c_kv_promoted = m.counter(
            "kv_tier_promoted_blocks_total",
            "host→pool promotion writes landed (re-prefill avoided)")
        self._c_kv_spilled = m.counter(
            "kv_tier_spilled_blocks_total",
            "pool blocks demoted into the host tier instead of dropped")

        self._cond = threading.Condition()
        self._queue: collections.deque[_Handle] = collections.deque()
        self._active: List[_Active] = []
        self._prefilling: List[_Active] = []
        self._stop = False
        self._warming = False
        self._busy = False  # scheduler outside its wait with device work
        self._fatal: Optional[BaseException] = None
        # set by fail_inflight (the supervisor's condemn): the scheduler
        # raises it at the next iteration boundary so the crash teardown
        # — the only place that may release a possibly-mid-step row's
        # blocks — runs exactly once, on the owning thread
        self._condemned: Optional[BaseException] = None
        # scheduler-loop heartbeat watermark: stamped every pass, so a
        # *wedged* scheduler (alive but stuck mid-iteration) reads as
        # stale-beat-with-pending-work to the supervisor's liveness probe
        self._beat_t = time.monotonic()
        self._submitted = 0
        self._completed = 0
        self._total_tokens = 0
        self._peak_active = 0
        self._req_seq = 0
        self._thread = threading.Thread(target=self._run,
                                        name="serving-engine", daemon=True)
        self._thread.start()

    def _note_weight_bytes(self) -> None:
        by_dtype: Dict[str, int] = collections.Counter()
        for leaf in jax.tree.leaves(self._params):
            by_dtype[str(leaf.dtype)] += leaf.nbytes
        for dtype, nbytes in by_dtype.items():
            self.registry.gauge(
                "serving_weight_bytes",
                "bytes of the tree the engine serves from, by leaf type",
                labels={"dtype": dtype}).set(nbytes)

    @staticmethod
    def blocks_per_sequence(model_cfg: Any, block_size: int) -> int:
        """Pool blocks a sequence of the model's full length holds."""
        return model_cfg.paged_model().cache_layout(
            model_cfg, KVCacheConfig(1, block_size)
        ).blocks_needed(model_cfg.max_seq_len)

    @classmethod
    def from_serving_config(cls, params: Any, model_cfg: Any, scfg: Any, *,
                            telemetry: Any = None, fwd: Any = None,
                            iteration_floor_s: float = 0.0,
                            draft_params: Any = None
                            ) -> "InferenceEngine":
        """Build an engine from a config/experiment.py ServingConfig
        (the `serving:` block of an experiment YAML). When the
        ``speculative:`` block is enabled the draft model shares the
        tokenizer/vocab and max_seq_len with the target; its weights
        come from ``draft_params`` or, absent one (no distilled draft
        checkpoint yet), a seeded random init — correct but slow, since
        the accept rule never trusts the draft."""
        buckets = BucketSpec.build(
            scfg.max_batch, min(scfg.max_prefill_len, model_cfg.max_seq_len))
        blocks = scfg.kv_blocks or scfg.max_batch * cls.blocks_per_sequence(
            model_cfg, scfg.kv_block_size)
        spec = getattr(scfg, "speculative", None)
        spec_k = 0
        draft_cfg = None
        if spec is not None and spec.enabled:
            spec_k = spec.k
            draft_cfg = dataclasses.replace(
                model_cfg, n_layers=spec.draft_layers,
                d_model=spec.draft_d_model, n_heads=spec.draft_n_heads,
                d_ff=spec.draft_d_ff, remat=False)
            if draft_params is None:
                draft_params = model_cfg.paged_model().init(
                    jax.random.PRNGKey(0), draft_cfg)
        return cls(params, model_cfg, buckets=buckets,
                   cache=KVCacheConfig(num_blocks=blocks,
                                       block_size=scfg.kv_block_size),
                   max_queue_depth=scfg.max_queue_depth,
                   telemetry=telemetry, fwd=fwd,
                   iteration_floor_s=iteration_floor_s,
                   prefix_cache=getattr(scfg, "prefix_cache", False),
                   chunk_prefill_len=getattr(scfg, "chunk_prefill_len", 0),
                   speculative_k=spec_k, draft_params=draft_params,
                   draft_cfg=draft_cfg)

    # -- client surface ----------------------------------------------------

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    def close(self, timeout: float = 30.0) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)

    @staticmethod
    def _req_args(req: Request, **extra: Any) -> Dict[str, Any]:
        """Span args identifying one request (per-request tracing)."""
        args: Dict[str, Any] = {"request_id": req.request_id, **extra}
        if req.trace_id:
            args["trace_id"] = req.trace_id
        return args

    def attach_tracer(self, tracer: Any) -> None:
        """Late-bind (or detach, with None) the per-request event tracer.
        A plain attribute swap is atomic, so flipping it while the
        scheduler runs is safe."""
        self._tracer = (tracer if tracer is not None
                        and getattr(tracer, "enabled", False) else None)

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16, *,
               eos_token_id: Optional[int] = None,
               request_id: Optional[str] = None,
               trace_id: Optional[str] = None,
               deadline_t: Optional[float] = None) -> _Handle:
        """Enqueue one request. Raises ValueError for never-servable
        requests and ServerOverloaded when the queue is full.
        ``deadline_t`` is an absolute ``time.monotonic()`` deadline:
        work still unfinished then is retired as "expired" at the next
        iteration boundary and its KV blocks freed."""
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if not self.chunk_prefill_len \
                and len(prompt) > self.buckets.max_prefill_len:
            # chunked prefill lifts this limit: any prompt that fits the
            # model context is served chunk_prefill_len tokens at a time
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest prefill "
                f"bucket {self.buckets.max_prefill_len}")
        total = len(prompt) + max_new_tokens
        if total > self.model_cfg.max_seq_len:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds model "
                f"max_seq_len {self.model_cfg.max_seq_len}")
        with self._cond:
            if self._fatal is not None:
                # ReplicaFailed (a RuntimeError) so the router treats a
                # dead-but-not-yet-removed replica as a failover target,
                # not a client error; active=False — never admitted, so
                # no poison-pill strike
                raise ReplicaFailed("serving engine died",
                                    active=False) from self._fatal
            if self._stop:
                raise RuntimeError("serving engine is closed")
            if len(self._queue) >= self.max_queue_depth:
                self._c_rejected.inc()
                raise ServerOverloaded(
                    f"queue full ({self.max_queue_depth} waiting)")
            self._req_seq += 1
            rid = request_id or f"req-{self._req_seq}"
            handle = _Handle(Request(prompt, int(max_new_tokens),
                                     eos_token_id, rid, trace_id,
                                     deadline_t))
            handle.submit_t = time.monotonic()
            if not self._busy:
                # first work after an idle stretch: the parked scheduler's
                # beat is arbitrarily old — restart the liveness clock so
                # the supervisor grants it a fresh window to wake up in
                self._beat_t = handle.submit_t
            self._queue.append(handle)
            self._submitted += 1
            self._c_admitted.inc()
            self._g_queue.set(len(self._queue))
            self._cond.notify_all()
        return handle

    def submit_with_backoff(self, prompt: Sequence[int],
                            max_new_tokens: int = 16, *,
                            eos_token_id: Optional[int] = None,
                            request_id: Optional[str] = None,
                            trace_id: Optional[str] = None,
                            policy: RetryPolicy = ADMISSION_RETRY) -> _Handle:
        """submit() under the repo-standard retry/backoff policy: full-
        jitter exponential backoff on ServerOverloaded, re-raised on
        exhaustion. The client half of admission control."""
        return retry_call(self.submit, prompt, max_new_tokens,
                          eos_token_id=eos_token_id, request_id=request_id,
                          trace_id=trace_id, policy=policy)

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16, *,
                 eos_token_id: Optional[int] = None,
                 timeout: Optional[float] = 120.0) -> RequestResult:
        return self.submit(prompt, max_new_tokens,
                           eos_token_id=eos_token_id).result(timeout)

    def abort(self, handle: _Handle) -> bool:
        """Cancel one in-flight request (client disconnect). The
        scheduler retires it at the next iteration boundary — never
        mid-step — releasing its pool blocks exactly as a natural finish
        would (tests pin the allocator accounting). The handle resolves
        with whatever was generated so far and ``finish_reason ==
        "aborted"``. Returns False if the request already finished."""
        with self._cond:
            if handle.done():
                return False
            handle.cancelled = True
            self._cond.notify_all()
        return True

    # -- model hot-swap ----------------------------------------------------

    def hot_swap(self, params: Any) -> None:
        """Queue a new parameter pytree; the scheduler installs it at the
        next iteration boundary (never mid-step), so in-flight sequences
        finish under whichever params their next step sees — the standard
        online-swap semantics. Another family's tree (or another depth's)
        is refused: the engine's model config, pools and programs stay.
        The tree is brought into its serving form here, on the caller's
        thread (:func:`serving_form`); the scheduler only installs it."""
        if jax.tree.structure(params) != jax.tree.structure(self._params):
            raise ValueError(
                f"hot_swap across model families is not served: the "
                f"{self._model.family} engine was given a tree of another "
                f"structure")
        params = serving_form(params, self.model_cfg, self._span)
        with self._cond:
            self._pending_params = params
            self._cond.notify_all()

    def hot_load(self, storage: Any, storage_id: str, *,
                 base_tmp: Optional[str] = None,
                 ckpt_subdir: str = "") -> float:
        """Hot-load a checkpoint from a StorageManager (CAS-backed
        managers reuse their chunk cache, making repeat loads cheap) and
        swap it in. Returns the load wall-time in seconds."""
        from determined_clone_tpu.core._serialization import load_pytree

        t0 = time.monotonic()
        with self._span("serving_hot_load", storage_id=storage_id):
            with storage.restore_path(storage_id, base_tmp) as d:
                src = os.path.join(d, ckpt_subdir) if ckpt_subdir else d
                new_params = load_pytree(src, like=self._params)
        self.hot_swap(new_params)
        dt = time.monotonic() - t0
        self.registry.histogram(
            "serving_hot_load_seconds",
            "checkpoint fetch + deserialize + swap").observe(dt)
        return dt

    def warmup(self) -> int:
        """Pre-compile the FULL bucket ladder — one prefill program per
        (batch-bucket, length-bucket) plus one decode program per
        batch-bucket — so no request ever pays an XLA compile. A warm
        burst only covers the shapes the burst happens to hit; paced
        arrivals later trickle into the running batch one or two at a
        time and exercise the small batch-bucket prefills for the first
        time, stalling the whole scheduler behind a mid-traffic compile
        that can dwarf the actual work. Serving stacks precompile at
        startup for exactly this reason.

        The dummy rows hold no real token (``token_mask`` all False), so
        nothing is written to the KV pools — warmup is invisible to
        every later request (the COW copy program is warmed by copying
        block 0 onto itself: bit-identical values). Requires an idle
        engine; the scheduler is parked for the duration (racing submits
        queue up and are served once warmup finishes). Returns
        :meth:`programs_compiled`, which now equals
        :meth:`program_budget` — the full ladder includes the draft
        model's mirror ladder, the k+1-token verify program per batch
        bucket, and the COW copy when those features are on.
        """
        with self._cond:
            self._await_idle_locked("warmup")
            self._warming = True
        t0 = time.monotonic()

        try:
            with self._span("serving_warmup"):
                lanes = [(self._fwd, self._params, self.model_cfg)]
                if self._spec_k:
                    lanes.append((self._draft_fwd, self._draft_params,
                                  self.draft_cfg))
                for b in self.buckets.batch_buckets:
                    tables = np.zeros((b, self._table_width), np.int32)
                    for fwd, params, cfg in lanes:
                        for t in (*self.buckets.prefill_len_buckets, 1):
                            held = self._pools_for(cfg)
                            tokens, *pools = fwd(
                                params, cfg, self._blank_rows(b, t), tables,
                                self._no_tokens, *held)
                            self._set_pools_for(cfg, pools[:len(held)])
                            tokens.block_until_ready()
                    if self._spec_k:
                        t = self._spec_k + 1
                        logits, *pools = self._verify_fwd(
                            self._params, self.model_cfg,
                            jnp.zeros((b, t), jnp.int32),
                            jnp.zeros((b, t), jnp.int32),
                            jnp.zeros((b, t), bool),
                            *self._pools, tables)
                        self._pools = tuple(pools)
                        # the verify step samples in a (tiny) program of
                        # its own per batch bucket: leave it cold and the
                        # first real request pays its compile
                        jnp.argmax(logits, axis=-1).block_until_ready()
                if self._copy is not None:
                    self._pools = self._copy(*self._pools, 0, 0)
                    if self._spec_k:
                        self._draft_pools = self._copy(
                            *self._draft_pools, 0, 0)
                    jax.block_until_ready(self._pools)
                if self._write is not None:
                    # warmed by writing block 0's own contents back:
                    # materialize the slice BEFORE the donated call, so
                    # the write is bit-identical (all zeros at warmup)
                    blk = [jnp.array(p[:, 0]) for p in self._pools]
                    self._pools = self._write(*self._pools, 0, *blk)
                    if self._spec_k:
                        blk = [jnp.array(p[:, 0]) for p in self._draft_pools]
                        self._draft_pools = self._write(
                            *self._draft_pools, 0, *blk)
                    jax.block_until_ready(self._pools)
        finally:
            with self._cond:
                self._warming = False
                self._cond.notify_all()
        self.registry.histogram(
            "serving_warmup_seconds",
            "full bucket-ladder precompile at startup"
        ).observe(time.monotonic() - t0)
        return self.programs_compiled()

    def _await_idle_locked(self, what: str) -> None:
        """Under ``self._cond``: refuse if traffic is queued or running,
        and wait out the scheduler's in-flight device call (queue and
        active both look empty while a prefill is on the device — the
        ``_busy`` flag covers that window, or donated pools would be
        used from two threads at once)."""
        if self._stop:
            raise RuntimeError("serving engine is closed")
        if self._fatal is not None:
            raise RuntimeError("serving engine died") from self._fatal
        if self._queue or self._active or self._prefilling:
            raise RuntimeError(f"{what} requires an idle engine")
        while self._busy and not self._stop and self._fatal is None:
            self._cond.wait()
        if self._stop:
            raise RuntimeError("serving engine is closed")
        if self._fatal is not None:
            raise RuntimeError("serving engine died") from self._fatal
        if self._queue or self._active or self._prefilling:
            raise RuntimeError(f"{what} requires an idle engine")

    def wait_idle(self, timeout: float = 60.0) -> None:
        """Block until nothing is queued, nothing is active, and the
        scheduler's in-flight device call (the ``_busy`` window) has
        finished — i.e. every request accepted so far has fully
        completed. This is the engine half of the fleet drain protocol:
        the caller stops routing new work here first, then waits out the
        in-flight decodes before swapping params or releasing the
        replica's slots. Raises TimeoutError if traffic never quiesces.
        """
        deadline = time.monotonic() + timeout
        with self._cond:
            while (self._queue or self._active or self._prefilling
                   or self._busy):
                if self._fatal is not None:
                    raise RuntimeError(
                        "serving engine died") from self._fatal
                if self._stop:
                    raise RuntimeError("serving engine is closed")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"engine not idle after {timeout}s "
                        f"(queue={len(self._queue)} "
                        f"active={len(self._active)} "
                        f"prefilling={len(self._prefilling)})")
                self._cond.wait(remaining)

    # -- self-healing surface (fleet supervisor) ---------------------------

    def liveness(self) -> Dict[str, Any]:
        """Snapshot for the supervisor's liveness probe. The wedged
        verdict is the caller's: ``pending and beat_age_s > deadline``
        means the scheduler has had work for that long without
        completing a pass — stale-beat-while-idle is just a parked
        thread and perfectly healthy."""
        now = time.monotonic()
        with self._cond:
            return {
                "thread_alive": self._thread.is_alive(),
                "fatal": self._fatal,
                "condemned": self._condemned is not None,
                "warming": self._warming,
                "pending": bool(self._queue or self._active
                                or self._prefilling or self._busy),
                "beat_age_s": now - self._beat_t,
            }

    def fail_inflight(self, reason: str) -> int:
        """Condemn this engine: immediately fail every queued and
        running request with :class:`ReplicaFailed` (so front-door
        waiters requeue to surviving replicas without waiting out a
        wedged thread) and mark the scheduler to tear itself down at its
        next wakeup. Blocks are NOT released here — the scheduler thread
        may still be mid-device-call against the pools; it releases them
        exactly once in its own crash teardown. Returns the number of
        requests newly failed."""
        condemned = ReplicaFailed(f"replica condemned: {reason}",
                                  active=True)
        with self._cond:
            if self._fatal is None:
                self._fatal = condemned
            if self._condemned is None:
                self._condemned = condemned
            queued = list(self._queue)
            self._queue.clear()
            self._g_queue.set(0)
            inflight = [a.handle
                        for a in self._active + self._prefilling]
            self._cond.notify_all()
        n = 0
        orphaned = ReplicaFailed(f"replica condemned: {reason}",
                                 active=False)
        for h in queued:
            n += 1 if h._fail(orphaned) else 0
        for h in inflight:
            n += 1 if h._fail(condemned) else 0
        return n

    def kv_outstanding(self) -> int:
        """KV blocks and state slots currently owned (active sequences +
        prefix-cache retains). Zero on an idle engine with no prefix
        cache."""
        return self._allocator.outstanding()

    def assert_kv_balanced(self, expected_outstanding: int = 0) -> None:
        """Chaos/test audit: raise AssertionError unless exactly
        ``expected_outstanding`` blocks are held (see
        :meth:`BlockAllocator.assert_balanced`)."""
        self._allocator.assert_balanced(expected_outstanding)

    # -- introspection -----------------------------------------------------

    def programs_compiled(self) -> int:
        """XLA programs across ALL the engine's jitted entry points —
        shared forward, draft forward, k+1-token verify, COW copy (the
        PR 2 retrace probe). The tier-1 compile-discipline test asserts
        this never exceeds :meth:`program_budget`."""
        total = 0
        seen = []
        for f in (self._fwd, self._draft_fwd, self._verify_fwd,
                  self._copy, self._write):
            if f is None:
                continue
            # jax keys the jit cache on the underlying function: _fwd
            # and _draft_fwd both wrap forward_paged, so they SHARE
            # one cache (that is what lets the draft ladder ride the
            # fleet-shared forward) — count each distinct cache once or
            # the draft programs get double-counted
            wrapped = getattr(f, "__wrapped__", f)
            if any(wrapped is w for w in seen):
                continue
            seen.append(wrapped)
            probe = getattr(f, "_cache_size", None)
            if not callable(probe):
                return -1
            total += int(probe())
        return total

    def program_budget(self) -> int:
        """Worst-case :meth:`programs_compiled` for the feature set this
        engine was built with; :meth:`warmup` compiles exactly this many."""
        return self.buckets.extended_budget(
            speculative=self._spec_k > 0,
            prefix_cache=self._prefix is not None,
            kv_store=self._kv_store is not None)

    def stats(self) -> EngineStats:
        with self._cond:
            proposed = int(self._c_spec_proposed.value)
            accepted = int(self._c_spec_accepted.value)
            return EngineStats(
                submitted=self._submitted,
                rejected=int(self._c_rejected.value),
                completed=self._completed,
                tokens_generated=self._total_tokens,
                peak_active=self._peak_active,
                queue_depth=len(self._queue),
                free_blocks=self._allocator.free_blocks(),
                programs_compiled=self.programs_compiled(),
                program_budget=self.program_budget(),
                prefix_hit_blocks=int(self._c_prefix_hit.value),
                prefix_miss_blocks=int(self._c_prefix_miss.value),
                prefix_cached_entries=(len(self._prefix)
                                       if self._prefix is not None else 0),
                spec_tokens_proposed=proposed,
                spec_tokens_accepted=accepted,
                spec_acceptance_rate=(accepted / proposed
                                      if proposed else None),
                kv_host_hit_blocks=int(self._c_kv_host_hit.value),
                kv_cas_hit_blocks=int(self._c_kv_cas_hit.value),
                kv_miss_blocks=int(self._c_kv_miss.value),
                kv_promoted_blocks=int(self._c_kv_promoted.value),
                kv_spilled_blocks=int(self._c_kv_spilled.value))

    # -- scheduler ---------------------------------------------------------

    def _run(self) -> None:
        try:
            while True:
                with self._cond:
                    self._busy = False
                    self._beat_t = time.monotonic()
                    self._cond.notify_all()  # wakes warmup's idle wait
                    while (not self._stop and self._condemned is None
                           and (self._warming
                                or (not self._queue and not self._active
                                    and not self._prefilling
                                    and self._flight is None
                                    and self._pending_params is None))):
                        self._cond.wait()
                    if self._stop:
                        closed = RuntimeError("serving engine closed")
                        for h, _was_active in self._teardown_locked():
                            h._fail(closed)
                        return
                    if self._condemned is not None:
                        raise self._condemned
                    if self._pending_params is not None:
                        self._params = self._pending_params
                        self._pending_params = None
                        self._note_weight_bytes()
                        # cached KV is a function of the params
                        if self._prefix is not None:
                            self._prefix.flush()
                            self._g_free_blocks.set(
                                self._allocator.free_blocks())
                        if self._kv_store is not None:
                            # new weights, new tier scope: old-params
                            # blocks stay fetchable under the old
                            # fingerprint (rollback warms), never here
                            self._params_fp = params_fingerprint(
                                self._params)
                    # spans the admission alone, never the wait above
                    with self._span("admit"):
                        admitted = self._admit_locked()
                    self._busy = True
                # fault points fire OUTSIDE the condition (a delay rule
                # must wedge only this scheduler, never a lock every
                # client thread needs), and only with a plan active
                if faults.active_plan() is not None:
                    for rid in admitted:
                        faults.point("engine.admit")
                        faults.point("engine.admit." + rid)
                    if self._pending_writes:
                        faults.point("kv_store.promote")
                    faults.point("engine.step")
                    if self._fault_scope:
                        faults.point("engine.step." + self._fault_scope)
                iter_t0 = time.monotonic()
                with self._span("engine_iteration"):
                    worked = self._reap_expired()
                    if self._pending_writes:
                        self._do_writes()
                        worked = True
                    # a prefill call goes out first (a first token does
                    # not wait behind this turn's decode step) and is
                    # read back last: the decode step is dispatched
                    # while the device runs it
                    prefill = None
                    if self._prefilling:
                        prefill = self._prefill_dispatch()
                        worked = True
                    if self._active and self._spec_k:
                        self._spec_step()
                        worked = True
                    elif self._active or self._flight is not None:
                        self._decode_step()
                        worked = True
                    if prefill is not None:
                        self._prefill_settle(prefill)
                    self._beat_t = time.monotonic()
                if worked and self.iteration_floor_s > 0.0:
                    pad = self.iteration_floor_s \
                        - (time.monotonic() - iter_t0)
                    if pad > 0.0:
                        time.sleep(pad)
        except BaseException as exc:  # noqa: BLE001 — fail every waiter
            queued = ReplicaFailed(f"serving engine died: {exc!r}",
                                   active=False)
            queued.__cause__ = exc
            running = ReplicaFailed(f"serving engine died: {exc!r}",
                                    active=True)
            running.__cause__ = exc
            with self._cond:
                if self._fatal is None:
                    self._fatal = exc
                self._busy = False
                handles = self._teardown_locked()
                self._cond.notify_all()
            # settle outside the condition: nothing here needs it, and
            # the waiters woken by these events immediately requeue
            for h, was_active in handles:
                h._fail(running if was_active else queued)

    def _teardown_locked(self):
        """Under ``self._cond``: the abnormal-retirement path. Releases
        every in-flight row's pool blocks (including pending COW source
        references) and the prefix cache's retains, clears the batch,
        and returns the handles to fail. Run only on the scheduler
        thread — it is the sole owner of the rows, so nothing can race
        the releases — and exactly once per row, keeping the allocator
        balanced (``assert_balanced``) through any crash or close.

        Returns ``(handle, was_active)`` pairs: the crash path needs to
        tell running rows (poison-pill strike candidates) from queued
        orphans; the stop path ignores the flag.
        """
        pairs = [(h, False) for h in self._queue]
        self._queue.clear()
        for block, _payload in self._pending_writes:
            self._allocator.release([block])
        self._pending_writes.clear()
        for a in self._active + self._prefilling:
            if a.pending_copy is not None:
                self._allocator.release([a.pending_copy[0]])
                a.pending_copy = None
            self._allocator.release(a.blocks)
            self._gauge_kinds(a.by_kind, -1)
            pairs.append((a.handle, True))
        self._active.clear()
        self._prefilling.clear()
        self._flight = None  # its rows' blocks went with the rows above
        if self._prefix is not None:
            self._prefix.flush()
        self._g_active.set(0)
        self._g_queue.set(0)
        self._g_free_blocks.set(self._allocator.free_blocks())
        return pairs

    def _admit_locked(self) -> List[str]:
        """Move queued requests into the prefilling set while slots AND
        pool blocks allow. FIFO — a head-of-line request the pool can't
        fit yet blocks later ones (no starvation by bypass). With the
        prefix cache on, each admission first aliases the longest
        resident prefix (retaining those blocks) and only allocates
        fresh blocks for the remainder; under pool pressure LRU cache
        entries are evicted (dropping the cache's references — blocks
        shared with running sequences survive) before admission defers.
        Returns the admitted request ids (the scheduler hits their
        admission fault points outside the lock).
        """
        now = time.monotonic()
        admitted: List[str] = []
        while self._queue and (len(self._active) + len(self._prefilling)
                               < self.buckets.max_batch):
            head = self._queue[0]
            if head.cancelled or (head.req.deadline_t is not None
                                  and now >= head.req.deadline_t):
                expired = not head.cancelled
                if expired:
                    self._c_expired.inc()
                self._queue.popleft()
                head._finish(RequestResult(
                    request_id=head.req.request_id,
                    prompt_len=len(head.req.prompt), tokens=[],
                    finish_reason="expired" if expired else "aborted",
                    queue_wait_s=0.0,
                    prefill_s=0.0, decode_s=0.0,
                    total_s=now - head.submit_t))
                continue
            plen = len(head.req.prompt)
            total = plen + head.req.max_new_tokens
            by_kind = self._layout.blocks_by_kind(total)
            n_slots = self._layout.state_slots
            need_total = sum(by_kind) - n_slots
            shared: List[int] = []
            fork_src: Optional[int] = None
            if self._prefix is not None:
                if self._kv_store is not None:
                    # warm the prefix cache from the lower tiers first,
                    # so the ordinary match below aliases promoted
                    # blocks exactly like always-resident ones
                    self._promote_locked(head.req.prompt)
                match = self._prefix.match(head.req.prompt)
                # always leave >= 1 prompt token to process: the last
                # prompt token is re-scored through the model to produce
                # the first sampled token (its K/V rewrite is what the
                # COW fork isolates from the shared block)
                skip = min(match.shared_len, plen - 1)
                shared = match.blocks
                if skip < match.shared_len:
                    # fully-shared prompt: the final shared block holds
                    # position plen-1 and WILL be written — fork it
                    fork_src = shared.pop()
                kept = len(shared)
                need = need_total - kept
            else:
                skip = 0
                kept = 0
                need = need_total
            if (self._allocator.free_blocks() < need
                    or self._allocator.free_slots() < n_slots):
                if self._prefix is not None:
                    self._prefix.evict(need)
                if (self._allocator.free_blocks() < need
                        or self._allocator.free_slots() < n_slots):
                    # defer admission; hand back the match references
                    if shared:
                        self._allocator.release(shared)
                    if fork_src is not None:
                        self._allocator.release([fork_src])
                    break
            self._queue.popleft()
            head.admit_t = now
            if self._tracer is not None:
                self._h_queue_wait.observe(now - head.submit_t,
                                           exemplar=head.req.request_id)
                self._tracer.instant(
                    "request_admitted", **self._req_args(
                        head.req,
                        queue_wait_s=round(now - head.submit_t, 6),
                        prompt_len=plen))
            else:
                self._h_queue_wait.observe(now - head.submit_t)
            fresh = self._allocator.allocate_blocks(need)
            # the slots' ids come last, as the layout lays its table
            blocks = shared + fresh + self._allocator.allocate_slots(n_slots)
            table = np.zeros((self._table_width,), np.int32)
            self._layout.lay_table(table, blocks)
            a = _Active(head, blocks, table, plen)
            a.by_kind = by_kind
            self._gauge_kinds(by_kind, +1)
            a.prefill_pos = skip
            if fork_src is not None:
                # fresh[0] backs the forked block's position range
                a.pending_copy = (fork_src, fresh[0])
            a.hit_blocks = kept + (1 if fork_src is not None else 0)
            a.miss_blocks = self.cache.blocks_needed(plen) - a.hit_blocks
            self._c_prefix_hit.inc(a.hit_blocks)
            self._c_prefix_miss.inc(a.miss_blocks)
            self._prefilling.append(a)
            admitted.append(head.req.request_id)
            self._peak_active = max(
                self._peak_active,
                len(self._active) + len(self._prefilling))
            self._g_active.set(len(self._active) + len(self._prefilling))
        self._g_queue.set(len(self._queue))
        self._g_free_blocks.set(self._allocator.free_blocks())
        return admitted

    # -- KV memory hierarchy (serving/kv_store.py) -------------------------

    def _payload_ok(self, payload: Dict[str, Any]) -> bool:
        """A tier payload is adoptable iff its arrays exactly match the
        pool slot shape/dtype (a config change or foreign entry must be
        a plain miss, never a bad scatter) and cover the draft pools
        when speculation is on."""
        want = list(zip(("k", "v"), self._pools))
        if self._spec_k:
            want += list(zip(("dk", "dv"), self._draft_pools))
        for name, pool in want:
            arr = payload.get(name) if isinstance(payload, dict) else None
            if arr is None:
                return False
            slot = pool.shape[:1] + pool.shape[2:]
            if (tuple(getattr(arr, "shape", ())) != tuple(slot)
                    or str(getattr(arr, "dtype", "")) != str(pool.dtype)):
                return False
        return True

    def _promote_locked(self, prompt: Tuple[int, ...]) -> None:
        """Under ``self._cond``: warm the prefix cache from the
        host/CAS tiers before matching one prompt. Walks the prompt's
        full blocks in chain order; for each key not already resident,
        fetches the exact payload, allocates a pool block, indexes it
        (the cache adopts the allocator reference) and queues the
        host→pool write — which lands in :meth:`_do_writes` before any
        admitted row's first forward, so a matched row always reads the
        promoted bytes. Chain continuity: the first miss ends the walk
        — a later hit would alias a block whose predecessors are
        absent. Tail blocks never promote (they never spilled)."""
        bs = self.cache.block_size
        prev = b""
        for i in range(len(prompt) // bs):
            key = PrefixCache._chain(prev, prompt[i * bs:(i + 1) * bs])
            prev = key
            if self._prefix.has_key(key):
                continue
            key_hex = key.hex()
            from_host = self._kv_store.contains(self._params_fp, key_hex)
            payload = self._kv_store.get(self._params_fp, key_hex)
            if payload is None or not self._payload_ok(payload):
                self._c_kv_miss.inc()
                break
            if self._allocator.free_blocks() < 1:
                self._prefix.evict(1)
                if self._allocator.free_blocks() < 1:
                    break
            block = self._allocator.allocate_blocks(1)[0]
            self._prefix.adopt(key, block, i)
            # extra reference pins the dst until the write lands — no
            # eviction or teardown between queue and write may free it
            self._allocator.retain([block])
            self._pending_writes.append((block, payload))
            (self._c_kv_host_hit if from_host
             else self._c_kv_cas_hit).inc()

    def _do_writes(self) -> None:
        """Land queued promotion writes before any prefill or decode
        touches the pools — a matched row's first forward must read the
        promoted bytes, not zeros. Drops each dst block's pinning
        reference once its scatter lands."""
        writes, self._pending_writes = self._pending_writes, []
        for block, payload in writes:
            self._pools = self._write(
                *self._pools, block,
                jnp.asarray(payload["k"]), jnp.asarray(payload["v"]))
            if self._spec_k:
                self._draft_pools = self._write(
                    *self._draft_pools, block,
                    jnp.asarray(payload["dk"]), jnp.asarray(payload["dv"]))
            self._allocator.release([block])
            self._c_kv_promoted.inc()

    def _spill_block(self, key: bytes, block: int, depth: int) -> bool:
        """PrefixCache demotion hook: capture one full block's exact
        K/V into the host tier. Runs on the scheduler thread while the
        cache still holds the block's reference, so the pool contents
        are intact and no donated call is in flight. Never raises — a
        failed spill just means the block is gone, as before the tier
        existed."""
        try:
            payload = {"k": np.asarray(self._pools[0][:, block]),
                       "v": np.asarray(self._pools[1][:, block])}
            if self._spec_k:
                payload["dk"] = np.asarray(self._draft_pools[0][:, block])
                payload["dv"] = np.asarray(self._draft_pools[1][:, block])
            self._kv_store.put(self._params_fp, key.hex(), payload)
        except Exception:  # noqa: BLE001 — demotion is best-effort
            return False
        self._c_kv_spilled.inc()
        return True

    def flush_kv_to_tier(self) -> int:
        """Demote every full-block prefix-cache entry into the
        host/CAS tiers, so a teardown (rollout, replace, stop)
        preserves the fleet's warm state instead of dropping it.
        Requires an idle engine (the fleet calls this after its drain;
        a dead or wedged engine raises, and the fleet degrades to a
        cold teardown). Entries stay resident afterwards — the tier
        holds copies; the usual flush/teardown still releases the
        blocks. Returns blocks spilled."""
        if self._prefix is None or self._kv_store is None:
            return 0
        n = 0
        with self._cond:
            self._await_idle_locked("flush_kv_to_tier")
            for key, block, depth in self._prefix.entries():
                if self._spill_block(key, block, depth):
                    n += 1
        return n

    def prefix_inventory(self) -> Optional[Dict[str, Any]]:
        """Router-facing digest of the chain keys this replica can
        serve cheaply: resident prefix-cache entries (roots first —
        a missed root zeroes coverage, so roots deserve the exact
        top-K slots) followed by this fingerprint's host-tier keys.
        None when the prefix cache is off."""
        if self._prefix is None:
            return None
        # the scheduler thread may be registering entries concurrently
        # (dict iteration can raise RuntimeError mid-insert) — retry a
        # couple of times, then serve an empty digest; the inventory is
        # a routing hint, never correctness
        for _ in range(3):
            try:
                resident = sorted(self._prefix.entries(),
                                  key=lambda e: e[2])
                break
            except RuntimeError:
                continue
        else:
            resident = []
        keys = [k.hex() for k, _block, _depth in resident]
        if self._kv_store is not None:
            seen = set(keys)
            keys += [k for k in self._kv_store.keys(self._params_fp)
                     if k not in seen]
        return PrefixInventory.build(keys).to_dict()

    def _reap_expired(self) -> bool:
        """Retire cancelled and deadline-expired rows at the iteration
        boundary, releasing their blocks (and a pending COW source's
        extra reference) exactly like a natural finish — expired work is
        aborted, never decoded into the void."""
        now = time.monotonic()
        doomed: List[Tuple[_Active, str]] = []
        for a in self._active + self._prefilling:
            if a.handle.cancelled:
                doomed.append((a, "aborted"))
            elif (a.handle.req.deadline_t is not None
                  and now >= a.handle.req.deadline_t):
                self._c_expired.inc()
                doomed.append((a, "expired"))
        if not doomed:
            return False
        dead = {id(a) for a, _r in doomed}
        for a, reason in doomed:
            if a.pending_copy is not None:
                self._allocator.release([a.pending_copy[0]])
                a.pending_copy = None
            self._retire(a, reason)
        with self._cond:
            self._active = [a for a in self._active if id(a) not in dead]
            self._prefilling = [a for a in self._prefilling
                                if id(a) not in dead]
            self._g_active.set(len(self._active) + len(self._prefilling))
            self._g_free_blocks.set(self._allocator.free_blocks())
        return True

    def _do_copies(self, rows: Sequence[_Active]) -> None:
        """Execute pending COW forks before the rows' first device call,
        then drop the extra reference that kept each source alive."""
        for a in rows:
            if a.pending_copy is None:
                continue
            src, dst = a.pending_copy
            self._pools = self._copy(*self._pools, src, dst)
            if self._spec_k:
                self._draft_pools = self._copy(*self._draft_pools, src, dst)
            self._allocator.release([src])
            a.pending_copy = None
            if self._tracer is not None:
                self._tracer.instant(
                    "request_cow_fork", **self._req_args(
                        a.handle.req, src_block=src, dst_block=dst))

    def _gauge_kinds(self, by_kind: Sequence[int], sign: int) -> None:
        """Blocks in use by kind; nothing for a cache of one kind."""
        for i, g in enumerate(self._g_kind_blocks):
            self._kind_blocks[i] += sign * by_kind[i]
            g.set(self._kind_blocks[i])

    def _keep_pools(self, out: Sequence[Any]) -> Tuple[Any, ...]:
        """Keep the pools a target program handed back; what it returned
        after them (``PagedModel.step_counters``: one device vector;
        ``token_records``: one device array) is the caller's to read."""
        n = len(self._pools)
        self._pools = tuple(out[:n])
        return tuple(out[n:])

    def _read_back(self, tokens: Any, extras: Tuple[Any, ...]
                   ) -> Tuple[np.ndarray, Dict[str, int],
                              Optional[np.ndarray]]:
        """The sampled tokens and what the program returned after its
        pools, in one transfer: the tokens, the program's counts by name
        (added to their counters) and its records of the call's tokens."""
        if not extras:
            return np.asarray(tokens), {}, None
        tokens, *extras = jax.device_get((tokens, *extras))
        counts = {name: int(n) for name, n in zip(
            self._step_counters, extras[0] if self._step_counters else ())}
        for counter, n in zip(self._c_steps, counts.values()):
            counter.inc(n)
        return tokens, counts, \
            extras[-1] if self._model.token_records else None

    def _pools_for(self, cfg: Any) -> Tuple[Any, ...]:
        return self._pools if cfg is self.model_cfg else self._draft_pools

    def _set_pools_for(self, cfg: Any, pools: Sequence[Any]) -> None:
        if cfg is self.model_cfg:
            self._pools = tuple(pools)
        else:
            self._draft_pools = tuple(pools)

    @staticmethod
    def _blank_rows(padded_b: int, t: int) -> np.ndarray:
        """``forward_paged``'s ``rows`` for a call of ``t`` tokens a row,
        every row still padding: no token, none real, none from the
        device."""
        rows = np.zeros((padded_b, t + 3), np.int32)
        rows[:, -1] = -1
        return rows

    def _tables_for(self, rows: Sequence[_Active], padded_b: int
                    ) -> np.ndarray:
        """The call's block table: each row's own line (laid at
        admission), zeros for the padding rows. Handed to the program as
        it is: the call transfers it."""
        tables = np.zeros((padded_b, self._table_width), np.int32)
        tables[:len(rows)] = [a.table for a in rows]
        return tables

    def _prefill_dispatch(self) -> _PrefillCall:
        """Dispatch one bucketed prefill call covering every prefilling
        row's next slice of prompt. Without chunking a row's slice is its
        whole remaining prompt (one call, as before); with chunking each
        row advances at most ``chunk_prefill_len`` positions per
        iteration, so a decode step never waits behind a long prompt.
        Nothing is read here: the turn's decode step is dispatched next,
        behind this call on the device, and :meth:`_prefill_settle` reads
        the call's first tokens after it. The rows are in no decode step
        meanwhile, so nothing else touches their blocks.
        """
        rows = list(self._prefilling)
        with self._span("prefill_prepare", rows=len(rows)):
            self._do_copies(rows)
            cnt = []
            for a in rows:
                remaining = a.prompt_len - a.prefill_pos
                if self.chunk_prefill_len:
                    remaining = min(remaining, self.chunk_prefill_len)
                cnt.append(remaining)
            b = bucket_for(len(rows), self.buckets.batch_buckets)
            t = bucket_for(max(cnt), self.buckets.prefill_len_buckets)
            # a row: its slice's tokens, where it starts, its length, and
            # no token from the device (forward_paged)
            packed = self._blank_rows(b, t)
            for i, a in enumerate(rows):
                lo, n = a.prefill_pos, cnt[i]
                packed[i, :n] = a.handle.req.prompt[lo:lo + n]
                packed[i, t:t + 2] = lo, n
            tables = self._tables_for(rows, b)
            reckoned = {}
            if self._tracer is not None and self._model.prefill_counts:
                pad = [0] * (b - len(rows))
                reckoned = self._model.prefill_counts(
                    self.model_cfg, self._layout,
                    [a.prefill_pos for a in rows] + pad, cnt + pad, t)
        t0 = time.monotonic()
        pt0 = time.perf_counter() if self._tracer is not None else 0.0
        with self._span("prefill_dispatch", batch=b, length=t):
            tokens, *pools = self._fwd(
                self._params, self.model_cfg, packed, tables,
                self._no_tokens, *self._pools)
            extras = self._keep_pools(pools)
            mirrored = None
            if self._spec_k:
                # mirror the slice into the draft pools so the proposal
                # loop sees the same context the target does
                mirrored, *pools = self._draft_fwd(
                    self._draft_params, self.draft_cfg, packed, tables,
                    self._no_tokens, *self._draft_pools)
                self._draft_pools = tuple(pools)
            for out in (tokens, *extras):
                out.copy_to_host_async()
        return _PrefillCall(rows, cnt, b, t, tokens, extras, mirrored, t0,
                            pt0, reckoned)

    def _prefill_settle(self, call: _PrefillCall) -> None:
        """Read back a prefill call (``serving_prefill``: the wait for the
        device and the one transfer) and commit it. Rows whose slice
        reached the end of the prompt take their first token from the
        slice's last logits and graduate to the decode set, which the next
        turn's step picks up; prefix-cache rows started at ``prefill_pos >
        0`` and their completed prompts are registered for future sharing.
        """
        rows, cnt = call.rows, call.counts
        with self._span("serving_prefill", batch=call.batch,
                        length=call.length, tokens=sum(cnt),
                        **call.reckoned) as prefill:
            if call.mirrored is not None:
                call.mirrored.block_until_ready()
            first, counted, records = self._read_back(call.tokens,
                                                      call.extras)
            prefill.set(**counted)
        dt = time.monotonic() - call.t0
        self._h_prefill.observe(dt)
        if self._tracer is not None:
            for i, a in enumerate(rows):
                self._tracer.record_span(
                    "request_prefill_chunk", call.pt0, dt, **self._req_args(
                        a.handle.req, pos=a.prefill_pos, tokens=cnt[i]))
        done_t = time.monotonic()
        still_prefilling: List[_Active] = []
        graduated: List[_Active] = []
        for i, a in enumerate(rows):
            a.handle.prefill_s += dt
            a.prefill_pos += cnt[i]
            if records is not None:
                a.records.append(records[i, :cnt[i]].copy())
            if a.prefill_pos < a.prompt_len:
                still_prefilling.append(a)
                continue
            a.handle.prefill_done_t = done_t
            if self._prefix is not None:
                self._prefix.register(
                    a.handle.req.prompt,
                    a.blocks[:self.cache.blocks_needed(a.prompt_len)])
            a.last_token = int(first[i])
            a.out.append(a.last_token)
            if not self._maybe_finish(a):
                graduated.append(a)
        with self._cond:
            self._prefilling = still_prefilling
            self._active.extend(graduated)
            self._g_active.set(len(self._active) + len(self._prefilling))
            self._g_free_blocks.set(self._allocator.free_blocks())

    def _decode_step(self) -> None:
        """One turn of the decode pipeline, which runs one step deep:
        dispatch a step for every active sequence that still wants a token,
        THEN read back and commit the step dispatched a turn ago, so the
        host's turn runs while the device computes. The step takes each
        row's input token from the device (``last_tokens[src]``: the host
        has not read it yet) or, for a row that was in no such step, from
        the host. Everything else a step needs is the host's own
        arithmetic; a finish by length is known a step ahead (such a row
        is not dispatched again), a finish by ``eos`` a step late (its
        extra step writes inside its own reservation and its token is
        dropped). With no row to dispatch (every active row's last token is
        in flight), the turn only reads that step: a drain, after which the
        next step takes every token from the host."""
        before = self._flight
        rows: List[_Active] = []
        lengths: List[int] = []  # each row's context at this step
        for a in self._active:
            # a row with a token in flight is one token further than ``out``
            n = len(a.out) + (a.flight is not None)
            if n < a.handle.req.max_new_tokens:
                rows.append(a)
                lengths.append(a.prompt_len + n)
        if not rows:
            self._flight = None
            if before is not None:
                self._settle(before)
            return
        b = bucket_for(len(rows), self.buckets.batch_buckets)
        # the host's phases of the step, each a span with the same args
        # (docs/observability.md "An engine iteration")
        size = {"batch": b, "rows": len(rows)}
        # cache rows the step reads, at the rows' real lengths
        attended = self._layout.step_rows(lengths, b)
        size.update(zip(self._row_args, attended))
        with self._span("serving_decode_step", **size,
                        overlapped=int(before is not None)):
            with self._span("decode_prepare", **size):
                # a row: its token as the host knows it, its position, one
                # real token, and where the device holds its token
                packed = self._blank_rows(b, 1)
                packed[:len(rows)] = [
                    (a.last_token, n - 1, 1,
                     a.slot if a.flight is not None else -1)
                    for a, n in zip(rows, lengths)]
                tables = self._tables_for(rows, b)
            with self._span("decode_dispatch", **size):
                t0 = time.monotonic()
                tokens, *pools = self._fwd(
                    self._params, self.model_cfg, packed, tables,
                    self._no_tokens if before is None else before.tokens,
                    *self._pools)
                extras = self._keep_pools(pools)
                for out in (tokens, *extras):
                    out.copy_to_host_async()
                self._flight = _Flight(rows, size, attended, tokens, extras,
                                       time.monotonic() - t0)
                for i, a in enumerate(rows):
                    a.flight, a.slot = self._flight, i
            if before is not None:
                self._c_overlapped.inc()
                self._settle(before)

    def _settle(self, flight: _Flight) -> None:
        """Read back one dispatched step (tokens, the program's counts and
        records, in one transfer) and commit it: append each row's token,
        retire the rows that finish. A row retired since the dispatch (an
        ``eos`` in the step before, an abort, a deadline) drops its token."""
        t0 = time.monotonic()
        with self._span("decode_readback", **flight.size):
            nxt, counted, records = self._read_back(flight.tokens,
                                                    flight.extras)
        # one observation a dispatched step: the host's time round its
        # dispatch and round its read-back
        self._h_decode.observe(flight.dispatch_s + time.monotonic() - t0)
        for counter, n in zip(self._c_rows, flight.attended):
            counter.inc(n)
        with self._span("decode_commit", **flight.size, **counted):
            finished = overrun = 0
            for i, (a, token) in enumerate(zip(flight.rows, nxt.tolist())):
                if a.flight is flight:
                    a.flight = None
                if a.retired:
                    overrun += 1
                    continue
                if records is not None:
                    a.records.append(records[i].copy())
                a.last_token = token
                a.out.append(token)
                finished += self._maybe_finish(a)
            if overrun:
                self._c_overrun.inc(overrun)
            if finished:
                with self._cond:
                    self._active = [a for a in self._active
                                    if not a.retired]
                    self._g_active.set(len(self._active)
                                       + len(self._prefilling))
                    self._g_free_blocks.set(self._allocator.free_blocks())

    def _spec_step(self) -> None:
        """One speculative iteration for every active sequence: the
        draft proposes k tokens with k T=1 calls, the target scores
        [last committed token, draft_1..draft_k] in ONE k+1-token verify
        call, and each row emits the target's own greedy picks up to and
        including the first draft disagreement (plus the bonus token on
        full agreement) — 1..k+1 tokens per iteration, bit-identical to
        one-at-a-time decode for ANY draft output.

        Per-row ``allow`` masks draft/verify slots past the row's
        remaining ``max_new_tokens`` allowance, so speculation never
        writes K/V beyond the row's allocated blocks; rejected drafts
        leave stale pool entries past the accepted frontier, which
        position-masked attention never reads and the next iteration's
        scatter overwrites (models/gpt.py:forward_paged_logits).
        """
        rows = list(self._active)
        k = self._spec_k
        b = bucket_for(len(rows), self.buckets.batch_buckets)
        size = {"batch": b, "rows": len(rows)}
        with self._span("decode_prepare", **size):
            tables = self._tables_for(rows, b)
            n0 = np.array([a.prompt_len + len(a.out) for a in rows])
            allow = np.array([min(k + 1,
                                  a.handle.req.max_new_tokens - len(a.out))
                              for a in rows])
        t0 = time.monotonic()
        pt0 = time.perf_counter() if self._tracer is not None else 0.0
        with self._span("serving_spec_step", k=k, **size):
            drafts = np.zeros((len(rows), k), np.int64)
            cur = np.array([a.last_token for a in rows])
            for j in range(k):
                # a draft row: its token, its position, whether the slot
                # is inside its allowance, none from the device
                packed = self._blank_rows(b, 1)
                packed[:len(rows), 0] = cur
                packed[:len(rows), 1] = n0 - 1 + j
                packed[:len(rows), 2] = j < allow
                with self._span("decode_dispatch", **size):
                    sampled, *pools = self._draft_fwd(
                        self._draft_params, self.draft_cfg, packed, tables,
                        self._no_tokens, *self._draft_pools)
                    self._draft_pools = tuple(pools)
                with self._span("decode_readback", **size):
                    cur = np.asarray(sampled)[:len(rows)]
                drafts[:, j] = cur
            tok = np.zeros((b, k + 1), np.int32)
            pos = np.zeros((b, k + 1), np.int32)
            msk = np.zeros((b, k + 1), bool)
            for i, a in enumerate(rows):
                tok[i, 0] = a.last_token
                tok[i, 1:] = drafts[i]
                pos[i] = np.arange(n0[i] - 1, n0[i] + k)
                msk[i] = np.arange(k + 1) < allow[i]
            with self._span("decode_dispatch", **size):
                logits, *pools = self._verify_fwd(
                    self._params, self.model_cfg, jnp.asarray(tok),
                    jnp.asarray(pos), jnp.asarray(msk),
                    *self._pools, tables)
                self._pools = tuple(pools)
            with self._span("decode_readback", **size):
                target = np.asarray(jnp.argmax(logits, axis=-1))
        step_dt = time.monotonic() - t0
        self._h_decode.observe(step_dt)
        with self._span("decode_commit", **size):
            survivors: List[_Active] = []
            step_proposed = step_accepted = 0
            for i, a in enumerate(rows):
                # accept while the draft echoes the target's own greedy pick;
                # target[i, j] is trustworthy for j < allow[i] because all of
                # its conditioning tokens are committed-or-accepted by then
                emitted = [int(target[i, 0])]
                j = 0
                while (j < allow[i] - 1 and j < k
                       and int(drafts[i, j]) == int(target[i, j])):
                    j += 1
                    emitted.append(int(target[i, j]))
                usable = int(min(k, allow[i] - 1))
                a.spec_proposed += usable
                a.spec_accepted += len(emitted) - 1
                step_proposed += usable
                step_accepted += len(emitted) - 1
                if self._tracer is not None:
                    self._tracer.record_span(
                        "request_spec_round", pt0, step_dt, **self._req_args(
                            a.handle.req, proposed=usable,
                            accepted=len(emitted) - 1, emitted=len(emitted)))
                for tk in emitted:
                    a.out.append(tk)
                    a.last_token = tk
                    if (a.handle.req.eos_token_id is not None
                            and tk == a.handle.req.eos_token_id):
                        break
                if not self._maybe_finish(a):
                    survivors.append(a)
            self._c_spec_proposed.inc(step_proposed)
            self._c_spec_accepted.inc(step_accepted)
            proposed = self._c_spec_proposed.value
            if proposed:
                self._g_spec_rate.set(
                    self._c_spec_accepted.value / proposed)
            with self._cond:
                self._active = survivors
                self._g_active.set(len(self._active) + len(self._prefilling))
                self._g_free_blocks.set(self._allocator.free_blocks())

    def _maybe_finish(self, a: _Active) -> bool:
        req = a.handle.req
        reason = None
        if req.eos_token_id is not None and a.last_token == req.eos_token_id:
            reason = "eos"
        elif len(a.out) >= req.max_new_tokens:
            reason = "length"
        if reason is None:
            return False
        self._retire(a, reason)
        return True

    def _retire(self, a: _Active, reason: str) -> None:
        now = time.monotonic()
        a.retired = True
        self._allocator.release(a.blocks)
        self._gauge_kinds(a.by_kind, -1)
        h = a.handle
        result = RequestResult(
            request_id=h.req.request_id,
            prompt_len=a.prompt_len,
            tokens=list(a.out),
            finish_reason=reason,
            queue_wait_s=max(0.0, h.admit_t - h.submit_t),
            prefill_s=h.prefill_s,
            decode_s=(now - h.prefill_done_t if h.prefill_done_t else 0.0),
            total_s=now - h.submit_t,
            prefix_hit_blocks=a.hit_blocks,
            prefix_miss_blocks=a.miss_blocks,
            spec_proposed=a.spec_proposed,
            spec_accepted=a.spec_accepted,
            trace_id=h.req.trace_id,
            token_records=np.concatenate(a.records) if a.records else None)
        if self._tracer is not None:
            self._h_total.observe(result.total_s,
                                  exemplar=h.req.request_id)
            self._tracer.instant(
                "request_retired", **self._req_args(
                    h.req, finish_reason=reason, tokens=len(a.out),
                    total_s=round(result.total_s, 6),
                    queue_wait_s=round(result.queue_wait_s, 6),
                    prefix_hit_blocks=a.hit_blocks,
                    spec_proposed=a.spec_proposed,
                    spec_accepted=a.spec_accepted))
        else:
            self._h_total.observe(result.total_s)
        self._c_completed.inc()
        self._c_tokens.inc(len(a.out))
        if a.spec_proposed:
            self._h_spec_accept.observe(a.spec_accepted / a.spec_proposed)
        with self._cond:
            self._completed += 1
            self._total_tokens += len(a.out)
        h._finish(result)
