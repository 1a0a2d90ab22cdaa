"""Continuous-batching serving surface (docs/serving.md): paged-KV
parity against the uncached forward, compile discipline under the bucket
budget, admission control/backpressure, checkpoint hot-load, the static
run-to-completion baseline, the HTTP front-end, and the KV-cached decode
FLOPs accounting that makes serving MFU honest."""
import dataclasses
import functools
import json
import urllib.error
import urllib.request
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import pytest

from determined_clone_tpu.core._serialization import save_pytree
from determined_clone_tpu.models import gpt
from determined_clone_tpu.serving import (
    BlockAllocator,
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
    ServerOverloaded,
    bucket_for,
    pow2_buckets,
)
from determined_clone_tpu.serving.http import (
    ServingHTTPServer,
    generate_over_http,
)
from determined_clone_tpu.storage import (
    CASStorageManager,
    SharedFSStorageManager,
)
from determined_clone_tpu.telemetry import flops as flops_mod
from determined_clone_tpu.utils.retry import RetryPolicy

CFG = gpt.GPTConfig(vocab_size=97, n_layers=2, d_model=32, n_heads=4,
                    d_ff=64, max_seq_len=48, remat=False,
                    attention_impl="mha")

BUCKETS = BucketSpec.build(4, 16)
CACHE = KVCacheConfig(num_blocks=16, block_size=8)

# mixed lengths on purpose: the parity + compile-discipline tests must
# exercise several (batch, prompt-length) shapes
PROMPTS = [[5, 17, 3, 88, 41], [9] * 11, [1, 2, 3]]


@pytest.fixture(scope="module")
def params():
    return gpt.init(jax.random.PRNGKey(0), CFG)


@functools.partial(jax.jit, static_argnums=1)
def _next_token(params, cfg, toks, n):
    return jnp.argmax(gpt.apply(params, cfg, toks)[0, n - 1])


def naive_greedy(params, prompt, max_new, cfg=CFG):
    """Reference decode: full-context uncached forward every step, one
    program for every length: the context is padded on the right to
    ``max_seq_len``, which no causal position on the left can see."""
    toks = list(prompt)
    for _ in range(max_new):
        padded = toks + [0] * (cfg.max_seq_len - len(toks))
        toks.append(int(_next_token(
            params, cfg, jnp.asarray([padded], jnp.int32), len(toks))))
    return toks[len(prompt):]


def same_leaves(x, y):
    """Leaf for leaf the same objects: nothing was cast or copied."""
    return all(a is b for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)))


def make_engine(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("cache", CACHE)
    return InferenceEngine(params, CFG, **kw)


# -- bucketing / allocator units --------------------------------------------

def test_pow2_buckets():
    assert pow2_buckets(1, 8) == (1, 2, 4, 8)
    assert pow2_buckets(4, 100) == (4, 8, 16, 32, 64, 128)
    assert bucket_for(5, (4, 8, 16)) == 8
    assert bucket_for(8, (4, 8, 16)) == 8
    with pytest.raises(ValueError):
        bucket_for(17, (4, 8, 16))
    with pytest.raises(ValueError):
        pow2_buckets(0, 4)


def test_bucket_spec_validation_and_budget():
    spec = BucketSpec(batch_buckets=(1, 2, 4), prefill_len_buckets=(8, 16))
    assert spec.max_batch == 4
    assert spec.max_prefill_len == 16
    assert spec.program_budget == 3 * 2 + 3
    with pytest.raises(ValueError):
        BucketSpec(batch_buckets=(3,), prefill_len_buckets=(8,))
    with pytest.raises(ValueError):
        BucketSpec(batch_buckets=(4, 2), prefill_len_buckets=(8,))
    with pytest.raises(ValueError):
        BucketSpec(batch_buckets=(), prefill_len_buckets=(8,))


def test_block_allocator():
    alloc = BlockAllocator(KVCacheConfig(num_blocks=4, block_size=8))
    assert alloc.free_blocks() == 4
    a = alloc.allocate(17)  # 3 blocks
    assert len(a) == 3 and alloc.free_blocks() == 1
    assert alloc.can_allocate(8) and not alloc.can_allocate(9)
    with pytest.raises(MemoryError):
        alloc.allocate(16)
    alloc.release(a)
    assert alloc.free_blocks() == 4
    with pytest.raises(ValueError):
        alloc.release(a[:1])  # double free
    with pytest.raises(ValueError):
        alloc.release([99])  # bogus id


# -- the tier-1 contract: parity + compile discipline ------------------------

@pytest.mark.parametrize("attention_impl", ["mha", "flash"])
def test_paged_decode_token_identical_and_compile_budget(geometry,
                                                         assert_pool_rows,
                                                         attention_impl):
    """Mixed-length requests through the continuous scheduler produce
    EXACTLY the tokens of the naive uncached forward (greedy), and this
    engine never adds more programs to the shared jitted forward than the
    bucket budget (the jit's cache is the process's: what an engine
    compiled is the growth of ``programs_compiled()`` over its traffic) —
    the two acceptance properties of the serving tentpole. Both
    hold whether or not a pool row is padded, and prefill and decode
    leave the padding zero; and whether a decode step gathers its context
    and attends it in plain XLA (``"mha"``) or reads it through the block
    table in the paged kernel (``"flash"``, interpreted off the chip)."""
    cfg, params = geometry
    expected = {i: naive_greedy(params, p, 12, cfg)
                for i, p in enumerate(PROMPTS)}
    cfg = dataclasses.replace(cfg, attention_impl=attention_impl)
    with InferenceEngine(params, cfg, buckets=BUCKETS, cache=CACHE) as eng:
        before = eng.programs_compiled()
        handles = [eng.submit(p, 12, request_id=str(i))
                   for i, p in enumerate(PROMPTS)]
        results = {int(h.result(timeout=120.0).request_id):
                   h.result(timeout=120.0) for h in handles}
        # a second wave at different batch sizes exercises more shapes
        again = [eng.submit(p, 5) for p in PROMPTS[:2]]
        for h in again:
            h.result(timeout=120.0)
        compiled = eng.programs_compiled()
        budget = eng.buckets.program_budget
        stats = eng.stats()
        assert_pool_rows(eng, cfg)
    for i in range(len(PROMPTS)):
        assert results[i].tokens == expected[i], f"request {i} diverged"
        assert results[i].finish_reason == "length"
        assert results[i].prompt_len == len(PROMPTS[i])
    assert compiled > 0 and 0 <= compiled - before <= budget, (
        before, compiled, budget)
    assert stats.completed == 5
    assert stats.tokens_generated == 3 * 12 + 2 * 5
    assert stats.free_blocks == CACHE.num_blocks  # everything released


def test_warmup_precompiles_full_ladder(params):
    """warmup() compiles EXACTLY the program budget up front, leaves the
    KV pools untouched (dummy calls are fully masked), and no later
    traffic — including the one-request-at-a-time arrival pattern that
    hits the small batch buckets a burst never exercises — adds a
    single program. The mid-traffic compile stall this prevents is what
    collapsed the bench's top load point ~10x before warmup existed."""
    expected = naive_greedy(params, PROMPTS[0], 8)
    # the jit's cache is the process's, so the count is the growth over
    # the warm-up; a pool of 17 blocks is this test's alone in the
    # process, so that growth is exactly this engine's ladder
    with make_engine(params, cache=KVCacheConfig(17, 8)) as eng:
        before = eng.programs_compiled()
        compiled = eng.warmup()
        assert compiled - before == eng.buckets.program_budget
        # trickle: each request admitted alone → batch-bucket-1 prefill,
        # the shape a warm burst at full batch never compiles
        for _ in range(2):
            r = eng.generate(PROMPTS[0], 8)
            assert r.tokens == expected  # pools uncorrupted by warmup
        # then a burst at full batch for the other buckets
        hs = [eng.submit(p, 4) for p in PROMPTS]
        for h in hs:
            h.result(timeout=120.0)
        assert eng.programs_compiled() == compiled  # nothing new to compile
        # ... and steps dispatched a step ahead (their token taken from
        # the device) were among that traffic
        assert eng.registry.counter(
            "serving_decode_steps_overlapped_total").value >= 6
    with make_engine(params) as eng:
        # white-box: an un-notified queue entry keeps the scheduler
        # parked, so the busy engine is observed deterministically
        eng._queue.append(object())
        with pytest.raises(RuntimeError, match="idle"):
            eng.warmup()
        eng._queue.clear()
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.warmup()


def test_eos_stops_early(params):
    ref = naive_greedy(params, PROMPTS[0], 12)
    eos = ref[3]
    # the engine stops at the FIRST occurrence of eos (an untrained model
    # may emit it earlier than position 3 — don't assume distinct tokens)
    stop = ref.index(eos) + 1
    with make_engine(params) as eng:
        r = eng.generate(PROMPTS[0], 12, eos_token_id=eos)
    assert r.finish_reason == "eos"
    assert r.tokens == ref[:stop]


def test_telemetry_spans_and_metrics(params):
    with make_engine(params) as eng:
        eng.generate(PROMPTS[0], 4)
        dump = eng.registry.dump()
    for name in ("serving_queue_wait_seconds", "serving_prefill_seconds",
                 "serving_decode_step_seconds",
                 "serving_request_total_seconds",
                 "serving_requests_completed_total",
                 "serving_tokens_generated_total"):
        assert name in dump, name


def test_decode_steps_count_attended_and_tabled_rows(params):
    """Every span of a decode step carries ``kv_rows`` (the live rows' real
    context lengths, summed) and ``table_rows`` (batch bucket x table
    width x block: what a read of whole tables moves), and the two
    counters hold their sums."""
    from determined_clone_tpu.telemetry import Tracer

    class Telemetry:
        registry = None
        tracer = Tracer(enabled=True, process_name="t")

    prompts, new = [PROMPTS[0], PROMPTS[1]], [4, 6]
    with make_engine(params, telemetry=Telemetry()) as eng:
        for h in [eng.submit(p, n) for p, n in zip(prompts, new)]:
            h.result(timeout=120.0)
        attended = eng.registry.counter(
            "serving_kv_rows_attended_total").value
        tabled = eng.registry.counter("serving_kv_rows_tabled_total").value
        width = eng._layout.table_width * CACHE.block_size
    steps = {name: [e["args"] for e in Telemetry.tracer.events()
                    if e["name"] == name]
             for name in ("decode_prepare", "serving_decode_step",
                          "decode_dispatch", "decode_readback",
                          "decode_commit")}
    # a step's read-back and commit come a turn late, with the step's own
    # args; the step's span alone says whether it was dispatched ahead
    assert {a.pop("overlapped") for a in steps["serving_decode_step"]} \
        == {0, 1}
    whole = steps["serving_decode_step"]
    assert whole and all(args == whole for args in steps.values())
    assert all(a["table_rows"] == a["batch"] * width for a in whole)
    assert all(a["rows"] <= a["kv_rows"] <= a["rows"] * width for a in whole)
    # a request's decode steps attend len(prompt) + 1 .. + new - 1 rows
    assert attended == sum(a["kv_rows"] for a in whole) == sum(
        len(p) + i for p, n in zip(prompts, new) for i in range(1, n))
    assert tabled == sum(a["table_rows"] for a in whole)


# -- one step ahead: the pipelined decode step --------------------------------

class _BeforeDecodeCall:
    """``eng._fwd`` with ``then()`` run on the scheduler thread just before
    the ``at``-th decode call (``rows`` is ``[b, 4]``) is dispatched: the
    step before it, if any, is then in flight and unread."""

    def __init__(self, eng, at, then):
        self.real, self.at, self.then, self.calls = eng._fwd, at, then, 0
        eng._fwd = self

    def __call__(self, params, cfg, rows, *rest):
        if rows.shape[1] == 4:
            self.calls += 1
            if self.calls == self.at:
                self.then()
        return self.real(params, cfg, rows, *rest)


def _counter(eng, name):
    return eng.registry.counter(name).value


@pytest.fixture(scope="module")
def varied(params):
    """Weights whose continuations vary from token to token: the seeded
    tree repeats a prompt's last token for ever, which a step fed the
    wrong row's token, or a stale one, would repeat just as well."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    noisy = jax.tree.unflatten(tree, [
        x + jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    assert len(set(naive_greedy(noisy, PROMPTS[2], 8))) >= 4
    return noisy


def test_pipelined_batch_matches_one_request_at_a_time(varied):
    """Requests that finish by length at different steps, with rows joining
    from prefill while a step is in flight, get token for token what each
    gets alone."""
    wave1 = [(PROMPTS[0], 3), (PROMPTS[1], 9), (PROMPTS[2], 6)]
    wave2 = [([7, 7, 2, 90], 5), ([33] * 9, 2), ([4, 8, 15, 16, 23, 42], 7)]
    params, late = varied, []
    with make_engine(params) as eng:
        _BeforeDecodeCall(eng, 2, lambda: late.extend(
            eng.submit(p, n) for p, n in wave2))
        with eng._cond:  # admitted together: one prefill call
            first = [eng.submit(p, n) for p, n in wave1]
        got = [h.result(timeout=120.0) for h in first]
        got += [h.result(timeout=120.0) for h in late]
        eng.wait_idle(timeout=60.0)
        assert _counter(eng, "serving_decode_steps_overlapped_total") > 0
        assert _counter(eng, "serving_decode_overrun_rows_total") == 0
        eng.assert_kv_balanced()
        alone = [eng.generate(p, n) for p, n in wave1 + wave2]
    assert len(late) == 3
    for r, ref, (p, n) in zip(got, alone, wave1 + wave2):
        assert r.tokens == ref.tokens == naive_greedy(params, p, n)
        assert r.finish_reason == "length" and len(r.tokens) == n


def test_eos_a_step_late_drops_the_extra_token(varied):
    """A finish by ``eos`` is seen when its step is read, a turn after the
    next step was dispatched with the row in it: the result still ends at
    the ``eos`` token, the extra step's token is dropped and counted, and
    every block comes back."""
    params, new = varied, 10
    prompts = [PROMPTS[0], PROMPTS[1], PROMPTS[2], [7, 7, 2, 90]]
    refs = [naive_greedy(params, p, new) for p in prompts]
    # eos: the first token a request emits that is not its first one (a
    # later step is in flight when it is read), except for the last
    # request, which stops on its first token, out of prefill
    eos = [next(t for t in ref if t != ref[0]) for ref in refs[:-1]] \
        + [refs[-1][0]]
    stops = [ref.index(e) + 1 for ref, e in zip(refs, eos)]
    overrun = sum(1 for stop in stops if 1 < stop < new)
    assert overrun == 3 and stops[-1] == 1
    with make_engine(params) as eng:
        with eng._cond:  # one prefill call, then decode steps alone
            hs = [eng.submit(p, new, eos_token_id=e)
                  for p, e in zip(prompts, eos)]
        results = [h.result(timeout=120.0) for h in hs]
        eng.wait_idle(timeout=60.0)
        assert _counter(eng, "serving_decode_overrun_rows_total") == overrun
        eng.assert_kv_balanced()
        assert eng.stats().free_blocks == CACHE.num_blocks
    for r, ref, stop in zip(results, refs, stops):
        assert r.finish_reason == "eos" and r.tokens == ref[:stop]


@pytest.mark.parametrize("how", ["abort", "deadline"])
def test_abort_and_deadline_while_a_step_is_in_flight(varied, how):
    """The row is retired at the next iteration boundary with what was
    committed; the token of the step in flight is dropped, its blocks are
    released once."""
    import threading
    import time

    reached, release = threading.Event(), threading.Event()
    with make_engine(varied) as eng:
        ref = eng.generate(PROMPTS[0], 12).tokens  # and the programs warm
        assert len(set(ref[:3])) > 1
        _BeforeDecodeCall(eng, 2, lambda: (reached.set(),
                                           release.wait(60.0)))
        deadline = time.monotonic() + 0.5 if how == "deadline" else None
        h = eng.submit(PROMPTS[0], 12, deadline_t=deadline)
        assert reached.wait(60.0)  # step 1 in flight, step 2 at the gate
        if how == "abort":
            assert eng.abort(h)
        else:
            time.sleep(max(0.0, deadline - time.monotonic()) + 0.01)
        release.set()
        r = h.result(timeout=120.0)
        eng.wait_idle(timeout=60.0)
        assert r.finish_reason == ("aborted" if how == "abort"
                                   else "expired")
        # the prefill's token and step 1's; step 2's was dropped
        assert r.tokens == ref[:2]
        assert _counter(eng, "serving_decode_overrun_rows_total") == 1
        assert eng._flight is None
        eng.assert_kv_balanced()
        assert eng.generate(PROMPTS[0], 12).tokens == ref  # and serves on


def test_a_forward_that_raises_fails_every_waiter_and_frees_every_block(
        params):
    """An error of a step in flight surfaces a turn late at the latest;
    whenever it does, running and queued requests all fail (none hangs)
    and the allocator is balanced."""
    from determined_clone_tpu.serving.engine import ReplicaFailed

    def boom():
        raise RuntimeError("device fell over")

    with make_engine(params) as eng:
        _BeforeDecodeCall(eng, 3, boom)
        with eng._cond:  # four running (max_batch), two queued behind them
            hs = [eng.submit(p, 12) for p in PROMPTS * 2]
        for h in hs:
            with pytest.raises(ReplicaFailed, match="device fell over"):
                h.result(timeout=120.0)
        assert eng._flight is None and not eng._active
        eng.assert_kv_balanced()
        with pytest.raises(ReplicaFailed):
            eng.submit(PROMPTS[0], 2)


def test_overlapped_is_one_on_steady_steps_and_zero_after_a_drain(varied):
    """``overlapped`` on ``serving_decode_step``: 0 on the first step of a
    busy stretch (nothing was in flight), 1 on every step dispatched while
    the one before it was unread. A prefill call does not drain the
    pipeline: it is dispatched before the turn's step and read after it, so
    the steps round it stay overlapped and its ``serving_prefill`` span
    lies outside every step's. The counter adds the flags up."""
    from determined_clone_tpu.telemetry import Tracer

    class Telemetry:
        registry = None
        tracer = Tracer(enabled=True, process_name="t")

    params, late = varied, []
    with make_engine(params, telemetry=Telemetry()) as eng:
        _BeforeDecodeCall(eng, 4, lambda: late.append(
            eng.submit(PROMPTS[1], 4)))
        first = eng.submit(PROMPTS[0], 9)
        assert first.result(timeout=120.0).tokens == naive_greedy(
            params, PROMPTS[0], 9)
        assert late[0].result(timeout=120.0).tokens == naive_greedy(
            params, PROMPTS[1], 4)
        eng.wait_idle(timeout=60.0)
        busy = _counter(eng, "serving_decode_steps_overlapped_total")
        eng.generate(PROMPTS[2], 3)  # after an idle engine: a first step
        total = _counter(eng, "serving_decode_steps_overlapped_total")
    events = sorted((e for e in Telemetry.tracer.events()
                     if e["name"] in ("serving_decode_step",
                                      "serving_prefill")),
                    key=lambda e: e["ts_us"])
    names = [e["name"] for e in events]
    steps = [e for e in events if e["name"] == "serving_decode_step"]
    flags = [e["args"]["overlapped"] for e in steps]
    # 8 steps of the first request, the late one's beside and after them,
    # then the third request's two
    assert flags[0] == 0 and flags[-2:] == [0, 1]
    assert all(flags[1:-2]) and len(flags) >= 8 + 2
    assert busy == sum(flags[:-2]) and total == sum(flags)
    # the late request's prefill call: between two overlapped steps, inside
    # neither's span
    assert names.count("serving_prefill") == 3
    late_call = events[[i for i, n in enumerate(names)
                        if n == "serving_prefill"][1]]
    i = events.index(late_call)
    assert names[i - 1] == names[i + 1] == "serving_decode_step"
    assert events[i - 1]["args"]["overlapped"] == 1 \
        and events[i + 1]["args"]["overlapped"] == 1
    assert events[i - 1]["ts_us"] + events[i - 1]["dur_us"] \
        <= late_call["ts_us"] + 0.2
    assert late_call["ts_us"] + late_call["dur_us"] \
        <= events[i + 1]["ts_us"] + 0.2


# -- admission control / backpressure ----------------------------------------

def test_admission_rejects_and_backoff(params):
    fast = RetryPolicy(name="t", max_attempts=2, base_delay_s=0.01,
                       multiplier=1.0, max_delay_s=0.01,
                       retryable=(ServerOverloaded,))
    with make_engine(params, max_queue_depth=0) as eng:
        with pytest.raises(ServerOverloaded):
            eng.submit(PROMPTS[0], 2)
        with pytest.raises(ServerOverloaded):
            eng.submit_with_backoff(PROMPTS[0], 2, policy=fast)
        assert eng.stats().rejected >= 3  # 1 direct + 2 backoff attempts


def test_never_servable_requests_rejected_upfront(params):
    with make_engine(params) as eng:
        with pytest.raises(ValueError):
            eng.submit([], 4)  # empty prompt
        with pytest.raises(ValueError):
            eng.submit(list(range(17)), 4)  # > largest prefill bucket
        with pytest.raises(ValueError):
            eng.submit([1, 2], CFG.max_seq_len)  # total > max_seq_len
        with pytest.raises(ValueError):
            eng.submit([1, 2], 0)  # no tokens requested


def test_closed_engine_refuses(params):
    eng = make_engine(params)
    eng.close()
    with pytest.raises(RuntimeError):
        eng.submit(PROMPTS[0], 2)


# -- checkpoint hot-load ------------------------------------------------------

def test_hot_load_from_cas_swaps_params(params, tmp_path):
    """Serve under params A, hot-load params B from a CAS-backed store,
    and the very next generation must match the naive forward under B —
    no restart, no re-jit (the programs this engine added stay within the
    budget, and B's tree added none to A's)."""
    params_b = gpt.init(jax.random.PRNGKey(7), CFG)
    store = CASStorageManager(
        SharedFSStorageManager(str(tmp_path / "store")))
    with store.store_path("ck-b", str(tmp_path)) as d:
        save_pytree(d, params_b)
    store.commit("ck-b")

    ref_a = naive_greedy(params, PROMPTS[0], 6)
    with make_engine(params) as eng:
        before = eng.programs_compiled()  # the jit is shared in a process
        assert eng.generate(PROMPTS[0], 6).tokens == ref_a
        under_a = eng.programs_compiled()
        dt = eng.hot_load(store, "ck-b", base_tmp=str(tmp_path))
        assert dt >= 0.0
        got = eng.generate(PROMPTS[0], 6).tokens
        assert eng.programs_compiled() == under_a  # no re-jit
        compiled = eng.programs_compiled() - before
        # the swap installed the restored tree (greedy token streams of
        # two untrained models can coincide — check the params, not the
        # sampled tokens, to prove the swap happened)
        swapped = jax.tree.leaves(eng._params)
    ref_b = naive_greedy(params_b, PROMPTS[0], 6)
    assert got == ref_b
    leaves_a = jax.tree.leaves(params)
    leaves_b = jax.tree.leaves(params_b)
    assert any(not jnp.array_equal(a, b)
               for a, b in zip(leaves_a, leaves_b))
    # installed in its serving form: the block matrices rounded once
    served_b = jax.tree.leaves(gpt.serving_params(params_b, CFG))
    assert [s.dtype for s in swapped] == [b.dtype for b in served_b]
    assert all(jnp.array_equal(s, b) for s, b in zip(swapped, served_b))
    assert not all(s.dtype == b.dtype for s, b in zip(swapped, leaves_b))
    assert compiled <= BUCKETS.program_budget


# -- the serving form of the weights ------------------------------------------

def _paged_call(params, t):
    """One ``forward_paged`` call on a pool that holds an 8-token context
    for each of 2 rows: a decode step (t = 1) or a prefill slice."""
    from determined_clone_tpu.serving.kv_cache import init_kv_pools

    key = jax.random.PRNGKey(3)
    k_pool, v_pool = (jax.random.normal(k, pool.shape, pool.dtype)
                      for k, pool in zip(jax.random.split(key),
                                         init_kv_pools(CFG, CACHE)))
    tokens = jax.random.randint(key, (2, t), 0, CFG.vocab_size)
    positions = 8 + jnp.tile(jnp.arange(t, dtype=jnp.int32), (2, 1))
    tables = jnp.arange(2 * 6, dtype=jnp.int32).reshape(2, 6)
    return gpt.forward_paged(
        params, CFG, tokens, positions, jnp.ones((2, t), bool),
        jnp.full((2,), t - 1, jnp.int32), k_pool, v_pool, tables)


@pytest.mark.parametrize("t", [1, 8], ids=["decode", "prefill-slice"])
def test_serving_form_gives_the_float32_trees_results_bitwise(params, t):
    """Rounding a matrix once is rounding it in every call: logits and
    both pools are the same bits."""
    served = gpt.serving_params(params, CFG)
    for got, want in zip(_paged_call(served, t), _paged_call(params, t)):
        assert got.dtype == want.dtype
        assert jnp.array_equal(got, want)


def test_serving_form_casts_the_block_matrices_only_and_only_once(params):
    served = gpt.PAGED.serving_params(params, CFG)
    assert jax.tree.structure(served) == jax.tree.structure(params)
    blocks = served["blocks"]
    for name in ("attn_qkv", "attn_out", "mlp_up", "mlp_down"):
        assert blocks[name]["kernel"].dtype == CFG.compute_dtype
        assert blocks[name]["bias"].dtype == CFG.compute_dtype
    # norms, table (and with it the head): the very buffers that came in
    for kept in (("blocks", "ln1"), ("blocks", "ln2"), ("final_norm",),
                 ("embed",)):
        was, now = params, served
        for k in kept:
            was, now = was[k], now[k]
        assert same_leaves(was, now)
        assert all(b.dtype == jnp.float32 for b in jax.tree.leaves(now))
    assert same_leaves(served, gpt.PAGED.serving_params(served, CFG))
    # a tree whose type is the compute type already is served as it lies
    cfg32 = dataclasses.replace(CFG, compute_dtype=jnp.float32)
    assert same_leaves(params, gpt.PAGED.serving_params(params, cfg32))


def test_serving_form_of_an_moe_tree_leaves_the_router_float32():
    cfg = dataclasses.replace(CFG, moe_experts=2, moe_k=1)
    served = gpt.serving_params(gpt.init(jax.random.PRNGKey(0), cfg), cfg)
    moe = served["blocks"]["moe"]
    assert moe["router"]["kernel"].dtype == jnp.float32
    assert {leaf.dtype for part in ("up", "down")
            for leaf in jax.tree.leaves(moe[part])} == {
                jnp.dtype(cfg.compute_dtype)}


def test_engine_records_what_it_cast_and_what_it_holds(params):
    """``serving_params_prepare`` carries the bytes cast and kept, the
    gauge the served tree's bytes by type. A tree already in its serving
    form is installed as it lies, prepared when the swap is queued."""
    from determined_clone_tpu.telemetry import Tracer

    class Telemetry:
        registry = None
        tracer = Tracer(enabled=True, process_name="t")

    served = gpt.serving_params(params, CFG)
    with make_engine(params, telemetry=Telemetry()) as eng:
        assert not same_leaves(eng._params, params)  # not the caller's
        eng.hot_swap(served)
        eng.generate(PROMPTS[2], 2)
        assert same_leaves(eng._params, served)
        gauges = {m.labels["dtype"]: m.value for m in eng.registry.metrics()
                  if m.name == "serving_weight_bytes"}
    cast = [e["args"] for e in Telemetry.tracer.events()
            if e["name"] == "serving_params_prepare"]
    blocks32 = sum(x.nbytes for name in ("attn_qkv", "attn_out", "mlp_up",
                                         "mlp_down")
                   for x in jax.tree.leaves(params["blocks"][name]))
    rest32 = sum(x.nbytes for x in jax.tree.leaves(params)) - blocks32
    assert cast == [
        {"cast_bytes": blocks32, "kept_bytes": rest32},
        {"cast_bytes": 0, "kept_bytes": rest32 + blocks32 // 2}]
    assert gauges == {"bfloat16": blocks32 // 2, "float32": rest32}


def test_replicas_of_one_fleet_share_the_weight_buffers(params):
    """The fleet converts once and hands that tree to every replica, at
    construction and at a rollout: no replica holds weights of its own."""
    from determined_clone_tpu.serving.fleet import ServingFleet

    def buffers(tree):
        return [x.unsafe_buffer_pointer() for x in jax.tree.leaves(tree)]

    fleet = ServingFleet(params, CFG, buckets=BUCKETS, cache=CACHE,
                         warmup=False)
    try:
        a, b = (fleet._replicas[r].engine for r in fleet.scale_up(2))
        assert a._params["blocks"]["mlp_up"]["kernel"].dtype == jnp.bfloat16
        assert buffers(a._params) == buffers(b._params) == buffers(
            fleet._params)
        fleet.rollout(gpt.init(jax.random.PRNGKey(7), CFG))
        assert a._params["blocks"]["mlp_up"]["kernel"].dtype == jnp.bfloat16
        assert buffers(a._params) == buffers(b._params) == buffers(
            fleet._params)
    finally:
        fleet.close()


# -- HTTP surface -------------------------------------------------------------

def test_http_generate_healthz_metrics(params):
    ref = naive_greedy(params, PROMPTS[2], 5)
    with make_engine(params) as eng, ServingHTTPServer(eng) as srv:
        out = generate_over_http(srv.url, PROMPTS[2], max_new_tokens=5)
        assert out["tokens"] == ref
        assert out["finish_reason"] == "length"
        assert out["latency"]["total_s"] >= 0

        with urllib.request.urlopen(f"{srv.url}/healthz",
                                    timeout=30) as resp:
            health = json.loads(resp.read())
        assert health["ok"] and health["stats"]["completed"] >= 1

        with urllib.request.urlopen(f"{srv.url}/metrics",
                                    timeout=30) as resp:
            metrics = resp.read().decode()
        assert "serving_requests_completed_total" in metrics


def test_http_error_codes(params):
    with make_engine(params) as eng, ServingHTTPServer(eng) as srv:
        bad = urllib.request.Request(
            f"{srv.url}/v1/generate", data=b'{"prompt": "nope"}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(bad, timeout=30)
        assert exc.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{srv.url}/nope", timeout=30)
        assert exc.value.code == 404


# -- KV-cached decode FLOPs (telemetry/flops.py) ------------------------------

@dataclass
class _TinyCfg:
    d_model: int = 4
    d_ff: int = 8
    n_layers: int = 2
    vocab_size: int = 16


def test_decode_flops_hand_computed():
    """d=4, f=8, L=2, V=16 at context 10, worked by hand:
    attention = L·(8d² + 4cd) = 2·(128 + 160) = 576
    mlp       = L·4df         = 2·128        = 256
    embedding = 2dV           =                128
    """
    out = flops_mod.gpt_decode_flops_per_token(_TinyCfg(), 10)
    assert out["attention"] == 576.0
    assert out["mlp"] == 256.0
    assert out["embedding"] == 128.0
    assert out["total"] == 960.0


def test_prefill_flops_hand_computed():
    """P=4 prompt: per-token at s=4 is 2·(128+64) + 256 + 128 = 768,
    times 4 tokens = 3072."""
    out = flops_mod.gpt_prefill_flops(_TinyCfg(), 4)
    assert out["total"] == 3072.0
    assert out["attention"] == 4 * 2 * (128 + 64)


def test_generation_flops_is_prefill_plus_decode_tail():
    """prefill(4) + decode@ctx5 + decode@ctx6: the first generated token
    falls out of the prefill logits, so n=3 pays only 2 decode steps."""
    cfg = _TinyCfg()
    total = flops_mod.gpt_generation_flops(cfg, 4, 3)
    expect = (flops_mod.gpt_prefill_flops(cfg, 4)["total"]
              + flops_mod.gpt_decode_flops_per_token(cfg, 5)["total"]
              + flops_mod.gpt_decode_flops_per_token(cfg, 6)["total"])
    assert total == expect == 3072.0 + 800.0 + 832.0


def test_decode_flops_linear_in_context_not_quadratic():
    """The whole point of the split: decode cost grows linearly with
    context while prefill per-token cost grows with prompt length."""
    cfg = _TinyCfg()
    d1 = flops_mod.gpt_decode_flops_per_token(cfg, 100)["total"]
    d2 = flops_mod.gpt_decode_flops_per_token(cfg, 200)["total"]
    d3 = flops_mod.gpt_decode_flops_per_token(cfg, 300)["total"]
    assert d3 - d2 == d2 - d1  # constant marginal cost per context token
