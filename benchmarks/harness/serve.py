"""A serving cell: ``InferenceEngine`` driven in-process through
``engine.submit`` by a closed loop of client threads.

Timeline of a run:

    weights from the seed, engine built, ``engine.warmup()``      (set-up)
    the closed loop runs for ``ramp_s`` seconds                    (set-up)
    window of --seconds: requests submitted in it are the sample
    the loop keeps its load up until every sampled request is done, then
    the engine closes on whatever was submitted later
    peak memory is read, then a seeded sample of the window's replies
    (the longest among them) is teacher-forced through the reference

``ttft`` is the engine's ``prefill_done_t`` minus the harness's submit time
(both ``time.monotonic`` in one process): the only first-token signal the
engine has, it streams nothing. ``tpot`` is (completion seen by the client
- ``prefill_done_t``) / (output tokens - 1).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.harness import check, device, stats, trace, traffic
from benchmarks.harness.spec import BENCH_DIR, Cell


@dataclasses.dataclass
class _Served:
    request: traffic.ServeRequest
    submit_t: float
    prefill_done_t: float = 0.0
    done_t: float = 0.0
    tokens: Optional[List[int]] = None
    finish_reason: str = ""
    error: Optional[str] = None


class _ClosedLoop:
    """``clients`` threads, each sending its next request when its last one
    completes. Requests are taken from one seeded list, in order."""

    def __init__(self, engine: Any, requests: List[traffic.ServeRequest],
                 clients: int) -> None:
        self.engine = engine
        self.requests = requests
        self.taken = 0  # requests handed to a client; read under ``lock``
        self.lock = threading.Lock()
        self.served: List[_Served] = []
        self.in_flight: Dict[int, float] = {}  # request index -> submit time
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._client,
                                         name=f"bench-client-{i}")
                        for i in range(clients)]

    def _client(self) -> None:
        import jax

        while not self.stop.is_set():
            with self.lock:
                i = self.taken
                if i >= len(self.requests):
                    return  # the list ran dry: ``requests_outlast_window``
                self.taken = i + 1
            req = self.requests[i]
            rec = _Served(req, time.monotonic())
            with self.lock:
                self.in_flight[i] = rec.submit_t
            try:
                with jax.profiler.TraceAnnotation("bench.client_submit"):
                    handle = self.engine.submit(
                        req.prompt, max_new_tokens=req.max_new_tokens)
                result = handle.result(timeout=300.0)
                rec.done_t = time.monotonic()
                rec.prefill_done_t = handle.prefill_done_t
                rec.tokens = list(result.tokens)
                rec.finish_reason = result.finish_reason
            except Exception as e:  # noqa: BLE001 - counted as a failure
                rec.done_t = time.monotonic()
                rec.error = repr(e)
            with self.lock:
                self.served.append(rec)
                del self.in_flight[i]

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def wait_until_done(self, submitted_before: float) -> None:
        """Block until every request submitted before that time has
        completed; the clients go on submitting meanwhile."""
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            with self.lock:
                pending = [t for t in self.in_flight.values()
                           if t < submitted_before]
            if not pending:
                return
            time.sleep(0.05)
        raise RuntimeError("sampled requests did not finish in 300 s")

    def join(self) -> None:
        for t in self.threads:
            t.join(timeout=60.0)
        if any(t.is_alive() for t in self.threads):
            raise RuntimeError("a client thread did not finish")


def _failed(rec: _Served) -> bool:
    return (rec.error is not None or rec.finish_reason != "length"
            or rec.tokens is None
            or len(rec.tokens) != rec.request.max_new_tokens)


def tokens_inside(rec: _Served, t0: float, t1: float) -> float:
    """Output tokens of one request that fall inside [t0, t1). The engine
    streams nothing, so the client knows only when the first token
    (``prefill_done_t``) and the last (``done_t``) came; the tokens between
    are spread evenly over that time. A request that straddles an edge of
    the window so counts for the part of its work done inside."""
    n = len(rec.tokens)
    first, last = rec.prefill_done_t, rec.done_t
    inside = 1.0 if t0 <= first < t1 else 0.0
    if n > 1 and last > first:
        overlap = max(0.0, min(last, t1) - max(first, t0))
        inside += (n - 1) * overlap / (last - first)
    return inside


def pick_sample(done: List[_Served], n: int, seed: int) -> List[_Served]:
    """``n`` of the window's finished requests, drawn from the seed, the
    longest (prompt + output) always among them."""
    if not done:
        return []
    ordered = sorted(done, key=lambda r: r.request.index)
    longest = max(ordered, key=lambda r: (len(r.request.prompt)
                                          + len(r.tokens or []),
                                          -r.request.index))
    rest = [r for r in ordered if r is not longest]
    rng = np.random.default_rng(seed + 2)
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]


def widest_gap_of_sample(ref: Any, params: Any, n_heads: int,
                         sample: List[_Served], pad_to: int,
                         precision: str = "f32",
                         scored_by: Optional[str] = None) -> Dict[str, Any]:
    """Teacher-force each sampled reply through the reference; the widest gap
    by which a served token's reference logit lies below the reference's
    best. With ``scored_by`` (the control), the tokens scored are not the
    served ones but, at each position of the same prompts and tokens, the
    token that the reference at that lower precision puts first."""
    widest, n_tokens, mismatches = 0.0, 0, 0
    for rec in sample:
        seq = list(rec.request.prompt) + list(rec.tokens)
        lo = len(rec.request.prompt) - 1
        logits = np.asarray(ref.teacher_forced_logits(
            params, seq, n_heads=n_heads, precision=precision,
            pad_to=pad_to))[lo:len(seq) - 1]
        if not np.isfinite(logits).all():
            return {"gap": float("nan"), "tokens": n_tokens}
        tokens = rec.tokens
        if scored_by is not None:
            low = np.asarray(ref.teacher_forced_logits(
                params, seq, n_heads=n_heads, precision=scored_by,
                pad_to=pad_to))[lo:len(seq) - 1]
            tokens = low.argmax(axis=-1).tolist()
        widest = max(widest, check.widest_logit_gap(logits, tokens))
        mismatches += int((logits.argmax(axis=-1)
                           != np.asarray(tokens)).sum())
        n_tokens += len(tokens)
    return {"gap": widest, "tokens": n_tokens, "not_argmax": mismatches}


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_process: float, dev: Dict[str, Any],
        control: Optional[str] = None) -> Dict[str, Any]:
    """Drive the cell and return the pieces of the result line. ``control``
    names a lower precision whose first tokens are scored on the same
    sample (``tools/readings.py``; never in a benchmark run)."""

    t_run = time.monotonic()
    adapter = cell.adapter()
    compiles = device.CompileCounter()
    mix, config = cell.traffic, cell.config
    d = adapter.dims(config)
    t_adapter = time.monotonic()
    requests = traffic.serve_requests(mix, d["vocab"], seed)
    t_requests = time.monotonic()
    clients = int(config["serving"]["max_batch"]) \
        if mix["clients"] == "max_batch" else int(mix["clients"])
    telemetry = adapter.new_telemetry() if traced else None
    tracer = trace.TraceWindow(os.path.join(BENCH_DIR, ".trace", cell.name)) \
        if traced else None
    trace_s = float(mix.get("trace_seconds", 4))

    params = adapter.make_weights(config, seed)
    engine = adapter.build_engine(config, params, telemetry)
    sync_t = None
    loop = _ClosedLoop(engine, requests, clients)
    try:
        t_built = time.monotonic()
        programs = engine.warmup()
        t_warm = time.monotonic()
        if telemetry is not None:
            sync_t = time.monotonic()
            telemetry.tracer.instant("bench_clock_sync")
        loop.start()
        time.sleep(float(mix["ramp_s"]))
        # -- the window opens ------------------------------------------------
        registry_before = _registry_reading(engine)
        requests_before = compiles.requests
        t0 = time.monotonic()
        setup_s = t0 - t_process
        if tracer is not None:
            time.sleep(min(1.0, seconds / 4))
            tracer.start()
            time.sleep(min(trace_s, seconds / 2))
            tracer.stop()
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = time.monotonic()
        with loop.lock:
            taken_at_t1 = loop.taken
        registry_after = _registry_reading(engine)
        compiled_inside = compiles.requests - requests_before
        # -- closed: the sample is fixed; the load stays until it is done -----
        loop.wait_until_done(submitted_before=t1)
        programs_after = engine.programs_compiled()
        spans = adapter.program_spans(telemetry.tracer, sync_t) \
            if telemetry is not None else []
    finally:
        if tracer is not None and tracer.running:
            tracer.stop()
        loop.stop.set()
        engine.close()  # fails what is still in flight (not in the sample)
        loop.join()
    leaked = engine.kv_outstanding()
    del engine, loop.engine
    gc.collect()
    memory_peak = device.peak_memory_bytes()

    served = loop.served
    sample = [r for r in served if t0 <= r.submit_t < t1]
    ok = [r for r in sample if not _failed(r)]
    completed_in = [r for r in served
                    if not _failed(r) and t0 <= r.done_t < t1]
    out_tokens = sum(tokens_inside(r, t0, t1) for r in served
                     if not _failed(r))
    ttft = [(r.prefill_done_t - r.submit_t) * 1e3 for r in ok]
    tpot = [(r.done_t - r.prefill_done_t) * 1e3 / (len(r.tokens) - 1)
            for r in ok if len(r.tokens) > 1]
    print(f"# set-up {setup_s:.2f} s: {t_run - t_process:.2f} to this call "
          f"(jax's import, the chip's runtime), {t_adapter - t_run:.2f} the "
          f"adapter's imports, {t_requests - t_adapter:.2f} the list "
          f"of {len(requests)} requests, {t_built - t_requests:.2f} weights "
          f"and engine, {t_warm - t_built:.2f} warm-up of {programs} "
          f"programs, {t0 - t_warm:.2f} ramp", flush=True)
    print(f"# window: {len(sample)} requests submitted, {len(ok)} sound, "
          f"{len(completed_in)} completed inside, {out_tokens:.1f} tokens "
          f"generated inside; {taken_at_t1} of {len(requests)} offered were "
          f"taken by its close; "
          f"ttft over {len(ttft)} samples, tpot over {len(tpot)}; "
          f"{programs} programs; {compiles.snapshot()}", flush=True)

    verdict = check.Verdict(cell.limits)
    verdict.require("no_compile_inside_window", compiled_inside == 0
                    and programs_after == programs,
                    detail=[compiled_inside, programs, programs_after])
    verdict.require("no_kv_block_leaked", leaked == 0, detail=leaked)
    # a list that runs dry thins the batch before the window closes: the rate
    # then reads lower the faster the engine is, and the tails swing (PR 39)
    verdict.require("requests_outlast_window",
                    taken_at_t1 + clients <= len(requests),
                    detail=[taken_at_t1, clients, len(requests)])
    verdict.require("every_request_sound", bool(sample)
                    and len(ok) == len(sample),
                    detail=[r.error or r.finish_reason
                            for r in sample if _failed(r)][:3])

    # -- the reference, once the program's state is freed -----------------------
    ref = cell.reference()
    picked = pick_sample(ok, int(mix["check_requests"]), seed)
    t_ref = time.monotonic()
    reading = widest_gap_of_sample(ref, params, d["heads"], picked,
                                   pad_to=int(mix["check_pad_to"]))
    print(f"# reference: {len(picked)} requests, {reading['tokens']} served "
          f"tokens ({reading.get('not_argmax')} not the reference's first) "
          f"in {time.monotonic() - t_ref:.2f} s", flush=True)
    verdict.require("sample_holds_served_tokens", reading["tokens"] > 0,
                    detail=reading["tokens"])
    verdict.compare("served_token_logit_gap", reading["gap"])

    controlled = None
    if control is not None:
        controlled = {"served_token_logit_gap": widest_gap_of_sample(
            ref, params, d["heads"], picked, pad_to=int(mix["check_pad_to"]),
            scored_by=control)["gap"]}

    result: Dict[str, Any] = {
        "checks": verdict.rows, "control": controlled,
        "correct": verdict.correct, "attempted": len(sample),
        "failed": len(sample) - len(ok), "memory_peak_bytes": memory_peak,
        "values": {
            "setup_s": setup_s,
            "serve_tokens_per_s": out_tokens / (t1 - t0),
            "ttft_p95_ms": stats.percentile(ttft, 95) if ttft else None,
            "tpot_p95_ms": stats.percentile(tpot, 95) if tpot else None,
        },
    }
    if traced:
        result["layer_context"] = {
            "cell": cell, "kind": "serve", "window_s": t1 - t0,
            "spans": [s for s in spans if t0 <= s[1] < t1],
            "registry": {k: registry_after[k] - registry_before[k]
                         for k in registry_after},
            "ttft_ms": ttft, "tpot_ms": tpot,
            "memory_peak_bytes": memory_peak,
            "trace": trace.reduce_trace(
                tracer.load(), program_spans=[s[:3] for s in spans],
                sync_monotonic=tracer.sync_monotonic),
        }
    return result


def _registry_reading(engine: Any) -> Dict[str, float]:
    """Counts and sums of the engine's histograms and counters that the
    per-layer metrics read, taken at the window's edges."""
    reg = engine.registry
    out: Dict[str, float] = {}
    for name in ("serving_queue_wait_seconds", "serving_prefill_seconds",
                 "serving_decode_step_seconds"):
        h = reg.histogram(name)
        out[name + ".count"] = float(h.count)
        out[name + ".sum"] = float(h.sum)
    out["serving_tokens_generated_total"] = float(
        reg.counter("serving_tokens_generated_total").value)
    return out
