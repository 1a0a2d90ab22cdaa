"""A training cell: one ``Trainer.fit`` that set-up drives through its first
steps and its warm-up and then hands, the same object, to the timed window.

Timeline of a run (steps = optimizer steps, unit = the mix's
``scheduling_unit``):

    steps 1, 2, 3      one report each: the losses and the state the
                       reference is compared with (set-up)
    to the unit grid,  compile has happened, queues are full, every
    then warm_units    program of a whole unit has run once (set-up)
    window             whole units between report boundaries, for --seconds
    after the fit      peak memory is read, then the reference follows the
                       first three steps and ``correct`` is decided

Nothing compiles inside the window (counted). No validation, checkpoint or
preemption falls inside it.
"""
from __future__ import annotations

import math
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from benchmarks.harness import check, device, flops, stats, trace, traffic
from benchmarks.harness.spec import BENCH_DIR, Cell

CHECK_STEPS = 3
WORK_DIR = os.path.join(BENCH_DIR, ".work")


class _Run:
    """The harness's side of the fit: it sits in the searcher's and the
    metrics backend's seats (``adapter.run_training``)."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 tracer: Optional[trace.TraceWindow],
                 compiles: device.CompileCounter, t_process: float,
                 adapter: Any) -> None:
        mix = cell.traffic
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.tracer, self.compiles, self.adapter = tracer, compiles, adapter
        self.t_process = t_process
        self.unit = int(mix["scheduling_unit"])
        # the first report on the unit's grid after the checked steps, then
        # warm_units whole units (the first whole unit compiles the metric
        # reduction for its length), then the window
        self.grid_steps = (CHECK_STEPS // self.unit + 1) * self.unit
        self.warm_steps = self.grid_steps + self.unit * int(mix["warm_units"])
        self.trace_s = float(mix.get("trace_seconds", 4))
        self.b1 = float(cell.config["training"]["optimizer"]["b1"])
        # (time, steps, loss, compile requests so far) at every report
        self.boundaries: List[Tuple[float, int, float, int]] = []
        self.window_start: Optional[float] = None
        self.deadline = math.inf
        self.setup_s: Optional[float] = None
        self.first_grad_norms: Any = None
        self.first_grad_probe: Any = None
        self.change_norms: Any = None
        # (time, steps) when the tracer had started and when it was about
        # to stop: the traced run's own rate leaves the profiler's stalls out
        self.traced_from: Optional[Tuple[float, int]] = None
        self.traced_to: Optional[Tuple[float, int]] = None

    # -- the searcher's seat -----------------------------------------------

    def next_target(self, done: int) -> Optional[int]:
        if done < CHECK_STEPS:
            return done + 1
        if self.seconds <= 0:
            return None
        if done < self.grid_steps:
            return self.grid_steps
        last_unit_s = self.boundaries[-1][0] - self.boundaries[-2][0] \
            if done > self.warm_steps else 0.0
        if time.monotonic() + last_unit_s > self.deadline:
            return None
        return done + self.unit

    # -- the metrics backend's seat ------------------------------------------

    def on_boundary(self, steps: int, metrics: Dict[str, Any],
                    get_state: Callable[[], Any]) -> None:
        import jax

        now = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.report_boundary"):
            loss = metrics.get("loss")
            loss = float(loss) if isinstance(loss, (int, float)) \
                else math.nan
            self.boundaries.append((now, steps, loss,
                                    self.compiles.requests))
            if steps == 1:
                mu = self.adapter.adam_first_moment(get_state().opt_state)
                self.first_grad_norms = jax.device_get(
                    jax.jit(check.leaf_norms)(mu)) / (1.0 - self.b1)
                self.first_grad_probe = jax.device_get(jax.jit(
                    check.leaf_projections)(
                    mu, probe_key(self.adapter, self.seed))) \
                    / (1.0 - self.b1)
            elif steps == CHECK_STEPS:
                self.change_norms = jax.device_get(self._change(
                    get_state().params))
            if steps == self.warm_steps:
                # the window opens here: everything before was set-up
                now = time.monotonic()
                self.boundaries[-1] = (now, steps, loss,
                                       self.compiles.requests)
                self.window_start = now
                self.deadline = now + self.seconds
                self.setup_s = now - self.t_process
            self._drive_tracer(now, steps)

    def _change(self, params: Any) -> Any:
        """Per-leaf norm of (params - the seeded initial weights); the
        initial weights are made again inside the call, where ``params``
        lives, and kept nowhere. The seed goes in as an argument: a constant
        would make a program of its own, and a cold compile, of every
        seed."""
        import jax

        config, adapter = self.cell.config, self.adapter
        shardings = jax.tree.map(lambda x: x.sharding, params)

        @jax.jit
        def change(p, key):
            p0 = jax.lax.with_sharding_constraint(
                adapter._weights(key, adapter.dims(config)), shardings)
            return check.leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))

        return change(params, adapter.seed_key(self.seed))

    def _drive_tracer(self, now: float, steps: int) -> None:
        """Trace from the window's second boundary for ``trace_s`` seconds,
        stopping at a boundary (the device is idle there)."""
        if self.tracer is None or self.window_start is None:
            return
        if self.tracer.started_at is None:
            if now > self.window_start:
                self.tracer.start()
                self.traced_from = (time.monotonic(), steps)
        elif self.tracer.running \
                and now - self.tracer.started_at >= self.trace_s:
            self.traced_to = (now, steps)
            self.tracer.stop()


def probe_key(adapter: Any, seed: int) -> Any:
    """The key of the directions the first gradient is projected on: from
    the run's seed, the same on the program's side and the reference's."""
    import jax

    return jax.random.fold_in(adapter.seed_key(seed), 7)


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_process: float, dev: Dict[str, Any],
        control: Optional[str] = None) -> Dict[str, Any]:
    """Drive the cell and return the pieces of the result line.

    ``seconds <= 0`` ends the fit after the checked steps (the readings a
    limit is set from need no window). ``control`` names a lower precision:
    the reference computed in it is then compared with the reference proper,
    as the program is (``tools/readings.py``; never in a benchmark run)."""
    import jax

    adapter = cell.adapter()
    compiles = device.CompileCounter()
    d = adapter.dims(cell.config)
    mix = cell.traffic
    global_batch = int(cell.config["training"]["global_batch_size"])
    seq_len = int(mix["seq_len"])
    batches = traffic.TrainBatches(mix, d["vocab"], global_batch, seed)
    tracer = trace.TraceWindow(os.path.join(BENCH_DIR, ".trace", cell.name)) \
        if traced else None
    run_ = _Run(cell, seed, seconds, tracer, compiles, t_process, adapter)

    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR, exist_ok=True)
    try:
        out = adapter.run_training(
            cell.config, seed=seed, chips=cell.chips, mesh=cell.mesh,
            seq_len=seq_len, scheduling_unit=run_.unit,
            prefetch_depth=int(mix["prefetch_depth"]), batches=batches,
            hooks=run_, observe=traced, workdir=WORK_DIR)
    finally:
        if tracer is not None and tracer.running:
            tracer.stop()
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    memory_peak = device.peak_memory_bytes()

    # -- the window ---------------------------------------------------------
    window = stats.whole_units(
        [(t, n) for t, n, _, _ in run_.boundaries],
        run_.window_start or math.inf, run_.deadline)
    inside = [b for b in run_.boundaries
              if run_.window_start is not None
              and run_.window_start <= b[0] <= run_.deadline]
    verdict = check.Verdict(cell.limits)
    verdict.require("window_holds_whole_units", window is not None
                    and window[2] >= 2, detail=window)
    seconds_in, steps_in, units_in = window or (math.nan, 0, 0)
    tokens_per_s = steps_in * global_batch * seq_len / seconds_in \
        if window else math.nan
    compiled_inside = inside[-1][3] - inside[0][3] if inside else -1
    verdict.require("no_compile_inside_window", compiled_inside == 0,
                    detail=compiled_inside)
    losses_in = [b[2] for b in inside[1:]]
    bad_units = sum(1 for v in losses_in if not math.isfinite(v))
    verdict.require("window_losses_finite", bad_units == 0
                    and bool(losses_in))
    first_loss = run_.boundaries[0][2] if run_.boundaries else math.nan
    verdict.require("loss_fell", bool(losses_in)
                    and losses_in[-1] < first_loss,
                    detail=[first_loss, losses_in[-1:] or None])
    unit_s = [round(b[0] - a[0], 4) for a, b in zip(inside, inside[1:])]
    print(f"# window: {units_in} units, {steps_in} steps, "
          f"{seconds_in:.4f} s (units: {unit_s}), {compiles.snapshot()}",
          flush=True)

    # -- the reference follows the first three steps ---------------------------
    ref = cell.reference()
    shardings = None
    if cell.chips > 1:
        shapes = jax.eval_shape(
            lambda: adapter._weights(jax.random.PRNGKey(0), d))
        shardings = check.spread_over(jax.devices(), shapes)
    key = probe_key(adapter, seed)

    def follow(precision: str) -> Dict[str, Any]:
        return ref.train_three_steps(
            adapter.make_weights(cell.config, seed, shardings),
            [batches.batch(i) for i in range(CHECK_STEPS)],
            n_heads=d["heads"],
            optimizer=cell.config["training"]["optimizer"],
            precision=precision,
            rows_per_block=int(cell.config["training"]["reference_rows"]),
            probe=check.leaf_projections, probe_arg=key)

    t_ref = time.monotonic()
    followed = follow("f32")
    print(f"# reference: {CHECK_STEPS} steps in "
          f"{time.monotonic() - t_ref:.2f} s", flush=True)
    controlled = None
    if control is not None:
        low = follow(control)
        controlled = {
            "loss_rel_gap": check.worst_loss_gap(
                low["losses"], followed["losses"]),
            "first_grad_leaf_norm_gap": check.worst_leaf_gap(
                low["first_grad_leaf_norms"],
                followed["first_grad_leaf_norms"]),
            "first_grad_leaf_difference": check.worst_leaf_difference(
                low["first_grad_probe"], followed["first_grad_probe"],
                followed["first_grad_leaf_norms"]),
            "param_change_leaf_norm_gap": check.worst_leaf_gap(
                low["param_change_leaf_norms"],
                followed["param_change_leaf_norms"]),
        }
    program_losses = [b[2] for b in run_.boundaries[:CHECK_STEPS]]
    print(f"# losses: program {program_losses} reference "
          f"{followed['losses']}", flush=True)
    verdict.compare("loss_rel_gap", check.worst_loss_gap(
        program_losses, followed["losses"]))
    verdict.compare("first_grad_leaf_norm_gap", check.worst_leaf_gap(
        run_.first_grad_norms, followed["first_grad_leaf_norms"]))
    verdict.compare("first_grad_leaf_difference",
                    check.worst_leaf_difference(
                        run_.first_grad_probe, followed["first_grad_probe"],
                        followed["first_grad_leaf_norms"]))
    verdict.compare("param_change_leaf_norm_gap", check.worst_leaf_gap(
        run_.change_norms, followed["param_change_leaf_norms"]))

    result: Dict[str, Any] = {
        "correct": verdict.correct, "attempted": steps_in,
        "failed": bad_units * run_.unit if window else max(steps_in, 1),
        "memory_peak_bytes": memory_peak, "checks": verdict.rows,
        "control": controlled,
        "values": {"setup_s": run_.setup_s,
                   "train_tokens_per_s_per_chip": tokens_per_s / cell.chips},
    }
    if traced:
        spans = out["spans"]
        if run_.traced_from and run_.traced_to:
            (ta, na), (tb, nb) = run_.traced_from, run_.traced_to
            tokens_per_s = (nb - na) * global_batch * seq_len / (tb - ta)
        in_window = [s for s in spans if inside
                     and inside[0][0] <= s[1] <= inside[-1][0]]
        result["layer_context"] = {
            "cell": cell, "kind": "train", "window_s": seconds_in,
            "spans": in_window, "tokens_per_s": tokens_per_s,
            "memory_peak_bytes": memory_peak,
            "flops_per_token": flops.train_flops_per_token(d, seq_len),
            "peak_flops_per_s": device.PEAKS[dev["kind"]]["bf16_flops_per_s"],
            "trace": trace.reduce_trace(
                tracer.load(),
                program_spans=[s[:3] for s in spans],
                sync_monotonic=tracer.sync_monotonic),
        }
    return result
