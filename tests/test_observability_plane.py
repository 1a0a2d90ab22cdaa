"""Cluster observability plane (docs/observability.md): the analytic FLOPs
engine, master-side aggregation (ingest gates, dedup, Prometheus rollups),
the in-process master's HTTP front-end, cross-component trace stitching
through a real experiment."""
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from determined_clone_tpu.api.inprocess import (
    InProcessMaster,
    InProcessSession,
    MasterHTTPServer,
)
from determined_clone_tpu.config import ExperimentConfig
from determined_clone_tpu.experiment import LocalExperimentRunner
from determined_clone_tpu.parallel import MeshSpec, make_mesh
from determined_clone_tpu.telemetry import flops as flops_mod
from determined_clone_tpu.telemetry import (
    parse_prometheus_text,
    validate_chrome_trace,
)
from determined_clone_tpu.telemetry.aggregate import (
    MAX_INGEST_BATCH,
    MAX_SAMPLE_BYTES,
    ClusterMetricsAggregator,
)
from determined_clone_tpu.training import JaxTrial
from determined_clone_tpu.utils.retry import RetryPolicy


# ---------------------------------------------------------------------------
# Analytic FLOPs / MFU engine
# ---------------------------------------------------------------------------

class TestFlops:
    def test_attention_formula(self):
        # L * (8 d^2 + 4 s d) per token
        assert flops_mod.attention_flops_per_token(
            d_model=64, seq_len=128, n_layers=2
        ) == 2 * (8 * 64**2 + 4 * 128 * 64)

    def test_mlp_dense_vs_moe(self):
        dense = flops_mod.mlp_flops_per_token(64, 256, n_layers=2)
        assert dense == 2 * 4 * 64 * 256
        # top-1 of 8 experts: one expert's compute + the router
        moe = flops_mod.mlp_flops_per_token(
            64, 256, n_layers=2, moe_experts=8, moe_k=1)
        assert moe == 2 * (4 * 64 * 256 + 2 * 64 * 8)

    def test_moe_layer_flops_hand_computed(self):
        # N=8 tokens, D=4, F=8, E=2 experts, cf=1.0 -> C = ceil(8/2) = 4
        # router  2*8*4*2        = 128
        # dispatch 2*8*2*4*4     = 512
        # up      2*2*4*4*8      = 512
        # down    2*2*4*8*4      = 512
        # combine 2*8*2*4*4      = 512
        out = flops_mod.moe_layer_flops(8, 4, 8, 2, capacity_factor=1.0)
        assert out["capacity"] == 4
        assert out["router"] == 128
        assert out["dispatch"] == 512
        assert out["up"] == 512
        assert out["down"] == 512
        assert out["combine"] == 512
        assert out["total"] == 2176

    def test_moe_capacity_shapes_the_count(self):
        # the einsum-dispatch count grows with E*C, not top-k: raising the
        # capacity factor raises expert + dispatch/combine terms alike
        lo = flops_mod.moe_layer_flops(8, 4, 8, 2, capacity_factor=1.0)
        hi = flops_mod.moe_layer_flops(8, 4, 8, 2, capacity_factor=1.25)
        assert hi["capacity"] == 5 and lo["capacity"] == 4
        assert hi["up"] / lo["up"] == pytest.approx(5 / 4)
        assert hi["dispatch"] / lo["dispatch"] == pytest.approx(5 / 4)
        assert hi["router"] == lo["router"]  # router sees N, not C
        # capacity floors at one slot per expert
        tiny = flops_mod.moe_layer_flops(2, 4, 8, 8, capacity_factor=1.0)
        assert tiny["capacity"] == 1

    def test_gpt_step_uses_exact_moe_count(self):
        class Cfg:
            n_layers, d_model, n_heads = 2, 64, 4
            d_ff, vocab_size, max_seq_len = 256, 512, 32
            moe_experts, moe_capacity_factor = 4, 1.0

        step = flops_mod.gpt_train_step_flops(Cfg(), batch_size=2)
        layer = flops_mod.moe_layer_flops(
            step.tokens, 64, 256, 4, capacity_factor=1.0)
        # the step-level mlp term is the exact capacity-based layer count
        # (x layers x train multiplier), not the top-k approximation
        assert step.breakdown["mlp"] == pytest.approx(
            Cfg.n_layers * layer["total"] * flops_mod.TRAIN_MULT)
        approx = (flops_mod.mlp_flops_per_token(
            64, 256, n_layers=2, moe_experts=4) * flops_mod.TRAIN_MULT
            * step.tokens)
        assert step.breakdown["mlp"] != pytest.approx(approx)

    def test_gpt_step_scales_with_batch(self):
        class Cfg:
            n_layers, d_model, n_heads = 2, 64, 4
            d_ff, vocab_size, max_seq_len = 256, 512, 32

        one = flops_mod.gpt_train_step_flops(Cfg(), batch_size=1)
        four = flops_mod.gpt_train_step_flops(Cfg(), batch_size=4)
        assert four.total == pytest.approx(4 * one.total)
        assert one.tokens == 32
        # training = 3x forward
        fwd = flops_mod.gpt_forward_flops_per_token(Cfg(), 32)
        assert one.per_token == pytest.approx(
            flops_mod.TRAIN_MULT * sum(fwd.values()))

    def test_dense_6n_fallback(self):
        assert flops_mod.dense_train_flops_per_token(1000) == 6000
        step = flops_mod.dense_train_step_flops(
            1000, batch_size=2, seq_len=8)
        assert step.total == 6000 * 16

    def test_mfu_and_cpu_peak_label(self):
        peak, label = flops_mod.peak_flops_estimate("cpu")
        assert label == "cpu:est"
        assert flops_mod.mfu(peak / 2, peak) == pytest.approx(0.5)
        assert flops_mod.mfu(peak, peak, n_devices=4) == pytest.approx(0.25)

    def test_tpu_peak_from_device_kind_and_unknown_has_none(self):
        peak, label = flops_mod.peak_flops_estimate("tpu", "TPU v5 lite")
        assert peak == flops_mod.TPU_PEAK_BF16_FLOPS["v5e"] == 197e12
        assert label == "tpu:v5e"
        # a kind that is not in the table: no peak, no MFU — never a
        # default, and the label says which kind it was
        peak, label = flops_mod.peak_flops_estimate("tpu", "TPU v9 mega")
        assert peak is None and label == "tpu:unknown-kind:TPU v9 mega"
        assert flops_mod.mfu(1e12, peak) is None
        bw, label = flops_mod.interconnect_bandwidth_estimate(
            "tpu", "TPU v9 mega")
        assert bw is None and label == "tpu:unknown-kind:TPU v9 mega"


# ---------------------------------------------------------------------------
# Master-side aggregation: ingest gates, dedup, rollups
# ---------------------------------------------------------------------------

def _telemetry_sample(metrics):
    return {"time": 1.0, "group": "telemetry", "metrics": metrics}


def _gauge(v):
    return {"type": "gauge", "value": v}


class TestAggregator:
    def test_idempotent_ingest_counts_duplicates(self):
        agg = ClusterMetricsAggregator()
        batch = [_telemetry_sample({"samples_per_sec": _gauge(10.0)})]
        assert agg.ingest(1, batch, idempotency_key="k1") == 1
        assert agg.ingest(1, batch, idempotency_key="k1") == 0
        text = agg.dump()
        assert "dct_master_ingest_duplicates_total 1" in text
        assert "dct_master_ingest_batches_total 1" in text

    def test_rejection_reasons_counted(self):
        agg = ClusterMetricsAggregator()
        agg.ingest(1, "not a list")                     # not_a_list
        agg.ingest(1, [{}] * (MAX_INGEST_BATCH + 1))    # batch_too_large
        agg.ingest(1, [{"group": 7}])                   # malformed
        agg.ingest(1, [{"group": "span",
                        "blob": "x" * (MAX_SAMPLE_BYTES + 1)}])  # oversized
        parsed = parse_prometheus_text(agg.dump())
        rejected = {labels["reason"]: v for n, labels, v in parsed["samples"]
                    if n == "dct_master_ingest_rejected_total"}
        assert rejected["not_a_list"] >= 1
        assert rejected["batch_too_large"] >= 1
        assert rejected["malformed"] >= 1
        assert rejected["oversized"] >= 1

    def test_rollup_sums_across_trials(self):
        agg = ClusterMetricsAggregator()
        agg.ingest(1, [_telemetry_sample(
            {"samples_per_sec": _gauge(10.0)})], idempotency_key="a")
        agg.ingest(2, [_telemetry_sample(
            {"samples_per_sec": _gauge(30.0)})], idempotency_key="b")
        parsed = parse_prometheus_text(agg.dump())
        flat = {(n, labels.get("trial_id")): v
                for n, labels, v in parsed["samples"]}
        assert flat[("samples_per_sec", "1")] == 10.0
        assert flat[("samples_per_sec", "2")] == 30.0
        assert flat[("dct_cluster_samples_per_sec", None)] == 40.0
        assert flat[("dct_cluster_samples_per_sec_avg", None)] == 20.0

    def test_summary_ranks_by_throughput(self):
        agg = ClusterMetricsAggregator()
        for tid, rate in ((1, 5.0), (2, 50.0), (3, 20.0)):
            agg.ingest(tid, [_telemetry_sample(
                {"samples_per_sec": _gauge(rate)})],
                idempotency_key=f"t{tid}")
        s = agg.summary(top_n=2)
        assert [t[0] for t in s["top_trials_by_throughput"]] == ["2", "3"]
        assert s["throughput_total"] == pytest.approx(75.0)


# ---------------------------------------------------------------------------
# The in-process master over real HTTP
# ---------------------------------------------------------------------------

class TestMasterHTTP:
    def test_metrics_endpoint_round_trips(self):
        master = InProcessMaster()
        with MasterHTTPServer(master) as srv:
            url = f"http://{srv.host}:{srv.port}"
            body = json.dumps({
                "samples": [_telemetry_sample(
                    {"samples_per_sec": _gauge(12.5)})],
                "idempotency_key": "once",
            }).encode()
            req = urllib.request.Request(
                f"{url}/api/v1/trials/7/profiler", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert json.loads(resp.read())["accepted"] == 1
            with urllib.request.urlopen(f"{url}/metrics",
                                        timeout=10) as resp:
                assert resp.headers["Content-Type"].startswith("text/plain")
                text = resp.read().decode()
        parsed = parse_prometheus_text(text)
        flat = {(n, labels.get("trial_id")): v
                for n, labels, v in parsed["samples"]}
        assert flat[("samples_per_sec", "7")] == 12.5
        assert flat[("dct_master_ingest_batches_total", None)] == 1.0
        assert parsed["types"]["samples_per_sec"] == "gauge"

    def test_session_shim_and_404(self):
        master = InProcessMaster()
        session = InProcessSession(master)
        assert session.get("/api/v1/cluster/metrics")["trials"] == 0
        from determined_clone_tpu.api.client import MasterError
        with pytest.raises(MasterError):
            session.get("/api/v1/nope")


# ---------------------------------------------------------------------------
# E2E: an experiment drives the whole plane
# ---------------------------------------------------------------------------

class PlaneTrial(JaxTrial):
    """Tiny quadratic trial that fails its first leg so the plane sees a
    restart (retry counters > 0, restart leg as a sibling trace lane)."""

    _failed = {}

    def initial_params(self, rng):
        return {"w": jnp.zeros(())}

    def optimizer(self):
        return optax.sgd(0.3)

    def loss(self, params, batch, rng):
        return (params["w"] - 1.0) ** 2, {}

    def training_data(self):
        if not PlaneTrial._failed.get("done"):
            PlaneTrial._failed["done"] = True
            raise RuntimeError("injected failure")
        for _ in range(64):
            yield np.zeros((2, 1), np.float32)

    def validation_data(self):
        return [np.zeros((2, 1), np.float32)]

    @property
    def global_batch_size(self):
        return 2


@pytest.fixture(scope="module")
def plane(tmp_path_factory):
    """One observability-enabled experiment run against an in-process
    master, shared by the assertions below."""
    PlaneTrial._failed = {}
    tmp_path = tmp_path_factory.mktemp("plane")
    cfg = ExperimentConfig.from_dict({
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 4}},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(tmp_path)},
        "hyperparameters": {"lr": 0.5},
        "max_restarts": 1,
        "observability": {"enabled": True, "ship_spans": True,
                          "ship_metrics": True},
    })
    master = InProcessMaster()
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    runner = LocalExperimentRunner(
        cfg, PlaneTrial, storage_path=str(tmp_path), mesh=mesh,
        master=master, experiment_id=1,
        restart_backoff=RetryPolicy(name="test", base_delay_s=0.0,
                                    max_delay_s=0.0, jitter="none"))
    result = runner.run()
    return master, runner, result


class TestExperimentE2E:
    def test_run_completed_with_restart(self, plane):
        _, _, result = plane
        t = list(result.trials.values())[0]
        assert t.state == "completed"
        assert t.restarts == 1

    def test_metrics_page_has_rollups_and_counters(self, plane):
        master, _, _ = plane
        parsed = parse_prometheus_text(master.metrics_text())
        names = {n for n, _, _ in parsed["samples"]}
        # rolled-up trial throughput + per-step MFU accounting
        assert "dct_cluster_samples_per_sec" in names
        assert "dct_cluster_mfu" in names
        assert "dct_cluster_flops_per_sec" in names
        # the runner lane's restart counter made it in and rolled up
        flat = {(n, labels.get("component")): v
                for n, labels, v in parsed["samples"]}
        assert flat[("trial_restarts_total", "runner")] == 1.0
        assert flat[("dct_cluster_trial_restarts_total", None)] == 1.0
        assert flat[("dct_master_ingest_duplicates_total", None)] == 0.0

    def test_mfu_gauges_carry_provenance(self, plane):
        master, _, _ = plane
        parsed = parse_prometheus_text(master.metrics_text())
        infos = [labels for n, labels, _ in parsed["samples"]
                 if n == "mfu_peak_info"]
        assert infos, "trainer never shipped mfu_peak_info"
        assert all(i["assumed"] == "cpu:est" for i in infos)
        assert all(i["flops_source"] == "dense_6n" for i in infos)
        mfus = [v for n, _, v in parsed["samples"] if n == "mfu"]
        assert mfus and all(v > 0 for v in mfus)

    def test_summary_view(self, plane):
        master, _, _ = plane
        s = master.summary()
        assert s["trials"] == 1
        assert s["top_trials_by_throughput"][0][0] == "0"
        assert s["counters"].get("trial_restarts_total") == 1

    def test_cli_trace_export_stitches_experiment(self, plane, tmp_path):
        from determined_clone_tpu.cli.cli import main

        master, runner, _ = plane
        out = tmp_path / "trace.json"
        with MasterHTTPServer(master) as srv:
            rc = main(["-m", f"{srv.host}:{srv.port}", "trace", "export",
                       "--experiment", "1", "-o", str(out)])
        assert rc == 0
        with open(out) as f:
            trace = json.load(f)
        assert validate_chrome_trace(trace) == []
        # >= 2 process lanes (runner + the trial), one shared trace_id
        lanes = trace["otherData"]["processes"]
        assert "runner" in lanes and "trial-0" in lanes
        assert len(lanes) >= 2
        assert trace["otherData"]["trace_ids"] == [runner.trace_id]
        # the restart shows as sibling trial_leg spans in the runner lane
        legs = [e for e in trace["traceEvents"]
                if e.get("name") == "trial_leg"]
        assert len(legs) == 2
        assert len({e["pid"] for e in legs}) == 1

    def test_cli_metrics_summary_and_raw(self, plane, capsys):
        from determined_clone_tpu.cli.cli import main

        master, _, _ = plane
        with MasterHTTPServer(master) as srv:
            addr = f"{srv.host}:{srv.port}"
            assert main(["-m", addr, "metrics"]) == 0
            human = capsys.readouterr().out
            assert main(["-m", addr, "metrics", "--raw"]) == 0
            raw = capsys.readouterr().out
        assert "trial" in human

        def stable(text):
            # dct_master_source_age_seconds is wall-clock-valued: the two
            # dumps happen at different instants, so ages differ
            return [s for s in parse_prometheus_text(text)["samples"]
                    if s[0] != "dct_master_source_age_seconds"]

        assert stable(raw) == stable(master.metrics_text())
