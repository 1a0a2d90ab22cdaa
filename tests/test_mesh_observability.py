"""Mesh observability tests (ISSUE 15): collective accounting from
post-SPMD HLO text, cross-device straggler detection, per-device Chrome
trace lanes, and the cluster rollup.

HLO fixtures use both replica-group syntaxes the parser understands
(explicit lists and the iota form) on a {dp: 4, tp: 2} logical mesh:
flattened partition ids arange(8).reshape(4, 2), so the dp groups are
{{0,2,4,6},{1,3,5,7}} (vary dp, hold tp) and the tp groups are
{{0,1},{2,3},{4,5},{6,7}}.
"""

import jax
import jax.numpy as jnp
import pytest

from determined_clone_tpu.telemetry import MetricsRegistry
from determined_clone_tpu.telemetry.aggregate import ClusterMetricsAggregator
from determined_clone_tpu.telemetry.chrome_trace import (
    stitch_chrome_trace,
    validate_chrome_trace,
)
from determined_clone_tpu.telemetry.collectives import (
    CollectiveSummary,
    comm_compute_fraction,
    export_collectives,
    parse_hlo_collectives,
    parse_replica_groups,
)
from determined_clone_tpu.telemetry.mesh import (
    MeshStragglerDetector,
    device_lane_records,
    per_device_completion_seconds,
)

MESH = {"dp": 4, "tp": 2}

HLO_ALL_REDUCE_DP = """
ENTRY main {
  %p0 = f32[128]{0} parameter(0)
  %ar = f32[128]{0} all-reduce(%p0), replica_groups={{0,2,4,6},{1,3,5,7}}, to_apply=%add
  ROOT %r = f32[128]{0} copy(%ar)
}
"""

# iota form of the SAME dp groups: arange(8).reshape(4,2) transposed to
# (tp, dp) and raveled -> [0,2,4,6,1,3,5,7], split into 2 groups of 4
HLO_ALL_GATHER_DP_IOTA = """
ENTRY main {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %ag = bf16[32,64]{1,0} all-gather(%p0), replica_groups=[2,4]<=[4,2]T(1,0), dimensions={0}
}
"""

HLO_REDUCE_SCATTER_TP = """
ENTRY main {
  %p0 = f32[64]{0} parameter(0)
  %rs = f32[32]{0} reduce-scatter(%p0), replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}, to_apply=%add
}
"""

# empty replica_groups: one group of all 8 partitions -> the full-mesh
# dp+tp combo
HLO_ALL_TO_ALL_FULL = """
ENTRY main {
  %p0 = f32[16,16]{1,0} parameter(0)
  %a2a = f32[16,16]{1,0} all-to-all(%p0), replica_groups={}, dimensions={0}
}
"""

# ring shift inside each tp group
HLO_PERMUTE_TP = """
ENTRY main {
  %p0 = f32[256]{0} parameter(0)
  %cp = f32[256]{0} collective-permute(%p0), source_target_pairs={{0,1},{1,0},{2,3},{3,2},{4,5},{5,4},{6,7},{7,6}}
}
"""

# async pair describes ONE transfer; tuple result sums both operands
HLO_ASYNC_VARIADIC = """
ENTRY main {
  %ars = (f32[64]{0}, f32[64]{0}) all-reduce-start(%a, %b), replica_groups={{0,2,4,6},{1,3,5,7}}, to_apply=%add
  %ard = (f32[64]{0}, f32[64]{0}) all-reduce-done(%ars)
}
"""

HLO_NO_COLLECTIVES = """
ENTRY main {
  %p0 = f32[128]{0} parameter(0)
  ROOT %t = f32[128]{0} tanh(%p0)
}
"""


class TestHloParsing:
    def test_all_reduce_dp_count_and_bytes(self):
        s = parse_hlo_collectives(HLO_ALL_REDUCE_DP, mesh=MESH)
        assert s.count("all-reduce", "dp") == 1
        assert s.bytes("all-reduce", "dp") == 128 * 4
        assert s.n_partitions == 8

    def test_all_gather_iota_groups_attribute_to_dp(self):
        s = parse_hlo_collectives(HLO_ALL_GATHER_DP_IOTA, mesh=MESH)
        assert s.count("all-gather", "dp") == 1
        assert s.bytes("all-gather", "dp") == 32 * 64 * 2  # bf16 result

    def test_reduce_scatter_tp(self):
        s = parse_hlo_collectives(HLO_REDUCE_SCATTER_TP, mesh=MESH)
        assert s.count("reduce-scatter", "tp") == 1
        assert s.bytes("reduce-scatter", "tp") == 32 * 4

    def test_all_to_all_empty_groups_span_full_mesh(self):
        s = parse_hlo_collectives(HLO_ALL_TO_ALL_FULL, mesh=MESH)
        assert s.count("all-to-all", "dp+tp") == 1

    def test_collective_permute_pairs_attribute_to_tp(self):
        s = parse_hlo_collectives(HLO_PERMUTE_TP, mesh=MESH)
        assert s.count("collective-permute", "tp") == 1
        assert s.bytes("collective-permute", "tp") == 256 * 4

    def test_async_pair_counts_once_and_sums_tuple(self):
        s = parse_hlo_collectives(HLO_ASYNC_VARIADIC, mesh=MESH)
        assert s.count("all-reduce") == 1
        assert s.bytes("all-reduce", "dp") == 2 * 64 * 4

    def test_no_collectives_is_empty(self):
        s = parse_hlo_collectives(HLO_NO_COLLECTIVES, mesh=MESH)
        assert s.total_ops == 0
        assert s.total_bytes == 0.0

    def test_without_mesh_ops_land_on_other(self):
        s = parse_hlo_collectives(HLO_ALL_REDUCE_DP)
        assert s.count("all-reduce", "other") == 1

    def test_iota_expansion(self):
        line = "x = f32[1] all-gather(y), replica_groups=[2,4]<=[4,2]T(1,0)"
        assert parse_replica_groups(line) == [[0, 2, 4, 6], [1, 3, 5, 7]]

    def test_fingerprint_tracks_structure(self):
        a = parse_hlo_collectives(HLO_ALL_REDUCE_DP, mesh=MESH)
        b = parse_hlo_collectives(HLO_ALL_REDUCE_DP, mesh=MESH)
        c = parse_hlo_collectives(HLO_REDUCE_SCATTER_TP, mesh=MESH)
        assert a.fingerprint() == b.fingerprint()
        assert a.fingerprint() != c.fingerprint()

    def test_comm_fraction_bounds_and_null_flops(self):
        s = parse_hlo_collectives(HLO_ALL_REDUCE_DP, mesh=MESH)
        assert comm_compute_fraction(
            s, None, interconnect_bytes_per_s=1e9,
            peak_flops_per_s=1e12) is None
        frac = comm_compute_fraction(
            s, 1e6, interconnect_bytes_per_s=1e9, peak_flops_per_s=1e12)
        assert 0.0 < frac < 1.0

    def test_export_lands_labeled_gauges(self):
        reg = MetricsRegistry()
        s = parse_hlo_collectives(HLO_ALL_REDUCE_DP, mesh=MESH)
        export_collectives(s, reg, program="fixture",
                           fingerprint="abcd", comm_fraction=0.25)
        text = reg.dump()
        assert 'xla_collective_ops_total{' in text
        assert 'kind="all-reduce"' in text and 'axis="dp"' in text
        assert 'xla_comm_compute_fraction{' in text


class TestStraggler:
    def test_uniform_windows_flag_nobody(self):
        det = MeshStragglerDetector()
        for _ in range(5):
            assert det.observe(
                {f"cpu:{i}": 0.10 + 0.001 * i for i in range(8)}) is None
        assert det.stragglers == 0

    def test_injected_slow_device_increments_exactly_once(self):
        """The acceptance criterion: one injected slow device raises
        exactly one mesh_straggler_events_total increment, labeled with
        THAT device."""
        reg = MetricsRegistry()
        det = MeshStragglerDetector(reg)
        base = {f"cpu:{i}": 0.10 for i in range(8)}
        det.observe(base)
        slow = dict(base, **{"cpu:5": 0.50})
        assert det.observe(slow) == "cpu:5"
        assert det.stragglers == 1
        assert det.by_device == {"cpu:5": 1}
        lines = [ln for ln in reg.dump().splitlines()
                 if ln.startswith("mesh_straggler_events_total{")]
        assert len(lines) == 1
        assert 'device="cpu:5"' in lines[0]
        assert lines[0].rstrip().endswith(" 1.0") or \
            lines[0].rstrip().endswith(" 1")

    def test_only_the_slowest_of_two_is_flagged(self):
        """Followers wait on the same collective as the gang — only the
        single slowest device is independently slow."""
        det = MeshStragglerDetector()
        window = {f"cpu:{i}": 0.10 for i in range(8)}
        window["cpu:2"] = 0.40
        window["cpu:6"] = 0.60
        assert det.observe(window) == "cpu:6"
        assert det.stragglers == 1

    def test_globally_slow_step_flags_nobody(self):
        det = MeshStragglerDetector()
        det.observe({f"cpu:{i}": 0.10 for i in range(8)})
        # everyone 5x slower (input stall): median moves with the gang
        assert det.observe({f"cpu:{i}": 0.50 for i in range(8)}) is None

    def test_min_devices_guard(self):
        det = MeshStragglerDetector()
        assert det.observe({"cpu:0": 9.0}) is None
        assert det.windows == 1

    def test_summary_shape(self):
        det = MeshStragglerDetector()
        base = {f"cpu:{i}": 0.10 for i in range(4)}
        det.observe(base)
        det.observe(dict(base, **{"cpu:1": 1.0}))
        s = det.summary()
        assert s["windows"] == 2 and s["stragglers"] == 1
        assert s["recent_events"][0]["device"] == "cpu:1"


class TestDeviceLanes:
    def test_stitched_trace_has_one_lane_per_device(self):
        n = 8
        durations = {f"cpu:{i}": 0.01 * (i + 1) for i in range(n)}
        records = device_lane_records(durations, start_s=0.0,
                                      wall_epoch=100.0, step_index=3)
        trace = stitch_chrome_trace(records)
        assert validate_chrome_trace(trace) == []
        procs = [e for e in trace["traceEvents"]
                 if e.get("name") == "process_name"]
        assert {e["args"]["name"] for e in procs} == {
            f"device:cpu:{i}" for i in range(n)}
        spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        assert len(spans) == n
        assert {e["pid"] for e in spans} == {e["pid"] for e in procs}

    def test_device_key_fallback_without_process_label(self):
        recs = device_lane_records({"cpu:0": 0.1, "cpu:1": 0.1},
                                   start_s=0.0)
        for r in recs:
            r.pop("process")
        trace = stitch_chrome_trace(recs)
        procs = {e["args"]["name"] for e in trace["traceEvents"]
                 if e.get("name") == "process_name"}
        assert procs == {"device:cpu:0", "device:cpu:1"}


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device simulated mesh")
class TestLiveMesh:
    """End-to-end on the conftest-forced 8-device CPU mesh: a real
    sharded program's compiled HLO must show the dp all-reduce, and the
    per-device completion probe must see every device."""

    def _mesh(self):
        from determined_clone_tpu.parallel.mesh import MeshSpec, make_mesh
        return make_mesh(MeshSpec(dp=-1), jax.devices()[:8])

    def test_sharded_grad_step_counts_dp_all_reduce(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from determined_clone_tpu.telemetry.xla import aot_compile

        mesh = self._mesh()
        x = jax.device_put(
            jnp.ones((8, 16), jnp.float32),
            NamedSharding(mesh, P("dp", None)))
        w = jax.device_put(jnp.ones((16,), jnp.float32),
                           NamedSharding(mesh, P()))

        @jax.jit
        def loss_grad(w, x):
            return jax.grad(lambda w: jnp.sum((x @ w) ** 2))(w)

        reg = MetricsRegistry()
        fn, record = aot_compile(loss_grad, (w, x), program="mesh_test",
                                 registry=reg, mesh=mesh)
        assert record is not None and record.collectives is not None
        # the data-parallel gradient reduction
        assert record.collectives.count("all-reduce", "dp") >= 1
        assert record.collectives.bytes("all-reduce", "dp") > 0
        assert 'xla_collective_ops_total{' in reg.dump()
        out = fn(w, x)
        assert jnp.isfinite(out).all()

    def test_per_device_completion_sees_every_device(self):
        import time
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        x = jax.device_put(jnp.ones((8, 4), jnp.float32),
                           NamedSharding(mesh, P("dp", None)))
        t0 = time.perf_counter()
        y = jax.jit(lambda a: a * 2.0)(x)
        durations = per_device_completion_seconds(y, t0)
        assert set(durations) == {f"cpu:{i}" for i in range(8)}
        assert all(d >= 0 for d in durations.values())


class TestClusterRollup:
    def test_mesh_rollup_from_exposition_text(self):
        reg = MetricsRegistry()
        s = parse_hlo_collectives(HLO_ALL_REDUCE_DP, mesh=MESH)
        export_collectives(s, reg, program="train_step",
                           fingerprint="abcd", comm_fraction=0.33)
        det = MeshStragglerDetector(reg)
        base = {f"cpu:{i}": 0.10 for i in range(8)}
        det.observe(base)
        det.observe(dict(base, **{"cpu:3": 0.9}))

        agg = ClusterMetricsAggregator()
        agg.ingest_prometheus_text("trial-1", reg.dump())
        roll = agg.mesh_rollup()
        assert roll is not None
        assert roll["collective_ops"]["all-reduce"]["dp"] == 1
        assert roll["straggler_events"]["cpu:3"] == 1
        assert roll["straggler_events_total"] == 1
        assert roll["worst_comm_fraction"]["fraction"] == \
            pytest.approx(0.33)
        # the re-exported cluster families + human summary
        dumped = agg.dump()
        assert "dct_mesh_collective_ops " in dumped
        assert "dct_mesh_straggler_events " in dumped
        text_summary = agg.summary()
        assert text_summary["mesh"] is not None

    def test_rollup_none_without_mesh_series(self):
        agg = ClusterMetricsAggregator()
        agg.ingest_prometheus_text("trial-1", "foo_total 1\n")
        assert agg.mesh_rollup() is None
