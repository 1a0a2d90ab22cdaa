"""The trace reduction, on a trace recorded on a v5e chip (``data/
probe1.xplane.pb``: ``tools/probe_trace.py``, six steps of a small jitted
program with a 2 ms sleep after each) and on made-up planes."""
import os

import pytest

from benchmarks.harness import trace
from benchmarks.tests.conftest import DATA


@pytest.fixture(scope="module")
def recorded():
    return trace.load_xplane(os.path.join(DATA, "probe1.xplane.pb"))


def test_recorded_trace_has_the_planes_the_reduction_reads(recorded):
    assert trace.OPS_LINE in recorded["/device:TPU:0"]
    assert len(recorded["/device:TPU:0"]["XLA Modules"]) == 6
    host = [e[0] for line in recorded[trace.HOST_PLANE].values()
            for e in line]
    assert host.count("bench.probe_step") == 6
    assert trace.SYNC_MARK in host


def test_reduction_of_the_recorded_trace(recorded):
    r = trace.reduce_trace(recorded)
    assert r["chips"] == 1
    # six steps of about 15 us inside about 17 ms of sleeping host
    assert 5e-5 < r["busy_s"] < 2e-4
    assert 0.01 < r["window_s"] < 0.03
    assert r["collective_s"] == 0.0
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("fusion convert_reduce_fusion")
    assert all("{" not in n for n in names)  # layouts are stripped
    gaps = dict(r["idle_gaps"])
    # the device waits while the host is inside the step's call and while
    # it sleeps; the gaps between one program's operations are kept apart
    assert gaps["bench.probe_step"] > gaps["bench.probe_sleep"] > 0.002
    assert trace.SHORT_GAP_NAME in gaps
    idle = sum(gaps.values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_program_spans_name_gaps_through_the_sync_mark():
    planes = {
        "/device:TPU:0": {trace.OPS_LINE: [
            ("%a.1 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)", 1.0, 0.1),
            ("%a.1 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)", 2.0, 0.1),
            ("%w.2 = (s32[]) while((s32[]) %t), body=%b", 1.0, 1.1)]},
        trace.HOST_PLANE: {"main": [(trace.SYNC_MARK, 0.5, 0.0)]},
    }
    # the sync mark was made at monotonic time 100.5 -> shift of -100
    r = trace.reduce_trace(planes, sync_monotonic=100.5,
                           program_spans=[("decode", 101.0, 1.5)])
    # the while covers both adds: the union is the while's interval
    assert r["busy_s"] == pytest.approx(1.1)
    assert r["idle_gaps"] == []
    # containers are left out of the ranking, their bodies' ops are kept
    assert r["device_ops"] == [["add a.1 f32[8]", pytest.approx(0.2)]]
    del planes["/device:TPU:0"][trace.OPS_LINE][2]
    r = trace.reduce_trace(planes, sync_monotonic=100.5,
                           program_spans=[("decode", 101.0, 1.5)])
    assert r["idle_gaps"] == [["decode", pytest.approx(0.9)]]


def test_collectives_are_told_by_opcode_not_by_operands():
    gather = ("%all-gather-start.3 = (bf16[8]{0}, bf16[32]{0}) "
              "all-gather-start(bf16[8]{0} %p), dimensions={0}")
    user = ("%fusion.9 = bf16[32]{0} fusion(bf16[32]{0} %all-gather-done.3)"
            ", kind=kLoop")
    assert trace.is_collective(trace.parse_op(gather)[1])
    assert not trace.is_collective(trace.parse_op(user)[1])
    planes = {f"/device:TPU:{i}": {trace.OPS_LINE: [(gather, 0.0, 0.2),
                                                    (user, 0.2, 0.6)]}
              for i in range(4)}
    r = trace.reduce_trace(planes)
    assert r["chips"] == 4
    assert r["collective_s"] == pytest.approx(0.2)
    assert r["busy_s"] == pytest.approx(0.8)


def test_merge():
    assert trace.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
