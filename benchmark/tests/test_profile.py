"""The trace reduction on a small trace recorded on the CPU (three runs of
one jitted matmul under a `bench_step` annotation); the XLA CPU client
thread stands in for a device plane."""

import os

import pytest

from benchmark import profile as prof

TRACE = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")
WINDOW_S = 0.0012145  # host-clock length of the recorded window


@pytest.fixture(scope="module")
def red():
    return prof.reduce(TRACE, device_prefix="/host:CPU", op_line="tf_XLAPjRtCpuClient")


def test_busy_is_a_union_inside_the_window(red):
    busy = prof.busy_seconds(red)
    assert 0 < busy < WINDOW_S
    iv = red["devices"][0]["busy"]
    assert all(a < b for a, b in iv)
    assert all(b1 <= a2 for (_, b1), (a2, _) in zip(iv, iv[1:]))


def test_breakdown_names_ops_and_gaps(red):
    bd = prof.breakdown(red)
    assert bd["device_ops"][0][0] == "dot_general.1"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(s >= 0 for _, s in bd["idle_gaps"])


def test_no_device_plane_reads_nothing():
    red = prof.reduce(TRACE)  # a CPU trace has no /device:<accelerator> plane
    assert prof.busy_seconds(red) is None
    assert prof.module_time(red, "jit_run") == (0.0, 0)


def test_union_and_module_time():
    assert prof._union([(5, 9), (0, 2), (1, 3), (8, 12)]) == [(0, 3), (5, 12)]
    mods = {"jit_run(1)": [(0, 250), (100, 350), (1000, 1100)], "jit_run(2)": [(0, 10**9)]}
    red = {"devices": [{"modules": mods, "lines": [], "busy": [], "ops": {}}], "host": []}
    assert prof.module_time(red, "jit_run(1)") == (pytest.approx(450e-9), 3)
    assert prof.module_time(red, "jit_run") == (0.0, 0)  # exact names only
