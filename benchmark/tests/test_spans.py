"""The per-layer readers of the program's spans: on synthetic rows and
reductions, and on a small trace recorded on the CPU with program spans
(`data/cpu_span_trace.xplane.pb`: three rounds of a `prepare_proposal`
span holding `ante` over a 0.5 ms sleep, 0.5 ms under no leaf,
`extend_dispatch` around a jitted matmul and `roots_wait` around its
result; the XLA CPU client thread stands in for a device plane)."""

import importlib.util
import os

import pytest

from benchmark import profile as prof
from benchmark import spans

HERE = os.path.dirname(__file__)
TRACE = os.path.join(HERE, "data", "cpu_span_trace.xplane.pb")
WINDOW_S = 0.0070106  # host-clock length of the recorded window


def _reader(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _row(height, ms, phase="prepare", end_ns=0, cpu_ms=0.0):
    return {"height": height, "phase": phase, "duration_ms": ms,
            "cpu_ms": cpu_ms, "start_ns": end_ns - int(ms * 1e6),
            "end_ns": end_ns}


@pytest.fixture()
def tables(monkeypatch):
    rows: dict[str, list[dict]] = {}
    monkeypatch.setattr(spans, "table", lambda name: rows.get(name, []))
    return rows


def test_propose_readers_sum_window_heights(tables):
    tables["prepare_proposal"] = [_row(h, 100.0) for h in (1, 2, 3, 4)]
    tables["ante"] = [_row(1, 500.0), _row(3, 40.0),
                      _row(3, 60.0, "process"), _row(4, 100.0)]
    tables["square_build"] = [_row(3, 200.0), _row(4, 200.0)]
    tables["square_construct"] = [_row(3, 100.0, "process")]
    ctx = {"kind": "propose", "records": [{}, {}]}  # heights 3 and 4
    assert spans.window_heights(ctx) == [3, 4]
    assert _reader("ante_s").read(ctx) == pytest.approx(0.1)
    assert _reader("square_layout_s").read(ctx) == pytest.approx(0.25)
    assert _reader("commit_s").read(ctx) is None  # no such rows: None
    assert _reader("ante_s").read({**ctx, "kind": "das"}) is None


def test_das_readers_take_rows_that_ended_in_the_window(tables):
    ctx = {"kind": "das", "start": 100.0, "end": 104.0,
           "rounds": [{"proofs": [(0, 0, {}, 100.5, 101.0),
                                  (0, 1, {}, 101.5, 102.0),
                                  (0, 2, {}, 103.9, 104.5)]}]}
    lo, hi = spans.window_ns(ctx)
    assert hi - lo == 4_000_000_000
    tables["proof_gather"] = [_row(0, 3.0, end_ns=lo + 10**9, cpu_ms=1.0),
                              _row(0, 5.0, end_ns=lo + 2 * 10**9, cpu_ms=2.0),
                              _row(0, 7.0, end_ns=hi + 10**8, cpu_ms=9.0)]
    tables["proof_encode"] = [_row(0, 1.0, end_ns=lo + 10**9, cpu_ms=0.5)]
    assert spans.answered(ctx) == 2
    assert _reader("das_gather_ms").read(ctx) == pytest.approx(4.0)
    assert _reader("das_encode_ms").read(ctx) == pytest.approx(0.5)
    assert _reader("das_cpu_ms").read(ctx) == pytest.approx(1.75)
    assert _reader("das_assemble_ms").read(ctx) is None


def _red(busy, host):
    return {"devices": [{"busy": busy, "modules": {}, "ops": {}, "lines": []}],
            "host": host}


def test_idle_unattributed_extremes():
    unattributed = _reader("idle_unattributed").unattributed
    busy = [(10, 20), (40, 50)]
    covered = [(0, 10, "ante"), (20, 40, "roots_wait"), (50, 100, "commit")]
    assert unattributed(_red(busy, covered), (0, 100)) == 0.0
    # Parents cover nothing; a leaf that runs only while the device is
    # busy covers no idle time.
    parents = [(0, 100, "prepare_proposal"), (0, 100, "square_pipeline"),
               (12, 18, "roots_wait")]
    assert unattributed(_red(busy, parents), (0, 100)) == 100.0
    assert unattributed(_red(busy, parents[:2]), (0, 100)) is None
    half = [(20, 40, "share_pack")]  # 20 of 80 idle ns covered
    assert unattributed(_red(busy, half), (0, 100)) == pytest.approx(75.0)


def test_idle_unattributed_on_a_recorded_trace():
    red = prof.reduce(TRACE, device_prefix="/host:CPU",
                      op_line="tf_XLAPjRtCpuClient")
    names = {name for *_, name in red["host"]}
    assert {"ante", "extend_dispatch", "roots_wait", "prepare_proposal"} <= names
    share = _reader("idle_unattributed").read(
        {"profile": red, "trace_seconds": WINDOW_S})
    assert 0.0 < share < 100.0
    # The breakdown names program spans among its idle gaps.
    labels = {label for label, _ in prof.breakdown(red)["idle_gaps"]}
    assert labels & set(spans.LEAVES)


def test_program_time_by_stable_name():
    reader = _reader("extend_dah_program_ms")
    mods = {"jit_extend_and_dah(123)": [(0, 300), (1000, 1300)],
            "jit_extend_and_dah_batched(9)": [(0, 10**6)],
            "jit_run(1)": [(0, 10**6)]}
    red = {"devices": [{"modules": mods, "busy": [], "ops": {}, "lines": []}],
           "host": []}
    journal = [{"source": "compute", "k": 512}] * 2
    ctx = {"kind": "propose", "profile": red, "k": 512,
           "spans": {"block_journal": journal}}
    assert reader.read(ctx) == pytest.approx(300e-9 * 1e3)
    del mods["jit_extend_and_dah(123)"]
    assert reader.read(ctx) is None  # a program with the old name reads None


def test_program_roofline_reads_the_stable_name():
    from benchmark.counts import extend_dah_bytes

    reader = _reader("extend_dah_program_roofline")
    mods = {"jit_extend_and_dah(77)": [(0, 340_000_000), (10**9, 1_340_000_000)]}
    red = {"devices": [{"modules": mods, "busy": [], "ops": {}, "lines": []}],
           "host": []}
    ctx = {"kind": "propose", "profile": red, "k": 512,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "spans": {"block_journal": [{"source": "compute", "k": 512}] * 2}}
    least_s = extend_dah_bytes(512) / 819e9
    assert reader.read(ctx) == pytest.approx(least_s / 0.34 * 100.0)
    assert 0.0 < reader.read(ctx) < 100.0
    assert reader.read({**ctx, "peaks": None}) is None
    mods["jit_run(1)"] = mods.pop("jit_extend_and_dah(77)")
    assert reader.read(ctx) is None  # the old module name reads None
