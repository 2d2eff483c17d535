"""Request/block-scoped tracing: one trace_id from RPC submission to the
DAH root (trace/context.py + trace/spans.py) plus the layer
instrumentation it threads through — mempool, square builder, device
journal, consensus phases — the e2e phase histogram, the upgraded
/healthz, and the fused-vs-staged parity sentinel.

The context/mempool/square/sentinel layers run without the signing stack;
the five-layer acceptance leg (rpc -> mempool -> square -> device journal
-> consensus under ONE trace_id) importorskips onto `cryptography`.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from celestia_app_tpu.constants import SHARE_SIZE
from celestia_app_tpu.mempool import PriorityMempool
from celestia_app_tpu.trace.context import (
    current_context,
    new_context,
    node_id,
    trace_span,
    use_context,
)
from celestia_app_tpu.trace.exposition import (
    handle_observability_get,
    register_health_provider,
    unregister_health_provider,
)
from celestia_app_tpu.trace.metrics import registry
from celestia_app_tpu.trace.spans import SPANS_TABLE, span_attributes
from celestia_app_tpu.trace.tracer import traced


def _spans_for(trace_id: str) -> list[dict]:
    return [r for r in traced().table(SPANS_TABLE) if r["traceId"] == trace_id]


def _metric_line(name: str, **labels) -> float | None:
    """Sum of every series of `name` matching the label filter (series
    carrying EXTRA labels — e.g. the per-namespace e2e/eviction children
    — aggregate instead of shadowing the unlabeled one)."""
    total, seen = 0.0, False
    for line in registry().render().splitlines():
        if line.startswith(name) and all(
            f'{k}="{v}"' in line for k, v in labels.items()
        ):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    return total if seen else None


class TestTraceContext:
    def test_child_keeps_trace_links_parent_and_merges_baggage(self):
        root = new_context(layer="rpc", plane="jsonrpc")
        child = root.child(height=7)
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert child.span_id != root.span_id
        # new_context stamps node_id so cross-node rows carry provenance;
        # the caller's baggage must survive the merge untouched.
        assert child.baggage["node_id"] == node_id()
        assert {k: v for k, v in child.baggage.items() if k != "node_id"} == {
            "layer": "rpc", "plane": "jsonrpc", "height": 7}
        assert child.start_unix_ns == root.start_unix_ns

    def test_use_context_and_nesting(self):
        assert current_context() is None
        ctx = new_context()
        with use_context(ctx):
            assert current_context() is ctx
            with trace_span("tracing_nested_span", k=4):
                inner = current_context()
                assert inner.trace_id == ctx.trace_id
                assert inner.parent_id == ctx.span_id
        assert current_context() is None

    def test_span_exports_otlp_row_and_event_table(self):
        ctx = new_context(layer="test")
        with trace_span("tracing_export_span", ctx=ctx, k=8) as sp:
            sp["result"] = "ok"
        rows = _spans_for(ctx.trace_id)
        assert len(rows) == 1
        row = rows[0]
        assert row["name"] == "tracing_export_span"
        assert row["parentSpanId"] == ctx.span_id
        assert int(row["endTimeUnixNano"]) >= int(row["startTimeUnixNano"])
        attrs = span_attributes(row)
        assert attrs["k"] == "8" and attrs["result"] == "ok"
        assert attrs["layer"] == "test"  # baggage lands on attributes
        event = traced().table("tracing_export_span")[-1]
        assert event["trace_id"] == ctx.trace_id
        assert event["duration_ms"] >= 0
        # The span histogram family exists with k as a label.
        assert _metric_line(
            "celestia_tracing_export_span_seconds_count", k="8"
        ) >= 1

    def test_trace_gate_mutes_exports_but_propagates_context(self, monkeypatch):
        monkeypatch.setenv("CELESTIA_TRACE", "off")
        ctx = new_context()
        with trace_span("tracing_muted_span", ctx=ctx):
            # Explicit threading must survive the mute.
            assert current_context().trace_id == ctx.trace_id
        assert _spans_for(ctx.trace_id) == []

    def test_spans_out_mirror(self, monkeypatch, tmp_path):
        from celestia_app_tpu.trace import spans as spans_mod

        monkeypatch.setenv("CELESTIA_SPANS_OUT", str(tmp_path))
        monkeypatch.setattr(spans_mod, "_FILE_HANDLE", None)
        monkeypatch.setattr(spans_mod, "_FILE_DIR", None)
        monkeypatch.setattr(spans_mod, "_FILE_BROKEN", False)
        ctx = new_context()
        with trace_span("tracing_mirror_span", ctx=ctx):
            pass
        files = list(tmp_path.glob("spans-*.jsonl"))
        assert len(files) == 1
        rows = [json.loads(l) for l in files[0].read_text().splitlines()]
        assert any(r["traceId"] == ctx.trace_id for r in rows)


def _record_profile(tmp_path, body) -> tuple[int, dict[str, list]]:
    """Run `body()` under a jax.profiler session; return the session's
    start on the host clock and {host event name: [(start_ns, end_ns)]}
    in absolute ns (events are offsets from `profile_start_time`)."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(path)
    start = None
    events: dict[str, list] = {}
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start = int(value)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns)))
    assert start is not None
    return start, {n: [(start + a, start + b) for a, b in iv]
                   for n, iv in events.items()}


class TestSpansOnTheProfilerClock:
    """Every span is a profiler annotation under its bare name, and its
    row's start/end sit on the clock the profiler stamps host events
    with."""

    def test_nested_spans_match_their_host_events(self, tmp_path):
        def body():
            with trace_span("clock_outer_span"):
                sum(range(20_000))
                with trace_span("clock_inner_span", k=2):
                    sum(range(50_000))

        _, events = _record_profile(tmp_path, body)
        for name in ("clock_outer_span", "clock_inner_span"):
            row = traced().table(name)[-1]
            [(lo, hi)] = events[name]
            assert abs(row["start_ns"] - lo) <= 100_000, (name, row, lo)
            assert abs(row["end_ns"] - hi) <= 100_000, (name, row, hi)
            assert 0 <= row["cpu_ms"] <= row["duration_ms"]
            assert row["duration_ms"] == pytest.approx(
                (row["end_ns"] - row["start_ns"]) / 1e6)
        outer = traced().table("clock_outer_span")[-1]
        inner = traced().table("clock_inner_span")[-1]
        assert outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
            <= outer["end_ns"]

    def test_tracer_span_and_muted_spans_annotate_too(self, tmp_path,
                                                      monkeypatch):
        def body():
            with traced().span("clock_tracer_span", k=4):
                sum(range(10_000))
            monkeypatch.setenv("CELESTIA_TRACE", "off")
            with trace_span("clock_muted_span"):
                pass
            monkeypatch.delenv("CELESTIA_TRACE")

        _, events = _record_profile(tmp_path, body)
        assert "clock_tracer_span" in events and "clock_muted_span" in events
        row = traced().table("clock_tracer_span")[-1]
        [(lo, hi)] = events["clock_tracer_span"]
        assert abs(row["start_ns"] - lo) <= 100_000
        assert abs(row["end_ns"] - hi) <= 100_000
        assert 0 <= row["cpu_ms"] <= row["duration_ms"]
        assert traced().table("clock_muted_span") == []

    def test_duration_is_timed_on_the_monotonic_clock(self, monkeypatch):
        """A wall-clock step inside the body bends neither the duration
        nor the order of start and end."""
        import time

        ctx = new_context()
        stamps = iter([2_000_000_000_000_000_000, 1_000_000_000_000_000_000])
        real = time.time_ns
        monkeypatch.setattr(time, "time_ns", lambda: next(stamps, real()))
        with trace_span("clock_stepped_span", ctx=ctx):
            pass
        row = traced().table("clock_stepped_span")[-1]
        assert row["start_ns"] == 2_000_000_000_000_000_000
        assert 0 <= row["duration_ms"] < 1_000
        assert row["end_ns"] == row["start_ns"] + round(
            row["duration_ms"] * 1e6)

    def test_rootless_span_outside_a_trace_writes_no_otlp_row(self):
        with trace_span("clock_rootless_span", root=False, batch=3):
            assert current_context() is None
        row = traced().table("clock_rootless_span")[-1]
        assert row["batch"] == 3 and row["end_ns"] >= row["start_ns"]
        assert "trace_id" not in row
        assert not [r for r in traced().table(SPANS_TABLE)
                    if r["name"] == "clock_rootless_span"]
        assert registry().get(
            "celestia_clock_rootless_span_seconds").snapshot().children

    def test_rootless_span_inside_a_trace_joins_it(self):
        ctx = new_context()
        with use_context(ctx):
            with trace_span("clock_joined_span", root=False):
                assert current_context().parent_id == ctx.span_id
        row = traced().table("clock_joined_span")[-1]
        assert row["trace_id"] == ctx.trace_id
        [otlp] = [r for r in _spans_for(ctx.trace_id)
                  if r["name"] == "clock_joined_span"]
        assert otlp["parentSpanId"] == ctx.span_id

    def test_cpu_clock_only_while_a_profiler_records(self):
        with trace_span("clock_unprofiled_span"):
            pass
        row = traced().table("clock_unprofiled_span")[-1]
        assert "cpu_ms" not in row and row["end_ns"] >= row["start_ns"]

    def test_baggage_rides_child_rows(self):
        with trace_span("clock_parent_span",
                        baggage={"height": 41, "phase": "process"}) as sp:
            with trace_span("clock_child_span"):
                pass
        child = traced().table("clock_child_span")[-1]
        assert child["height"] == 41 and child["phase"] == "process"
        # After the body the span's own measurement is readable.
        assert sp["duration_ms"] >= child["duration_ms"]

    def test_one_span_primitive(self):
        """TraceAnnotation is entered in one place: trace/context.py."""
        import os

        pkg = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "celestia_app_tpu")
        users = []
        for dirpath, _, files in os.walk(pkg):
            for fn in files:
                if fn.endswith(".py"):
                    path = os.path.join(dirpath, fn)
                    with open(path, encoding="utf-8") as f:
                        if "TraceAnnotation" in f.read():
                            users.append(os.path.relpath(path, pkg))
        assert users == [os.path.join("trace", "context.py")]


class TestMempoolTracing:
    def _tx(self, i: int, size: int = 8) -> bytes:
        return bytes([i]) * size

    def test_insert_reap_update_share_the_submission_trace(self):
        mp = PriorityMempool()
        ctx = new_context(layer="rpc")
        with use_context(ctx):
            assert mp.insert(self._tx(1), 10, 0)  # picks up current ctx
        assert mp.ctx_for(self._tx(1)).trace_id == ctx.trace_id
        assert mp.insert(self._tx(2), 5, 0, ctx=new_context())
        out = mp.reap()
        assert out[0] == self._tx(1)  # priority order
        names = {
            r["name"]: r for r in _spans_for(ctx.trace_id)
        }
        assert "mempool_insert" in names
        # The reap span joins the FIRST reaped tx's trace.
        assert "mempool_reap" in names
        reap_attrs = span_attributes(names["mempool_reap"])
        assert reap_attrs["n_txs"] == "2"
        # Committing tx 1 journals the update and closes its lifecycle.
        total_before = _metric_line(
            "celestia_e2e_seconds_count", phase="total"
        ) or 0
        mp.update(1, [self._tx(1)])
        upd = traced().table("mempool_update")[-1]
        assert upd["committed"] == 1 and upd["expired"] == 0
        assert _metric_line(
            "celestia_e2e_seconds_count", phase="total"
        ) == total_before + 1
        assert _metric_line("celestia_mempool_txs") == 1.0
        assert _metric_line("celestia_mempool_size_bytes") == 8.0

    def test_eviction_reasons_reconcile_gauges(self):
        before = {
            reason: _metric_line(
                "celestia_mempool_evictions_total", reason=reason
            ) or 0
            for reason in ("priority", "ttl", "recheck")
        }
        mp = PriorityMempool(max_pool_bytes=24, ttl_num_blocks=2)
        assert mp.insert(self._tx(1), 1, 0)
        assert mp.insert(self._tx(2), 2, 0)
        assert mp.insert(self._tx(3), 3, 0)
        # Pool full of 3x8 bytes: a higher-priority insert evicts tx 1.
        assert mp.insert(self._tx(4), 9, 0)
        assert not mp.has_tx(self._tx(1))
        assert (
            _metric_line("celestia_mempool_evictions_total", reason="priority")
            == before["priority"] + 1
        )
        # recheck eviction (remove_tx) now counts too.
        mp.remove_tx(self._tx(2))
        assert (
            _metric_line("celestia_mempool_evictions_total", reason="recheck")
            == before["recheck"] + 1
        )
        # TTL expiry at height 2 drops the height-0 remainder.
        mp.update(2, [])
        assert len(mp) == 0
        assert (
            _metric_line("celestia_mempool_evictions_total", reason="ttl")
            == before["ttl"] + 2
        )
        assert _metric_line("celestia_mempool_txs") == 0.0
        assert _metric_line("celestia_mempool_size_bytes") == 0.0

    def test_mempool_wait_phase_observed_on_first_reap_only(self):
        before = _metric_line(
            "celestia_e2e_seconds_count", phase="mempool_wait"
        ) or 0
        mp = PriorityMempool()
        mp.insert(self._tx(9), 1, 0, ctx=new_context())
        mp.reap()
        # A reaped-but-uncommitted tx is reaped again next block: its
        # residency must not be re-observed (duplicates would own the
        # histogram tail).
        mp.reap()
        assert _metric_line(
            "celestia_e2e_seconds_count", phase="mempool_wait"
        ) == before + 1


class TestSquareBuildTracing:
    def test_build_span_carries_counts_and_size(self):
        from celestia_app_tpu.square.builder import build

        ctx = new_context(layer="block")
        with use_context(ctx):
            sq, kept = build([], 16)
        rows = [
            r for r in _spans_for(ctx.trace_id) if r["name"] == "square_build"
        ]
        assert len(rows) == 1
        attrs = span_attributes(rows[0])
        assert attrs["k"] == str(sq.size)
        assert attrs["n_txs"] == "0" and attrs["n_blobs"] == "0"
        assert int(attrs["layout_solves"]) >= 1


class TestDeviceJournalTraceId:
    def test_block_journal_row_carries_active_trace(self):
        from celestia_app_tpu.da.eds import ExtendedDataSquare

        ctx = new_context(layer="block")
        with use_context(ctx):
            ExtendedDataSquare.compute(
                np.zeros((4, 4, SHARE_SIZE), dtype=np.uint8)
            )
        row = traced().table("block_journal")[-1]
        assert row["source"] == "compute" and row["trace_id"] == ctx.trace_id


class TestParitySentinel:
    def test_sentinel_matches_fused_against_staged(self, monkeypatch):
        from celestia_app_tpu.da import eds

        monkeypatch.setenv("CELESTIA_PARITY_SENTINEL", "1")
        before = _metric_line(
            "celestia_parity_checks_total", result="match"
        ) or 0
        eds.ExtendedDataSquare.compute(
            np.zeros((4, 4, SHARE_SIZE), dtype=np.uint8)
        )
        eds.drain_parity_checks(timeout_s=300.0)
        assert _metric_line(
            "celestia_parity_checks_total", result="match"
        ) == before + 1
        assert traced().table("parity_mismatch") == []

    def test_sentinel_disabled_by_default(self, monkeypatch):
        from celestia_app_tpu.da import eds

        monkeypatch.delenv("CELESTIA_PARITY_SENTINEL", raising=False)
        count_before = eds._PARITY_COUNT
        eds.ExtendedDataSquare.compute(
            np.zeros((4, 4, SHARE_SIZE), dtype=np.uint8)
        )
        assert eds._PARITY_COUNT == count_before


class TestHealthz:
    def test_bare_healthz_unchanged(self):
        from celestia_app_tpu.trace import exposition

        # Pin the no-providers shape regardless of what other tests left
        # registered (servers unregister on stop, but don't depend on it).
        with exposition._HEALTH_LOCK:
            saved = dict(exposition._HEALTH_PROVIDERS)
            exposition._HEALTH_PROVIDERS.clear()
        try:
            status, _, body = handle_observability_get("/healthz")
            payload = json.loads(body)
            # The SLO judgment block is always present (PR 7); with no
            # providers registered, nothing else is.
            assert status == 200
            assert payload["status"] == "SERVING"
            assert set(payload) == {"status", "slo"}
            assert payload["slo"]["status"] in ("OK", "BURNING")
        finally:
            with exposition._HEALTH_LOCK:
                exposition._HEALTH_PROVIDERS.update(saved)

    def test_layers_report_and_survive_provider_faults(self):
        def good():
            return {"height": 12, "mempool": {"txs": 3}}

        def bad():
            raise RuntimeError("boom")

        register_health_provider("good", good)
        register_health_provider("bad", bad)
        try:
            status, _, body = handle_observability_get("/healthz")
            payload = json.loads(body)
            assert status == 200 and payload["status"] == "SERVING"
            assert payload["layers"]["good"]["height"] == 12
            assert "RuntimeError" in payload["layers"]["bad"]["error"]
        finally:
            unregister_health_provider("good")
            unregister_health_provider("bad")
        status, _, body = handle_observability_get("/healthz")
        payload = json.loads(body)
        assert payload["status"] == "SERVING" and "layers" not in payload

    def test_unregister_checks_identity(self):
        def one():
            return {}

        def two():
            return {}

        register_health_provider("dup", one)
        register_health_provider("dup", two)  # replacement wins
        try:
            unregister_health_provider("dup", one)  # stale: must not unhook
            _, _, body = handle_observability_get("/healthz")
            assert "dup" in json.loads(body)["layers"]
        finally:
            unregister_health_provider("dup")


class TestFiveLayerAcceptance:
    def test_single_trace_id_spans_five_layers(self):
        """Acceptance: a trace_id issued at tx submission shows up on
        spans from rpc, mempool, app/square, device journal, and
        consensus — resolvable via /trace_tables/spans — and the e2e
        histogram carries every lifecycle phase."""
        pytest.importorskip("cryptography")
        from celestia_app_tpu.rpc.server import ServingNode
        from celestia_app_tpu.testutil.testnode import (
            deterministic_genesis,
            funded_keys,
        )
        from celestia_app_tpu.tx.messages import Coin, MsgSend
        from celestia_app_tpu.tx.sign import Fee, build_and_sign

        keys = funded_keys(2)
        node = ServingNode(genesis=deterministic_genesis(keys), keys=keys)
        addr = keys[0].public_key().address()
        to = keys[1].public_key().address()
        from celestia_app_tpu.state.accounts import AuthKeeper

        acct = AuthKeeper(node.app.cms.working).get_account(addr)
        raw = build_and_sign(
            [MsgSend(addr, to, (Coin("utia", 100),))],
            keys[0], node.chain_id, acct.account_number, acct.sequence,
            Fee((Coin("utia", 20_000),), 100_000),
        )
        reply = node.rpc_broadcast_tx(raw.hex(), relay=False)
        assert reply["code"] == 0
        trace_id = reply["trace_id"]
        node.produce_block()

        # Resolve the trace through the exposition surface.
        status, ctype, body = handle_observability_get("/trace_tables/spans")
        assert status == 200 and ctype == "application/x-ndjson"
        rows = [
            json.loads(l) for l in body.decode().strip().splitlines()
        ]
        mine = [r for r in rows if r["traceId"] == trace_id]
        layers = {span_attributes(r).get("layer") for r in mine}
        names = {r["name"] for r in mine}
        assert {"rpc", "mempool", "app", "square", "device", "consensus"} <= layers
        assert {
            "tx_submit", "mempool_insert", "mempool_reap", "block_propose",
            "prepare_proposal", "square_build", "square_pipeline",
            "block_prevotes", "block_precommits", "block_commit",
        } <= names
        # Parent links resolve within the trace (one tree, no orphans
        # beyond the roots created at submission/adoption).
        by_id = {r["spanId"] for r in mine}
        linked = [r for r in mine if r["parentSpanId"] in by_id]
        assert len(linked) >= 5

        # The device journal row for the block carries the same trace.
        jrows = [
            r for r in traced().table("block_journal")
            if r.get("trace_id") == trace_id
        ]
        assert jrows and jrows[-1]["source"] == "compute"

        # All lifecycle phases observed at least once.
        for phase in ("submit", "mempool_wait", "reap", "square_build",
                      "dispatch", "propose", "prevote", "precommit",
                      "commit", "total"):
            assert (_metric_line("celestia_e2e_seconds_count", phase=phase)
                    or 0) >= 1, phase

        # /healthz reports the node layer once serving wires it.
        from celestia_app_tpu.rpc.server import serve

        server = serve(node, port=0, block_interval_s=None)
        try:
            _, _, hbody = handle_observability_get("/healthz")
            payload = json.loads(hbody)
            layer = payload["layers"][f"node:{server.port}"]
            assert layer["height"] == node.app.height
            assert layer["mempool"]["txs"] == 0
        finally:
            server.stop()
