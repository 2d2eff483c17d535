"""The fleet observability plane: Histogram.merge semantics, the
prometheus-text scrape parser, the merged /fleet view (byte-identical on
every plane, unreachable peers marked rather than dropped), the DAS
coverage map, and cross-node trace ADOPTION — one client trace fetching
two in-process nodes leaves spans rows on both that stitch under a
single trace_id with distinct node_id attributes.

Everything here is crypto-free: stub peers are either fetch-seam dicts
(no sockets) or trace/exposition.serve_observability mounts, never the
rpc/ serving stack (whose import chain needs `cryptography`).
"""

from __future__ import annotations

import json
import random
import re
import urllib.request

import pytest

from celestia_app_tpu.serve import api as serve_api
from celestia_app_tpu.trace import fleet
from celestia_app_tpu.trace.context import (
    TRACE_HEADER,
    new_context,
    serialize_context,
)
from celestia_app_tpu.trace.exposition import (
    handle_observability_get,
    serve_observability,
)
from celestia_app_tpu.trace.metrics import Histogram, HistogramSnapshot, Registry
from celestia_app_tpu.trace.spans import span_attributes
from celestia_app_tpu.trace.tracer import traced

BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0)


@pytest.fixture(autouse=True)
def _clean_fleet_state():
    fleet._reset_for_tests()
    serve_api._reset_coverage_for_tests()
    yield
    fleet._reset_for_tests()
    serve_api._reset_coverage_for_tests()


def _hist(observations, **labels) -> HistogramSnapshot:
    h = Histogram("t_seconds", "", BUCKETS)
    for v in observations:
        h.observe(v, **labels)
    return h.snapshot()


class TestHistogramMerge:
    def test_same_label_children_sum_count_for_count(self):
        a = _hist([0.02, 0.02, 0.3], phase="total")
        b = _hist([0.02, 0.7], phase="total")
        merged = Histogram.merge([a, b])
        assert merged.count(phase="total") == 5
        # Counts are additive per bucket, so the merged quantile equals
        # the quantile of ONE histogram holding all observations.
        combined = _hist([0.02, 0.02, 0.3, 0.02, 0.7], phase="total")
        for q in (0.5, 0.9, 0.99):
            assert merged.quantile(q, phase="total") == pytest.approx(
                combined.quantile(q, phase="total")
            )

    def test_mismatched_label_sets_union(self):
        a = _hist([0.02], phase="total")
        b = _hist([0.3], phase="gather")
        merged = Histogram.merge([a, b])
        assert merged.count(phase="total") == 1
        assert merged.count(phase="gather") == 1
        assert merged.count() == 2  # no selector: every child

    def test_empty_snapshots_are_identity(self):
        a = _hist([0.02, 0.3], phase="total")
        empty = HistogramSnapshot((), {})
        also_empty = Histogram("t_seconds", "", BUCKETS).snapshot()
        merged = Histogram.merge([empty, a, also_empty])
        assert merged.buckets == a.buckets
        assert merged.children == a.children
        # All-empty merge is an empty snapshot, not an error.
        nothing = Histogram.merge([empty, also_empty])
        assert nothing.count() == 0
        assert nothing.quantile(0.99) is None

    def test_mismatched_bucket_layouts_raise(self):
        a = _hist([0.02])
        other = Histogram("t_seconds", "", (0.01, 0.1, 1.0))
        other.observe(0.02)
        with pytest.raises(ValueError, match="bucket layouts"):
            Histogram.merge([a, other.snapshot()])

    def test_inf_tail_clamps_quantile_to_largest_finite_bound(self):
        # Every observation past the last finite bound: the merged tail
        # sums like any bucket, and quantile() still clamps the estimate
        # to the largest finite bound instead of inventing a value.
        a = _hist([5.0, 9.0], phase="total")
        b = _hist([7.0], phase="total")
        merged = Histogram.merge([a, b])
        assert merged.count(phase="total") == 3
        assert merged.quantile(0.99, phase="total") == BUCKETS[-1]


def _peer_registry(latencies, proofs_total: float, throttled: float = 0.0):
    """A stub peer's registry: the two families the aggregator merges."""
    r = Registry()
    h = r.histogram("celestia_proof_latency_seconds", "lat", buckets=BUCKETS)
    for v in latencies:
        h.observe(v, phase="total")
    r.counter("celestia_proofs_served_total", "served").inc(
        proofs_total, plane="rest", kind="share_proof"
    )
    if throttled:
        r.counter("celestia_qos_throttled_total", "qos").inc(
            throttled, namespace="t01", kind="proof_rate"
        )
    return r


def _stub_fetch(peer_pages: dict):
    """fetch(url, path) over {url: {path: text-or-dict}}; a url absent
    from the dict raises like a dead socket."""

    def fetch(url, path):
        pages = peer_pages.get(url)
        if pages is None:
            raise OSError("connection refused")
        page = pages[path]
        return page if isinstance(page, str) else json.dumps(page)

    return fetch


def _stub_pages(registry, status="ok"):
    return {
        "/metrics": registry.render(),
        "/healthz": {"status": status, "degraded": {}},
        "/slo": {"slos": {}},
        "/heal": {"engines": {}},
    }


class TestParsePrometheusText:
    def test_roundtrip_is_exact(self):
        r = _peer_registry([0.02, 0.02, 0.3, 0.7], 41.0, throttled=3.0)
        kinds, scalars, hists = fleet.parse_prometheus_text(r.render())
        assert kinds["celestia_proof_latency_seconds"] == "histogram"
        assert kinds["celestia_proofs_served_total"] == "counter"
        assert fleet._sum_family(
            scalars, "celestia_proofs_served_total"
        ) == 41.0
        assert fleet._sum_family(
            scalars, "celestia_qos_throttled_total"
        ) == 3.0
        parsed = hists["celestia_proof_latency_seconds"]
        direct = r.get("celestia_proof_latency_seconds").snapshot()
        assert parsed.buckets == direct.buckets
        assert parsed.count(phase="total") == direct.count(phase="total")
        for q in (0.5, 0.99):
            assert parsed.quantile(q, phase="total") == pytest.approx(
                direct.quantile(q, phase="total")
            )


class TestFleetAggregator:
    def test_three_stub_peers_merge(self):
        per_host = {
            "http://a": [0.02, 0.02, 0.3],
            "http://b": [0.02, 0.7],
            "http://c": [0.05, 0.05, 0.05, 0.9],
        }
        pages = {
            url: _stub_pages(_peer_registry(obs, 10.0 * (i + 1)))
            for i, (url, obs) in enumerate(per_host.items())
        }
        agg = fleet.configure(
            pages, interval_s=3600, fetch=_stub_fetch(pages)
        )
        state = agg.scrape()
        assert state["fleet"]["hosts_total"] == 3
        assert state["fleet"]["hosts_reachable"] == 3
        assert state["fleet"]["proofs_served_total"] == 60.0
        # ACCEPTANCE: the fleet p99 equals the bucket-merge of the
        # per-host snapshots — never a quantile-of-quantiles.
        expected = Histogram.merge(
            [_hist(obs, phase="total") for obs in per_host.values()]
        )
        lat = state["fleet"]["proof_latency"]
        assert lat["samples"] == 9
        assert lat["p99_s"] == pytest.approx(
            expected.quantile(0.99, phase="total"), abs=1e-6
        )
        assert lat["p50_s"] == pytest.approx(
            expected.quantile(0.5, phase="total"), abs=1e-6
        )

    def test_unreachable_peer_marked_not_dropped(self):
        pages = {"http://up": _stub_pages(_peer_registry([0.02], 5.0))}
        agg = fleet.configure(
            ["http://up", "http://down"],
            interval_s=3600, fetch=_stub_fetch(pages),
        )
        state = agg.scrape()
        assert state["fleet"]["hosts_total"] == 2
        assert state["fleet"]["hosts_reachable"] == 1
        down = state["hosts"]["http://down"]
        assert down["peer_unreachable"] is True
        assert down["reachable"] is False
        assert "connection refused" in down["error"]
        assert state["hosts"]["http://up"]["reachable"] is True

    def test_per_host_rate_from_scrape_deltas(self):
        reg = _peer_registry([0.02], 100.0)
        pages = {"http://a": _stub_pages(reg)}
        agg = fleet.configure(
            ["http://a"], interval_s=3600, fetch=_stub_fetch(pages)
        )
        agg.scrape()
        # 60 more proofs land between rounds; the second round's row
        # carries a non-negative per-second rate off the counter delta.
        reg.counter("celestia_proofs_served_total", "served").inc(
            60.0, plane="rest", kind="share_proof"
        )
        pages["http://a"] = _stub_pages(reg)
        state = agg.scrape()
        row = state["hosts"]["http://a"]
        assert row["proofs_served_total"] == 160.0
        assert row["proofs_per_s"] is not None and row["proofs_per_s"] >= 0

    def test_fleet_response_503_when_unconfigured(self, monkeypatch):
        monkeypatch.delenv("CELESTIA_FLEET_PEERS", raising=False)
        status, ctype, body = fleet.fleet_response()
        assert status == 503
        assert b"no fleet aggregator configured" in body

    def test_fleet_byte_identical_across_planes(self):
        pages = {
            "http://a": _stub_pages(_peer_registry([0.02, 0.3], 7.0)),
            "http://b": _stub_pages(_peer_registry([0.05], 3.0)),
            "http://c": _stub_pages(_peer_registry([0.7], 1.0)),
        }
        fleet.configure(
            list(pages), interval_s=3600, fetch=_stub_fetch(pages)
        )
        responses = {
            plane: handle_observability_get("/fleet", plane=plane)
            for plane in ("jsonrpc", "rest", "grpc")
        }
        bodies = {plane: r[2] for plane, r in responses.items()}
        assert all(r[0] == 200 for r in responses.values())
        assert bodies["jsonrpc"] == bodies["rest"] == bodies["grpc"]
        merged = json.loads(bodies["rest"])
        assert merged["fleet"]["hosts_reachable"] == 3
        assert merged["fleet"]["proofs_served_total"] == 11.0


class TestCoverageMap:
    def test_rank_precedence_never_downgrades(self):
        serve_api.coverage_tick(9, 2, [(0, 0)], "verified")
        serve_api.coverage_tick(9, 2, [(0, 0)], "sampled")  # weaker: no-op
        serve_api.coverage_tick(9, 2, [(0, 1)], "sampled")
        serve_api.coverage_tick(9, 2, [(0, 1)], "withheld")  # refusal wins
        serve_api.coverage_tick(9, 2, [(1, 0)], "tampered")
        payload = serve_api.coverage_payload(9)
        assert payload["map"][0][:2] == "vw"
        assert payload["map"][1][0] == "t"
        counts = payload["counts"]
        assert counts["verified"] == 1
        assert counts["withheld"] == 1
        assert counts["tampered"] == 1
        assert counts["sampled"] == 0
        # Refused cells COUNT as covered: a refusal is a detection
        # datapoint, not a sampling gap.
        assert payload["ratio"] == pytest.approx(3 / 16)

    def test_ratio_gauge_tracks_last_ticked_height(self):
        serve_api.coverage_tick(5, 2, [(r, c) for r in range(4)
                                       for c in range(4)], "sampled")
        from celestia_app_tpu.trace.metrics import registry

        gauge = registry().get("celestia_das_coverage_ratio")
        assert gauge is not None
        values = {tuple(sorted(lbl.items())): v for lbl, v in gauge.samples()}
        assert values[(("k", "2"),)] == 1.0

    def test_coverage_response_status_codes(self):
        serve_api.coverage_tick(7, 2, [(0, 0)], "sampled")
        ok = serve_api.coverage_response({"height": "7"})
        assert ok[0] == 200
        assert json.loads(ok[2])["square_size"] == 2
        missing = serve_api.coverage_response({"height": "999"})
        assert missing[0] == 404
        malformed = serve_api.coverage_response({"height": "seven"})
        assert malformed[0] == 400
        summary = serve_api.coverage_response({})
        assert summary[0] == 200
        assert "7" in json.loads(summary[2])["heights"]

    def test_coverage_rides_all_three_planes(self):
        serve_api.coverage_tick(3, 2, [(0, 0), (1, 1)], "verified")
        bodies = {
            plane: handle_observability_get(
                "/das/coverage?height=3", plane=plane
            )[2]
            for plane in ("jsonrpc", "rest", "grpc")
        }
        assert bodies["jsonrpc"] == bodies["rest"] == bodies["grpc"]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("k", [1, 2, 32, 64])
    def test_kept_counts_match_a_full_recount(self, k, seed):
        """Random tick sequences over all four states, repeats, attempted
        downgrades and out-of-range coordinates: the kept tally answers
        exactly what a recount of the grid (and of an independent model
        of it) answers, down to the rendered /das/coverage bytes."""
        rng = random.Random(seed * 1000 + k)
        n = 2 * k
        height = 100 + k
        model: dict[tuple[int, int], int] = {}
        for _ in range(200):
            state = rng.choice(serve_api.COVERAGE_STATES)
            coords = [
                (rng.randint(-2, n + 1), rng.randint(-2, n + 1))
                for _ in range(rng.randint(0, 6))
            ]
            # Re-tick a cell already decided, stronger or weaker.
            if model and rng.random() < 0.5:
                coords.append(rng.choice(sorted(model)))
            serve_api.coverage_tick(height, k, coords, state)
            rank = serve_api._STATE_RANK[state]
            for row, col in coords:
                if 0 <= row < n and 0 <= col < n:
                    model[(row, col)] = max(model.get((row, col), 0), rank)

        cov = serve_api._COVERAGE[height]
        grid = bytearray(n * n)
        for (row, col), rank in model.items():
            grid[row * n + col] = rank
        assert cov.cells == grid
        recount = {name: 0 for name in serve_api._RANK_NAME}
        for c in grid:
            recount[serve_api._RANK_NAME[c]] += 1
        ratio = sum(1 for c in grid if c) / len(grid)
        assert cov.counts() == recount
        assert cov.ratio() == ratio

        expected = {
            "height": height,
            "square_size": k,
            "ratio": ratio,
            "counts": recount,
        }
        if n <= serve_api.MAX_COVERAGE_MAP_EDGE:
            expected["map"] = [
                "".join(serve_api._RANK_CHAR[c] for c in grid[r * n:(r + 1) * n])
                for r in range(n)
            ]
            expected["map_omitted"] = False
        else:
            expected["map_omitted"] = True
        assert cov.payload() == expected
        status, _, body = serve_api.coverage_response({"height": str(height)})
        assert (status, body) == (200, serve_api.render(expected))
        assert serve_api.coverage_snapshot()[str(height)] == {
            "square_size": k, "ratio": ratio, "counts": recount,
        }
        from celestia_app_tpu.trace.metrics import registry

        gauge = registry().get("celestia_das_coverage_ratio")
        values = {tuple(sorted(lbl.items())): v for lbl, v in gauge.samples()}
        assert values[(("k", str(k)),)] == ratio

    def test_tick_ratio_and_counts_never_scan_the_map(self):
        class NoScan(bytearray):
            def _scan(self, *args, **kwargs):
                raise AssertionError("the coverage map was scanned")

            __iter__ = __contains__ = __bytes__ = _scan
            count = find = rfind = index = rindex = decode = _scan

            def __getitem__(self, key):
                if isinstance(key, slice):
                    self._scan()
                return super().__getitem__(key)

        cov = serve_api.CoverageMap(height=1, k=64)
        cov.tick([(0, 0), (5, 7)], "sampled")
        cov.cells = NoScan(cov.cells)
        cov.tick([(0, 0), (1, 1), (127, 127), (128, 0)], "verified")
        cov.tick([(1, 1)], "sampled")
        cov.tick([(2, 3)], "withheld")
        cov.tick([(2, 3), (4, 4)], "tampered")
        assert cov.counts() == {
            "unseen": 128 * 128 - 6, "sampled": 1, "verified": 3,
            "withheld": 0, "tampered": 2,
        }
        assert cov.ratio() == 6 / (128 * 128)


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=5) as resp:
        return resp.status, dict(resp.headers), resp.read()


class TestCrossNodeAdoption:
    def test_one_trace_stitches_two_nodes(self, monkeypatch):
        monkeypatch.delenv("CELESTIA_TRACE", raising=False)
        a = serve_observability(node_id="node-a")
        b = serve_observability(node_id="node-b")
        try:
            ctx = new_context(layer="test")
            wire = serialize_context(ctx)
            for srv in (a, b):
                status, _, _ = _get(
                    srv.url + "/healthz", headers={TRACE_HEADER: wire}
                )
                assert status == 200
            rows = [
                r for r in traced().tail("spans", 400)
                if r.get("traceId") == ctx.trace_id
            ]
            # ACCEPTANCE: spans rows from BOTH servers share the client's
            # trace_id, carry DISTINCT node_ids, and hang off the
            # client's span (adopted, not re-minted).
            node_ids = {span_attributes(r).get("node_id") for r in rows}
            assert {"node-a", "node-b"} <= node_ids
            # Every row descends from the client's context (adopted, not
            # re-minted): the rpc_get span is a child of the per-server
            # ADOPTED span, whose parent is the client's span — so each
            # row carries a parent (a re-minted root would carry none)
            # and the parents are distinct per server while the trace_id
            # is one.
            parents = {r.get("parentSpanId") for r in rows}
            assert all(parents)
            assert len(parents) == len(rows) >= 2
        finally:
            a.stop()
            b.stop()

    def test_malformed_header_never_fails_the_request(self):
        srv = serve_observability(node_id="node-x")
        try:
            status, _, body = _get(
                srv.url + "/healthz",
                headers={TRACE_HEADER: "not-a-trace-context"},
            )
            assert status == 200
            assert json.loads(body)["status"]
        finally:
            srv.stop()

    def test_404_carries_content_length(self):
        srv = serve_observability(node_id="node-y")
        try:
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _get(srv.url + "/definitely_not_a_route")
            err = exc_info.value
            assert err.code == 404
            body = err.read()
            assert int(err.headers["Content-Length"]) == len(body)
            assert json.loads(body)["error"] == "not found"
        finally:
            srv.stop()

    def test_metrics_carries_scrape_timestamp(self, monkeypatch):
        monkeypatch.setenv("CELESTIA_SCRAPE_TS_S", "0")
        srv = serve_observability(node_id="node-z")
        try:
            _, _, body = _get(srv.url + "/metrics")
            m = re.search(
                rb"^celestia_scrape_timestamp_seconds (\S+)$",
                body, re.MULTILINE,
            )
            assert m is not None
            import time

            assert float(m.group(1)) == pytest.approx(time.time(), abs=60)
        finally:
            srv.stop()
