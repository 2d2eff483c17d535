"""The batched proof-serving plane (serve/): cache tiers, sampler queue,
chaos fallback, the DAS surface on the serving planes, loadgen smoke.

Runs without the signing stack: squares are deterministic synthetic
blocks admitted straight into a ForestCache; the full ServingNode
retention/commit flow is a crypto-gated test (importorskip).
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
from celestia_app_tpu.da.eds import ExtendedDataSquare
from celestia_app_tpu.serve.api import DasProvider, UnknownHeight, render
from celestia_app_tpu.serve.cache import ForestCache
from celestia_app_tpu.serve.sampler import ProofSampler, serve_mode
from celestia_app_tpu.trace.metrics import registry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def det_square(k: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ns = np.sort(rng.integers(0, 128, k * k).astype(np.uint8))
    ods = rng.integers(0, 256, (k * k, SHARE_SIZE), dtype=np.uint8)
    ods[:, :NAMESPACE_SIZE] = 0
    ods[:, NAMESPACE_SIZE - 1] = ns
    return ods.reshape(k, k, SHARE_SIZE)


def make_eds(k: int = 4, seed: int = 1) -> ExtendedDataSquare:
    return ExtendedDataSquare.compute(det_square(k, seed))


class TestForestCache:
    def test_lru_eviction_spills_then_drops(self):
        cache = ForestCache(heights=2, spill=1)
        e1 = cache.put(1, make_eds(seed=1))
        e2 = cache.put(2, make_eds(seed=2))
        assert e1.device_resident and e2.device_resident
        cache.put(3, make_eds(seed=3))  # evicts 1 -> host tier
        entry, tier = cache.get(1)
        assert tier == "host" and entry is e1 and not e1.device_resident
        cache.put(4, make_eds(seed=4))  # evicts 2 -> host; 1 drops (spill=1)
        assert cache.get(1) == (None, "miss")
        _, tier2 = cache.get(2)
        assert tier2 == "host"
        stats = cache.stats()
        assert stats["device_heights"] == [3, 4]
        assert stats["host_heights"] == [2]
        assert stats["last_eviction"] == 2
        assert stats["misses"] >= 1
        assert stats["hit_ratio"] is not None

    def test_lookup_refreshes_lru_order(self):
        cache = ForestCache(heights=2, spill=2)
        cache.put(1, make_eds(seed=1))
        cache.put(2, make_eds(seed=2))
        cache.get(1)  # 1 is now most-recent
        cache.put(3, make_eds(seed=3))
        assert cache.get(1)[1] == "device"
        assert cache.get(2)[1] == "host"

    def test_reput_promotes_from_spill(self):
        cache = ForestCache(heights=1, spill=2)
        eds1 = make_eds(seed=1)
        cache.put(1, eds1)
        cache.put(2, make_eds(seed=2))  # spills 1
        assert cache.get(1)[1] == "host"
        cache.put(1, make_eds(seed=1))  # fresh admission promotes
        assert cache.get(1)[1] == "device"

    def test_retention_disabled_returns_none(self, monkeypatch):
        monkeypatch.setenv("CELESTIA_SERVE_HEIGHTS", "0")
        cache = ForestCache()
        assert cache.put(1, make_eds()) is None

    def test_hit_miss_counters_tick(self):
        cache = ForestCache(heights=1, spill=1)
        cache.put(1, make_eds())
        before_hits = _counter_value(
            "celestia_serve_cache_hits_total", tier="device"
        )
        before_miss = _counter_value("celestia_serve_cache_misses_total")
        cache.get(1)
        cache.get(99)
        assert _counter_value(
            "celestia_serve_cache_hits_total", tier="device"
        ) == before_hits + 1
        assert _counter_value(
            "celestia_serve_cache_misses_total"
        ) == before_miss + 1


def _counter_value(name: str, **labels) -> float:
    """Sum over samples matching the label SUBSET (a family may carry
    more labels than the query — e.g. proofs_served's capped namespace)."""
    metric = registry().get(name)
    if metric is None:
        return 0.0
    return sum(
        value for sample_labels, value in metric.samples()
        if all(sample_labels.get(k) == v for k, v in labels.items())
    )


class TestSamplerQueue:
    def test_concurrent_submitters_are_batched(self):
        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(1, make_eds(k=4))
        sampler = ProofSampler()
        root = entry.eds.data_root()
        results: dict[int, object] = {}
        errors: list[Exception] = []
        barrier = threading.Barrier(6)

        def worker(i):
            try:
                barrier.wait(timeout=10)
                results[i] = sampler.share_proof(entry, i % 8, (i * 3) % 8)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors
        assert len(results) == 6
        for i, proof in results.items():
            assert proof.verify(root)
            assert proof == sampler.host_proof(entry, i % 8, (i * 3) % 8)

    def test_host_mode_env_pins_the_fallback_path(self, monkeypatch):
        monkeypatch.setenv("CELESTIA_SERVE_MODE", "host")
        assert serve_mode() == "host"
        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(1, make_eds(k=4))
        proofs = ProofSampler().sample_batch(entry, [(1, 2), (7, 0)])
        monkeypatch.delenv("CELESTIA_SERVE_MODE")
        batched = ProofSampler().sample_batch(entry, [(1, 2), (7, 0)])
        assert proofs == batched  # the seam's whole point

    def test_bad_coordinates_raise_before_any_dispatch(self):
        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(1, make_eds(k=4))
        with pytest.raises(ValueError):
            ProofSampler().sample_batch(entry, [(0, 0), (8, 0)])


class TestServeSpans:
    """The serve path's steps as spans: gather and assembly per batch
    group (with its tier), encoding per sample, and each group's summed
    queue wait on its `proof_serve` row."""

    def test_gather_assemble_encode_rows_and_queue_wait(self, tmp_path):
        import time

        import jax

        from celestia_app_tpu.trace.tracer import traced

        since = time.time_ns()
        provider = DasProvider(cache=ForestCache(heights=1, spill=1))
        provider.cache.put(5, make_eds(k=4, seed=41))
        provider.cache.put(6, make_eds(k=4, seed=42))  # height 5 spills
        provider.share_proof_payload(6, 1, 2)  # warm, outside the profile
        # Under a profiler session, as a traced run: rows carry cpu_ms.
        jax.profiler.start_trace(str(tmp_path))
        try:
            since = time.time_ns()
            provider.share_proof_payload(6, 1, 2)
            provider.share_proof_payload(5, 3, 0)
        finally:
            jax.profiler.stop_trace()

        def rows(name):
            return [r for r in traced().table(name)
                    if r.get("start_ns", r["ts_ns"]) >= since]

        gather, assemble, encode = (rows("proof_gather"),
                                    rows("proof_assemble"),
                                    rows("proof_encode"))
        assert [r["tier"] for r in gather] == ["device", "host"]
        assert [r["batch"] for r in gather] == [1, 1]
        assert [r["batch"] for r in assemble] == [1, 1]
        assert len(encode) == 2
        for r in gather + assemble + encode:
            assert 0 <= r["cpu_ms"] <= r["duration_ms"] + 1e-3
            assert r["end_ns"] >= r["start_ns"]
        serve = rows("proof_serve")
        assert len(serve) == 2
        assert all(r["queue_wait_ms"] >= 0 for r in serve)
        # Outside a profiler session no thread CPU clock is read.
        provider.share_proof_payload(6, 2, 2)
        assert "cpu_ms" not in traced().table("proof_encode")[-1]

    def test_serve_spans_join_a_request_trace_only(self):
        """Outside a request's trace the serve steps write their rows but
        no OTLP rows; inside one they hang under the request's span."""
        import time

        from celestia_app_tpu.trace.context import new_context, use_context
        from celestia_app_tpu.trace.spans import SPANS_TABLE
        from celestia_app_tpu.trace.tracer import traced

        steps = {"proof_gather", "proof_assemble", "proof_encode"}
        provider = DasProvider(cache=ForestCache(heights=1, spill=1))
        provider.cache.put(7, make_eds(k=4, seed=44))
        since = time.time_ns()
        provider.share_proof_payload(7, 0, 1)
        assert not [r for r in traced().table(SPANS_TABLE)
                    if r["ts_ns"] >= since and r["name"] in steps]
        assert "trace_id" not in traced().table("proof_encode")[-1]
        ctx = new_context()
        with use_context(ctx):
            provider.share_proof_payload(7, 1, 1)
        joined = [r for r in traced().table(SPANS_TABLE)
                  if r["traceId"] == ctx.trace_id]
        assert {r["name"] for r in joined} == steps
        assert {r["parentSpanId"] for r in joined} == {ctx.span_id}

    def test_queue_wait_sums_the_group(self):
        from celestia_app_tpu.serve import sampler as sampler_mod
        from celestia_app_tpu.trace.tracer import traced

        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(1, make_eds(k=4, seed=43))
        pending = [sampler_mod._Pending(entry, i, i, "row") for i in range(3)]
        for i, p in enumerate(pending):
            p.t_submit -= 0.010 * (i + 1)  # waited 10, 20 and 30 ms
        ProofSampler()._serve_batch(pending)
        row = traced().table("proof_serve")[-1]
        assert row["batch"] == 3
        assert 60.0 <= row["queue_wait_ms"] < 60.0 + 3 * 50.0
        assert all(p.proof is not None for p in pending)


class TestChaosFallback:
    def test_injected_proof_fault_served_by_host_path_bit_identical(self):
        from celestia_app_tpu import chaos
        from celestia_app_tpu.chaos import degrade

        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(1, make_eds(k=4, seed=9))
        sampler = ProofSampler()
        coords = [(0, 1), (5, 6), (3, 3)]
        baseline = sampler.sample_batch(entry, coords)
        before = _counter_value(
            "celestia_recoveries_total", seam="proof.serve", outcome="degraded"
        )
        chaos.install("seed=2,proof_fail=1.0")
        try:
            under_chaos = sampler.sample_batch(entry, coords)
        finally:
            chaos.uninstall()
            degrade.reset_for_tests()
        assert under_chaos == baseline
        assert _counter_value(
            "celestia_recoveries_total", seam="proof.serve", outcome="degraded"
        ) == before + 1
        assert _counter_value(
            "celestia_chaos_injections_total", seam="proof.serve"
        ) > 0

    def test_sampling_drill_smoke(self):
        """The chaos_soak sampling drill in tier-1 (small fixed seed)."""
        spec = importlib.util.spec_from_file_location(
            "chaos_soak", os.path.join(REPO_ROOT, "scripts", "chaos_soak.py")
        )
        soak = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(soak)
        result = soak.run_sampling_drill(k=4, samples=24)
        assert result["ok"], result
        assert result["bit_identical"] and result["all_verify"]
        assert result["injections"] > 0


class _ServeStubNode:
    """Crypto-free node surface for the REST/gRPC planes, carrying a live
    DasProvider over one cached deterministic square."""

    chain_id = "serve-test"

    def __init__(self):
        self.cache = ForestCache(heights=2, spill=2)
        self.eds = make_eds(k=4, seed=11)
        self.cache.put(1, self.eds)
        self._provider = DasProvider(cache=self.cache)

    def das_provider(self):
        return self._provider


class TestDasPlanes:
    """GET /das/* on the shared handler + the gRPC Das service: one
    payload renderer, byte-identical everywhere."""

    @pytest.fixture()
    def planes(self):
        pytest.importorskip("grpc")
        from celestia_app_tpu.rpc.api_gateway import serve_api
        from celestia_app_tpu.rpc.grpc_plane import GrpcNode, serve_grpc
        from celestia_app_tpu.trace.exposition import (
            register_das_provider,
            unregister_das_provider,
        )

        node = _ServeStubNode()
        register_das_provider(node.das_provider())
        gw = serve_api(node)
        plane = serve_grpc(node)
        client = GrpcNode(plane.target)
        try:
            yield node, gw, plane, client
        finally:
            client.close()
            gw.stop()
            plane.stop()
            unregister_das_provider()

    def test_rest_grpc_debug_and_grpc_service_byte_identical(self, planes):
        node, gw, plane, client = planes
        path = "/das/share_proof?height=1&row=2&col=5"
        bodies = []
        for url in (gw.url, plane.debug_url):
            with urllib.request.urlopen(url + path, timeout=10) as resp:
                assert resp.status == 200
                bodies.append(resp.read())
        assert bodies[0] == bodies[1]
        # The real gRPC service carries the SAME canonical bytes.
        assert client.share_proof_bytes(1, 2, 5) == bodies[0]
        payload = json.loads(bodies[0])
        assert payload["height"] == 1 and payload["square_size"] == 4
        # The served proof verifies against the committed data root.
        from celestia_app_tpu.rpc.codec import share_proof_from_json

        proof = share_proof_from_json(payload["proof"])
        assert proof.verify(bytes.fromhex(payload["data_root"]))

    def test_column_axis_on_every_plane(self, planes):
        node, gw, plane, client = planes
        path = "/das/share_proof?height=1&row=6&col=3&axis=col"
        bodies = []
        for url in (gw.url, plane.debug_url):
            with urllib.request.urlopen(url + path, timeout=10) as resp:
                bodies.append(resp.read())
        assert bodies[0] == bodies[1]
        assert client.share_proof_bytes(1, 6, 3, axis="col") == bodies[0]
        payload = json.loads(bodies[0])
        assert payload["axis"] == "col"
        from celestia_app_tpu.rpc.codec import share_proof_from_json

        proof = share_proof_from_json(payload["proof"])
        assert proof.verify(bytes.fromhex(payload["data_root"]))
        # Column roots occupy the second 2k leaves of the data-root tree.
        assert proof.row_proof.start_row == 2 * 4 + 3

    def test_namespace_route_identity_and_verify(self, planes):
        node, gw, plane, client = planes
        ns_hex = bytes(node.eds.ods_namespaces()[3].tobytes()).hex()
        path = f"/das/shares?height=1&namespace={ns_hex}"
        bodies = []
        for url in (gw.url, plane.debug_url):
            with urllib.request.urlopen(url + path, timeout=10) as resp:
                bodies.append(resp.read())
        assert bodies[0] == bodies[1]
        assert client.shares_by_namespace_bytes(1, ns_hex) == bodies[0]
        payload = json.loads(bodies[0])
        assert payload["found"] and payload["shares"] >= 1
        from celestia_app_tpu.rpc.codec import share_proof_from_json

        proof = share_proof_from_json(payload["proof"])
        assert proof.verify(bytes.fromhex(payload["data_root"]))

    def test_absent_namespace_answers_found_false(self, planes):
        node, gw, plane, client = planes
        payload = client.shares_by_namespace(1, "ee" * NAMESPACE_SIZE)
        assert payload["found"] is False and payload["proof"] is None

    def test_error_statuses(self, planes):
        import grpc

        node, gw, plane, client = planes
        # Unknown height: 404 on HTTP, NOT_FOUND on gRPC.
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                gw.url + "/das/share_proof?height=9&row=0&col=0", timeout=10
            )
        assert exc.value.code == 404
        with pytest.raises(grpc.RpcError) as gexc:
            client.share_proof_bytes(9, 0, 0)
        assert gexc.value.code() == grpc.StatusCode.NOT_FOUND
        # Bad params: 400 / INVALID_ARGUMENT.
        with pytest.raises(urllib.error.HTTPError) as exc2:
            urllib.request.urlopen(
                gw.url + "/das/share_proof?height=1&row=zap&col=0", timeout=10
            )
        assert exc2.value.code == 400
        with pytest.raises(grpc.RpcError) as gexc2:
            client.shares_by_namespace_bytes(1, "nothex")
        assert gexc2.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        # Out-of-square coordinate: 400, not a 500.
        with pytest.raises(urllib.error.HTTPError) as exc3:
            urllib.request.urlopen(
                gw.url + "/das/share_proof?height=1&row=0&col=99", timeout=10
            )
        assert exc3.value.code == 400

    def test_adversary_detections_map_to_typed_grpc_statuses(self, planes):
        """The gRPC plane must carry the same detection semantics the
        HTTP planes express as 410/502: a withheld share answers
        FAILED_PRECONDITION (ShareWithheld is a LookupError — without
        the typed clause it escaped as an opaque UNKNOWN) and a
        tampered square answers DATA_LOSS, never INVALID_ARGUMENT
        (BadProofDetected subclasses ValueError)."""
        import grpc

        from celestia_app_tpu import chaos

        node, gw, plane, client = planes
        chaos.install("seed=11,withhold_frac=0.25")
        try:
            adv = chaos.active_adversary()
            withheld = adv.withheld_set(1, 8)  # k=4 -> 8x8 EDS
            hit = next(iter(withheld))
            with pytest.raises(grpc.RpcError) as gexc:
                client.share_proof_bytes(1, *hit)
            assert gexc.value.code() == grpc.StatusCode.FAILED_PRECONDITION
            assert "withholding detected" in gexc.value.details()
            # The HTTP twin of the same coordinate: 410 Gone.
            with pytest.raises(urllib.error.HTTPError) as hexc:
                urllib.request.urlopen(
                    gw.url + "/das/share_proof?height=1"
                    f"&row={hit[0]}&col={hit[1]}",
                    timeout=10,
                )
            assert hexc.value.code == 410
        finally:
            chaos.uninstall()
        chaos.install("seed=11,wrong_root=1")
        try:
            with pytest.raises(grpc.RpcError) as gexc2:
                client.share_proof_bytes(1, 0, 0)
            assert gexc2.value.code() == grpc.StatusCode.DATA_LOSS
        finally:
            chaos.uninstall()

    def test_healing_in_progress_is_retryable_on_every_plane(self, planes):
        """ISSUE-12 satellite: a sample arriving mid-heal answers a
        RETRYABLE status — 503 + Retry-After on the HTTP twins
        (byte-identical bodies) and UNAVAILABLE on the gRPC Das service
        — never the terminal 410/502 the detections answer."""
        import grpc

        from celestia_app_tpu.serve.heal import HealingEngine

        node, gw, plane, client = planes
        engine = HealingEngine(
            node.das_provider(), name="planes", retry_after_s=2.0
        )
        try:
            assert engine.note("withheld", 1)  # mark mid-heal, no worker
            bodies = []
            for url in (gw.url, plane.debug_url):
                with pytest.raises(urllib.error.HTTPError) as exc:
                    urllib.request.urlopen(
                        url + "/das/share_proof?height=1&row=0&col=0",
                        timeout=10,
                    )
                assert exc.value.code == 503
                assert exc.value.headers.get("Retry-After") == "2"
                bodies.append(exc.value.read())
            assert bodies[0] == bodies[1]
            payload = json.loads(bodies[0])
            assert payload["healing"] is True
            with pytest.raises(grpc.RpcError) as gexc:
                client.share_proof_bytes(1, 0, 0)
            assert gexc.value.code() == grpc.StatusCode.UNAVAILABLE
            assert "healed" in gexc.value.details()
        finally:
            engine.close()

    def test_no_provider_is_503(self):
        from celestia_app_tpu.trace.exposition import (
            handle_observability_get,
            unregister_das_provider,
        )

        unregister_das_provider()
        status, _, body = handle_observability_get(
            "/das/share_proof?height=1&row=0&col=0"
        )
        assert status == 503
        assert b"no DAS provider" in body

    def test_proofs_served_counter_carries_the_plane(self, planes):
        node, gw, plane, client = planes
        before = _counter_value(
            "celestia_proofs_served_total", plane="rest", kind="share_proof"
        )
        urllib.request.urlopen(
            gw.url + "/das/share_proof?height=1&row=0&col=0", timeout=10
        ).read()
        assert _counter_value(
            "celestia_proofs_served_total", plane="rest", kind="share_proof"
        ) == before + 1
        gbefore = _counter_value(
            "celestia_proofs_served_total", plane="grpc", kind="share_proof"
        )
        client.share_proof_bytes(1, 0, 0)
        assert _counter_value(
            "celestia_proofs_served_total", plane="grpc", kind="share_proof"
        ) == gbefore + 1


class TestProviderRebuild:
    def test_miss_routes_through_rebuild_and_readmits(self):
        eds = make_eds(k=4, seed=21)
        calls = []

        def rebuild(height):
            calls.append(height)
            return eds if height == 7 else None

        provider = DasProvider(
            cache=ForestCache(heights=2, spill=2), rebuild=rebuild
        )
        payload = provider.share_proof_payload(7, 1, 1)
        assert calls == [7]
        assert payload["data_root"] == eds.data_root().hex()
        # Re-admitted: the second query is a cache hit, no rebuild.
        provider.share_proof_payload(7, 2, 2)
        assert calls == [7]
        with pytest.raises(UnknownHeight):
            provider.share_proof_payload(8, 0, 0)

    def test_payload_is_plane_free_and_canonical(self):
        provider = DasProvider(cache=ForestCache(heights=1, spill=1))
        provider.cache.put(3, make_eds(k=4, seed=22))
        payload = provider.share_proof_payload(3, 0, 0)
        blob = render(payload)
        assert json.loads(blob) == payload
        assert blob == render(json.loads(blob))  # canonical fixpoint


class TestSloAndHealth:
    def test_default_slos_include_proof_p99(self):
        from celestia_app_tpu.trace.slo import default_slos

        spec = {s.name: s for s in default_slos()}["proof_p99"]
        assert spec.metric == "celestia_proof_latency_seconds"
        assert dict(spec.labels) == {"phase": "total"}

    def test_burn_rate_engine_evaluates_proof_p99(self, monkeypatch):
        """The acceptance wire: served samples land on the histogram the
        engine's default proof_p99 spec judges every tick."""
        from celestia_app_tpu.trace import slo

        monkeypatch.setenv("CELESTIA_SLO_TICK_S", "0")
        engine = slo._reset_for_tests()
        try:
            cache = ForestCache(heights=1, spill=1)
            entry = cache.put(1, make_eds(k=4, seed=41))
            ProofSampler().share_proof(entry, 0, 0)
            engine.tick()  # snapshot baseline
            ProofSampler().share_proof(entry, 1, 1)
            results = engine.tick()
            assert results["proof_p99"]["state"] in ("ok", "fast_burn")
            assert "burn" in results["proof_p99"]
            assert results["proof_p99"]["kind"] == "quantile"
        finally:
            slo._reset_for_tests()

    def test_latency_histogram_has_all_phases(self):
        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(1, make_eds(k=4, seed=31))
        ProofSampler().share_proof(entry, 0, 0)
        hist = registry().get("celestia_proof_latency_seconds")
        phases = {
            dict(key).get("phase")
            for key, _ in hist.snapshot().children.items()
        }
        # Only the per-sample total is left on the latency family; the
        # steps are spans, each with its own histogram.
        assert phases == {"total"}
        for step in ("gather", "assemble"):
            assert registry().get(
                f"celestia_proof_{step}_seconds"
            ).snapshot().children


class TestLoadgenSmoke:
    def test_loadgen_round_trip_and_artifacts(self, tmp_path):
        spec = importlib.util.spec_from_file_location(
            "das_loadgen", os.path.join(REPO_ROOT, "scripts", "das_loadgen.py")
        )
        lg = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lg)
        out = tmp_path / "metrics"
        round_out = tmp_path / "DAS_r09.json"
        rc = lg.main([
            "--heights", "2", "--k", "4", "--samples", "60", "--threads", "3",
            "--verify", "20",
            "--metrics-out", str(out), "--round-out", str(round_out),
        ])
        assert rc == 0
        record = json.loads(round_out.read_text())
        assert record["n"] == 9
        assert record["proofs_per_s"] > 0
        assert record["proof_p99_ms"] >= record["proof_p50_ms"]
        prom = (out / "das_loadgen.prom").read_text()
        assert "celestia_proof_latency_seconds" in prom
        # (The record's bench_trend das-series seat is pinned in
        # tests/test_bench_trend.py::TestDasSeries.)


class TestServingNodeFlow:
    def test_commit_retention_and_jsonrpc_methods(self):
        """The full crypto-gated flow: blocks commit -> heights retained
        -> rpc_get_share_proof serves them -> /healthz reports the cache."""
        pytest.importorskip("cryptography")
        from celestia_app_tpu.rpc.server import ServingNode
        from celestia_app_tpu.shares.namespace import Namespace
        from celestia_app_tpu.shares.sparse import Blob
        from celestia_app_tpu.testutil.testnode import (
            deterministic_genesis,
            funded_keys,
        )
        from celestia_app_tpu.user import TxClient

        keys = funded_keys(2)
        node = ServingNode(genesis=deterministic_genesis(keys), keys=keys)
        client = TxClient(node, keys)
        blob = Blob(Namespace.v0(b"\x07" * 10), b"\xab" * 2048)
        client.submit_pay_for_blob([blob])
        height = node.app.height
        stats = node.serve_cache.stats()
        assert height in stats["device_heights"]
        payload = node.rpc_get_share_proof(height, 0, 0)
        from celestia_app_tpu.rpc.codec import share_proof_from_json

        proof = share_proof_from_json(payload["proof"])
        root = bytes.fromhex(payload["data_root"])
        assert proof.verify(root)
        # The served root IS the committed block's data hash.
        assert root == node._blocks_by_height[height][0].hash
        # Namespace query for the submitted blob.
        ns_payload = node.rpc_get_shares_by_namespace(
            height, blob.namespace.to_bytes().hex()
        )
        assert ns_payload["found"] and ns_payload["shares"] >= 4
        nsp = share_proof_from_json(ns_payload["proof"])
        assert nsp.verify(root)
        # /healthz layer shape.
        snap = node.health_snapshot()
        assert snap["serve"]["device_heights"] == stats["device_heights"]
        assert snap["serve"]["hit_ratio"] is not None


class TestReadPathNamespaceAccounting:
    """ISSUE-10 satellite: the read path joins the PR 4 per-tenant
    accounting — celestia_proofs_served_total carries the payload's
    capped namespace, celestia_proof_latency_seconds{phase=total} the
    served share's."""

    def test_share_proof_payload_namespace_label(self):
        from celestia_app_tpu.serve.api import payload_namespace_label
        from celestia_app_tpu.trace.square_journal import (
            capped_namespace_label,
        )

        ns = bytes(28) + b"\x07"
        # The label routes through the process-wide cap: whatever the cap
        # says (admitted or folded to `other`) is what the payload gets.
        want = capped_namespace_label("7")
        assert payload_namespace_label(
            {"proof": {"namespace": ns.hex()}}
        ) == want
        assert payload_namespace_label({"namespace": ns.hex()}) == want
        # No namespace, absent payload, junk hex: the reserved bucket.
        assert payload_namespace_label({}) == "other"
        assert payload_namespace_label(None) == "other"
        assert payload_namespace_label({"namespace": "zz"}) == "other"
        # Parity shares are not a tenant: 0xff..ff folds to `other`,
        # matching the sampler's _proof_namespace_label twin (a uniform
        # DAS workload is 3/4 parity — it must not burn a capped slot or
        # split this counter from the latency histogram).
        from celestia_app_tpu.constants import PARITY_NAMESPACE_BYTES

        parity_hex = PARITY_NAMESPACE_BYTES.hex()
        assert payload_namespace_label(
            {"namespace": parity_hex}
        ) == "other"
        assert payload_namespace_label(
            {"proof": {"namespace": parity_hex}}
        ) == "other"

    def test_served_counter_carries_capped_namespace(self):
        from celestia_app_tpu.serve.api import count_served
        from celestia_app_tpu.trace.square_journal import (
            capped_namespace_label,
        )

        ns = bytes(28) + b"\x2a"
        want = capped_namespace_label("2a")
        before = _counter_value(
            "celestia_proofs_served_total",
            plane="test", kind="share_proof", namespace=want,
        )
        count_served("test", "share_proof",
                     {"proof": {"namespace": ns.hex()}})
        assert _counter_value(
            "celestia_proofs_served_total",
            plane="test", kind="share_proof", namespace=want,
        ) == before + 1

    def test_latency_total_labeled_by_served_namespace(self):
        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(11, make_eds(k=2))
        sampler = ProofSampler()
        hist = registry().get("celestia_proof_latency_seconds")
        snap_before = hist.snapshot() if hist is not None else None
        proof = sampler.share_proof(entry, 0, 0)
        assert proof.verify(entry.eds.data_root())
        from celestia_app_tpu.trace.square_journal import (
            capped_namespace_label,
            namespace_label,
        )

        label = capped_namespace_label(namespace_label(proof.namespace))
        hist = registry().get("celestia_proof_latency_seconds")
        snap = hist.snapshot()
        if snap_before is not None:
            snap = snap.delta(snap_before)
        assert snap.count(phase="total", namespace=label) == 1
        # A parity-quadrant sample folds into the reserved bucket.
        other_before = snap.count(phase="total", namespace="other")
        sampler.share_proof(entry, 3, 3)  # parity quadrant at k=2
        snap2 = hist.snapshot()
        if snap_before is not None:
            snap2 = snap2.delta(snap_before)
        assert snap2.count(phase="total", namespace="other") == other_before + 1
