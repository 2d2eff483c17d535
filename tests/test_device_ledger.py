"""Device-attribution ledger (trace/device_ledger.py): the program
ledger billing compile vs dispatch through real pipelines, ownership
reconciliation against the measured high-water (owners re-zero on
evict, the unattributed residual is the slack), the sustained-growth
leak trigger wiring into the flight recorder, three-plane byte
identity for GET /device, and the /fleet device rollup.

Runs without the signing stack — squares are deterministic synthetic
blocks (same fixture family as tests/test_attestation.py).
"""

from __future__ import annotations

import gc
import glob
import json
import os

import numpy as np
import pytest

from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
from celestia_app_tpu.da.eds import ExtendedDataSquare
from celestia_app_tpu.serve.shard import build_entry
from celestia_app_tpu.trace import device_ledger as dl
from celestia_app_tpu.trace import fleet
from celestia_app_tpu.trace import flight_recorder as fr
from celestia_app_tpu.trace.exposition import handle_observability_get
from celestia_app_tpu.trace.metrics import Registry


def det_square(k: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ns = np.sort(rng.integers(0, 128, k * k).astype(np.uint8))
    ods = rng.integers(0, 256, (k * k, SHARE_SIZE), dtype=np.uint8)
    ods[:, :NAMESPACE_SIZE] = 0
    ods[:, NAMESPACE_SIZE - 1] = ns
    return ods.reshape(k, k, SHARE_SIZE)


def _row(snap: dict, family: str) -> dict | None:
    for r in snap["programs"]:
        if r["family"] == family:
            return r
    return None


class TestRealPipelineTick:
    """The ledger observed through REAL programs, not stubs.  Runs
    first in this file on purpose: lru-cached builders hold their
    _Tracked wrappers for the whole process, so these tests must see
    the session's live records BEFORE any _reset_for_tests orphans
    them (a reset drops the record a cached wrapper still ticks)."""

    def test_compute_and_forest_build_tick_the_ledger(self):
        ods = det_square(4)
        eds = ExtendedDataSquare.compute(ods)
        ExtendedDataSquare.compute(ods)  # second call = a real dispatch
        build_entry(1, eds)

        snap = dl.snapshot()
        fams = {r["family"] for r in snap["programs"]}
        assert "forest" in fams
        # Some extend+DAH lowering ran for k=4 (which one depends on the
        # $CELESTIA_PIPE_* seats; the ledger attributes whichever did).
        extend_rows = [
            r for r in snap["programs"]
            if r["k"] == 4 and r["family"] != "forest" and r["dispatches"]
        ]
        assert extend_rows, snap["programs"]

        forest = _row(snap, "forest")
        assert forest["builds"] >= 1
        assert forest["dispatches"] >= 1
        # First dispatch is the trace+compile bill — always nonzero.
        assert forest["compile_s"] > 0
        assert forest["resident"] is True  # lru builder still holds it
        assert snap["programs_resident"]["forest"] >= 1

    def test_programs_are_named_after_their_family(self):
        """The device trace names a program `jit_<family>`, never the
        builder closure's `jit_run`: a name that outlives its
        fingerprint."""
        import jax.numpy as jnp

        from celestia_app_tpu.kernels import fused
        from celestia_app_tpu.kernels.rs import active_construction

        ods = jnp.asarray(det_square(4))
        fn = fused.jit_extend_and_dah(4, active_construction())
        eds = fn(ods)[0]
        lowered = {
            "extend_and_dah": fn.lower(ods).as_text(),
            "forest": fused.jit_forest(4).lower(eds).as_text(),
        }
        for family, text in lowered.items():
            assert text.startswith(f"module @jit_{family} "), text[:80]
        live = [rec for rec in dl._PROGRAMS.values()
                if rec["ref"] is not None and rec["ref"]() is not None]
        assert {"extend_and_dah", "forest"} <= {r["family"] for r in live}
        for rec in live:
            inner = getattr(rec["ref"]()._fn, "__wrapped__", None)
            if inner is not None:
                assert inner.__name__ == rec["family"]

    def test_snapshot_rows_are_sorted_and_shaped(self):
        snap = dl.snapshot()
        keys = [
            (r["family"], r["k"], r["construction"], r["mode"],
             r["batch"], r["shards"])
            for r in snap["programs"]
        ]
        assert keys == sorted(keys)
        for r in snap["programs"]:
            assert r["dispatch_s"] >= 0.0
            assert r["compile_s"] >= 0.0
            assert isinstance(r["resident"], bool)


@pytest.fixture()
def _clean_ledger():
    dl._reset_for_tests()
    yield
    dl._reset_for_tests()


class TestProgramLedgerUnit:
    def test_first_call_bills_compile_then_dispatches(self, _clean_ledger):
        w = dl.track(lambda x: x + 1, "unit_fam", k=8, mode="test")
        assert w(1) == 2
        assert w(2) == 3
        assert w(3) == 4
        row = _row(dl.snapshot(), "unit_fam")
        assert row["builds"] == 1
        assert row["dispatches"] == 3
        assert row["compile_s"] > 0
        assert row["dispatch_s"] > 0
        assert row["resident"] is True
        assert row["last_dispatch_age_s"] is not None

    def test_eviction_flips_resident_but_keeps_counters(self, _clean_ledger):
        w = dl.track(lambda x: x, "evict_fam", k=4)
        w(0)
        del w
        gc.collect()
        row = _row(dl.snapshot(), "evict_fam")
        assert row["resident"] is False
        assert row["dispatches"] == 1  # history survives the eviction

    def test_rebuild_revives_the_same_record(self, _clean_ledger):
        w1 = dl.track(lambda x: x, "revive_fam", k=4)
        w1(0)
        del w1
        gc.collect()
        w2 = dl.track(lambda x: x * 2, "revive_fam", k=4)
        row = _row(dl.snapshot(), "revive_fam")
        assert row["builds"] == 2
        assert row["dispatches"] == 1  # carried over
        assert row["resident"] is True
        assert w2(3) == 6

    def test_wrapper_attribute_passthrough(self, _clean_ledger):
        class Prog:
            lowered = "yes"

            def __call__(self, x):
                return x

        w = dl.track(Prog(), "attr_fam")
        assert w.lowered == "yes"


class TestReconciliation:
    def test_owned_plus_residual_covers_measured(self, _clean_ledger):
        dl.register_owner("t_live", lambda: 1000)
        dl.note_owned_bytes("t_keyed", "a", 500)
        dl.note_owned_bytes("t_keyed", "b", 250)
        rec = dl.reconcile()
        assert rec["owners"]["t_live"] == 1000
        assert rec["owners"]["t_keyed"] == 750
        assert rec["owned_bytes"] == 1750
        # The reconciliation invariant: every measured byte is either
        # claimed by an owner or sits in the residual gauge.
        assert rec["owned_bytes"] + rec["unattributed_residual"] == max(
            rec["measured_bytes"], rec["owned_bytes"]
        )

    def test_renoting_a_key_replaces_not_accumulates(self, _clean_ledger):
        dl.note_owned_bytes("t_keyed", "a", 500)
        dl.note_owned_bytes("t_keyed", "a", 100)
        assert dl.reconcile()["owners"]["t_keyed"] == 100

    def test_forget_drops_one_key(self, _clean_ledger):
        dl.note_owned_bytes("t_keyed", "a", 500)
        dl.note_owned_bytes("t_keyed", "b", 250)
        dl.forget_owned_bytes("t_keyed", "a")
        assert dl.reconcile()["owners"]["t_keyed"] == 250

    def test_evicted_owner_rezeroes_in_the_gauge(self, _clean_ledger):
        dl.register_owner("t_gone", lambda: 4096)
        dl.reconcile()
        dl.unregister_owner("t_gone")
        rec = dl.reconcile()
        assert "t_gone" not in rec["owners"]
        # The published gauge re-zeros rather than serving 4096 forever.
        from celestia_app_tpu.trace.metrics import registry

        text = registry().render()
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("celestia_device_bytes") and "t_gone" in ln
        )
        assert line.rsplit(" ", 1)[1] in ("0", "0.0")

    def test_raising_callback_reports_zero(self, _clean_ledger):
        def boom():
            raise RuntimeError("mid-evict")

        dl.register_owner("t_boom", boom)
        assert dl.reconcile()["owners"]["t_boom"] == 0


class TestLeakTrigger:
    def test_sustained_residual_growth_fires_flight_bundle(
        self, _clean_ledger, monkeypatch, tmp_path
    ):
        monkeypatch.setenv("CELESTIA_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("CELESTIA_FLIGHT_MIN_INTERVAL_S", "0")
        monkeypatch.setenv("CELESTIA_DEVICE_LEAK_TICKS", "2")
        fr._reset_for_tests()

        # Deterministic growth: a measured high-water that climbs 1 MiB
        # per tick with zero owners is an unattributed residual climbing
        # in lockstep — the leak signature.
        measured = {"v": 0}

        def climbing():
            measured["v"] += 1 << 20
            return measured["v"], "stub"

        monkeypatch.setattr(dl, "_measured_bytes", climbing)

        r1 = dl.reconcile()  # baseline: no prior residual, streak 0
        assert r1["residual_growth_streak"] == 0
        r2 = dl.reconcile()
        assert r2["residual_growth_streak"] == 1
        r3 = dl.reconcile()  # streak hits leak_ticks(2) -> fires
        assert r3["residual_growth_streak"] == 2

        bundles = glob.glob(
            str(tmp_path / "flight-device_residual_growth-*.json")
        )
        assert len(bundles) == 1
        bundle = json.load(open(bundles[0]))
        assert bundle["context"]["streak"] == 2
        assert bundle["context"]["source"] == "stub"
        # Satellite contract: every flight bundle embeds the device
        # ledger snapshot (a fresh one, not the rate-limited cache).
        assert "ownership" in bundle["device"]
        assert "programs" in bundle["device"]

        # One bundle per episode: the streak re-arms, so the NEXT tick
        # starts over instead of dumping every tick of the same leak.
        r4 = dl.reconcile()
        assert r4["residual_growth_streak"] == 1
        assert len(glob.glob(str(tmp_path / "flight-*.json"))) == 1


class TestDevicePlaneIdentity:
    def test_device_byte_identical_across_planes(
        self, _clean_ledger, monkeypatch
    ):
        monkeypatch.setenv("CELESTIA_DEVICE_TICK_S", "3600")
        w = dl.track(lambda x: x, "plane_fam", k=16, mode="t")
        w(1)
        dl.register_owner("plane_owner", lambda: 123)
        dl.note_warmup(16, "vandermonde", "fused")

        responses = {
            plane: handle_observability_get("/device", plane=plane)
            for plane in ("jsonrpc", "rest", "grpc")
        }
        assert all(r[0] == 200 for r in responses.values())
        assert all(r[1] == "application/json" for r in responses.values())
        bodies = {plane: r[2] for plane, r in responses.items()}
        assert bodies["jsonrpc"] == bodies["rest"] == bodies["grpc"]

        payload = json.loads(bodies["rest"])
        for key in ("programs", "programs_resident", "ownership",
                    "autotuner_seats", "warmup"):
            assert key in payload
        assert payload["programs_resident"]["plane_fam"] == 1
        assert payload["ownership"]["owners"]["plane_owner"] == 123
        assert payload["warmup"] == [
            {"k": 16, "construction": "vandermonde", "mode": "fused"}
        ]

    def test_tick_cache_serves_identical_bytes_within_interval(
        self, _clean_ledger, monkeypatch
    ):
        monkeypatch.setenv("CELESTIA_DEVICE_TICK_S", "3600")
        first = dl.device_payload()
        dl.register_owner("late_owner", lambda: 999)  # arrives mid-tick
        second = dl.device_payload()
        assert first == second  # frozen until the tick expires

    def test_snapshot_dump_writes_atomic_json(
        self, _clean_ledger, monkeypatch, tmp_path
    ):
        out = tmp_path / "device.json"
        monkeypatch.setenv("CELESTIA_DEVICE_SNAPSHOT", str(out))
        w = dl.track(lambda x: x, "dump_fam", k=4)
        w(0)
        dl._dump_snapshot()  # what the atexit hook runs
        data = json.loads(out.read_text())
        assert any(r["family"] == "dump_fam" for r in data["programs"])
        assert not out.with_suffix(".json.tmp").exists()


_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0)


def _peer_registry(latencies, proofs_total: float):
    r = Registry()
    h = r.histogram("celestia_proof_latency_seconds", "lat", buckets=_BUCKETS)
    for v in latencies:
        h.observe(v, phase="total")
    r.counter("celestia_proofs_served_total", "served").inc(
        proofs_total, plane="rest", kind="share_proof"
    )
    return r


def _stub_fetch(peer_pages: dict):
    def fetch(url, path):
        pages = peer_pages.get(url)
        if pages is None:
            raise OSError("connection refused")
        page = pages[path]
        return page if isinstance(page, str) else json.dumps(page)

    return fetch


def _stub_pages(registry, device=None):
    pages = {
        "/metrics": registry.render(),
        "/healthz": {"status": "ok", "degraded": {}},
        "/slo": {"slos": {}},
        "/heal": {"engines": {}},
    }
    if device is not None:
        pages["/device"] = device
    return pages


class TestFleetDeviceMerge:
    @pytest.fixture(autouse=True)
    def _clean_fleet(self):
        fleet._reset_for_tests()
        yield
        fleet._reset_for_tests()

    def test_fleet_rolls_up_device_blocks(self):
        device_a = {
            "programs": [{"family": "forest"}, {"family": "extend_and_dah"}],
            "programs_resident": {"forest": 1, "extend_and_dah": 1},
            "ownership": {
                "owned_bytes": 1000,
                "measured_bytes": 1500,
                "unattributed_residual": 500,
            },
        }
        device_b = {
            "programs": [{"family": "forest"}],
            "programs_resident": {"forest": 1},
            "ownership": {
                "owned_bytes": 300,
                "measured_bytes": 300,
                "unattributed_residual": 0,
            },
        }
        pages = {
            "http://a": _stub_pages(_peer_registry([0.02], 7.0), device_a),
            "http://b": _stub_pages(_peer_registry([0.05], 3.0), device_b),
            # http://c predates the device ledger: no /device page, and
            # _stub_fetch raises KeyError for it — the host row must
            # still merge (rolling-upgrade safety).
            "http://c": _stub_pages(_peer_registry([0.7], 1.0)),
        }
        fleet.configure(
            list(pages), interval_s=3600, fetch=_stub_fetch(pages)
        )
        status, _, body = handle_observability_get("/fleet", plane="rest")
        assert status == 200
        merged = json.loads(body)

        assert merged["fleet"]["hosts_reachable"] == 3
        dev = merged["fleet"]["device"]
        assert dev["hosts_reporting"] == 2
        assert dev["programs_resident"] == 3
        assert dev["owned_bytes"] == 1300
        assert dev["unattributed_residual"] == 500

        hosts = merged["hosts"]
        assert hosts["http://a"]["device"]["programs"] == 2
        assert hosts["http://a"]["device"]["measured_bytes"] == 1500
        assert "device" not in hosts["http://c"]
        assert hosts["http://c"]["reachable"] is True
