"""Chaos seams + graceful degradation (celestia_app_tpu/chaos/).

Tier-1 seats for the failure machinery, all crypto-free:

  * spec parsing + per-seam deterministic injection;
  * the fast chaos smoke: scripts/chaos_soak.py's device/WAL/gossip/
    breaker drills at small k with a fixed seed, so the injection seams
    cannot bit-rot (the full soak is the same functions, bigger knobs);
  * the degradation ladder: fused -> staged within the breaker window
    under persistent injected device failure, bit-identical roots,
    /healthz DEGRADED;
  * BlockPipeline failure propagation: a dead worker raises the stored
    exception at the next put()/drain() instead of wedging the caller;
  * crash-restart determinism: a validator killed between WAL fsync and
    broadcast refuses the conflicting vote after restart and rejoins
    via the idempotent re-sign — double-sign safety across the crash,
    torn tail included.
"""

from __future__ import annotations

import importlib.util
import os
import time

import numpy as np
import pytest

from celestia_app_tpu import chaos
from celestia_app_tpu.chaos import degrade
from celestia_app_tpu.chaos.spec import ChaosInjected, ChaosInjector, parse_spec
from celestia_app_tpu.constants import SHARE_SIZE

_SOAK = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts", "chaos_soak.py",
)

PREVOTE = 1  # consensus/votes.py constant, sans its crypto import


def _load_soak():
    spec = importlib.util.spec_from_file_location("chaos_soak", _SOAK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.uninstall()
    degrade.reset_for_tests()
    yield
    chaos.uninstall()
    degrade.reset_for_tests()


def _injections(seam: str) -> float:
    from celestia_app_tpu.trace.metrics import registry

    counter = registry().counter("celestia_chaos_injections_total")
    with counter._lock:
        return counter._values.get((("seam", seam),), 0.0)


class TestSpec:
    def test_parse_happy_path(self):
        params = parse_spec(
            "seed=7,dispatch_fail=0.05,upload_stall_ms=200,"
            "gossip_drop=0.1,wal_torn_tail=1,rpc_slow_ms=100"
        )
        assert params["seed"] == 7
        assert params["dispatch_fail"] == pytest.approx(0.05)
        assert params["wal_torn_tail"] == 1

    def test_unknown_key_and_malformed_pair_rejected(self):
        with pytest.raises(ValueError):
            parse_spec("seed=7,dispatch_fial=0.5")  # typo must not no-op
        with pytest.raises(ValueError):
            parse_spec("dispatch_fail")
        with pytest.raises(ValueError):
            parse_spec("dispatch_fail=lots")

    def test_injection_sequence_deterministic_per_seam(self):
        """Same spec -> same per-seam verdict sequence, regardless of how
        calls to OTHER seams interleave."""
        a = ChaosInjector(parse_spec("seed=3,gossip_drop=0.5"))
        b = ChaosInjector(parse_spec("seed=3,gossip_drop=0.5"))
        seq_a = [bool(a.gossip_send().get("drop")) for _ in range(32)]
        seq_b = []
        for _ in range(32):
            b.mempool_insert()  # interleaved other-seam traffic
            seq_b.append(bool(b.gossip_send().get("drop")))
        assert seq_a == seq_b
        assert any(seq_a) and not all(seq_a)

    def test_install_validates_dict_specs_too(self):
        with pytest.raises(ValueError, match="dispatch_fial"):
            chaos.install({"dispatch_fial": 1.0})  # typo'd dict = loud

    def test_env_spec_activates_and_cache_follows_changes(self, monkeypatch):
        monkeypatch.setenv("CELESTIA_CHAOS", "seed=1,mempool_drop=1.0")
        assert chaos.mempool_insert() is True
        monkeypatch.setenv("CELESTIA_CHAOS", "")
        assert chaos.mempool_insert() is False

    def test_dispatch_fail_targets_fused_rung_only(self):
        inj = ChaosInjector(parse_spec("seed=2,dispatch_fail=1.0"))
        with pytest.raises(ChaosInjected):
            inj.device_dispatch("fused")
        with pytest.raises(ChaosInjected):  # the epilogue rung is fused-family
            inj.device_dispatch("fused_epi")
        inj.device_dispatch("staged")  # no raise: the ladder's escape rung
        inj_all = ChaosInjector(
            parse_spec("seed=2,dispatch_fail=1.0,dispatch_fail_all=1")
        )
        with pytest.raises(ChaosInjected):
            inj_all.device_dispatch("staged")


class TestDegradationLadder:
    def test_breaker_flips_fused_to_staged_with_healthz(self):
        """The acceptance drill: persistent injected device failure flips
        pipeline_mode to staged within the breaker window, with
        celestia_degraded and /healthz reflecting it — and the root
        unchanged.  End-to-end DETECTION rides the same drill: the
        `degraded` SLO must enter fast-burn (a page) and the flight
        recorder must write bundles, within the drill's block budget,
        with the detection latency reported."""
        import os

        soak = _load_soak()
        result = soak.run_breaker_drill(k=4)
        assert result["ok"], result
        assert result["mode_after"] == "staged"
        assert result["health_status"] == "DEGRADED"
        assert result["roots_identical"]
        # The telemetry plane judged the incident itself:
        assert result["paged"]
        assert result["detection_blocks"] is not None
        assert result["detection_blocks"] <= result["blocks_run"]
        assert result["detection_wall_ms"] > 0
        assert "degraded" in result["slo_health"]["burning"]
        # ... and black-boxed it: both the trip and the page dumped.
        assert result["breaker_bundle"] and os.path.isfile(result["breaker_bundle"])
        assert result["flight_bundle"] and os.path.isfile(result["flight_bundle"])

    def test_ladder_steps_and_reset(self):
        ladder = degrade.DeviceDegradation()
        assert ladder.effective_mode("fused") == "fused"
        assert ladder.degrade("fused") == "staged"
        assert ladder.effective_mode("fused") == "staged"
        assert ladder.state() == {"device": "staged"}
        assert ladder.degrade("fused") == "host"
        assert ladder.degrade("fused") is None  # the floor
        ladder.reset()
        assert ladder.effective_mode("fused") == "fused"
        assert ladder.state() is None

    def test_ladder_respects_env_base(self):
        ladder = degrade.DeviceDegradation()
        # env already staged: first degrade goes straight to host.
        assert ladder.degrade("staged") == "host"
        assert ladder.effective_mode("staged") == "host"

    def test_ladder_from_epilogue_seat(self):
        """A process seated on fused_epi walks the full four-rung ladder:
        fused_epi -> fused -> staged -> host — the epilogue's custom
        kernel is distrusted first, the plain fused program second."""
        ladder = degrade.DeviceDegradation()
        assert ladder.effective_mode("fused_epi") == "fused_epi"
        assert ladder.degrade("fused_epi") == "fused"
        assert ladder.state() == {"device": "fused"}
        assert ladder.degrade("fused_epi") == "staged"
        assert ladder.degrade("fused_epi") == "host"
        assert ladder.degrade("fused_epi") is None  # the floor
        ladder.reset()
        # A fused-based process never CLIMBS to the epilogue rung: the
        # floor only ever steps down from the seated base.
        assert ladder.effective_mode("fused") == "fused"
        ladder.degrade("staged")
        assert ladder.effective_mode("fused_epi") == "host"

    def test_breaker_drill_from_epilogue_seat(self):
        """ISSUE 6 acceptance: with the rs_xor-era seat installed
        ($CELESTIA_PIPE_FUSED=epi), the breaker drill still steps the
        ladder to a bit-identical root — through the extra rung."""
        soak = _load_soak()
        result = soak.run_breaker_drill(k=4, base_env="epi")
        assert result["ok"], result
        assert result["mode_after"] == "staged"
        assert result["roots_identical"]

    def test_concurrent_trips_step_one_rung_not_two(self):
        """Two breaker trips from one burst of FUSED failures must not
        double-step the ladder past the staged rung: the second caller's
        `observed` rung is already below the floor, so it adopts the
        existing step instead of stacking another."""
        ladder = degrade.DeviceDegradation()
        assert ladder.degrade("fused", observed="fused") == "staged"
        # The racing thread also saw FUSED fail, but the floor has moved:
        assert ladder.degrade("fused", observed="fused") == "staged"
        assert ladder.effective_mode("fused") == "staged"
        # A genuine staged-rung failure still steps down.
        assert ladder.degrade("fused", observed="staged") == "host"

    def test_guarded_dispatch_retries_within_rung(self):
        """Transient failures are retried with backoff inside the rung;
        the ladder does not move."""
        calls = {"n": 0}

        def flaky(_x):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            return "ok"

        breaker = degrade.CircuitBreaker(threshold=5)
        mode, out = degrade.guarded_dispatch(
            lambda m: flaky, "x", breaker=breaker, sleep=lambda s: None
        )
        assert out == "ok" and calls["n"] == 3
        assert degrade.degraded_state() is None

    def test_guarded_dispatch_raises_when_floor_exhausts(self, monkeypatch):
        def always_fail(_x):
            raise RuntimeError("dead device")

        breaker = degrade.CircuitBreaker(threshold=2)
        with pytest.raises(RuntimeError, match="dead device"):
            degrade.guarded_dispatch(
                lambda m: always_fail, "x", breaker=breaker,
                sleep=lambda s: None,
            )
        # It walked the whole ladder before giving up.
        assert degrade.degraded_state() == {"device": "host"}
        # And SUBSEQUENT calls keep raising promptly: the breaker stayed
        # past its threshold (>=, not ==), so the next block's dispatch
        # must not spin in the retry loop forever.
        calls = {"n": 0}

        def count_and_fail(_x):
            calls["n"] += 1
            raise RuntimeError("still dead")

        with pytest.raises(RuntimeError, match="still dead"):
            degrade.guarded_dispatch(
                lambda m: count_and_fail, "x", breaker=breaker,
                sleep=lambda s: None,
            )
        assert calls["n"] == 1  # one attempt, immediate re-raise


class TestChaosSmoke:
    """The tier-1 chaos smoke: the soak machinery at small k, fixed seed."""

    SPEC = (
        "seed=5,dispatch_fail=0.2,upload_stall_ms=1,upload_fail=0.1,"
        "gossip_drop=0.25,gossip_dup=0.15,wal_torn_tail=2"
    )

    def test_device_soak_bit_identical_roots_under_chaos(self):
        soak = _load_soak()
        before = _injections("device.dispatch") + _injections("device.upload")
        result = soak.run_device_soak(5, 4, self.SPEC)
        after = _injections("device.dispatch") + _injections("device.upload")
        assert result["roots_identical"], result
        assert after > before, "smoke ran but injected nothing"

    def test_wal_tear_drill(self):
        soak = _load_soak()
        result = soak.run_wal_tear_drill(self.SPEC)
        assert result["ok"], result
        assert result["torn_on_disk"], "the tail was never torn"
        assert result["salvaged_bytes"] > 0

    def test_gossip_drill_converges(self):
        soak = _load_soak()
        before = _injections("gossip.send")
        result = soak.run_gossip_drill(self.SPEC, n_msgs=20)
        assert result["ok"], result
        assert _injections("gossip.send") > before

    def test_batched_fault_drill_falls_down_the_ladder(self):
        """A persistent batched-dispatch fault: every root still
        bit-identical, the unbatched fallback fired, and the ladder
        landed on staged."""
        soak = _load_soak()
        result = soak.run_batched_fault_drill(k=2, blocks=4, batch=2)
        assert result["ok"], result
        assert result["unbatched_falls"] >= 1
        assert result["final_mode"] == "staged"

    def test_attestation_drill_identity_and_refusal(self):
        """verify_fail=1.0 forces the batched verifier onto the host
        path: the accept/reject vector and attestation bytes stay
        identical, recoveries tick only on the drilled leg, and a
        malformed square's attestation refuses (BadProofDetected)."""
        soak = _load_soak()
        result = soak.run_attestation_drill(k=2, samples=6)
        assert result["ok"], result
        assert result["healthy_falls"] == 0
        assert result["fallback_falls"] >= 1
        assert result["tampered_refused"]

    def test_withholding_drill_detection_curve(self, monkeypatch, tmp_path):
        """The ISSUE-10 withholding drill at smoke scale: monotone
        detection curve, honest leg bit-identical with every adversary
        key at 0, repair-to-recovery lands on the committed DAH, and the
        detection storm black-boxes exactly once."""
        monkeypatch.setenv("CELESTIA_FLIGHT_DIR", str(tmp_path))
        soak = _load_soak()
        result = soak.run_withholding_drill(
            k=4, fracs=(0.1, 0.25), trials=25
        )
        assert result["ok"], result
        assert result["honest_identical"]
        assert result["all_monotone"]
        assert result["repair"]["recovered"]
        assert result["flight_dumps"] == 1
        # The measured curve ascends toward 1-(1-f)^s.
        top = result["detection"][-1]["p_detect"]
        assert top["64"] >= top["2"]

    def test_adversary_detection_drill_always_detects(self, monkeypatch,
                                                      tmp_path):
        """Malformed-square and wrong-root injections: every corrupted
        proof refused, nothing invalid served, repair rejects both, one
        flight bundle per drill under the rate limit."""
        monkeypatch.setenv("CELESTIA_FLIGHT_DIR", str(tmp_path))
        soak = _load_soak()
        result = soak.run_adversary_detection_drill(k=4)
        assert result["ok"], result
        assert result["malform"]["served_invalid"] == 0
        assert result["malform"]["detected"] == result["malform"]["corrupted_shares"]
        assert result["wrong_root"]["samples_detected"] == result["wrong_root"]["samples_probed"]
        assert result["malform"]["repair_detected"]
        assert result["wrong_root"]["repair_detected"]
        assert result["flight_dumps"] == 1

    def test_soak_main_smoke(self, capsys, monkeypatch, tmp_path):
        """The script's own entry point end to end (tiny knobs).

        main() arms the flight recorder via $CELESTIA_FLIGHT_DIR for the
        whole process; monkeypatch scopes that to this test so later
        tests don't inherit an armed recorder."""
        monkeypatch.setenv("CELESTIA_FLIGHT_DIR", str(tmp_path))
        soak = _load_soak()
        rc = soak.main([
            "--blocks", "3", "--k", "4",
            "--adv-trials", "20",
            "--spec", "seed=9,dispatch_fail=0.3,gossip_drop=0.2,"
                      "wal_torn_tail=1",
        ])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "chaos_soak: OK" in out
        assert "celestia_chaos_injections_total" in out
        # The adversarial drills print their verdicts.
        assert "withholding drill" in out
        assert "adversary drill" in out
        # The per-drill detection-latency summary prints, and the
        # breaker drills page via the SLO engine.
        assert "time-to-detection per drill" in out
        assert "slo_fast_burn" in out
        assert "celestia_slo_violations_total" in out


class TestPipelinePropagation:
    """BlockPipeline: worker death raises at put()/drain(), never hangs."""

    def _blocks(self, n, k=4):
        return [
            (i, np.zeros((k, k, SHARE_SIZE), dtype=np.uint8))
            for i in range(n)
        ]

    def test_upload_failure_propagates_on_drain(self):
        from celestia_app_tpu.parallel.pipeline import BlockPipeline

        chaos.install("seed=1,upload_fail=1.0")  # exhausts the retry budget
        pipe = BlockPipeline(4, depth=2)
        try:
            pipe.submit(self._blocks(1)[0][1], tag=0)
            with pytest.raises(RuntimeError, match="feeder failed"):
                for _ in pipe.drain():
                    pass
        finally:
            chaos.uninstall()
            pipe.close()

    def test_submit_raises_after_feeder_death_instead_of_hanging(self):
        from celestia_app_tpu.parallel.pipeline import BlockPipeline

        chaos.install("seed=1,upload_fail=1.0")
        pipe = BlockPipeline(4, depth=1)
        try:
            ods = self._blocks(1)[0][1]
            with pytest.raises((RuntimeError, TimeoutError)):
                # depth=1: once the feeder dies, puts would previously
                # block forever; now either the stored error or the
                # deadline surfaces.
                for i in range(16):
                    pipe.submit(ods, tag=i, timeout_s=5.0)
        finally:
            chaos.uninstall()
            pipe.close()

    def test_transient_upload_faults_are_retried(self):
        from celestia_app_tpu.parallel.pipeline import stream_blocks

        chaos.install("seed=6,upload_fail=0.3")
        try:
            blocks = self._blocks(6)
            out = list(stream_blocks(iter(blocks), 4, depth=2))
            assert [t for t, _ in out] == list(range(6))
        finally:
            chaos.uninstall()

    def test_submit_deadline_surfaces_as_timeout(self):
        """Sustained back-pressure past an explicit deadline raises
        TimeoutError instead of blocking forever."""
        import queue as _q

        from celestia_app_tpu.parallel.pipeline import BlockPipeline

        pipe = BlockPipeline(4, depth=1)
        try:
            # Wedge the intake artificially: fill _tasks so the put must
            # wait, while workers are blocked behind a full _done that
            # nobody drains.
            for i in range(8):
                try:
                    pipe._tasks.put(
                        (self._blocks(1)[0][1], i, time.perf_counter()),
                        timeout=0.2,
                    )
                except _q.Full:
                    break
            with pytest.raises(TimeoutError, match="back-pressure"):
                pipe.submit(self._blocks(1)[0][1], tag=99, timeout_s=0.5)
        finally:
            pipe.close()

    def test_drain_does_not_hang_when_workers_hard_died(self, monkeypatch):
        """drain() with a full intake and DEAD workers must surface the
        stored error, not spin on the sentinel put forever (the silent
        wedge: dispatcher hard-dead, uploader parked on the hand-off)."""
        from celestia_app_tpu.parallel import pipeline as pl

        monkeypatch.setattr(pl.threading.Thread, "start", lambda self: None)
        pipe = pl.BlockPipeline(4, depth=1)  # workers never actually run
        pipe._error = RuntimeError("hard death")
        pipe._tasks.put(
            (self._blocks(1)[0][1], 0, time.perf_counter())
        )  # intake full
        pipe._done.put(pl._SENTINEL)  # what the death wrapper force-feeds
        with pytest.raises(RuntimeError, match="feeder failed"):
            for _ in pipe.drain():
                pass

    def test_deferred_device_fault_feeds_the_breaker(self):
        """A fault surfacing at the drain's sync (async dispatch defers
        real execution errors there) still steps the ladder."""
        from celestia_app_tpu.chaos.degrade import note_async_device_failure

        for _ in range(degrade.DEVICE_BREAKER.threshold):
            note_async_device_failure("fused")
        assert degrade.degraded_state() == {"device": "staged"}

    def test_close_leak_counter_registered(self):
        # The genuine-wedge path is (deliberately) hard to reach; pin the
        # counter's registration + README row via the registry.
        from celestia_app_tpu.parallel.pipeline import _close_leak_counter

        c = _close_leak_counter()
        assert c.name == "celestia_pipeline_close_leaked_total"


class TestCrashRestartDeterminism:
    """Satellite: kill a node between WAL fsync and broadcast; restart;
    the node must refuse the conflicting vote and rejoin without
    double-signing (crypto-free, like test_round_journal.py)."""

    A, B = b"\xaa" * 32, b"\xbb" * 32

    def test_fsync_then_crash_then_conflicting_vote_refused(self, tmp_path):
        from celestia_app_tpu.consensus.wal import VoteWAL

        path = str(tmp_path / "wal.jsonl")
        wal = VoteWAL(path)
        # The record-then-sign contract: may_sign journals durably FIRST.
        assert wal.may_sign(5, 0, PREVOTE, self.A)
        # CRASH between fsync and broadcast: no close(), no vote sent.
        del wal

        wal2 = VoteWAL(path)
        # A different proposal at the same coordinates (the equivocation
        # x/slashing tombstones for) draws NO signature...
        assert not wal2.may_sign(5, 0, PREVOTE, self.B)
        # ...but re-signing the SAME vote is allowed — how the restarted
        # node rejoins and re-broadcasts without equivocating.
        assert wal2.may_sign(5, 0, PREVOTE, self.A)
        # And fresh coordinates are unaffected.
        assert wal2.may_sign(6, 0, PREVOTE, self.B)
        wal2.close()

    def test_crash_with_torn_tail_salvages_and_stays_safe(self, tmp_path):
        from celestia_app_tpu.consensus.wal import VoteWAL

        path = str(tmp_path / "wal.jsonl")
        chaos.install("seed=1,wal_torn_tail=1")
        try:
            wal = VoteWAL(path)
            assert wal.may_sign(7, 0, PREVOTE, self.A)  # append + torn tail
            assert wal._torn
            del wal  # crash: the fsync'd partial record is on disk
        finally:
            chaos.uninstall()
        size_before = os.path.getsize(path)
        wal2 = VoteWAL(path)
        # Replay salvaged: torn bytes truncated, the complete record kept.
        assert wal2.salvaged_bytes > 0
        assert os.path.getsize(path) == size_before - wal2.salvaged_bytes
        assert not wal2.may_sign(7, 0, PREVOTE, self.B)
        assert wal2.may_sign(7, 0, PREVOTE, self.A)
        wal2.close()

    def test_live_self_heal_keeps_later_records_replayable(self, tmp_path):
        """A torn tail mid-run must not corrupt the NEXT append: the live
        WAL truncates back to the last complete record before writing."""
        from celestia_app_tpu.consensus.wal import VoteWAL

        path = str(tmp_path / "wal.jsonl")
        chaos.install("seed=1,wal_torn_tail=1")
        try:
            wal = VoteWAL(path)
            assert wal.may_sign(1, 0, PREVOTE, self.A)  # torn after this
            assert wal.may_sign(2, 0, PREVOTE, self.A)  # heals, then appends
            wal.close()
        finally:
            chaos.uninstall()
        wal2 = VoteWAL(path)
        assert not wal2.may_sign(1, 0, PREVOTE, self.B)
        assert not wal2.may_sign(2, 0, PREVOTE, self.B)
        wal2.close()

    def test_mid_file_garbage_does_not_truncate_later_records(self, tmp_path):
        from celestia_app_tpu.consensus.wal import VoteWAL

        path = str(tmp_path / "wal.jsonl")
        wal = VoteWAL(path)
        assert wal.may_sign(1, 0, PREVOTE, self.A)
        wal.close()
        with open(path, "a") as f:
            # Newline'd mid-file corruption, including lines that PARSE
            # as JSON but are not records (non-dicts, missing keys):
            # replay must skip them all, never crash on them.
            f.write("NOT-JSON-GARBAGE\n")
            f.write("123\n")
            f.write("null\n")
            f.write('{"k":"vote"}\n')
            f.write('{"k":"lock","h":3}\n')
            # A bare \r inside garbage must NOT read as a line break —
            # that would make everything after it look like a torn tail
            # and TRUNCATE later valid records (a double-sign window).
            f.write("garbage\rwith\rcarriage\rreturns\n")
        wal2 = VoteWAL(path)
        assert wal2.may_sign(2, 0, PREVOTE, self.A)
        wal2.close()
        wal3 = VoteWAL(path)
        # Both complete records survive the garbage line between them.
        assert not wal3.may_sign(1, 0, PREVOTE, self.B)
        assert not wal3.may_sign(2, 0, PREVOTE, self.B)
        wal3.close()


class TestTransportAndSeams:
    def test_deliver_retries_transient_then_gates_on_streak(self):
        from celestia_app_tpu.rpc import transport

        calls = {"n": 0}

        def flaky(msg):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("blip")

        streak: dict = {}
        assert transport.deliver(flaky, {"kind": "vote", "vote": "aa"},
                                 streak=streak, key="p", sleep=lambda s: None)
        assert calls["n"] == 2 and streak == {}

        def dead(msg):
            raise ConnectionError("down")

        # First failure exhausts the retry budget and starts the streak...
        assert not transport.deliver(dead, {"kind": "vote", "vote": "bb"},
                                     streak=streak, key="p",
                                     sleep=lambda s: None)
        n_before = calls["n"]
        # ...after which the peer gets exactly ONE attempt per message.
        attempts = {"n": 0}

        def dead2(msg):
            attempts["n"] += 1
            raise ConnectionError("still down")

        assert not transport.deliver(dead2, {"kind": "vote", "vote": "cc"},
                                     streak=streak, key="p",
                                     sleep=lambda s: None)
        assert attempts["n"] == 1
        assert streak["p"] == 2

    def test_reorder_delay_lets_later_messages_overtake(self):
        """An injected reorder-delay must produce genuine reordering: the
        delayed message lands on a timer thread, so a message sent AFTER
        it arrives FIRST."""
        from celestia_app_tpu.rpc import transport

        delivered: list[str] = []
        streak: dict = {}

        def send(msg):
            delivered.append(msg["vote"])

        chaos.install("seed=1,gossip_delay_ms=150,gossip_reorder=1.0")
        try:
            assert transport.deliver(send, {"kind": "vote", "vote": "late"},
                                     streak=streak, key="p")
            assert delivered == []  # in flight on the timer, not inline
        finally:
            chaos.uninstall()
        transport.deliver(send, {"kind": "vote", "vote": "early"},
                          streak=streak, key="p")
        assert delivered == ["early"]  # overtook the delayed one
        transport.drain_delayed()
        assert delivered == ["early", "late"]

    def test_mempool_insert_seam_drops_transiently(self):
        from celestia_app_tpu.mempool import PriorityMempool

        pool = PriorityMempool()
        chaos.install("seed=1,mempool_drop=1.0")
        try:
            assert not pool.insert(b"tx-1", priority=1, height=1)
            assert len(pool) == 0
        finally:
            chaos.uninstall()
        # The submitter's retry (chaos gone) gets it in.
        assert pool.insert(b"tx-1", priority=1, height=1)
        assert len(pool) == 1

    def test_rpc_handle_seam_raises_injected(self):
        chaos.install("seed=1,rpc_fail=1.0")
        try:
            with pytest.raises(ChaosInjected):
                chaos.rpc_handle()
        finally:
            chaos.uninstall()

    def test_healthz_degraded_state(self):
        from celestia_app_tpu.trace.exposition import health_payload

        assert health_payload()["status"] == "SERVING"
        degrade.DEVICE_DEGRADATION.degrade("fused")
        try:
            payload = health_payload()
            assert payload["status"] == "DEGRADED"
            assert payload["degraded"] == {"device": "staged"}
        finally:
            degrade.reset_for_tests()
        assert health_payload()["status"] == "SERVING"


class TestAdversary:
    """chaos/adversary.py: the protocol-adversary layer (ISSUE 10) —
    spec keys, determinism, tampering, and the serve-plane seams."""

    @staticmethod
    def _square(k=2, seed=41):
        import numpy as np

        from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
        from celestia_app_tpu.da.eds import ExtendedDataSquare

        rng = np.random.default_rng(seed)
        n = k * k
        ods = rng.integers(0, 256, (n, SHARE_SIZE), dtype=np.uint8)
        ods[:, :NAMESPACE_SIZE] = 0
        ods[:, NAMESPACE_SIZE - 1] = np.sort(
            rng.integers(0, 200, n).astype(np.uint8)
        )
        return ExtendedDataSquare.compute(ods.reshape(k, k, SHARE_SIZE))

    def test_adversary_keys_parse_and_zero_means_none(self):
        chaos.install("seed=3,withhold_frac=0.25,malform_shares=2,wrong_root=1")
        adv = chaos.active_adversary()
        assert adv is not None
        assert adv.withhold_frac == 0.25
        assert adv.malform_shares == 2 and adv.wrong_root
        # Every key at 0 = NO adversary (the honest fast path).
        chaos.install("seed=3,withhold_frac=0,malform_shares=0,wrong_root=0")
        assert chaos.active_adversary() is None
        chaos.uninstall()
        assert chaos.active_adversary() is None

    def test_unknown_adversary_key_still_rejected(self):
        import pytest

        with pytest.raises(ValueError):
            chaos.install("withold_frac=0.1")  # typo'd key must be loud

    def test_withheld_set_deterministic_and_order_independent(self):
        chaos.install("seed=7,withhold_frac=0.25")
        a = chaos.active_adversary()
        s1 = a.withheld_set(5, 8)
        # A FRESH injector from the same spec draws the same set, and
        # querying another height first must not perturb it (the
        # per-(seed, seam, height, width) RNG contract).
        chaos.install("seed=7,withhold_frac=0.25")
        b = chaos.active_adversary()
        b.withheld_set(9, 8)
        assert b.withheld_set(5, 8) == s1
        assert len(s1) == int(0.25 * 64)
        # A different seed draws a different set.
        chaos.install("seed=8,withhold_frac=0.25")
        assert chaos.active_adversary().withheld_set(5, 8) != s1
        chaos.uninstall()

    def test_tampered_entry_is_memoized_and_cache_untouched(self):
        import numpy as np

        from celestia_app_tpu.serve.cache import ForestCache

        eds = self._square(k=2)
        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(7, eds)
        honest = np.asarray(entry.eds._eds).copy()
        chaos.install("seed=5,malform_shares=2,wrong_root=1")
        try:
            adv = chaos.active_adversary()
            t1 = adv.tamper_entry(entry)
            t2 = adv.tamper_entry(entry)
            assert t1 is t2, "one corrupted square per height, not per call"
            assert t1.data_root != entry.data_root
            assert not np.array_equal(np.asarray(t1.eds._eds), honest)
            # The honest cache entry is untouched (consensus state safe).
            assert np.array_equal(np.asarray(entry.eds._eds), honest)
        finally:
            chaos.uninstall()

    def test_withheld_sample_never_served_others_fine(self):
        from celestia_app_tpu.serve.cache import ForestCache
        from celestia_app_tpu.serve.sampler import ProofSampler, ShareWithheld

        import pytest

        eds = self._square(k=2)
        root = eds.data_root()
        cache = ForestCache(heights=1, spill=1)
        entry = cache.put(2, eds)
        sampler = ProofSampler()
        chaos.install("seed=6,withhold_frac=0.3")
        try:
            adv = chaos.active_adversary()
            withheld = adv.withheld_set(2, 4)
            hit = next(iter(withheld))
            ok = next(
                (r, c) for r in range(4) for c in range(4)
                if (r, c) not in withheld
            )
            with pytest.raises(ShareWithheld):
                sampler.share_proof(entry, *hit)
            proof = sampler.share_proof(entry, *ok)
            assert proof.verify(root)
        finally:
            chaos.uninstall()

    def test_verification_gate_refuses_tampered_proofs_both_lowerings(
        self, monkeypatch
    ):
        from celestia_app_tpu.serve.api import DasProvider
        from celestia_app_tpu.serve.cache import ForestCache
        from celestia_app_tpu.serve.sampler import BadProofDetected, ProofSampler

        import pytest

        eds = self._square(k=2)
        cache = ForestCache(heights=1, spill=1)
        cache.put(4, eds)
        provider = DasProvider(cache=cache, sampler=ProofSampler())
        chaos.install("seed=9,wrong_root=1")
        try:
            entry = provider.entry(4)
            with pytest.raises(BadProofDetected):
                provider.sampler.sample_batch(entry, [(0, 0)])
            monkeypatch.setenv("CELESTIA_SERVE_MODE", "host")
            with pytest.raises(BadProofDetected):
                provider.sampler.sample_batch(entry, [(1, 1)])
        finally:
            monkeypatch.delenv("CELESTIA_SERVE_MODE", raising=False)
            chaos.uninstall()

    def test_shares_by_namespace_rides_the_verification_gate(self):
        """GetSharesByNamespace builds its proof outside the sampler's
        batch queue, but under a tampering adversary it must hit the
        SAME verification gate: a forged root (or corrupted shares)
        raises BadProofDetected — never a 200 endorsing forged state —
        while the honest path is untouched."""
        import numpy as np

        import pytest

        from celestia_app_tpu.serve.api import DasProvider
        from celestia_app_tpu.serve.cache import ForestCache
        from celestia_app_tpu.serve.sampler import BadProofDetected, ProofSampler

        eds = self._square(k=2)
        cache = ForestCache(heights=1, spill=1)
        cache.put(5, eds)
        provider = DasProvider(cache=cache, sampler=ProofSampler())
        from celestia_app_tpu.constants import NAMESPACE_SIZE

        sq = np.asarray(eds.squared())
        ns_hex = bytes(sq[0, 0][:NAMESPACE_SIZE].tobytes()).hex()
        # Honest: the namespace payload serves and verifies.
        payload = provider.shares_payload(5, ns_hex)
        assert payload["found"]
        # Wrong root: EVERY namespace payload is refused (the honest
        # proof cannot chain to the forged root).
        chaos.install("seed=9,wrong_root=1")
        try:
            with pytest.raises(BadProofDetected):
                provider.shares_payload(5, ns_hex)
        finally:
            chaos.uninstall()
        # Malform: seed=8 corrupts ODS shares (1,0) and (1,1) at this
        # square size — a range containing a corrupted share is refused
        # (honest committed structure, corrupted served bytes), while a
        # range of untouched shares still serves honestly-verifying
        # proofs (the malform detection model: you detect what you
        # sample).
        ns_hit = bytes(sq[1, 0][:NAMESPACE_SIZE].tobytes()).hex()
        chaos.install("seed=8,malform_shares=2")
        try:
            adv = chaos.active_adversary()
            assert {(1, 0), (1, 1)} <= set(adv.malformed_coords(5, 4))
            with pytest.raises(BadProofDetected):
                provider.shares_payload(5, ns_hit)
        finally:
            chaos.uninstall()

    def test_repair_sweep_rides_the_ladder(self):
        """An injected dispatch fault during a repair sweep steps the
        fused-family batched rung down to the grouped (staged) sweep —
        roots still exact."""
        import numpy as np

        from celestia_app_tpu.chaos import degrade
        from celestia_app_tpu.da import DataAvailabilityHeader, repair
        from celestia_app_tpu.kernels.fused import pipeline_mode

        k = 2
        eds = self._square(k=k, seed=43)
        full = np.asarray(eds.squared())
        dah = DataAvailabilityHeader.from_eds(eds)
        present = np.zeros((2 * k, 2 * k), dtype=bool)
        rng = np.random.default_rng(3)
        for r in range(2 * k):
            present[r, rng.choice(2 * k, size=k, replace=False)] = True
        damaged = np.where(present[..., None], full, 0).astype(np.uint8)
        degrade.reset_for_tests()
        chaos.install("seed=2,dispatch_fail=1.0")
        try:
            out = repair(damaged, present, dah)
            assert np.array_equal(out.squared(), full)
            assert pipeline_mode() == "staged"
        finally:
            chaos.uninstall()
            degrade.reset_for_tests()
