"""Tier-1 seat for scripts/bench_trend.py: a BENCH_r*.json trajectory of
every shape a round can leave (failed, whole, stability rerun,
front-truncated, opt-in giant k) must parse and pass the gate (self-test
mode, no device), a synthetic regression must be flagged, and malformed
inputs must fail fast instead of silently dropping out of the
trajectory.  No BENCH round is checked in: the trajectories are
synthetic, in tmp_path."""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(REPO_ROOT, "scripts", "bench_trend.py")


def _load():
    spec = importlib.util.spec_from_file_location("bench_trend", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _round_file(tmp_path, n, results, stability=None, errors=None,
                platform=None):
    summary = {"metric": "x", "value": 1.0, "unit": "MB/s", "results": results}
    if stability is not None:
        summary["stability_pct"] = stability
    if errors is not None:
        summary["errors"] = errors
    if platform is not None:
        summary["platform"] = platform
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps({
        "n": n, "cmd": "bench", "rc": 0,
        "tail": "noise line\n" + json.dumps(summary),
        "parsed": None,
    }))
    return str(path)


_PARTS = {"rs_fft": 1.7666, "rs_fft_md": 1.7393, "rs_dense": 1.0594,
          "nmt_dah_jnp": 0.3621, "nmt_dah": 0.3621}


def _summary(results, platform, **extra):
    return {"metric": "ODS MB/s erasure-extended + DAH-hashed per chip",
            "value": results[0]["mb_per_s"] if results else 0,
            "unit": "MB/s", "platform": platform, "results": results,
            "baseline_note": "synthetic", **extra}


def _trajectory(tmp_path):
    """Six rounds, one of each shape the driver has left: r01 failed
    (rc=1, no data); r02 whole; r03 whole with the compute@512
    stability rerun; r04/r05 front-truncated tails (parts salvaged);
    r06 the opt-in giant-k row.  Returns the paths, oldest first."""
    def put(n, rc, tail):
        path = tmp_path / f"BENCH_r{n:02d}.json"
        path.write_text(json.dumps(
            {"n": n, "cmd": "bench", "rc": rc, "tail": tail, "parsed": None}
        ))
        return str(path)

    def row(mode, k, rate, **extra):
        return {"mode": mode, "k": k, "mb_per_s": rate,
                "seconds_per_block": round(k * k * 512 / 1e6 / rate, 4),
                **extra}

    paths = [put(1, 1, "RuntimeError: Unable to initialize backend\n")]
    r02 = _summary([row("compute", 512, 360.0), row("compute", 128, 110.0),
                    row("extend", 128, 30.0), row("host", 128, 2.2)],
                   "tpu", parts={"k": 512, "seconds": _PARTS})
    paths.append(put(2, 0, "noise line\n" + json.dumps(r02) + "\n"))
    r03 = _summary([row("compute", 512, 378.7),
                    row("compute", 512, 379.4, rerun=True),
                    row("compute", 128, 111.9), row("extend", 128, 32.0)],
                   "tpu", stability_pct=0.2)
    paths.append(put(3, 0, "noise line\n" + json.dumps(r03) + "\n"))
    for n, stab in ((4, 6.4), (5, 6.1)):
        full = json.dumps(_summary(
            [row("compute", 128, 16.3, loadavg=1.51)], "cpu",
            parts={"k": 128, "seconds": _PARTS,
                   "tuned": {"rs": "rs_dense", "sha": "jnp"},
                   "applied": {"rs": "rs_dense", "sha": "jnp"}},
            stability_pct=stab, errors=["stage failed"],
        ))
        cut = full.index('"loadavg"') - 8  # inside the results list
        paths.append(put(n, 0, full[cut:] + "\n"))
    r06 = _summary([row("compute", 1024, 0.307)], "cpu")
    paths.append(put(6, 0, json.dumps(r06) + "\n"))
    return paths


class TestTrajectory:
    def test_check_mode_reproduces_rounds_and_passes(self, tmp_path, capsys):
        bt = _load()
        _trajectory(tmp_path)
        assert bt.main(["--dir", str(tmp_path), "--check"]) == 0
        out = capsys.readouterr().out
        # The r02/r03 full summaries, the r04/r05 salvaged parts, and the
        # r06 giant-k opt-in row all land in one table.
        assert "compute@512" in out
        assert "parts.rs_dense" in out
        assert "trend gate OK" in out
        # Chip compute rows stop at r03 while later rounds keep moving:
        # the gate must SAY it is comparing stale numbers, not stay
        # silent.
        assert "STALE" in out and "compute@512" in out

    def test_check_fails_on_clean_exit_round_with_no_recoverable_data(
        self, tmp_path
    ):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute", "k": 128, "mb_per_s": 100.0},
        ])
        (tmp_path / "BENCH_r02.json").write_text(json.dumps({
            "n": 2, "cmd": "bench", "rc": 0,
            "tail": "all summary output lost", "parsed": None,
        }))
        # Default mode tolerates the gap (the r01 data still renders)...
        assert bt.main(["--dir", str(tmp_path)]) == 0
        # ...but --check calls it what it is: a tooling regression.
        assert bt.main(["--dir", str(tmp_path), "--check"]) == 2

    def test_rounds_salvage_what_each_tail_holds(self, tmp_path):
        bt = _load()
        rounds = bt.load_series(_trajectory(tmp_path))
        by_n = {r["round"]: r for r in rounds}
        assert not by_n[1]["ok"] and not by_n[1]["modes"]  # rc=1, no data
        assert ("compute", 512) in by_n[2]["modes"]
        # r03 ran compute@512 twice (stability rerun): both kept.
        assert len(by_n[3]["modes"][("compute", 512)]) == 2
        # r04/r05 tails are front-truncated: parts salvaged, flagged.
        for n in (4, 5):
            assert by_n[n]["partial"]
            assert "rs_dense" in by_n[n]["parts"]
            assert by_n[n]["stability_pct"] is not None


class TestRegressionGate:
    def test_injected_synthetic_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _trajectory(tmp_path)
        # Next round: compute@512 collapses 379 -> 40 MB/s.
        _round_file(tmp_path, 7, [
            {"mode": "compute", "k": 512, "mb_per_s": 40.0,
             "seconds_per_block": 3.0},
        ])
        assert bt.main(["--dir", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "compute@512" in out and "regressions:" in out

    def test_drop_within_threshold_plus_stability_passes(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute", "k": 128, "mb_per_s": 100.0},
        ])
        # 17% down, but threshold 10 + stability 8 allows it.
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 128, "mb_per_s": 83.0},
        ], stability=8.0)
        assert bt.main(["--dir", str(tmp_path)]) == 0
        # Without the stability allowance the same drop fails.
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 128, "mb_per_s": 83.0},
        ])
        assert bt.main(["--dir", str(tmp_path)]) == 1

    def test_link_bound_modes_gated_only_with_all_series(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "stream", "k": 128, "mb_per_s": 30.0},
        ])
        _round_file(tmp_path, 2, [
            {"mode": "stream", "k": 128, "mb_per_s": 2.0},
        ])
        assert bt.main(["--dir", str(tmp_path)]) == 0
        assert bt.main(["--dir", str(tmp_path), "--all-series"]) == 1


def _round_file_with_parts(tmp_path, n, parts_seconds, tuned=None,
                           results=None, platform=None, applied=None):
    summary = {
        "metric": "x", "value": 1.0, "unit": "MB/s",
        "results": results or [],
        "parts": {"k": 512, "seconds": parts_seconds,
                  **({"tuned": tuned} if tuned else {}),
                  **({"applied": applied} if applied else {})},
    }
    if platform:
        summary["platform"] = platform
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps({
        "n": n, "cmd": "bench", "rc": 0,
        "tail": json.dumps(summary), "parsed": summary,
    }))
    return str(path)


class TestSeatChanges:
    """A tuned-seat flip (the rs_xor / fused_epi candidates landing) must
    surface as a SEAT CHANGE, never as a phantom regression or a STALE
    series — the ISSUE 6 trend-gate satellite."""

    def test_seat_flip_is_reported_not_regressed(self, tmp_path, capsys):
        bt = _load()
        _round_file_with_parts(
            tmp_path, 1, {"rs_dense": 1.0, "nmt_dah": 0.4},
            tuned={"rs": "rs_dense", "sha": "pallas", "pipe": "fused"},
            platform="tpu",
        )
        # Next chip round: rs_xor measured, wins the seat outright.
        _round_file_with_parts(
            tmp_path, 2, {"rs_dense": 1.0, "rs_xor": 0.5, "nmt_dah": 0.4},
            tuned={"rs": "rs_xor", "sha": "pallas", "pipe": "fused_epi"},
            platform="tpu",
        )
        assert bt.main(["--dir", str(tmp_path)]) == 0  # no regression
        out = capsys.readouterr().out
        assert "SEAT CHANGE: rs rs_dense -> rs_xor" in out
        assert "SEAT CHANGE: pipe fused -> fused_epi" in out
        assert "regressions:" not in out

    def test_new_candidate_single_point_never_gates(self, tmp_path):
        """rs_xor appearing for the first time has one datapoint — the
        gate needs two, so a brand-new series can never fail the run."""
        bt = _load()
        _round_file_with_parts(tmp_path, 1, {"rs_dense": 1.0})
        _round_file_with_parts(
            tmp_path, 2, {"rs_dense": 1.0, "rs_xor": 99.0})
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_hw_gated_candidate_missing_on_cpu_round_is_not_stale(
        self, tmp_path, capsys
    ):
        """A chip round measures rs_xor; the next round falls back to CPU
        and cannot.  That is a platform gap, not a STALE series."""
        bt = _load()
        _round_file_with_parts(
            tmp_path, 1,
            {"rs_dense": 1.0, "rs_xor": 0.9, "rs_dense_pl": 0.95},
            platform="tpu",
        )
        _round_file_with_parts(
            tmp_path, 2, {"rs_dense": 6.0}, platform="cpu",
        )
        bt.main(["--dir", str(tmp_path)])
        out = capsys.readouterr().out
        assert "hw-gated: parts.rs_xor" in out
        assert "hw-gated: parts.rs_dense_pl" in out
        assert "STALE" not in out

    def test_unknown_platform_newest_round_stays_stale(
        self, tmp_path, capsys
    ):
        """A newest round whose platform tag was LOST (truncated tail)
        may well have been the chip: hw-gated's 'no chip' claim must not
        fire — the honest report is STALE."""
        bt = _load()
        _round_file_with_parts(
            tmp_path, 1, {"rs_dense": 1.0, "rs_xor": 0.9}, platform="tpu",
        )
        _round_file_with_parts(tmp_path, 2, {"rs_dense": 1.0})  # no tag
        bt.main(["--dir", str(tmp_path)])
        out = capsys.readouterr().out
        assert "STALE: gated series parts.rs_xor" in out
        assert "hw-gated" not in out

    def test_cpu_fallback_round_never_regresses_chip_numbers(
        self, tmp_path, capsys
    ):
        """fused_epi (and every parts series) is measured on BOTH
        platforms; a CPU-fallback round's seconds must not gate against a
        chip round's — same-platform comparison only."""
        bt = _load()
        _round_file_with_parts(
            tmp_path, 1, {"rs_dense": 0.2, "fused": 0.3, "fused_epi": 0.25},
            platform="tpu",
        )
        _round_file_with_parts(
            tmp_path, 2, {"rs_dense": 6.0, "fused": 9.0, "fused_epi": 8.0},
            platform="cpu",
        )
        assert bt.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "regressions:" not in out
        # A genuine same-platform collapse still gates.
        _round_file_with_parts(
            tmp_path, 3, {"rs_dense": 30.0, "fused": 9.0, "fused_epi": 8.0},
            platform="cpu",
        )
        assert bt.main(["--dir", str(tmp_path)]) == 1

    def test_unknown_platform_priors_still_gate(self, tmp_path):
        """A salvaged round that lost its platform tag must keep gating:
        only a KNOWN different platform excludes a prior — silently
        dropping unknowns would weaken the gate for exactly the rounds
        whose tails were truncated."""
        bt = _load()
        _round_file_with_parts(tmp_path, 1, {"rs_dense": 0.2})  # no platform
        _round_file_with_parts(
            tmp_path, 2, {"rs_dense": 30.0}, platform="tpu",
        )
        assert bt.main(["--dir", str(tmp_path)]) == 1  # still flagged

    def test_operator_override_is_reported(self, tmp_path, capsys):
        bt = _load()
        _round_file_with_parts(
            tmp_path, 1, {"rs_dense": 1.0, "rs_xor": 0.5},
            tuned={"rs": "rs_xor", "sha": "pallas"},
            applied={"rs": "rs_dense", "sha": "pallas"},
            platform="tpu",
        )
        bt.main(["--dir", str(tmp_path)])
        out = capsys.readouterr().out
        assert "OPERATOR OVERRIDE: rs ran rs_dense" in out

    def test_json_output_carries_seats(self, tmp_path, capsys):
        bt = _load()
        _round_file_with_parts(
            tmp_path, 1, {"rs_dense": 1.0},
            tuned={"rs": "rs_dense", "sha": "pallas"}, platform="tpu")
        _round_file_with_parts(
            tmp_path, 2, {"rs_dense": 1.0, "rs_xor": 0.5},
            tuned={"rs": "rs_xor", "sha": "pallas"}, platform="tpu")
        assert bt.main(["--dir", str(tmp_path), "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["seat_changes"] == [{
            "seat": "rs", "from": "rs_dense", "to": "rs_xor",
            "from_round": 1, "round": 2,
        }]


class TestStreamBatchSeries:
    """The continuous-batching stream_b{1,2,4} rows (bench.py stream
    stage) are gated series with the same same-platform comparability
    rule as the hw-gated parts candidates."""

    def test_stream_batch_modes_are_gated(self, tmp_path, capsys):
        bt = _load()
        assert set(bt.STREAM_BATCH_MODES) <= set(bt.GATED_MODES)
        _round_file(tmp_path, 1, [
            {"mode": "stream_b1", "k": 128, "mb_per_s": 30.0},
            {"mode": "stream_b4", "k": 128, "mb_per_s": 50.0},
        ])
        # batch-4 collapses to below batch-1: a real batching regression.
        _round_file(tmp_path, 2, [
            {"mode": "stream_b1", "k": 128, "mb_per_s": 30.0},
            {"mode": "stream_b4", "k": 128, "mb_per_s": 20.0},
        ])
        assert bt.main(["--dir", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "stream_b4@128" in out and "regressions:" in out

    def test_stream_batch_within_threshold_passes(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "stream_b2", "k": 128, "mb_per_s": 40.0},
        ])
        _round_file(tmp_path, 2, [
            {"mode": "stream_b2", "k": 128, "mb_per_s": 38.0},
        ])
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_stream_batch_cross_platform_prior_not_compared(self, tmp_path):
        """A CPU-fallback round's batching margin is never gated against
        a chip round's — the hw-gated-platform rule."""
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "stream_b4", "k": 128, "mb_per_s": 400.0},
        ], platform="tpu")
        _round_file(tmp_path, 2, [
            {"mode": "stream_b4", "k": 128, "mb_per_s": 25.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0
        # A genuine same-platform collapse still gates.
        _round_file(tmp_path, 3, [
            {"mode": "stream_b4", "k": 128, "mb_per_s": 2.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 1

    def test_stream_batch_rows_salvage_from_truncated_tail(self, tmp_path):
        """The salvage regex must keep digit-bearing modes (stream_b4):
        a front-truncated tail that only holds the row fragments still
        contributes the series."""
        bt = _load()
        tail = (
            '... truncated ... {"mode": "stream_b4", "k": 128, '
            '"mb_per_s": 44.0, "seconds_per_block": 0.19} trailing'
        )
        path = tmp_path / "BENCH_r01.json"
        path.write_text(json.dumps({
            "n": 1, "cmd": "bench", "rc": 0, "tail": tail, "parsed": None,
        }))
        rounds = bt.load_series([str(path)])
        assert rounds[0]["modes"] == {("stream_b4", 128): [44.0]}


class TestGiantKSeries:
    """compute rows at new giant sizes (BENCH_K=1024/2048) are LEARNED —
    gated under the same-platform rule like every compute row — and their
    absence from a default-plan round is an opt-in plan gap, never STALE
    or an unknown series."""

    def test_giant_k_round_learned_and_gated_same_platform(self, tmp_path,
                                                           capsys):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute", "k": 1024, "mb_per_s": 2.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 1024, "mb_per_s": 1.9},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0  # within threshold
        out = capsys.readouterr().out
        assert "compute@1024" in out  # rendered as a gated series
        assert "not gated" not in out.split("compute@1024")[1].splitlines()[0]
        # A real same-platform collapse gates like any compute row.
        _round_file(tmp_path, 3, [
            {"mode": "compute", "k": 1024, "mb_per_s": 0.5},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "compute@1024" in capsys.readouterr().out

    def test_giant_k_cross_platform_prior_not_compared(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute", "k": 1024, "mb_per_s": 900.0},
        ], platform="tpu")
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 1024, "mb_per_s": 2.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_giant_k_absent_from_default_round_is_opt_in_not_stale(
        self, tmp_path, capsys
    ):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute", "k": 1024, "mb_per_s": 2.0},
            {"mode": "compute", "k": 128, "mb_per_s": 50.0},
        ], platform="cpu")
        # Default plan next round: no BENCH_K row.
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 128, "mb_per_s": 51.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "opt-in: compute@1024" in out
        assert "STALE" not in out

    def test_giant_k_opt_in_lands_in_json_not_stale(self, tmp_path, capsys):
        import json as _json

        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute", "k": 2048, "mb_per_s": 1.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 128, "mb_per_s": 50.0},
        ], platform="cpu")
        bt.main(["--dir", str(tmp_path), "--json"])
        payload = _json.loads(capsys.readouterr().out)
        assert [s["series"] for s in payload["opt_in"]] == ["compute@2048"]
        assert payload["stale"] == []


class TestShardedComputeSeries:
    """compute_sharded<N> sweep rows (BENCH_MODE=compute_sharded,
    kernels/panel_sharded): gated PER SHARD COUNT under the
    same-platform rule; a shard count (or the whole sweep) absent from
    a round is an opt-in plan gap, never STALE."""

    def test_sweep_rows_gate_per_shard_count(self, tmp_path, capsys):
        bt = _load()
        assert bt.is_gated_mode("compute_sharded8")
        assert bt.is_gated_mode("compute_sharded1")
        assert not bt.is_gated_mode("compute_shardedx")
        _round_file(tmp_path, 1, [
            {"mode": "compute_sharded1", "k": 256, "mb_per_s": 2.0},
            {"mode": "compute_sharded8", "k": 256, "mb_per_s": 1.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "compute_sharded1", "k": 256, "mb_per_s": 2.1},
            {"mode": "compute_sharded8", "k": 256, "mb_per_s": 0.98},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "compute_sharded8@256" in out
        line = next(
            ln for ln in out.splitlines() if "compute_sharded8@256" in ln
        )
        assert "not gated" not in line
        # A same-platform collapse of ONE shard count gates; the other
        # series' stability does not mask it.
        _round_file(tmp_path, 3, [
            {"mode": "compute_sharded1", "k": 256, "mb_per_s": 2.1},
            {"mode": "compute_sharded8", "k": 256, "mb_per_s": 0.2},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "compute_sharded8@256" in capsys.readouterr().out

    def test_shard_counts_never_gate_each_other(self, tmp_path):
        """An 8-shard leg slower than the 1-shard leg (the CPU
        machinery curve) is NOT a regression — the series are keyed per
        shard count."""
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute_sharded1", "k": 256, "mb_per_s": 5.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "compute_sharded1", "k": 256, "mb_per_s": 5.0},
            {"mode": "compute_sharded8", "k": 256, "mb_per_s": 0.5},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_cross_platform_prior_not_compared(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute_sharded8", "k": 512, "mb_per_s": 900.0},
        ], platform="tpu")
        _round_file(tmp_path, 2, [
            {"mode": "compute_sharded8", "k": 512, "mb_per_s": 1.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_absent_sweep_is_opt_in_plan_gap_not_stale(self, tmp_path,
                                                       capsys):
        import json as _json

        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "compute_sharded8", "k": 256, "mb_per_s": 1.0},
            {"mode": "compute", "k": 128, "mb_per_s": 50.0},
        ], platform="cpu")
        # Default plan next round: no compute_sharded rows.
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 128, "mb_per_s": 51.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "opt-in: compute_sharded8@256" in out
        assert "STALE" not in out
        bt.main(["--dir", str(tmp_path), "--json"])
        payload = _json.loads(capsys.readouterr().out)
        assert [s["series"] for s in payload["opt_in"]] == [
            "compute_sharded8@256"
        ]
        assert payload["stale"] == []


class TestMalformedInputsFailFast:
    def test_unreadable_json_exits_2(self, tmp_path):
        bt = _load()
        (tmp_path / "BENCH_r01.json").write_text("{not json")
        assert bt.main(["--dir", str(tmp_path)]) == 2

    def test_missing_required_keys_exits_2(self, tmp_path):
        bt = _load()
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({"n": 1}))
        assert bt.main(["--dir", str(tmp_path)]) == 2

    def test_result_row_missing_fields_exits_2(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute"}])  # no k / mb_per_s
        assert bt.main(["--dir", str(tmp_path)]) == 2

    def test_no_files_exits_2(self, tmp_path):
        bt = _load()
        assert bt.main(["--dir", str(tmp_path)]) == 2

    def test_all_rounds_empty_exits_2(self, tmp_path):
        bt = _load()
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({
            "n": 1, "cmd": "bench", "rc": 1, "tail": "boom", "parsed": None,
        }))
        assert bt.main(["--dir", str(tmp_path)]) == 2


class TestMetricsOut:
    def test_writes_trend_tables(self, tmp_path):
        bt = _load()
        out_dir = tmp_path / "metrics"
        _trajectory(tmp_path)
        assert bt.main([
            "--dir", str(tmp_path), "--metrics-out", str(out_dir), "--json",
        ]) == 0
        prom = (out_dir / "bench_trend.prom").read_text()
        assert "celestia_bench_trend_mb_per_s" in prom
        assert 'mode="compute"' in prom
        rows = [
            json.loads(line)
            for line in (out_dir / "bench_trend.jsonl").read_text().splitlines()
        ]
        assert any(r.get("mode") == "compute" and r.get("k") == 512 for r in rows)
        assert any(r.get("part") == "rs_dense" for r in rows)


def _das_file(tmp_path, n, proofs_per_s, p99_ms, platform="cpu", **extra):
    path = tmp_path / f"DAS_r{n:02d}.json"
    path.write_text(json.dumps({
        "n": n, "proofs_per_s": proofs_per_s, "proof_p50_ms": p99_ms / 3,
        "proof_p99_ms": p99_ms, "samples": 100, "k": 8, "mode": "batched",
        "platform": platform, **extra,
    }))
    return str(path)


def _swarm_extra(sweeps: dict[int, float], burn: float = 0.1):
    """The das-v2 swarm block: sweep rows per shard count + tenant
    columns (scripts/das_loadgen.py swarm --round-out shape)."""
    return {
        "schema": "das-v2", "workload": "swarm", "clients": 1000,
        "arrival": "poisson", "rate": 300.0, "slo_ms": 250.0,
        "headline_shards": max(sweeps),
        "sweep": [
            {"shards": s, "proofs_per_s": v, "proof_p50_ms": 10.0,
             "proof_p99_ms": 40.0, "samples": 100}
            for s, v in sorted(sweeps.items())
        ],
        "tenants": {
            "t00": {"samples": 60, "p50_ms": 9.0, "p99_ms": 38.0,
                    "slo_burn": burn},
            "t01": {"samples": 40, "p50_ms": 11.0, "p99_ms": 44.0,
                    "slo_burn": burn},
        },
    }


class TestDasSeries:
    """The proof-serving trajectory (scripts/das_loadgen.py --round-out)
    rides the same trend table and regression gate as the bench rounds."""

    def test_checked_in_das_round_parses_and_renders(self, capsys):
        bt = _load()
        assert bt.main(["--check"]) == 0
        out = capsys.readouterr().out
        assert "das r01" in out and "proofs/s" in out

    def test_das_throughput_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=400.0, p99_ms=50.0)
        _das_file(tmp_path, 2, proofs_per_s=200.0, p99_ms=50.0)  # -50%
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "das.proofs_per_s" in capsys.readouterr().out

    def test_das_p99_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=400.0, p99_ms=50.0)
        _das_file(tmp_path, 2, proofs_per_s=400.0, p99_ms=120.0)  # p99 2.4x
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "das.proof_p99_ms" in capsys.readouterr().out

    def test_das_improvement_passes(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=400.0, p99_ms=50.0)
        _das_file(tmp_path, 2, proofs_per_s=500.0, p99_ms=40.0)
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_das_cross_platform_prior_not_compared(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        # A chip round's proofs/sec must not gate a CPU-fallback round.
        _das_file(tmp_path, 1, proofs_per_s=40_000.0, p99_ms=1.0,
                  platform="tpu")
        _das_file(tmp_path, 2, proofs_per_s=300.0, p99_ms=80.0,
                  platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_malformed_das_round_exits_2(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        (tmp_path / "DAS_r01.json").write_text(json.dumps({"n": 1}))
        assert bt.main(["--dir", str(tmp_path)]) == 2

    def test_das_series_in_json_output(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=400.0, p99_ms=50.0)
        assert bt.main(["--dir", str(tmp_path), "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["das_rounds"] == [1]

    def test_das_metrics_out(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=400.0, p99_ms=50.0)
        out_dir = tmp_path / "metrics"
        assert bt.main([
            "--dir", str(tmp_path), "--metrics-out", str(out_dir), "--json",
        ]) == 0
        prom = (out_dir / "bench_trend.prom").read_text()
        assert "celestia_bench_trend_das" in prom
        assert 'series="proofs_per_s"' in prom


class TestSwarmRounds:
    """The das-v2 swarm round shape (das_loadgen --clients): shard-count
    sweep rows gate same-platform per shard count; a workload or shard
    count no prior round measured is a PLAN GAP, never STALE or a
    phantom regression; tenant columns are shape-validated at load."""

    def test_swarm_round_parses_with_sweep_and_tenants(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=300.0, p99_ms=60.0,
                  **_swarm_extra({1: 300.0, 8: 900.0}))
        assert bt.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "shards=1" in out and "shards=8" in out
        assert "worst burn" in out

    def test_sweep_regression_same_shard_count_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=300.0, p99_ms=60.0,
                  **_swarm_extra({1: 300.0, 8: 900.0}))
        _das_file(tmp_path, 2, proofs_per_s=300.0, p99_ms=60.0,
                  **_swarm_extra({1: 300.0, 8: 450.0}))  # shards=8 -50%
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "das.sweep8.proofs_per_s" in capsys.readouterr().out

    def test_new_shard_count_is_plan_gap_not_regression(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=300.0, p99_ms=60.0,
                  **_swarm_extra({1: 300.0}))
        _das_file(tmp_path, 2, proofs_per_s=300.0, p99_ms=60.0,
                  **_swarm_extra({1: 300.0, 8: 10.0}))  # 8 is NEW
        assert bt.main(["--dir", str(tmp_path)]) == 0
        assert "sweep shards=8 first measured in r02" in (
            capsys.readouterr().out
        )

    def test_swarm_does_not_gate_against_closed_loop(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        # A rate-capped open-loop swarm number far below the closed-loop
        # saturation number is a WORKLOAD change, not a regression.
        _das_file(tmp_path, 1, proofs_per_s=900.0, p99_ms=20.0)
        _das_file(tmp_path, 2, proofs_per_s=200.0, p99_ms=300.0,
                  **_swarm_extra({1: 200.0, 8: 600.0}))
        assert bt.main(["--dir", str(tmp_path)]) == 0
        assert "workload 'swarm' first measured in r02" in (
            capsys.readouterr().out
        )

    def test_sweep_cross_platform_prior_not_compared(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=9000.0, p99_ms=1.0,
                  platform="tpu", **_swarm_extra({8: 90_000.0}))
        _das_file(tmp_path, 2, proofs_per_s=300.0, p99_ms=60.0,
                  platform="cpu", **_swarm_extra({8: 900.0}))
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_malformed_sweep_row_exits_2(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        extra = _swarm_extra({1: 300.0})
        del extra["sweep"][0]["proofs_per_s"]
        _das_file(tmp_path, 1, proofs_per_s=300.0, p99_ms=60.0, **extra)
        assert bt.main(["--dir", str(tmp_path)]) == 2

    def test_malformed_tenant_column_exits_2(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        extra = _swarm_extra({1: 300.0})
        del extra["tenants"]["t00"]["slo_burn"]
        _das_file(tmp_path, 1, proofs_per_s=300.0, p99_ms=60.0, **extra)
        assert bt.main(["--dir", str(tmp_path)]) == 2

    def test_all_failed_tenant_column_is_valid(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        extra = _swarm_extra({1: 300.0})
        # A tenant whose every request failed: no percentiles, maxed
        # burn — honest, not malformed.
        extra["tenants"]["t00"] = {
            "samples": 0, "failed": 40, "p50_ms": None, "p99_ms": None,
            "slo_burn": 100.0,
        }
        _das_file(tmp_path, 1, proofs_per_s=300.0, p99_ms=60.0, **extra)
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_sweep_rows_land_in_metrics_out(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=300.0, p99_ms=60.0,
                  **_swarm_extra({1: 300.0, 8: 900.0}))
        out_dir = tmp_path / "metrics"
        assert bt.main([
            "--dir", str(tmp_path), "--metrics-out", str(out_dir), "--json",
        ]) == 0
        prom = (out_dir / "bench_trend.prom").read_text()
        assert 'shards="8"' in prom

    def test_different_headline_shards_do_not_gate(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        # r01 headlines the 8-shard leg, r02 only swept shards=1: the
        # much-lower 1-shard headline is a MESH-WIDTH change, not a
        # regression (the shards=1 sweep row is flat and still gated).
        _das_file(tmp_path, 1, proofs_per_s=900.0, p99_ms=20.0,
                  **_swarm_extra({1: 300.0, 8: 900.0}))
        _das_file(tmp_path, 2, proofs_per_s=300.0, p99_ms=60.0,
                  **_swarm_extra({1: 300.0}))
        assert bt.main(["--dir", str(tmp_path)]) == 0
        assert "headline shards=1 first measured in r02" in (
            capsys.readouterr().out
        )

    def test_plan_gaps_in_json_output(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=900.0, p99_ms=20.0)
        _das_file(tmp_path, 2, proofs_per_s=200.0, p99_ms=300.0,
                  **_swarm_extra({1: 200.0}))
        assert bt.main(["--dir", str(tmp_path), "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert any("workload 'swarm'" in g for g in out["das_plan_gaps"])

def _adv_file(tmp_path, n, *, total_ms=30.0, recovered=True, monotone=True,
              honest=True, malform=True, wrong_root=True, platform="cpu",
              heal=None):
    p = ({"2": 0.5, "4": 0.7, "8": 0.9} if monotone
         else {"2": 0.9, "4": 0.5, "8": 0.7})
    path = tmp_path / f"ADV_r{n:02d}.json"
    rec = {
        "n": n, "schema": "adv-v2" if heal else "adv-v1",
        "platform": platform, "k": 8,
        "trials": 50, "sample_counts": [2, 4, 8],
        "detection": [{"withhold_frac": 0.25, "p_detect": p,
                       "monotone": monotone}],
        "repair": {"withhold_frac": 0.25, "withheld_shares": 64,
                   "detect_ms": 1.0, "repair_ms": total_ms - 1.0,
                   "total_ms": total_ms, "recovered": recovered},
        "honest_identical": honest, "all_monotone": monotone,
        "adversaries_detected": {"malform": malform,
                                 "wrong_root": wrong_root},
    }
    if heal:
        rec["heal"] = heal
    path.write_text(json.dumps(rec))
    return str(path)


def _heal_block(*, heal_total_ms=18.0, quorum_total_ms=120.0, healed=True,
                served=True, root_identical=True, never_tampered=True,
                quorum_healed=True):
    return {
        "single": {
            "k": 8, "withhold_frac": 0.25, "detect_ms": 7.0,
            "detect_samples": 6, "phases_ms": {"gather": 1.0},
            "heal_total_ms": heal_total_ms, "restored_ms": 26.0,
            "healed": healed, "served_after_heal": served,
            "root_identical": root_identical,
            "tampered_never_served": never_tampered,
            "quarantine_outcome": "irrecoverable",
        },
        "quorum": {
            "nodes": 3, "k": 8, "withhold_frac": 0.25, "hold_p": 0.75,
            "union_coverage": 0.98, "detect_ms": [9.0, 5.0, 6.0],
            "total_ms": quorum_total_ms, "healed": quorum_healed,
            "served_after_heal": served, "root_identical": root_identical,
        },
    }


class TestAdvSeries:
    """The adversarial-drill trajectory (scripts/chaos_soak.py --adv-out):
    invariants gate hard, repair-to-recovery latency gates like a parts
    time under the same-platform rule."""

    def test_checked_in_adv_round_parses_and_renders(self, capsys):
        bt = _load()
        assert bt.main(["--check"]) == 0
        out = capsys.readouterr().out
        assert "adv r01" in out and "monotone=True" in out

    def test_non_monotone_detection_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, monotone=False)
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "adv.detection_monotone" in capsys.readouterr().out

    def test_honest_divergence_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, honest=False)
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "adv.honest_identical" in capsys.readouterr().out

    def test_undetected_adversary_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, wrong_root=False)
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "adv.detected.wrong_root" in capsys.readouterr().out

    def test_failed_recovery_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, recovered=False)
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "adv.repair_recovered" in capsys.readouterr().out

    def test_repair_latency_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, total_ms=30.0)
        _adv_file(tmp_path, 2, total_ms=90.0)  # 3x slower recovery
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "adv.repair_total_ms" in capsys.readouterr().out

    def test_cross_platform_latency_prior_not_compared(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, total_ms=2.0, platform="tpu")
        _adv_file(tmp_path, 2, total_ms=90.0, platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_healthy_round_passes_and_lands_in_json(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1)
        assert bt.main(["--dir", str(tmp_path), "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["adv_rounds"] == [1]

    def test_malformed_adv_round_exits_2(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        (tmp_path / "ADV_r01.json").write_text(json.dumps({"n": 1}))
        assert bt.main(["--dir", str(tmp_path)]) == 2


class TestHealSeries:
    """ISSUE-12: the heal block (schema adv-v2) rides the adversarial
    gate — invariants (healed / served_after_heal / root_identical /
    tampered_never_served, plus the quorum leg) hard-fail, the detect-
    to-restored latencies gate lower-better under the same-platform
    rule, and adv-v1 rounds without a heal block stay additive (never
    gated, never STALE)."""

    def test_checked_in_round_renders_heal_line(self, capsys):
        bt = _load()
        assert bt.main(["--check"]) == 0
        out = capsys.readouterr().out
        assert "heal: single detect" in out
        assert "quorum 3 nodes" in out

    def test_heal_invariants_hard_fail(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, heal=_heal_block(served=False))
        assert bt.main(["--dir", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "heal.single.served_after_heal" in out
        assert "heal.quorum.served_after_heal" in out

    def test_tampered_served_hard_fails(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, heal=_heal_block(never_tampered=False))
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "heal.single.tampered_never_served" in capsys.readouterr().out

    def test_unhealed_quorum_node_hard_fails(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, heal=_heal_block(quorum_healed=False))
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "heal.quorum.healed" in capsys.readouterr().out

    def test_heal_latency_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, heal=_heal_block(heal_total_ms=18.0))
        _adv_file(tmp_path, 2, heal=_heal_block(heal_total_ms=60.0))
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "heal.single.total_ms" in capsys.readouterr().out

    def test_quorum_latency_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, heal=_heal_block(quorum_total_ms=100.0))
        _adv_file(tmp_path, 2, heal=_heal_block(quorum_total_ms=400.0))
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "heal.quorum.total_ms" in capsys.readouterr().out

    def test_pre_heal_rounds_are_additive_not_gated(self, tmp_path):
        """An adv-v1 prior (no heal block) never gates the heal series,
        and a newest round WITHOUT a heal block is not penalized (the
        loop may simply not have been drilled that round)."""
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1)  # adv-v1, no heal block
        _adv_file(tmp_path, 2, heal=_heal_block())
        assert bt.main(["--dir", str(tmp_path)]) == 0
        _adv_file(tmp_path, 3)  # newest drops the block: still fine
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_cross_platform_heal_prior_not_compared(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _adv_file(tmp_path, 1, platform="tpu",
                  heal=_heal_block(heal_total_ms=2.0, quorum_total_ms=10.0))
        _adv_file(tmp_path, 2, platform="cpu",
                  heal=_heal_block(heal_total_ms=60.0,
                                   quorum_total_ms=500.0))
        assert bt.main(["--dir", str(tmp_path)]) == 0


class TestRepairGatedSeries:
    """ISSUE-10 satellite: `repair` promoted from --all-series-only into
    the default gated set (compute-bound after the batched rework);
    `repair_grouped` (the bench's A/B baseline row) stays ungated."""

    def test_repair_is_gated_by_default(self, tmp_path, capsys):
        bt = _load()
        assert "repair" in bt.GATED_MODES
        assert "repair" not in bt.LINK_BOUND_MODES
        _round_file(tmp_path, 1, [
            {"mode": "repair", "k": 128, "mb_per_s": 60.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "repair", "k": 128, "mb_per_s": 30.0},  # -50%
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "repair@128" in capsys.readouterr().out

    def test_repair_same_platform_prior_rule(self, tmp_path):
        bt = _load()
        # A chip repair number must not gate a CPU-fallback round.
        _round_file(tmp_path, 1, [
            {"mode": "repair", "k": 128, "mb_per_s": 400.0},
        ], platform="tpu")
        _round_file(tmp_path, 2, [
            {"mode": "repair", "k": 128, "mb_per_s": 60.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_repair_grouped_baseline_not_gated(self, tmp_path):
        bt = _load()
        assert "repair_grouped" not in bt.GATED_MODES
        _round_file(tmp_path, 1, [
            {"mode": "repair_grouped", "k": 128, "mb_per_s": 60.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "repair_grouped", "k": 128, "mb_per_s": 10.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0


class TestMempoolSeries:
    """ISSUE-15: the BENCH_MODE=mempool concurrent-admission A/B —
    `mempool_sharded` gates like a rate under the same-platform rule,
    `mempool_global` (the frozen single-lock baseline rung) stays
    ungated like repair_grouped, and absence from a default-plan round
    is a plan gap, never STALE."""

    def test_sharded_is_gated_global_is_not(self, tmp_path, capsys):
        bt = _load()
        assert "mempool_sharded" in bt.GATED_MODES
        assert "mempool_global" not in bt.GATED_MODES
        _round_file(tmp_path, 1, [
            {"mode": "mempool_sharded", "k": 8, "mb_per_s": 900.0},
            {"mode": "mempool_global", "k": 8, "mb_per_s": 450.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "mempool_sharded", "k": 8, "mb_per_s": 400.0},  # -55%
            {"mode": "mempool_global", "k": 8, "mb_per_s": 100.0},  # ungated
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "mempool_sharded@8" in out
        assert "mempool_global@8" not in out.split("regressions:")[-1]

    def test_same_platform_prior_rule(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "mempool_sharded", "k": 8, "mb_per_s": 9000.0},
        ], platform="tpu")
        _round_file(tmp_path, 2, [
            {"mode": "mempool_sharded", "k": 8, "mb_per_s": 900.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_absence_from_default_round_is_plan_gap(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [
            {"mode": "mempool_sharded", "k": 8, "mb_per_s": 900.0},
        ], platform="cpu")
        _round_file(tmp_path, 2, [
            {"mode": "compute", "k": 128, "mb_per_s": 10.0},
        ], platform="cpu")
        assert bt.main(["--dir", str(tmp_path), "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert all(s["series"] != "mempool_sharded@8" for s in out["stale"])
        assert any(s["series"] == "mempool_sharded@8" for s in out["opt_in"])


def _qos_tenants(burns, throttled=None, p99=None):
    throttled = throttled or {}
    p99 = p99 or {}
    return {
        t: {
            "served": 100, "samples": 100, "failed": 0,
            "throttled": throttled.get(t, 0),
            "p50_ms": 10.0, "p99_ms": p99.get(t, 50.0),
            "slo_burn": burn,
        }
        for t, burn in burns.items()
    }


def _qos_round_file(tmp_path, n=1, *, spam_throttled=500,
                    base_burns=None, spam_burns=None, spam_p99=None):
    base_burns = base_burns or {"t00": 1.0, "t01": 2.0}
    spam_burns = spam_burns or {"t00": 1.0, "t01": 2.0, "t07": 0.5}
    rec = {
        "n": n, "schema": "qos-v1", "k": 16, "platform": "cpu",
        "clients": 100, "tenants": 8, "rate": 100.0, "slo_ms": 250.0,
        "spam_tenant": "t07", "spam_namespace": "8",
        "proof_rate_limit": 40.0, "spam_mult": 10.0, "spam_arrivals": 800,
        "legs": {
            "baseline": {
                "samples": 200, "proofs_per_s": 100.0,
                "proof_p99_ms": 60.0, "throttled": 0,
                "tenants": _qos_tenants(base_burns),
            },
            "spam": {
                "samples": 220, "proofs_per_s": 100.0,
                "proof_p99_ms": 60.0, "throttled": spam_throttled,
                "tenants": _qos_tenants(
                    spam_burns, throttled={"t07": spam_throttled},
                    p99=spam_p99,
                ),
            },
        },
    }
    path = tmp_path / f"QOS_r{n:02d}.json"
    path.write_text(json.dumps(rec))
    return str(path)


def _bench_seed_round(tmp_path):
    # bench_trend needs at least one readable BENCH round in --dir.
    _round_file(tmp_path, 1, [
        {"mode": "compute", "k": 128, "mb_per_s": 10.0},
    ], platform="cpu")


def _fleet_extra(host_rates, cross_p99_ms=120.0, coverage=0.6):
    return {
        "workload": "fleet",
        "fleet": {
            "hosts": [
                {"url": f"http://h{i}", "samples": 100, "proofs_per_s": r,
                 "p50_ms": 40.0, "p99_ms": 110.0, "coverage_ratio": coverage}
                for i, r in enumerate(host_rates)
            ],
            "cross_host_p50_ms": cross_p99_ms / 3,
            "cross_host_p99_ms": cross_p99_ms,
            "coverage_ratio": coverage,
        },
    }


class TestFleetSeries:
    """The fleet block (das_loadgen --urls): aggregate cluster rate /
    bucket-merged cross-host p99 / coverage gate same-platform among
    fleet-bearing rounds only; the first fleet round is a plan gap."""

    def test_checked_in_fleet_round_loads_and_gates_ok(self):
        bt = _load()
        import glob

        paths = sorted(glob.glob(os.path.join(REPO_ROOT, "DAS_r*.json")))
        rounds = bt.load_das_series(paths)
        with_fleet = [r for r in rounds if r.get("fleet")]
        assert with_fleet, "DAS_r04.json fleet block must be checked in"
        newest = with_fleet[-1]
        assert newest["fleet"]["hosts"] >= 2
        assert newest["fleet"]["proofs_per_s"] > 0
        assert newest["fleet"]["cross_host_p99_ms"] > 0
        assert 0 < newest["fleet"]["coverage_ratio"] <= 1
        assert newest["workload"] == "fleet"
        assert bt.find_das_regressions(rounds, 10.0) == []

    def test_first_fleet_round_is_plan_gap_not_stale(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=900.0, p99_ms=20.0)
        _das_file(tmp_path, 2, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([50.0, 50.0, 50.0]))
        assert bt.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "das fleet leg (--urls, 3 hosts) first measured in r02" in out
        assert "fleet: 3 hosts" in out

    def test_fleet_rate_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([50.0, 50.0, 50.0]))
        _das_file(tmp_path, 2, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([25.0, 25.0, 25.0]))  # cluster -50%
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "das.fleet.proofs_per_s" in capsys.readouterr().out

    def test_cross_host_p99_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([50.0, 50.0], cross_p99_ms=100.0))
        _das_file(tmp_path, 2, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([50.0, 50.0], cross_p99_ms=300.0))
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "das.fleet.cross_host_p99_ms" in capsys.readouterr().out

    def test_coverage_collapse_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([50.0, 50.0], coverage=0.9))
        _das_file(tmp_path, 2, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([50.0, 50.0], coverage=0.2))
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "das.fleet.coverage_ratio" in capsys.readouterr().out

    def test_fleet_does_not_gate_against_closed_loop_headline(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        # A rate-capped 3-host open-loop round after a closed-loop
        # saturation round: workload changed, top-level numbers must not
        # gate across the pair.
        _das_file(tmp_path, 1, proofs_per_s=2000.0, p99_ms=50.0)
        _das_file(tmp_path, 2, proofs_per_s=170.0, p99_ms=1100.0,
                  **_fleet_extra([57.0, 57.0, 57.0]))
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_cross_platform_fleet_prior_not_compared(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=9000.0, p99_ms=1.0,
                  platform="tpu", **_fleet_extra([3000.0, 3000.0]))
        _das_file(tmp_path, 2, proofs_per_s=150.0, p99_ms=900.0,
                  platform="cpu", **_fleet_extra([50.0, 50.0]))
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_fleet_series_lands_in_metrics_out(self, tmp_path):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        _das_file(tmp_path, 1, proofs_per_s=150.0, p99_ms=900.0,
                  **_fleet_extra([50.0, 50.0, 50.0]))
        out_dir = tmp_path / "metrics"
        assert bt.main(["--dir", str(tmp_path),
                        "--metrics-out", str(out_dir), "--json"]) == 0
        text = (out_dir / "bench_trend.prom").read_text()
        assert 'series="fleet.proofs_per_s"' in text
        assert 'series="fleet.cross_host_p99_ms"' in text
        assert 'series="fleet.coverage_ratio"' in text

    @pytest.mark.parametrize("mutilate", [
        lambda fl: fl["hosts"].pop(),              # < 2 hosts
        lambda fl: fl["hosts"][0].pop("p99_ms"),   # host row incomplete
        lambda fl: fl.pop("cross_host_p99_ms"),    # merged quantile gone
        lambda fl: fl.pop("coverage_ratio"),
    ])
    def test_malformed_fleet_block_exits_2(self, tmp_path, mutilate):
        bt = _load()
        _round_file(tmp_path, 1, [{"mode": "compute", "k": 8, "mb_per_s": 5.0}])
        extra = _fleet_extra([50.0, 50.0])
        mutilate(extra["fleet"])
        _das_file(tmp_path, 1, proofs_per_s=150.0, p99_ms=900.0, **extra)
        assert bt.main(["--dir", str(tmp_path)]) == 2


class TestQosRounds:
    """ISSUE-15: QOS_rNN.json (das_loadgen --qos-out) — per-tenant
    throttled/served/burn columns validated, enforcement invariants
    gated, malformed exits 2."""

    def test_checked_in_qos_round_loads_and_gates_ok(self):
        bt = _load()
        import glob

        paths = sorted(glob.glob(os.path.join(REPO_ROOT, "QOS_r*.json")))
        assert paths, "QOS_r01.json must be checked in"
        rounds = bt.load_qos_series(paths)
        newest = rounds[-1]
        spam = newest["legs"]["spam"]["tenants"][newest["spam_tenant"]]
        assert spam["throttled"] > 0
        assert bt.find_qos_regressions(rounds, 10.0) == []

    def test_valid_round_passes(self, tmp_path):
        bt = _load()
        _bench_seed_round(tmp_path)
        _qos_round_file(tmp_path)
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_unthrottled_spammer_is_a_regression(self, tmp_path, capsys):
        bt = _load()
        _bench_seed_round(tmp_path)
        _qos_round_file(tmp_path, spam_throttled=0)
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "qos.spammer_throttled" in capsys.readouterr().out

    def test_honest_tenant_burn_regression_flagged(self, tmp_path, capsys):
        bt = _load()
        _bench_seed_round(tmp_path)
        _qos_round_file(
            tmp_path,
            base_burns={"t00": 1.0, "t01": 2.0},
            spam_burns={"t00": 1.0, "t01": 9.0, "t07": 0.5},  # t01 3x worse
        )
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "qos.t01.slo_burn" in capsys.readouterr().out

    def test_honest_tenant_p99_regression_flagged(self, tmp_path, capsys):
        bt = _load()
        _bench_seed_round(tmp_path)
        _qos_round_file(
            tmp_path, spam_p99={"t00": 500.0},  # baseline p99 is 50 ms
        )
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "qos.t00.p99_ms" in capsys.readouterr().out

    def test_spammer_own_columns_never_gate(self, tmp_path):
        bt = _load()
        _bench_seed_round(tmp_path)
        # The spammer's burn is terrible in the spam leg — that IS the
        # enforcement; only honest tenants gate.
        _qos_round_file(
            tmp_path,
            base_burns={"t00": 1.0, "t07": 0.0},
            spam_burns={"t00": 1.0, "t07": 99.0},
        )
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_quantization_slack_small_burn_moves_pass(self, tmp_path):
        bt = _load()
        _bench_seed_round(tmp_path)
        # 0.0 -> 0.4 burn is inside the absolute slack (one violation in
        # a small sample moves burn in steps).
        _qos_round_file(
            tmp_path,
            base_burns={"t00": 0.0, "t01": 2.0},
            spam_burns={"t00": 0.4, "t01": 2.0, "t07": 0.5},
        )
        assert bt.main(["--dir", str(tmp_path)]) == 0

    @pytest.mark.parametrize("mutilate", [
        lambda r: r.pop("spam_tenant"),
        lambda r: r.pop("legs"),
        lambda r: r["legs"].pop("baseline"),
        lambda r: r["legs"]["spam"]["tenants"]["t00"].pop("slo_burn"),
        lambda r: r["legs"]["spam"]["tenants"]["t00"].pop("throttled"),
        lambda r: r.update(spam_tenant="t99"),
    ])
    def test_malformed_exits_2(self, tmp_path, mutilate):
        bt = _load()
        _bench_seed_round(tmp_path)
        path = _qos_round_file(tmp_path)
        rec = json.loads(open(path).read())
        mutilate(rec)
        open(path, "w").write(json.dumps(rec))
        assert bt.main(["--dir", str(tmp_path)]) == 2


def _sweep_round_file(tmp_path, n=1, dryrun=False, plan=None, legs=None,
                      platform="tpu", schema="sweep-v1"):
    plan = plan if plan is not None else ["parts", "mempool"]
    if legs is None:
        status = "planned" if dryrun else "ok"
        legs = {name: {"status": status, "seconds": 0.0} for name in plan}
    rec = {
        "schema": schema,
        "round": n,
        "plan": plan,
        "legs": legs,
        "platform": "unprobed" if dryrun else platform,
    }
    if dryrun:
        rec["dryrun"] = True
    path = os.path.join(tmp_path, f"SWEEP_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump(rec, f)
    return path


class TestSweepRounds:
    """ISSUE-18: SWEEP_rNN.json (scripts/chip_sweep.py) — the chip
    sitting's journal: per-leg status + /device families load, a dryrun
    plan reads as wholly-open debt, never-ok legs stay open, plan
    growth is a NOTE not a regression, malformed raises."""

    def test_chip_sweep_dryrun_journal_round_trips(self, tmp_path):
        # Cross-tool contract: the journal chip_sweep WRITES is the
        # journal bench_trend READS — generate it with the real tool.
        bt = _load()
        spec = importlib.util.spec_from_file_location(
            "chip_sweep",
            os.path.join(REPO_ROOT, "scripts", "chip_sweep.py"),
        )
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        assert cs.main(["--dryrun", "--out-dir", str(tmp_path)]) == 0

        r = bt.load_sweep_round(os.path.join(tmp_path, "SWEEP_r01.json"))
        assert r["dryrun"] is True
        assert r["platform"] == "unprobed"
        assert len(r["plan"]) == 13
        assert all(
            leg["status"] == "planned" for leg in r["legs"].values()
        )
        gaps = bt.sweep_plan_gaps([r])
        assert len(gaps) == 1
        assert "dryrun plan" in gaps[0]
        assert "no leg has paid the standing debt" in gaps[0]

    def test_device_families_extracted_per_leg(self, tmp_path):
        bt = _load()
        path = _sweep_round_file(tmp_path, legs={
            "parts": {
                "status": "ok", "seconds": 41.5,
                "device": {"programs": [
                    {"family": "extend_and_dah", "k": 512},
                    {"family": "forest", "k": 512},
                    {"family": "extend_and_dah", "k": 512, "mode": "epi"},
                ]},
            },
            "mempool": {"status": "timeout", "seconds": 1800.0},
        })
        r = bt.load_sweep_round(path)
        assert r["legs"]["parts"]["device_families"] == [
            "extend_and_dah", "forest",
        ]
        assert r["legs"]["parts"]["seconds"] == 41.5
        assert r["legs"]["mempool"]["device_families"] == []

    def test_never_ok_legs_stay_open_debt(self, tmp_path):
        bt = _load()
        path = _sweep_round_file(tmp_path, legs={
            "parts": {"status": "ok", "seconds": 10.0},
            "mempool": {"status": "timeout", "seconds": 1800.0},
        })
        gaps = bt.sweep_plan_gaps([bt.load_sweep_round(path)])
        assert len(gaps) == 1
        assert "'mempool'" in gaps[0] and "timeout" in gaps[0]
        assert "still open" in gaps[0]

    def test_planned_leg_that_never_ran_is_missing(self, tmp_path):
        bt = _load()
        path = _sweep_round_file(
            tmp_path, plan=["parts", "repair"],
            legs={"parts": {"status": "ok", "seconds": 10.0}},
        )
        gaps = bt.sweep_plan_gaps([bt.load_sweep_round(path)])
        assert any("'repair'" in g and "missing" in g for g in gaps)

    def test_new_leg_is_plan_gap_not_stale(self, tmp_path):
        bt = _load()
        p1 = _sweep_round_file(tmp_path, n=1, plan=["parts"])
        p2 = _sweep_round_file(tmp_path, n=2, plan=["parts", "hbm_k512"])
        rounds = bt.load_sweep_series([p1, p2])
        assert [r["round"] for r in rounds] == [1, 2]
        gaps = bt.sweep_plan_gaps(rounds)
        assert any(
            "'hbm_k512'" in g and "plan gap, not STALE" in g for g in gaps
        )
        # The ok legs themselves are NOT gaps.
        assert not any("'parts'" in g for g in gaps)

    def test_main_reports_sweep_series_without_gating(self, tmp_path, capsys):
        bt = _load()
        _bench_seed_round(tmp_path)
        _sweep_round_file(tmp_path, legs={
            "parts": {"status": "ok", "seconds": 10.0},
            "mempool": {"status": "error", "seconds": 3.0},
        })
        assert bt.main(["--dir", str(tmp_path), "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["sweep_rounds"] == [1]
        assert any("'mempool'" in g for g in out["sweep_plan_gaps"])

    @pytest.mark.parametrize("mutilate", [
        lambda r: r.pop("schema"),
        lambda r: r.pop("round"),
        lambda r: r.pop("plan"),
        lambda r: r.pop("legs"),
        lambda r: r.update(schema="sweep-v9"),
    ])
    def test_malformed_sweep_raises(self, tmp_path, mutilate):
        bt = _load()
        path = _sweep_round_file(tmp_path)
        rec = json.loads(open(path).read())
        mutilate(rec)
        open(path, "w").write(json.dumps(rec))
        with pytest.raises(bt.MalformedRound):
            bt.load_sweep_round(path)

    def test_unreadable_sweep_exits_2_via_main(self, tmp_path, capsys):
        bt = _load()
        _bench_seed_round(tmp_path)
        with open(os.path.join(tmp_path, "SWEEP_r01.json"), "w") as f:
            f.write("{not json")
        assert bt.main(["--dir", str(tmp_path)]) == 2


def _tl_round_file(tmp_path, n, phases, gaps=None, platform="cpu",
                   **overrides):
    def dist(shares):
        return {
            name: {"mean_ms": 1.0, "p95_ms": 2.0, "share": share}
            for name, share in (shares or {}).items()
        }

    payload = {
        "schema": "tl-v1", "n": n, "platform": platform, "k": 16,
        "blocks": 8, "phases": dist(phases), "gaps": dist(gaps),
        "critical_counts": {}, "total_ms": 100.0,
    }
    payload.update(overrides)
    path = tmp_path / f"TL_r{n:02d}.json"
    path.write_text(json.dumps(payload))
    return str(path)


class TestTimelineSeries:
    """TL_rNN.json height-anatomy rounds (scripts/block_anatomy.py
    --round-out): per-phase SHARE of height time gated against the best
    same-platform prior with a 0.05 absolute slack floor."""

    def test_checked_in_tl_round_parses_and_passes_check(self, capsys):
        import glob

        bt = _load()
        paths = sorted(glob.glob(os.path.join(REPO_ROOT, "TL_r*.json")))
        assert paths, "expected the checked-in TL_r01.json at the repo root"
        rounds = bt.load_tl_series(paths)
        assert rounds[0]["round"] == 1
        assert rounds[0]["platform"], "CPU-fallback rounds must say so"
        for d in rounds[0]["phases"].values():
            assert 0.0 <= d["share"] <= 1.0
        assert bt.main(["--check"]) == 0
        out = capsys.readouterr().out
        assert "tl r01" in out

    def test_phase_share_regression_is_flagged(self, tmp_path, capsys):
        bt = _load()
        _bench_seed_round(tmp_path)
        _tl_round_file(tmp_path, 1, {"dispatch": 0.30, "drain": 0.10})
        # drain quietly grows its slice 0.10 -> 0.45 while dispatch
        # stays flat: only the grower is flagged.
        _tl_round_file(tmp_path, 2, {"dispatch": 0.30, "drain": 0.45})
        assert bt.main(["--dir", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "tl.drain.share" in out
        assert "tl.dispatch.share" not in out
        assert bt.main(["--dir", str(tmp_path), "--json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        series = [r["series"] for r in payload["regressions"]]
        assert series == ["tl.drain.share"]
        assert payload["tl_rounds"] == [1, 2]

    def test_small_share_growth_rides_the_absolute_floor(self, tmp_path):
        # 1% -> 5% is inside the 0.05 absolute slack: sub-5%-share
        # phases must not trip the gate on scheduler noise.
        bt = _load()
        _bench_seed_round(tmp_path)
        _tl_round_file(tmp_path, 1, {"upload": 0.01, "dispatch": 0.60})
        _tl_round_file(tmp_path, 2, {"upload": 0.05, "dispatch": 0.60})
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_gap_shares_gate_too(self, tmp_path, capsys):
        bt = _load()
        _bench_seed_round(tmp_path)
        _tl_round_file(tmp_path, 1, {"dispatch": 0.50},
                       gaps={"intake_wait": 0.10})
        _tl_round_file(tmp_path, 2, {"dispatch": 0.50},
                       gaps={"intake_wait": 0.40})
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "tl.intake_wait.gap_share" in capsys.readouterr().out

    def test_cross_platform_tl_prior_not_compared(self, tmp_path):
        bt = _load()
        _bench_seed_round(tmp_path)
        _tl_round_file(tmp_path, 1, {"dispatch": 0.05}, platform="tpu")
        _tl_round_file(tmp_path, 2, {"dispatch": 0.90}, platform="cpu")
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_new_phase_is_additive_never_a_regression(self, tmp_path):
        bt = _load()
        _bench_seed_round(tmp_path)
        _tl_round_file(tmp_path, 1, {"dispatch": 0.50})
        _tl_round_file(tmp_path, 2, {"dispatch": 0.50,
                                     "forest_build": 0.40})
        assert bt.main(["--dir", str(tmp_path)]) == 0

    def test_best_prior_wins_not_the_latest(self, tmp_path, capsys):
        # The gate compares against the BEST (smallest) prior share, so
        # two already-degraded rounds cannot ratchet the baseline up.
        bt = _load()
        _bench_seed_round(tmp_path)
        _tl_round_file(tmp_path, 1, {"drain": 0.10})
        _tl_round_file(tmp_path, 2, {"drain": 0.40})
        _tl_round_file(tmp_path, 3, {"drain": 0.41})
        assert bt.main(["--dir", str(tmp_path)]) == 1
        assert "tl.drain.share" in capsys.readouterr().out

    @pytest.mark.parametrize("mutilate", [
        lambda r: r.pop("schema"),
        lambda r: r.pop("n"),
        lambda r: r.pop("phases"),
        lambda r: r.update(schema="tl-v9"),
        lambda r: r.update(phases={}),
        lambda r: r["phases"]["dispatch"].pop("share"),
    ])
    def test_malformed_tl_round_raises(self, tmp_path, mutilate):
        bt = _load()
        path = _tl_round_file(tmp_path, 1, {"dispatch": 0.5})
        rec = json.loads(open(path).read())
        mutilate(rec)
        open(path, "w").write(json.dumps(rec))
        with pytest.raises(bt.MalformedRound):
            bt.load_tl_round(path)

    def test_unreadable_tl_exits_2_via_main(self, tmp_path):
        bt = _load()
        _bench_seed_round(tmp_path)
        with open(os.path.join(tmp_path, "TL_r01.json"), "w") as f:
            f.write("{not json")
        assert bt.main(["--dir", str(tmp_path)]) == 2
