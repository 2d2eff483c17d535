#!/usr/bin/env python
"""Bench trajectory reader + regression gate over the BENCH_r*.json rounds.

Each driver round leaves one `BENCH_rNN.json` at the repo root:
`{n, cmd, rc, tail, parsed}` where `tail` is the LAST 2000 bytes of the
bench's stdout — usually ending in the one-line JSON summary bench.py
prints, but possibly truncated at the front (the r04/r05 rounds lose the
`results` array and keep only the trailing `parts`/`stability_pct`
fields) or missing entirely (r01 died before printing).  This tool
reads the whole series, salvages what each round actually recorded, and
prints the per-mode trend table nobody could previously assemble:

    python scripts/bench_trend.py            # table + gate
    python scripts/bench_trend.py --check    # tier-1 self-test mode

The GATE (exit 1) is stability-aware and fires when the newest datapoint
of a gated series drops more than `--threshold` percent (default 10)
plus that round's measured `stability_pct` below the best earlier
datapoint.  Gated by default: the device-resident `compute` rows (the
ROADMAP headline), the batched `repair` rows (compute-bound since the
ISSUE-10 rework; the same-platform prior rule applies), the multi-chip
`compute_sharded<N>` sweep rows (one series PER SHARD COUNT — bench.py
BENCH_MODE=compute_sharded; opt-in like the giant-k rows, so absence
from a default-plan round is a plan gap, never STALE), and the `parts`
decomposition seconds.  The link-bound modes (extend / stream / host)
ride the host-to-chip transfer, whose cost varied between rounds
(BENCH_r03's stream row collapsed 13x while compute improved 24x), so
they are REPORTED but only gated under `--all-series`.  Malformed or empty inputs exit 2 — a bad bench JSON
fails tier-1 fast instead of silently dropping out of the trajectory.

`--metrics-out <dir>` writes the same artifacts bench.py does — a
`bench_trend.prom` Prometheus textfile and `bench_trend.jsonl` rows
(tracer table `bench_trend`) — so the next chip round's numbers land in
the same tables as the live exposition.

The PROOF-SERVING trajectory rides the same gate: any `DAS_rNN.json`
records at the repo root (written by `scripts/das_loadgen.py
--round-out`) contribute a proofs/sec series (gated like a rate, higher
is better) and a proof-p99 series (gated like a parts time, lower is
better), under the same same-platform comparability rule.

The ADVERSARIAL-DRILL trajectory (`ADV_rNN.json`, written by
`scripts/chaos_soak.py --adv-out`) gates differently — it records
INVARIANTS first, latency second:

  * every detection-probability curve must be monotone non-decreasing
    in sample count and the honest leg byte-identical (a violated
    invariant is a hard regression regardless of priors);
  * the tampering adversaries (malform / wrong_root) must have been
    detected on every probe;
  * repair-to-recovery total_ms gates like a parts time (lower better)
    against same-platform priors.

The HEAL series (schema adv-v2, rounds carrying a "heal" block from the
chaos_soak healing drills) extends the same shape: the detect -> repair
-> re-serve loop's invariants gate hard — the heal must complete
(`healed`), the previously-withheld coordinate must serve post-heal
(`served_after_heal`), recovered roots must be bit-identical to the
committed DAH (`root_identical`), tampered state must never have been
served in the heal window (`tampered_never_served`), and the quorum leg
must heal every node — while the single-node and quorum detect-to-
restored latencies (`heal_total_ms` / `total_ms`) gate lower-better
against same-platform priors that also carry a heal block (older
adv-v1 rounds simply predate the loop: additive, never STALE).

The HEIGHT-ANATOMY trajectory (`TL_rNN.json`, written by
`scripts/block_anatomy.py --round-out`) gates SHARES, not seconds: each
`tl.<phase>.share` / `tl.<gap>.gap_share` series is the phase's fraction
of all accounted height time over an N-block streamed run.  The newest
round gates against the best (smallest) same-platform prior share with a
0.05 absolute slack floor — a phase quietly growing its slice of the
height critical path fails `--check` even when every absolute latency
still looks healthy.  Phases a prior round never measured are additive.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The continuous-batching stream rows (bench.py STREAM_BATCHES): B same-k
# squares coalesced into one vmapped dispatch, rate-shaped like every
# other mode.  Gated — the batch-B-vs-batch-1 margin is the feature under
# regression watch — with the same same-platform comparability rule the
# hw-gated parts candidates lean on (a CPU-fallback round's batching
# margin is never compared against a chip round's, and vice versa;
# _comparable_priors drops cross-platform priors for these series too).
STREAM_BATCH_MODES = ("stream_b1", "stream_b2", "stream_b4")
# Modes whose rate is device-resident and comparable across rounds.
# `repair` joined the gated set with the ISSUE-10 batched-repair rework:
# the damaged square ships once and every sweep + the re-extension run
# device-resident, so the row is compute-bound like `compute`, no longer
# dominated by link quality.  `repair_grouped` (the frozen per-pattern-
# group baseline bench.py re-measures at k=128 for the speedup record)
# stays ungated: it exists to be compared against, not to regress.
#
# `mempool_sharded` (bench.py BENCH_MODE=mempool, the concurrent-
# broadcast admission A/B at k=<threads>) gates like a rate under the
# same-platform rule; `mempool_global` — the frozen single-lock baseline
# rung the A/B measures against — stays ungated like repair_grouped: it
# exists to be compared against, not to regress.  Both are opt-in rows
# (only BENCH_MODE=mempool produces them), so absence from a default-
# plan round is a plan gap, never STALE.
GATED_MODES = ("compute", "repair", "mempool_sharded") + STREAM_BATCH_MODES
MEMPOOL_MODES = ("mempool_sharded", "mempool_global")
# The multi-chip extend sweep rows (bench.py BENCH_MODE=compute_sharded,
# kernels/panel_sharded): mode compute_sharded<N>, one series PER SHARD
# COUNT — each N gates against prior rounds carrying the same N under
# the same-platform rule (the das-v2 sweep pattern applied to the write
# side: a 1-shard leg is never a regression against an 8-shard leg).
# Like giant-k rows they are opt-in (only BENCH_MODE=compute_sharded
# produces them), so their absence from a default-plan round is a plan
# gap, never STALE; a shard count no prior round measured is likewise a
# plan gap, not an unknown series.
SHARDED_COMPUTE_RE = re.compile(r"^compute_sharded\d+$")


def is_gated_mode(mode: str) -> bool:
    return mode in GATED_MODES or bool(SHARDED_COMPUTE_RE.match(mode))


# Modes bound by the host<->device link; reported, not gated by default.
LINK_BOUND_MODES = ("extend", "stream", "host")
# The default bench plan stops at k=512 (the paper's north star); rows at
# larger k exist only when a round was driven with BENCH_K=1024/2048 (the
# giant-square frontier).  Such per-k series are LEARNED like any other
# gated series — newest-vs-best-prior under the same-platform rule — but
# their absence from a default-plan round is a plan gap, not staleness:
# the gate must neither cry STALE about a row the plan cannot produce nor
# treat compute@1024 as an unknown series.
DEFAULT_PLAN_MAX_K = 512
# Parts candidates only measured on TPU (the Pallas lowerings): their
# absence from a CPU-fallback round is a platform gap, not a stale series
# — the trend gate must not cry STALE when a chip round simply didn't
# happen.  fused / fused_epi are NOT here: bench measures them on every
# platform (the epilogue rides an XLA composition off-chip), so they are
# never absent — cross-platform comparability is instead handled by the
# regression gate's same-platform rule below.
HW_GATED_PARTS = (
    "rs_dense_pl", "rs_xor", "nmt_dah_pallas", "nmt_dah_plf",
)

# [a-z0-9_]: the stream_b<N> continuous-batching modes carry a digit.
_MODE_ROW_RE = re.compile(r'\{"mode":\s*"[a-z0-9_]+",\s*"k":\s*\d+[^{}]*\}')
_STABILITY_RE = re.compile(r'"stability_pct":\s*([0-9.]+)')
_ERRORS_RE = re.compile(r'"errors":\s*(\[[^\]]*\])')


class MalformedRound(ValueError):
    """A BENCH_r*.json that cannot be read at all (exit 2 material)."""


def _balanced_object(text: str, start: int) -> str | None:
    """The JSON object starting at text[start] == '{', by brace balance
    (good enough here: bench summaries never put braces in strings)."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
            if depth == 0:
                return text[start : i + 1]
    return None


def _salvage_tail(tail: str) -> dict:
    """Partial recovery from a front-truncated summary line: individual
    mode rows, the parts decomposition, stability, errors."""
    out: dict = {"partial": True}
    rows = []
    for m in _MODE_ROW_RE.finditer(tail):
        try:
            rows.append(json.loads(m.group(0)))
        except ValueError:
            continue
    if rows:
        out["results"] = rows
    i = tail.rfind('"parts": {')
    if i >= 0:
        obj = _balanced_object(tail, i + len('"parts": '))
        if obj is not None:
            try:
                out["parts"] = json.loads(obj)
            except ValueError:
                pass
    m = _STABILITY_RE.search(tail)
    if m:
        out["stability_pct"] = float(m.group(1))
    m = _ERRORS_RE.search(tail)
    if m:
        try:
            out["errors"] = json.loads(m.group(1))
        except ValueError:
            pass
    return out


def _summary_from_tail(tail: str) -> dict | None:
    """The full summary line if the tail still holds it whole."""
    for line in reversed(tail.splitlines()):
        if line.startswith('{"metric"'):
            try:
                return json.loads(line)
            except ValueError:
                return None
    return None


def load_round(path: str) -> dict:
    """One round's recoverable record:

    {round, rc, ok, partial, platform, headline, stability_pct, errors,
     modes: {(mode, k): [mb_per_s, ...]}, parts: {name: seconds} | None,
     tuned: {rs, sha, pipe} | None, applied: {rs, sha, pipe} | None}
    """
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedRound(f"{path}: not readable JSON: {e}") from e
    for key in ("n", "rc", "tail"):
        if key not in raw:
            raise MalformedRound(f"{path}: missing required key {key!r}")
    rec = {
        "round": int(raw["n"]),
        "path": os.path.basename(path),
        "rc": raw["rc"],
        "ok": raw["rc"] == 0,
        "partial": False,
        "platform": None,
        "headline": None,
        "stability_pct": None,
        "errors": None,
        "modes": {},
        "parts": None,
        "tuned": None,
        "applied": None,
    }
    summary = raw.get("parsed")
    if not isinstance(summary, dict):
        summary = _summary_from_tail(raw["tail"]) if rec["ok"] else None
        if summary is None and rec["ok"]:
            summary = _salvage_tail(raw["tail"])
    if not summary:
        return rec
    rec["partial"] = bool(summary.get("partial"))
    rec["platform"] = summary.get("platform")
    rec["headline"] = summary.get("value")
    rec["stability_pct"] = summary.get("stability_pct")
    rec["errors"] = summary.get("errors")
    for row in summary.get("results", []):
        mode, k = row.get("mode"), row.get("k")
        if mode is None or k is None or "mb_per_s" not in row:
            raise MalformedRound(
                f"{path}: result row missing mode/k/mb_per_s: {row}"
            )
        rec["modes"].setdefault((str(mode), int(k)), []).append(
            float(row["mb_per_s"])
        )
    parts = summary.get("parts")
    if isinstance(parts, dict) and isinstance(parts.get("seconds"), dict):
        rec["parts"] = {
            str(n): float(s) for n, s in parts["seconds"].items()
        }
        for seat_key in ("tuned", "applied"):
            seats = parts.get(seat_key)
            if isinstance(seats, dict):
                rec[seat_key] = {str(a): str(b) for a, b in seats.items()}
    return rec


def load_series(paths: list[str]) -> list[dict]:
    """The BENCH rounds; none at all is a valid state (no chip round has
    been taken yet), rounds that all failed to yield data are not."""
    rounds = sorted((load_round(p) for p in paths), key=lambda r: r["round"])
    if rounds and not any(r["modes"] or r["parts"] for r in rounds):
        raise MalformedRound("no round contributed any data")
    return rounds


# --- DAS loadgen rounds (scripts/das_loadgen.py --round-out) -----------------

def load_das_round(path: str) -> dict:
    """One DAS_rNN.json: {n, proofs_per_s, proof_p99_ms, [platform, ...]}.
    Malformed files exit 2 like a bad bench round — a broken loadgen
    record must not silently drop out of the trajectory.

    Swarm rounds (schema "das-v2", das_loadgen --clients) additionally
    carry the shard-count SWEEP (the scaling curve: one row per
    $CELESTIA_SERVE_SHARDS setting over an identical open-loop plan)
    and per-tenant p99/SLO-burn columns; both are validated here so a
    half-written swarm record exits 2 instead of gating on garbage.
    Pre-swarm rounds carry neither — they stay valid as the closed-loop
    workload (see find_das_regressions: workloads never gate each
    other)."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedRound(f"{path}: not readable JSON: {e}") from e
    for key in ("n", "proofs_per_s", "proof_p99_ms"):
        if key not in raw or raw[key] is None:
            raise MalformedRound(f"{path}: missing required key {key!r}")
    rec = {
        "round": int(raw["n"]),
        "path": os.path.basename(path),
        "proofs_per_s": float(raw["proofs_per_s"]),
        "proof_p99_ms": float(raw["proof_p99_ms"]),
        "platform": raw.get("platform"),
        "workload": raw.get("workload", "closed"),
        # Which sweep leg produced the headline numbers (swarm rounds):
        # top-level gating is only meaningful between rounds whose
        # headline came from the same mesh width.
        "headline_shards": raw.get("headline_shards"),
        "sweep": {},
        "tenants": {},
    }
    for row in raw.get("sweep") or []:
        for key in ("shards", "proofs_per_s", "proof_p99_ms"):
            if not isinstance(row, dict) or row.get(key) is None:
                raise MalformedRound(
                    f"{path}: sweep row missing {key!r}: {row!r}"
                )
        rec["sweep"][int(row["shards"])] = {
            "proofs_per_s": float(row["proofs_per_s"]),
            "proof_p99_ms": float(row["proof_p99_ms"]),
        }
    for tenant, cols in (raw.get("tenants") or {}).items():
        if not isinstance(cols, dict) or cols.get("slo_burn") is None:
            raise MalformedRound(
                f"{path}: tenant {tenant!r} missing 'slo_burn'"
            )
        # A tenant whose every request FAILED has no latency percentiles
        # (samples==0, failed>0, burn maxed) — that is a valid, honest
        # column; a served tenant without a p99 is malformed.
        all_failed = (
            cols.get("samples") == 0 and (cols.get("failed") or 0) > 0
        )
        if cols.get("p99_ms") is None and not all_failed:
            raise MalformedRound(
                f"{path}: tenant {tenant!r} missing 'p99_ms'"
            )
        if float(cols["slo_burn"]) < 0:
            raise MalformedRound(
                f"{path}: tenant {tenant!r} slo_burn negative"
            )
        rec["tenants"][str(tenant)] = {
            "p99_ms": (
                float(cols["p99_ms"]) if cols.get("p99_ms") is not None
                else None
            ),
            "slo_burn": float(cols["slo_burn"]),
        }
    # The verify-plane block (das_loadgen --attest): batched vs host
    # verified-samples/sec and attestation vs independent bytes-per-
    # sample.  Optional — pre-verify rounds stay valid — but when
    # present every gated column must be there, or the record is as
    # broken as a missing proofs_per_s.
    rec["verify"] = {}
    if raw.get("verify") is not None:
        ver = raw["verify"]
        for key in (
            "verified_per_s_batched", "verified_per_s_host",
            "attest_bytes_per_sample", "independent_bytes_per_sample",
        ):
            if not isinstance(ver, dict) or ver.get(key) is None:
                raise MalformedRound(
                    f"{path}: verify block missing {key!r}"
                )
            rec["verify"][key] = float(ver[key])
    # The fleet block (das_loadgen --urls): the multi-node leg — per-host
    # proofs/sec, the bucket-merged cross-host tail (the same
    # Histogram.merge math GET /fleet serves), end-of-run coverage.
    # Optional — pre-fleet rounds stay valid (das_plan_gaps classifies
    # the first fleet round as a plan gap, never STALE) — but a
    # half-written fleet block exits 2 like any other malformed round.
    rec["fleet"] = None
    if raw.get("fleet") is not None:
        fl = raw["fleet"]
        hosts = fl.get("hosts") if isinstance(fl, dict) else None
        if not isinstance(hosts, list) or len(hosts) < 2:
            raise MalformedRound(
                f"{path}: fleet block needs a 'hosts' list of >= 2 rows"
            )
        for row in hosts:
            for key in ("url", "proofs_per_s", "p99_ms"):
                if not isinstance(row, dict) or row.get(key) is None:
                    raise MalformedRound(
                        f"{path}: fleet host row missing {key!r}: {row!r}"
                    )
        for key in ("cross_host_p50_ms", "cross_host_p99_ms",
                    "coverage_ratio"):
            if fl.get(key) is None:
                raise MalformedRound(
                    f"{path}: fleet block missing {key!r}"
                )
        rec["fleet"] = {
            "hosts": len(hosts),
            # The fleet's aggregate serve rate: hosts ran the identical
            # plan, so the sum is the cluster's measured throughput.
            "proofs_per_s": round(
                sum(float(r["proofs_per_s"]) for r in hosts), 2
            ),
            "cross_host_p50_ms": float(fl["cross_host_p50_ms"]),
            "cross_host_p99_ms": float(fl["cross_host_p99_ms"]),
            "coverage_ratio": float(fl["coverage_ratio"]),
        }
    return rec


def load_das_series(paths: list[str]) -> list[dict]:
    """The proof-serving trajectory; [] when no loadgen round exists yet
    (the series is additive — bench rounds alone stay valid)."""
    return sorted((load_das_round(p) for p in paths), key=lambda r: r["round"])


def _gate_das_points(pts, platforms, key, better, threshold_pct,
                     series: str) -> dict | None:
    """One higher/lower-better gate over a das point list under the
    same-platform rule; None when nothing regressed."""
    if len(pts) < 2:
        return None
    priors = _comparable_priors(pts, platforms)
    if not priors:
        return None
    last_round, last = pts[-1]
    best_prior = max(priors) if better == "higher" else min(priors)
    if best_prior <= 0:
        return None
    worse_pct = (
        (best_prior - last) / best_prior * 100.0
        if better == "higher"
        else (last - best_prior) / best_prior * 100.0
    )
    if worse_pct > threshold_pct:
        return {
            "series": series, "unit": key,
            "round": last_round, "value": last, "best_prior": best_prior,
            "worse_pct": round(worse_pct, 2),
            "allowed_pct": round(threshold_pct, 2),
        }
    return None


def find_das_regressions(das_rounds: list[dict], threshold_pct: float) -> list[dict]:
    """proofs/sec gates like a rate (higher better), proof-p99 like a
    parts time (lower better); same-platform comparability rule as the
    bench series (a CPU loadgen number is not a regression against a
    chip round's).

    Two extra comparability rules for the swarm era:

      * the top-level numbers gate only WITHIN one workload — a swarm
        round's open-loop rate-capped proofs/sec is not a regression
        against a closed-loop round's saturation number (see
        das_plan_gaps: cross-workload absence is a plan gap, not STALE);
      * each SWEEP shard count gates against prior rounds carrying the
        SAME shard count — the scaling curve's rows are their own
        series, and a shard count no prior round measured is a plan
        gap, never a phantom regression.
    """
    platforms = {r["round"]: r.get("platform") for r in das_rounds}
    out = []
    if das_rounds:
        # Top-level comparability key: workload AND the mesh width that
        # produced the headline leg — a 1-shard headline is not a
        # regression against an 8-shard headline any more than a swarm
        # number is against a closed-loop one (the sweep rows below
        # carry the per-shard-count trajectories either way).
        newest_key = (
            das_rounds[-1].get("workload", "closed"),
            das_rounds[-1].get("headline_shards"),
        )
        same = [
            r for r in das_rounds
            if (r.get("workload", "closed"),
                r.get("headline_shards")) == newest_key
        ]
        for key, better in (
            ("proofs_per_s", "higher"), ("proof_p99_ms", "lower")
        ):
            hit = _gate_das_points(
                [(r["round"], r[key]) for r in same], platforms,
                key, better, threshold_pct, f"das.{key}",
            )
            if hit:
                out.append(hit)
        for shards in sorted((das_rounds[-1].get("sweep") or {})):
            comparable = [
                r for r in das_rounds if shards in (r.get("sweep") or {})
            ]
            for key, better in (
                ("proofs_per_s", "higher"), ("proof_p99_ms", "lower")
            ):
                hit = _gate_das_points(
                    [(r["round"], r["sweep"][shards][key])
                     for r in comparable],
                    platforms, key, better, threshold_pct,
                    f"das.sweep{shards}.{key}",
                )
                if hit:
                    out.append(hit)
        # The verify plane (rounds carrying a --attest block): batched
        # verified-samples/sec gates like a rate, attestation bytes-per-
        # sample like a parts time (lower better — the dedup is the
        # point).  Rounds without the block are neither priors nor
        # regressions (plan gap, see das_plan_gaps).
        if das_rounds[-1].get("verify"):
            with_verify = [r for r in das_rounds if r.get("verify")]
            for key, better in (
                ("verified_per_s_batched", "higher"),
                ("attest_bytes_per_sample", "lower"),
            ):
                hit = _gate_das_points(
                    [(r["round"], r["verify"][key]) for r in with_verify],
                    platforms, key, better, threshold_pct,
                    f"das.verify.{key}",
                )
                if hit:
                    out.append(hit)
        # The fleet plane (rounds carrying a --urls block): aggregate
        # cluster proofs/sec gates like a rate, the bucket-merged
        # cross-host p99 like a parts time, and end-of-run coverage
        # like a rate (a coverage collapse means the cluster stopped
        # deciding its squares).  Rounds without the block are neither
        # priors nor regressions (plan gap, see das_plan_gaps); the
        # same-platform rule applies as everywhere else.
        if das_rounds[-1].get("fleet"):
            with_fleet = [r for r in das_rounds if r.get("fleet")]
            for key, better in (
                ("proofs_per_s", "higher"),
                ("cross_host_p99_ms", "lower"),
                ("coverage_ratio", "higher"),
            ):
                hit = _gate_das_points(
                    [(r["round"], r["fleet"][key]) for r in with_fleet],
                    platforms, key, better, threshold_pct,
                    f"das.fleet.{key}",
                )
                if hit:
                    out.append(hit)
    return out


def das_plan_gaps(das_rounds: list[dict]) -> list[str]:
    """Classify what the newest das round does NOT share with its
    priors — workload shapes and sweep shard counts absent from older
    rounds are PLAN GAPS (the plan grew; nothing went stale), mirroring
    the bench series' opt-in/hw-gated classification."""
    if len(das_rounds) < 2:
        return []
    newest = das_rounds[-1]
    priors = das_rounds[:-1]
    gaps = []
    workload = newest.get("workload", "closed")
    if all(r.get("workload", "closed") != workload for r in priors):
        gaps.append(
            f"das workload {workload!r} first measured in "
            f"r{newest['round']:02d} (plan gap, not STALE)"
        )
    elif all(
        (r.get("workload", "closed"), r.get("headline_shards"))
        != (workload, newest.get("headline_shards"))
        for r in priors
    ):
        gaps.append(
            f"das headline shards={newest.get('headline_shards')} first "
            f"measured in r{newest['round']:02d} (plan gap, not STALE)"
        )
    for shards in sorted(newest.get("sweep") or {}):
        if all(shards not in (r.get("sweep") or {}) for r in priors):
            gaps.append(
                f"das sweep shards={shards} first measured in "
                f"r{newest['round']:02d} (plan gap, not STALE)"
            )
    if newest.get("verify") and all(not r.get("verify") for r in priors):
        gaps.append(
            f"das verify plane (--attest) first measured in "
            f"r{newest['round']:02d} (plan gap, not STALE)"
        )
    if newest.get("fleet") and all(not r.get("fleet") for r in priors):
        gaps.append(
            f"das fleet leg (--urls, {newest['fleet']['hosts']} hosts) "
            f"first measured in r{newest['round']:02d} "
            "(plan gap, not STALE)"
        )
    return gaps


# --- adversarial-drill rounds (scripts/chaos_soak.py --adv-out) --------------

def load_adv_round(path: str) -> dict:
    """One ADV_rNN.json: detection-probability table + repair-to-recovery
    + adversary-detected verdicts.  Missing required keys exit 2 like any
    other malformed round."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedRound(f"{path}: not readable JSON: {e}") from e
    for key in ("n", "detection", "repair", "honest_identical",
                "adversaries_detected"):
        if key not in raw:
            raise MalformedRound(f"{path}: missing required key {key!r}")
    return {
        "round": int(raw["n"]),
        "path": os.path.basename(path),
        "platform": raw.get("platform"),
        "k": raw.get("k"),
        "detection": raw["detection"],
        "repair": raw["repair"],
        "honest_identical": bool(raw["honest_identical"]),
        "all_monotone": bool(raw.get("all_monotone", False)),
        "adversaries_detected": dict(raw["adversaries_detected"]),
        # adv-v2: the healing drill's single-node + quorum legs; None on
        # rounds that predate the detect->act loop (additive series).
        "heal": raw.get("heal"),
    }


def load_adv_series(paths: list[str]) -> list[dict]:
    """[] when no adversarial round exists yet (the series is additive)."""
    return sorted((load_adv_round(p) for p in paths), key=lambda r: r["round"])


def find_adv_regressions(adv_rounds: list[dict], threshold_pct: float) -> list[dict]:
    """Invariants gate hard (no prior needed); repair-to-recovery
    latency gates like a parts time against same-platform priors."""
    out = []
    if not adv_rounds:
        return out
    newest = adv_rounds[-1]
    rnd = newest["round"]
    if not newest["honest_identical"]:
        out.append({
            "series": "adv.honest_identical", "unit": "invariant",
            "round": rnd, "value": False, "best_prior": True,
            "worse_pct": 100.0, "allowed_pct": 0.0,
        })
    if not newest["all_monotone"]:
        out.append({
            "series": "adv.detection_monotone", "unit": "invariant",
            "round": rnd, "value": False, "best_prior": True,
            "worse_pct": 100.0, "allowed_pct": 0.0,
        })
    for name, ok in sorted(newest["adversaries_detected"].items()):
        if not ok:
            out.append({
                "series": f"adv.detected.{name}", "unit": "invariant",
                "round": rnd, "value": False, "best_prior": True,
                "worse_pct": 100.0, "allowed_pct": 0.0,
            })
    if not newest["repair"].get("recovered"):
        out.append({
            "series": "adv.repair_recovered", "unit": "invariant",
            "round": rnd, "value": False, "best_prior": True,
            "worse_pct": 100.0, "allowed_pct": 0.0,
        })
    platforms = {r["round"]: r.get("platform") for r in adv_rounds}

    def _gate_lower_better(series: str, pts: list[tuple[int, float]]) -> None:
        if len(pts) < 2 or pts[-1][0] != rnd:
            return
        priors = _comparable_priors(pts, platforms)
        if not priors:
            return
        best_prior = min(priors)
        last = pts[-1][1]
        if best_prior > 0:
            worse_pct = (last - best_prior) / best_prior * 100.0
            if worse_pct > threshold_pct:
                out.append({
                    "series": series, "unit": "ms",
                    "round": rnd, "value": last,
                    "best_prior": best_prior,
                    "worse_pct": round(worse_pct, 2),
                    "allowed_pct": round(threshold_pct, 2),
                })

    _gate_lower_better("adv.repair_total_ms", [
        (r["round"], float(r["repair"]["total_ms"]))
        for r in adv_rounds
        if r["repair"].get("total_ms") is not None
    ])

    # --- the heal series (schema adv-v2; additive — rounds without a
    # heal block predate the detect->act loop and are neither gated nor
    # STALE) ----------------------------------------------------------------
    heal = newest.get("heal")
    if heal is not None:
        single = heal.get("single") or {}
        for inv in ("healed", "served_after_heal", "root_identical",
                    "tampered_never_served"):
            if not single.get(inv):
                out.append({
                    "series": f"heal.single.{inv}", "unit": "invariant",
                    "round": rnd, "value": False, "best_prior": True,
                    "worse_pct": 100.0, "allowed_pct": 0.0,
                })
        quorum = heal.get("quorum")
        if quorum is not None:
            for inv in ("healed", "served_after_heal", "root_identical"):
                if not quorum.get(inv):
                    out.append({
                        "series": f"heal.quorum.{inv}", "unit": "invariant",
                        "round": rnd, "value": False, "best_prior": True,
                        "worse_pct": 100.0, "allowed_pct": 0.0,
                    })
        _gate_lower_better("heal.single.total_ms", [
            (r["round"], float(r["heal"]["single"]["heal_total_ms"]))
            for r in adv_rounds
            if r.get("heal")
            and (r["heal"].get("single") or {}).get("heal_total_ms")
            is not None
        ])
        _gate_lower_better("heal.quorum.total_ms", [
            (r["round"], float(r["heal"]["quorum"]["total_ms"]))
            for r in adv_rounds
            if r.get("heal")
            and (r["heal"].get("quorum") or {}).get("total_ms") is not None
        ])
    return out


# --- QoS enforcement rounds (scripts/das_loadgen.py --qos-out) ---------------

def load_qos_round(path: str) -> dict:
    """One QOS_rNN.json (schema qos-v1): the swarm harness's whale +
    small-tenants + spammer run under a $CELESTIA_QOS policy — a
    `baseline` leg (no spammer) and a `spam` leg (spammer at a multiple
    of its proof-rate limit) over the SAME open-loop plan, each with
    per-tenant served/throttled/p99/slo_burn columns.  Malformed files
    exit 2 like any other round — a half-written enforcement record must
    not gate on garbage."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedRound(f"{path}: not readable JSON: {e}") from e
    for key in ("n", "schema", "legs", "spam_tenant"):
        if key not in raw:
            raise MalformedRound(f"{path}: missing required key {key!r}")
    legs = raw["legs"]
    for leg in ("baseline", "spam"):
        if not isinstance(legs.get(leg), dict):
            raise MalformedRound(f"{path}: missing leg {leg!r}")
        tenants = legs[leg].get("tenants")
        if not isinstance(tenants, dict) or not tenants:
            raise MalformedRound(f"{path}: leg {leg!r} has no tenants")
        for tenant, cols in tenants.items():
            for col in ("served", "throttled", "slo_burn"):
                if not isinstance(cols, dict) or cols.get(col) is None:
                    raise MalformedRound(
                        f"{path}: leg {leg!r} tenant {tenant!r} missing "
                        f"{col!r}"
                    )
    if raw["spam_tenant"] not in legs["spam"]["tenants"]:
        raise MalformedRound(
            f"{path}: spam_tenant {raw['spam_tenant']!r} absent from the "
            "spam leg's tenant columns"
        )
    return {
        "round": int(raw["n"]),
        "path": os.path.basename(path),
        "platform": raw.get("platform"),
        "k": raw.get("k"),
        "spam_tenant": str(raw["spam_tenant"]),
        "legs": legs,
    }


def load_qos_series(paths: list[str]) -> list[dict]:
    """[] when no QoS round exists yet (the series is additive)."""
    return sorted((load_qos_round(p) for p in paths), key=lambda r: r["round"])


def find_qos_regressions(qos_rounds: list[dict],
                         threshold_pct: float) -> list[dict]:
    """QoS rounds gate on INVARIANTS of the newest round (no priors
    needed — the enforcement story must hold per round):

      * the spammer was actually throttled (an enforcement record where
        nothing got enforced recorded nothing);
      * every HONEST tenant's SLO burn in the spam leg is no worse than
        its baseline-leg burn (small absolute slack for quantization:
        one violation in a small sample moves burn in steps);
      * every honest tenant's p99 in the spam leg is no worse than
        baseline + the gate threshold (+ a 5 ms absolute floor for
        clock noise on fast samples).
    """
    out = []
    if not qos_rounds:
        return out
    newest = qos_rounds[-1]
    rnd = newest["round"]
    spam_cols = newest["legs"]["spam"]["tenants"][newest["spam_tenant"]]
    if not spam_cols.get("throttled"):
        out.append({
            "series": "qos.spammer_throttled", "unit": "invariant",
            "round": rnd, "value": 0, "best_prior": ">0",
            "worse_pct": 100.0, "allowed_pct": 0.0,
        })
    base = newest["legs"]["baseline"]["tenants"]
    spam = newest["legs"]["spam"]["tenants"]
    for tenant in sorted(set(base) & set(spam)):
        if tenant == newest["spam_tenant"]:
            continue  # the spammer's own numbers are the enforcement
        b, s = base[tenant], spam[tenant]
        burn_ceiling = max(float(b["slo_burn"]) * (1 + threshold_pct / 100),
                           float(b["slo_burn"]) + 0.5)
        if float(s["slo_burn"]) > burn_ceiling:
            out.append({
                "series": f"qos.{tenant}.slo_burn", "unit": "burn",
                "round": rnd, "value": float(s["slo_burn"]),
                "best_prior": float(b["slo_burn"]),
                "worse_pct": round(
                    (float(s["slo_burn"]) - float(b["slo_burn"]))
                    / max(float(b["slo_burn"]), 1e-9) * 100.0, 2),
                "allowed_pct": round(threshold_pct, 2),
            })
        bp, sp = b.get("p99_ms"), s.get("p99_ms")
        if bp is not None and sp is not None:
            # Per-tenant p99 over ~10^2 samples is the single worst
            # observation; the small-sample allowance (2x + 20 ms
            # scheduler-noise floor) keeps the gate about enforcement
            # failures, not about which sample drew the worst timeslice.
            p99_ceiling = max(
                float(bp) * (1 + threshold_pct / 100) + 5.0,
                float(bp) * 2.0 + 20.0,
            )
            if float(sp) > p99_ceiling:
                out.append({
                    "series": f"qos.{tenant}.p99_ms", "unit": "ms",
                    "round": rnd, "value": float(sp),
                    "best_prior": float(bp),
                    "worse_pct": round(
                        (float(sp) - float(bp)) / max(float(bp), 1e-9)
                        * 100.0, 2),
                    "allowed_pct": round(threshold_pct, 2),
                })
    return out


# --- timeline rounds (scripts/block_anatomy.py) ------------------------------

def load_tl_round(path: str) -> dict:
    """One TL_rNN.json (schema tl-v1): the height-anatomy phase budget —
    per-phase / per-gap mean, p95 and share-of-height-time over an
    N-block streamed run, plus critical-phase counts.  The share columns
    are the gated series: a phase quietly growing its slice of height
    time is a regression even when absolute latency stays flat."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedRound(f"{path}: not readable JSON: {e}") from e
    for key in ("schema", "n", "phases"):
        if key not in raw:
            raise MalformedRound(f"{path}: missing required key {key!r}")
    if raw["schema"] != "tl-v1":
        raise MalformedRound(f"{path}: unknown schema {raw['schema']!r}")
    phases = raw["phases"]
    if not isinstance(phases, dict) or not phases:
        raise MalformedRound(f"{path}: 'phases' must be a non-empty dict")
    for name, d in phases.items():
        if not isinstance(d, dict) or "share" not in d:
            raise MalformedRound(
                f"{path}: phase {name!r} carries no 'share' column"
            )
    return {
        "round": int(raw["n"]),
        "path": os.path.basename(path),
        "platform": raw.get("platform"),
        "k": raw.get("k"),
        "blocks": raw.get("blocks"),
        "phases": phases,
        "gaps": raw.get("gaps") or {},
        "critical_counts": raw.get("critical_counts") or {},
        "total_ms": raw.get("total_ms"),
    }


def load_tl_series(paths: list[str]) -> list[dict]:
    """Timeline rounds sorted by round number; [] when no timeline round
    exists yet (the series is additive)."""
    return sorted((load_tl_round(p) for p in paths),
                  key=lambda r: r["round"])


def find_tl_regressions(tl_rounds: list[dict],
                        threshold_pct: float) -> list[dict]:
    """Gate the newest timeline round's per-phase (and per-gap) share of
    height time against the best same-platform prior.  Shares are
    dimensionless fractions of the run's accounted time, so the gate is
    platform-comparable in a way raw milliseconds are not — but a CPU
    round still only gates against CPU priors, because the critical
    phase itself changes across backends (compile-bound vs drain-bound).
    The 0.05 absolute slack floor keeps sub-5%-share phases from tripping
    the gate on scheduler noise."""
    out: list[dict] = []
    if len(tl_rounds) < 2:
        return out
    newest = tl_rounds[-1]
    priors = [
        r for r in tl_rounds[:-1]
        if r.get("platform") == newest.get("platform")
    ]
    if not priors:
        return out
    rnd = newest["round"]
    for section, label in (("phases", "share"), ("gaps", "gap_share")):
        for name, d in sorted((newest.get(section) or {}).items()):
            value = float(d["share"])
            prior_shares = [
                float(p[section][name]["share"])
                for p in priors
                if name in (p.get(section) or {})
            ]
            if not prior_shares:
                continue  # a NEW phase is growth, not regression
            best = min(prior_shares)
            allowed = best + max(best * threshold_pct / 100.0, 0.05)
            if value > allowed:
                out.append({
                    "series": f"tl.{name}.{label}", "unit": "share",
                    "round": rnd, "value": value, "best_prior": best,
                    "worse_pct": round(
                        (value - best) / max(best, 1e-9) * 100.0, 2),
                    "allowed_pct": round(
                        (allowed - best) / max(best, 1e-9) * 100.0, 2),
                })
    return out


# --- chip-sweep rounds (scripts/chip_sweep.py) -------------------------------

def load_sweep_round(path: str) -> dict:
    """One SWEEP_rNN.json (schema sweep-v1): the push-button standing-
    debt sitting — per-leg status + timing, each leg carrying the
    child's /device snapshot (compile/dispatch ledger + ownership).
    A half-written journal is resumable by chip_sweep --resume, but a
    file this reader cannot parse at all exits 2 like any round."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        raise MalformedRound(f"{path}: not readable JSON: {e}") from e
    for key in ("schema", "round", "plan", "legs"):
        if key not in raw:
            raise MalformedRound(f"{path}: missing required key {key!r}")
    if raw["schema"] != "sweep-v1":
        raise MalformedRound(f"{path}: unknown schema {raw['schema']!r}")
    legs = {
        name: {
            "status": rec.get("status", "missing"),
            "seconds": float(rec.get("seconds", 0.0)),
            "device_families": sorted({
                row.get("family", "?")
                for row in (rec.get("device") or {}).get("programs", [])
            }),
        }
        for name, rec in raw["legs"].items()
    }
    return {
        "path": path,
        "round": int(raw["round"]),
        "platform": raw.get("platform", "unprobed"),
        "dryrun": bool(raw.get("dryrun", False)),
        "plan": list(raw["plan"]),
        "legs": legs,
    }


def load_sweep_series(paths: list[str]) -> list[dict]:
    """[] until the first sitting lands (the series is additive)."""
    return sorted(
        (load_sweep_round(p) for p in paths), key=lambda r: r["round"]
    )


def sweep_plan_gaps(sweep_rounds: list[dict]) -> list[str]:
    """What the newest sitting did NOT cover: planned legs that never
    ran ok are COVERAGE GAPS (the debt is still standing for them), and
    legs first appearing in this round are plan gaps like the das
    series' — the plan grew, nothing went stale."""
    if not sweep_rounds:
        return []
    newest = sweep_rounds[-1]
    gaps = []
    if newest["dryrun"]:
        gaps.append(
            f"sweep r{newest['round']:02d} is a dryrun plan — no leg has "
            "paid the standing debt yet"
        )
        return gaps
    for name in newest["plan"]:
        status = newest["legs"].get(name, {}).get("status", "missing")
        if status != "ok":
            gaps.append(
                f"sweep leg {name!r} is {status} in r{newest['round']:02d}"
                " — its standing-debt item is still open"
            )
    priors = [r for r in sweep_rounds[:-1] if not r["dryrun"]]
    if priors:
        for name in newest["plan"]:
            if all(name not in r["plan"] for r in priors):
                gaps.append(
                    f"sweep leg {name!r} first planned in "
                    f"r{newest['round']:02d} (plan gap, not STALE)"
                )
    return gaps


# --- trend assembly ---------------------------------------------------------

def mode_series(rounds: list[dict]) -> dict[tuple[str, int], list[tuple[int, float]]]:
    """{(mode, k): [(round, best mb/s)]} — duplicates within a round (the
    compute@512 stability rerun) collapse to their max."""
    series: dict[tuple[str, int], list[tuple[int, float]]] = {}
    for r in rounds:
        for key, vals in sorted(r["modes"].items()):
            series.setdefault(key, []).append((r["round"], max(vals)))
    return series


def parts_series(rounds: list[dict]) -> dict[str, list[tuple[int, float]]]:
    """{part name: [(round, seconds)]} (lower is better)."""
    series: dict[str, list[tuple[int, float]]] = {}
    for r in rounds:
        for name, secs in sorted((r["parts"] or {}).items()):
            series.setdefault(name, []).append((r["round"], secs))
    return series


def _stability(rounds: list[dict], rnd: int) -> float:
    for r in rounds:
        if r["round"] == rnd:
            return float(r["stability_pct"] or 0.0)
    return 0.0


def _comparable_priors(
    pts: list[tuple[int, float]], platforms: dict[int, str | None]
) -> list[float]:
    """Prior datapoints the newest one may fairly be compared against.

    A CPU-fallback round's numbers are not a regression against a chip
    round's (a fused_epi measured at CPU speed after a TPU round would
    read as a 100x collapse): a prior whose platform is KNOWN and
    DIFFERENT from the newest round's known platform is excluded.
    Unknown platforms (salvaged tails carry none) stay comparable on
    BOTH sides — dropping them would silently weaken the gate for
    exactly the rounds that already lost their results array, and the
    legacy all-priors behavior is what the checked-in r01..r05 series
    were gated under."""
    last_round = pts[-1][0]
    plat = platforms.get(last_round)
    priors = pts[:-1]
    if plat is not None:
        priors = [
            p for p in priors
            if platforms.get(p[0]) in (None, plat)
        ]
    return [v for _, v in priors]


def find_regressions(
    rounds: list[dict],
    threshold_pct: float,
    gate_modes: tuple[str, ...] = GATED_MODES,
    gate_all: bool = False,
) -> list[dict]:
    """Newest datapoint vs best earlier SAME-PLATFORM datapoint per gated
    series (see _comparable_priors); the effective threshold widens by
    the newest round's stability_pct."""
    platforms = {r["round"]: r.get("platform") for r in rounds}
    out = []
    for (mode, k), pts in sorted(mode_series(rounds).items()):
        if not gate_all and not (mode in gate_modes
                                 or SHARDED_COMPUTE_RE.match(mode)):
            continue
        if len(pts) < 2:
            continue
        priors = _comparable_priors(pts, platforms)
        if not priors:
            continue  # nothing measured on this platform before
        last_round, last = pts[-1]
        best_prior = max(priors)
        if best_prior <= 0:
            continue
        allowed = threshold_pct + _stability(rounds, last_round)
        worse_pct = (best_prior - last) / best_prior * 100.0
        if worse_pct > allowed:
            out.append({
                "series": f"{mode}@{k}", "unit": "mb_per_s",
                "round": last_round, "value": last, "best_prior": best_prior,
                "worse_pct": round(worse_pct, 2), "allowed_pct": round(allowed, 2),
            })
    for name, pts in sorted(parts_series(rounds).items()):
        if len(pts) < 2:
            continue
        priors = _comparable_priors(pts, platforms)
        if not priors:
            continue
        last_round, last = pts[-1]
        best_prior = min(priors)
        if best_prior <= 0:
            continue
        allowed = threshold_pct + _stability(rounds, last_round)
        worse_pct = (last - best_prior) / best_prior * 100.0
        if worse_pct > allowed:
            out.append({
                "series": f"parts.{name}", "unit": "seconds",
                "round": last_round, "value": last, "best_prior": best_prior,
                "worse_pct": round(worse_pct, 2), "allowed_pct": round(allowed, 2),
            })
    return out


def seat_changes(rounds: list[dict]) -> list[dict]:
    """Tuned-seat flips between consecutive rounds that recorded a tuner
    verdict.  A flip (e.g. rs rs_dense -> rs_xor) is NEWS, not a fault:
    the >3% hysteresis already demanded a real win, so the trend tool
    names it a seat change — otherwise a newly seated candidate reads as
    a series appearing from nowhere while the dethroned incumbent's
    series looks abandoned."""
    seated = [r for r in rounds if r["tuned"]]
    out = []
    for prev, cur in zip(seated, seated[1:]):
        for key in sorted(set(prev["tuned"]) | set(cur["tuned"])):
            a, b = prev["tuned"].get(key), cur["tuned"].get(key)
            if a is not None and b is not None and a != b:
                out.append({
                    "seat": key, "from": a, "to": b,
                    "from_round": prev["round"], "round": cur["round"],
                })
    return out


def seat_overrides(rounds: list[dict]) -> list[dict]:
    """Seats where the newest round's APPLIED config diverges from its
    tuner pick — an operator-set env knob won over the autotuner (the
    bench honors operator knobs by design).  Worth a line: later rows in
    that round did NOT run the tuner's winner, so its series reflect the
    operator's choice, not the measured-best."""
    for r in reversed(rounds):
        if r["tuned"] and r["applied"]:
            return [
                {"seat": k, "tuned": r["tuned"][k],
                 "applied": r["applied"][k], "round": r["round"]}
                for k in sorted(set(r["tuned"]) & set(r["applied"]))
                if r["tuned"][k] != r["applied"][k]
            ]
    return []


def stale_gated_series(
    rounds: list[dict],
    gate_modes: tuple[str, ...] = GATED_MODES,
    gate_all: bool = False,
) -> list[dict]:
    """Gated series whose newest datapoint predates the newest round that
    recorded ANY data — the gate is comparing stale numbers for them (the
    checked-in compute rows stop at r03 because the r04/r05 tails lost
    the results array).  Reported loudly, not failed: a truncated tail
    must not mask the rounds that DID measure.

    Hardware-gated parts candidates (HW_GATED_PARTS) absent from a
    newest round that did not run on the chip get `hw_gated: True`
    instead: a CPU-fallback round CANNOT measure them, so their absence
    is a platform gap, not a stale series the gate should shout about.

    Giant-k mode rows (k > DEFAULT_PLAN_MAX_K — compute@1024 and
    friends, measured only under an explicit BENCH_K) get `opt_in: True`
    the same way: the default plan never produces them, so their absence
    from a default round is a plan gap.  When two giant-k rounds DO
    exist, find_regressions gates them like any other series under the
    same-platform rule — the downgrade is only about absence.
    """
    newest = max(
        (r["round"] for r in rounds if r["modes"] or r["parts"]), default=None
    )
    if newest is None:
        return []
    newest_rec = next(r for r in rounds if r["round"] == newest)
    # The hw-gated downgrade ("this candidate CANNOT be measured off the
    # chip") only applies when the newest round's platform is KNOWN and
    # non-TPU.  Unknown (a salvaged tail lost the tag) stays on the STALE
    # path: claiming "no chip" for a round that may well have been the
    # chip would hide that the gate is comparing stale chip numbers.
    plat = newest_rec.get("platform")
    newest_known_off_chip = plat is not None and plat != "tpu"
    out = []
    for (mode, k), pts in sorted(mode_series(rounds).items()):
        sharded = bool(SHARDED_COMPUTE_RE.match(mode))
        if not gate_all and not (mode in gate_modes or sharded):
            continue
        if pts[-1][0] < newest:
            entry = {"series": f"{mode}@{k}", "last_round": pts[-1][0],
                     "newest_round": newest}
            if (k > DEFAULT_PLAN_MAX_K or sharded
                    or mode in MEMPOOL_MODES):
                # Opt-in series (explicit BENCH_K / BENCH_MODE=
                # compute_sharded / BENCH_MODE=mempool): absence from a
                # default-plan round is a plan gap, never STALE.
                entry["opt_in"] = True
            out.append(entry)
    for name, pts in sorted(parts_series(rounds).items()):
        if pts[-1][0] < newest:
            entry = {"series": f"parts.{name}", "last_round": pts[-1][0],
                     "newest_round": newest}
            if name in HW_GATED_PARTS and newest_known_off_chip:
                entry["hw_gated"] = True
            out.append(entry)
    return out


def render_table(rounds: list[dict]) -> str:
    """The human trend table: one column per round, one row per series."""
    rnds = [r["round"] for r in rounds]
    lines = []
    header = ["series".ljust(16)] + [f"r{n:02d}".rjust(9) for n in rnds]
    lines.append("  ".join(header))
    modes = mode_series(rounds)

    def fmt_row(label, pts, unit):
        by_round = dict(pts)
        cells = [
            (f"{by_round[n]:9.2f}" if n in by_round else "        -")
            for n in rnds
        ]
        return "  ".join([label.ljust(16)] + cells) + f"  {unit}"

    for mode in GATED_MODES + LINK_BOUND_MODES:
        for (m, k), pts in sorted(modes.items()):
            if m == mode:
                gated = "" if mode in GATED_MODES else " (not gated)"
                lines.append(fmt_row(f"{m}@{k}", pts, f"MB/s{gated}"))
    for (m, k), pts in sorted(modes.items()):
        if m not in GATED_MODES + LINK_BOUND_MODES:
            gated = "" if is_gated_mode(m) else " (not gated)"
            lines.append(fmt_row(f"{m}@{k}", pts, f"MB/s{gated}"))
    for name, pts in sorted(parts_series(rounds).items()):
        lines.append(fmt_row(f"parts.{name}", pts, "s"))
    notes = []
    for r in rounds:
        tags = []
        if not r["ok"]:
            tags.append("FAILED (rc!=0)")
        if r["partial"]:
            tags.append("tail truncated; salvaged")
        if r["errors"]:
            tags.append(f"errors: {'; '.join(map(str, r['errors']))}")
        if r["stability_pct"] is not None:
            tags.append(f"stability ±{r['stability_pct']}%")
        if tags:
            notes.append(f"  r{r['round']:02d}: {', '.join(tags)}")
    if notes:
        lines.append("round notes:")
        lines.extend(notes)
    return "\n".join(lines)


def write_metrics_out(out_dir: str, rounds: list[dict],
                      regressions: list[dict],
                      das_rounds: list[dict] | None = None) -> None:
    """bench_trend.prom + bench_trend.jsonl, the bench.py --metrics-out
    shapes (private registry/tracer: this run's view only)."""
    if REPO_ROOT not in sys.path:  # `python scripts/bench_trend.py` puts
        sys.path.insert(0, REPO_ROOT)  # scripts/, not the repo, on the path
    from celestia_app_tpu.trace.metrics import Registry
    from celestia_app_tpu.trace.tracer import Tracer

    os.makedirs(out_dir, exist_ok=True)
    reg = Registry()
    tracer = Tracer(env_gated=False)
    rate = reg.gauge("celestia_bench_trend_mb_per_s",
                     "per-round bench rate by series")
    secs = reg.gauge("celestia_bench_trend_part_seconds",
                     "per-round parts decomposition seconds")
    reg.counter("celestia_bench_trend_regressions_total",
                "series flagged by the trend gate").inc(len(regressions))
    for (mode, k), pts in sorted(mode_series(rounds).items()):
        for rnd, v in pts:
            rate.set(v, mode=mode, k=str(k), round=f"r{rnd:02d}")
            tracer.write("bench_trend", round=rnd, mode=mode, k=k,
                         mb_per_s=v)
    for name, pts in sorted(parts_series(rounds).items()):
        for rnd, v in pts:
            secs.set(v, part=name, round=f"r{rnd:02d}")
            tracer.write("bench_trend", round=rnd, part=name, seconds=v)
    if das_rounds:
        das = reg.gauge("celestia_bench_trend_das",
                        "per-round DAS loadgen series (proofs/sec, p99 ms; "
                        "swarm sweep rows per shard count)")
        for r in das_rounds:
            das.set(r["proofs_per_s"], series="proofs_per_s",
                    round=f"r{r['round']:02d}")
            das.set(r["proof_p99_ms"], series="proof_p99_ms",
                    round=f"r{r['round']:02d}")
            tracer.write("bench_trend", round=r["round"],
                         proofs_per_s=r["proofs_per_s"],
                         proof_p99_ms=r["proof_p99_ms"])
            for shards, row in sorted((r.get("sweep") or {}).items()):
                das.set(row["proofs_per_s"], series="proofs_per_s",
                        shards=str(shards), round=f"r{r['round']:02d}")
                tracer.write("bench_trend", round=r["round"],
                             shards=shards,
                             proofs_per_s=row["proofs_per_s"],
                             proof_p99_ms=row["proof_p99_ms"])
            for key, value in sorted((r.get("verify") or {}).items()):
                das.set(value, series=f"verify.{key}",
                        round=f"r{r['round']:02d}")
            for key, value in sorted((r.get("fleet") or {}).items()):
                das.set(float(value), series=f"fleet.{key}",
                        round=f"r{r['round']:02d}")
    for reg_row in regressions:
        tracer.write("bench_trend", regression=True, **reg_row)
    with open(os.path.join(out_dir, "bench_trend.prom"), "w") as f:
        f.write(reg.render())
    with open(os.path.join(out_dir, "bench_trend.jsonl"), "w") as f:
        jsonl = tracer.export_jsonl("bench_trend")
        f.write(jsonl + "\n" if jsonl else "")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="bench round JSONs (default: BENCH_r*.json at the repo root)")
    ap.add_argument("--dir", default=REPO_ROOT,
                    help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="regression threshold in percent (widened by the "
                         "round's stability_pct)")
    ap.add_argument("--all-series", action="store_true",
                    help="gate the link-bound modes too")
    ap.add_argument("--check", action="store_true",
                    help="tier-1 self-test: parse + gate the checked-in "
                         "rounds, no device needed")
    ap.add_argument("--metrics-out", metavar="DIR",
                    help="write bench_trend.prom + bench_trend.jsonl here")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable summary instead of the table")
    args = ap.parse_args(argv)

    paths = args.files or sorted(glob.glob(os.path.join(args.dir, "BENCH_r*.json")))
    das_paths = (
        [] if args.files
        else sorted(glob.glob(os.path.join(args.dir, "DAS_r*.json")))
    )
    adv_paths = (
        [] if args.files
        else sorted(glob.glob(os.path.join(args.dir, "ADV_r*.json")))
    )
    qos_paths = (
        [] if args.files
        else sorted(glob.glob(os.path.join(args.dir, "QOS_r*.json")))
    )
    sweep_paths = (
        [] if args.files
        else sorted(glob.glob(os.path.join(args.dir, "SWEEP_r*.json")))
    )
    tl_paths = (
        [] if args.files
        else sorted(glob.glob(os.path.join(args.dir, "TL_r*.json")))
    )
    if not (paths or das_paths or adv_paths or qos_paths or sweep_paths
            or tl_paths):
        print(f"bench_trend: MALFORMED: no round files under {args.dir}",
              file=sys.stderr)
        return 2
    try:
        rounds = load_series(paths)
        das_rounds = load_das_series(das_paths)
        adv_rounds = load_adv_series(adv_paths)
        qos_rounds = load_qos_series(qos_paths)
        sweep_rounds = load_sweep_series(sweep_paths)
        tl_rounds = load_tl_series(tl_paths)
    except MalformedRound as e:
        print(f"bench_trend: MALFORMED: {e}", file=sys.stderr)
        return 2
    if args.check:
        # Self-test: every round that EXITED cleanly must have contributed
        # data — a bench whose summary line stopped parsing entirely is a
        # tooling regression, not a quiet gap in the table.
        for r in rounds:
            if r["ok"] and not r["modes"] and not r["parts"]:
                print(f"bench_trend: MALFORMED: {r['path']} exited 0 but no "
                      "summary data could be recovered from its tail",
                      file=sys.stderr)
                return 2
    regressions = find_regressions(
        rounds, args.threshold, gate_all=args.all_series
    )
    regressions += find_das_regressions(das_rounds, args.threshold)
    regressions += find_adv_regressions(adv_rounds, args.threshold)
    regressions += find_qos_regressions(qos_rounds, args.threshold)
    regressions += find_tl_regressions(tl_rounds, args.threshold)
    das_gaps = das_plan_gaps(das_rounds)
    sweep_gaps = sweep_plan_gaps(sweep_rounds)
    stale = stale_gated_series(rounds, gate_all=args.all_series)
    seats = seat_changes(rounds)
    overrides = seat_overrides(rounds)
    if args.metrics_out:
        write_metrics_out(args.metrics_out, rounds, regressions, das_rounds)
    if args.json:
        print(json.dumps({
            "rounds": [r["round"] for r in rounds],
            "das_rounds": [r["round"] for r in das_rounds],
            "adv_rounds": [r["round"] for r in adv_rounds],
            "qos_rounds": [r["round"] for r in qos_rounds],
            "sweep_rounds": [r["round"] for r in sweep_rounds],
            "tl_rounds": [r["round"] for r in tl_rounds],
            "sweep_plan_gaps": sweep_gaps,
            "regressions": regressions,
            "stale": [s for s in stale
                      if not s.get("hw_gated") and not s.get("opt_in")],
            "hw_gated": [s for s in stale if s.get("hw_gated")],
            "opt_in": [s for s in stale if s.get("opt_in")],
            "seat_changes": seats,
            "seat_overrides": overrides,
            "das_plan_gaps": das_gaps,
            "threshold_pct": args.threshold,
        }))
    else:
        print(render_table(rounds))
        for r in das_rounds:
            print(f"  das r{r['round']:02d}: "
                  f"{r['proofs_per_s']:9.2f} proofs/s  "
                  f"p99 {r['proof_p99_ms']:8.3f} ms"
                  + (f"  [{r.get('workload', 'closed')}]")
                  + (f"  [{r['platform']}]" if r.get("platform") else ""))
            for shards, row in sorted((r.get("sweep") or {}).items()):
                print(f"    shards={shards}: "
                      f"{row['proofs_per_s']:9.2f} proofs/s  "
                      f"p99 {row['proof_p99_ms']:8.3f} ms")
            if r.get("tenants"):
                worst = max(
                    r["tenants"].items(), key=lambda kv: kv[1]["slo_burn"]
                )
                print(f"    tenants: {len(r['tenants'])}, worst burn "
                      f"{worst[0]}={worst[1]['slo_burn']} "
                      f"(p99 {worst[1]['p99_ms']} ms)")
            if r.get("fleet"):
                fl = r["fleet"]
                print(f"    fleet: {fl['hosts']} hosts "
                      f"{fl['proofs_per_s']:9.2f} proofs/s  "
                      f"cross-host p99 {fl['cross_host_p99_ms']:8.3f} ms  "
                      f"coverage {fl['coverage_ratio']:.4f}")
        for gap in das_gaps:
            print(f"  NOTE: {gap}")
        for r in sweep_rounds:
            ok = sum(1 for leg in r["legs"].values()
                     if leg["status"] == "ok")
            print(f"  sweep r{r['round']:02d}: {ok}/{len(r['plan'])} legs ok"
                  + ("  [dryrun]" if r["dryrun"] else "")
                  + (f"  [{r['platform']}]" if r.get("platform") else ""))
        for gap in sweep_gaps:
            print(f"  NOTE: {gap}")
        for r in qos_rounds:
            spam = r["legs"]["spam"]["tenants"][r["spam_tenant"]]
            honest = [
                t for t in r["legs"]["spam"]["tenants"]
                if t != r["spam_tenant"]
            ]
            worst = max(
                (float(r["legs"]["spam"]["tenants"][t]["slo_burn"])
                 for t in honest),
                default=0.0,
            )
            print(f"  qos r{r['round']:02d}: spammer {r['spam_tenant']} "
                  f"throttled={spam.get('throttled')} "
                  f"served={spam.get('served')}; honest tenants "
                  f"{len(honest)}, worst spam-leg burn {worst}"
                  + (f"  [{r['platform']}]" if r.get("platform") else ""))
        for r in tl_rounds:
            worst = max(
                r["phases"].items(), key=lambda kv: kv[1]["share"],
                default=None,
            )
            crit = r.get("critical_counts") or {}
            crit_s = ", ".join(
                f"{name}x{n}" for name, n in
                sorted(crit.items(), key=lambda kv: -kv[1])
            ) or "-"
            print(f"  tl r{r['round']:02d}: {r.get('blocks', '?')} blocks "
                  f"k={r.get('k', '?')}; top phase "
                  f"{worst[0]}={worst[1]['share'] * 100:.1f}% "
                  f"(mean {worst[1]['mean_ms']} ms); critical {crit_s}"
                  + (f"  [{r['platform']}]" if r.get("platform") else ""))
        for r in adv_rounds:
            rep = r["repair"]
            print(f"  adv r{r['round']:02d}: monotone={r['all_monotone']} "
                  f"honest={r['honest_identical']} "
                  f"detected={r['adversaries_detected']} "
                  f"repair {rep.get('total_ms')} ms "
                  f"(recovered={rep.get('recovered')})"
                  + (f"  [{r['platform']}]" if r.get("platform") else ""))
            heal = r.get("heal")
            if heal:
                single = heal.get("single") or {}
                quorum = heal.get("quorum") or {}
                print(f"    heal: single detect {single.get('detect_ms')} ms"
                      f" + heal {single.get('heal_total_ms')} ms -> restored"
                      f" {single.get('restored_ms')} ms "
                      f"(healed={single.get('healed')}, served="
                      f"{single.get('served_after_heal')})"
                      + (f"; quorum {quorum.get('nodes')} nodes "
                         f"{quorum.get('total_ms')} ms "
                         f"(healed={quorum.get('healed')})"
                         if quorum else ""))
        for c in seats:
            print(f"  SEAT CHANGE: {c['seat']} {c['from']} -> {c['to']} "
                  f"(r{c['from_round']:02d} -> r{c['round']:02d}; the >3% "
                  "hysteresis demanded a real win, so series moving between "
                  "these candidates is expected, not a regression)")
        for o in overrides:
            print(f"  OPERATOR OVERRIDE: {o['seat']} ran {o['applied']} in "
                  f"r{o['round']:02d} though the tuner picked {o['tuned']} — "
                  "that round's later rows reflect the operator's knob")
        for s in stale:
            if s.get("hw_gated"):
                print(f"  hw-gated: {s['series']} not measurable in "
                      f"r{s['newest_round']:02d} (no chip; last chip value "
                      f"r{s['last_round']:02d}) — platform gap, not stale")
            elif s.get("opt_in"):
                print(f"  opt-in: {s['series']} is a giant-k row the "
                      f"default plan never measures (last BENCH_K round "
                      f"r{s['last_round']:02d}) — plan gap, not stale; "
                      "same-platform gating applies when it is measured")
            else:
                print(f"  STALE: gated series {s['series']} last measured in "
                      f"r{s['last_round']:02d} (newest data is "
                      f"r{s['newest_round']:02d}) — the gate compares old "
                      "numbers")
        if regressions:
            print("regressions:")
            for r in regressions:
                print(f"  {r['series']}: r{r['round']:02d} {r['value']} vs "
                      f"best prior {r['best_prior']} ({r['unit']}): worse by "
                      f"{r['worse_pct']}% > allowed {r['allowed_pct']}%")
        else:
            gate = "all series" if args.all_series else "compute + parts"
            print(f"trend gate OK ({gate}, threshold {args.threshold}%"
                  f" + per-round stability)")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
