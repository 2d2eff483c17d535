#!/usr/bin/env python
"""One-sitting chip sweep: the ROADMAP standing-debt list as a button.

Every perf claim since r03/r04 is a 1-core CPU-fallback number, and the
debt list has grown with the machinery.  This orchestrator runs the
WHOLE list in one sitting on whatever chip is in front of it:

    parts            bench.py parts/autotuner (do rs_xor / fused_epi
                     take seats on real hardware?)
    stream           BENCH_MODE=stream — emits the b{1,2,4} vmapped
                     batching rows in one leg
    repair           BENCH_MODE=repair (past 2.38x?)
    compute_sharded  BENCH_MODE=compute_sharded at k in {1024, 2048,
                     4096} (XOR all-reduce on real ICI)
    panel            the panel-streamed giant squares at the same ks
    das_shard_sweep  das_loadgen --shard-sweep (does the r02 CPU
                     inversion flip?)
    mempool          BENCH_MODE=mempool on a many-core host
    withhold_heal    das_loadgen --withhold-frac ... --heal (the
                     adversarial drills' repair legs)
    hbm_k512         the k=512 HBM high-water recipe (device allocator
                     gauge replaces the RSS proxy)

Robustness is the bench.py contract, applied per leg:

  * the parent NEVER imports jax — a backend preflight probe runs in a
    subprocess under a hard timeout;
  * every leg is its own subprocess with its own timeout, so one wedged
    program costs one leg, not the sitting;
  * the journal (SWEEP_rNN.json at the repo root) is rewritten
    atomically after EVERY leg — a mid-sweep crash leaves a resumable
    record, and `--resume` skips legs already marked ok;
  * each leg runs with $CELESTIA_DEVICE_SNAPSHOT pointing at a per-leg
    file, so the child's atexit /device dump (compile/dispatch ledger +
    memory ownership, trace/device_ledger.py) lands in the journal next
    to that leg's numbers — the sweep records not just how fast, but
    what was resident and who owned the bytes.

`--dryrun` resolves every leg to its exact argv + env overlay and
journals the plan without spawning anything (no jax anywhere): the
tier-1 CPU smoke test calls main(["--dryrun", ...]) in-process.

scripts/bench_trend.py learns the round shape (load_sweep_round /
sweep_plan_gaps) so the sweep's coverage is gated like every other
series.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import signal
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SWEEP_SCHEMA = "sweep-v1"

# Giant-square sizes for the sharded/panel legs (ROADMAP: "k in
# {1024, 2048, 4096}").  CPU dryruns keep the list; real runs may trim
# it with --giant-ks when the sitting's budget demands.
GIANT_KS = (1024, 2048, 4096)


def _leg(name: str, kind: str, argv: list[str], env: dict[str, str],
         timeout_s: float, note: str) -> dict:
    return {
        "name": name,
        "kind": kind,  # "bench" | "das"
        "argv": argv,
        "env": env,
        "timeout_s": timeout_s,
        "note": note,
    }


def build_plan(args) -> list[dict]:
    """The standing-debt list, resolved to exact argv + env overlays.

    Pure function of the CLI args — no jax import, no filesystem writes
    — so --dryrun and the tier-1 smoke can exercise the whole plan
    cheaply, and a resumed sitting rebuilds the identical plan.
    """
    py = sys.executable
    bench = [py, os.path.join(REPO_ROOT, "bench.py")]
    das = [py, os.path.join(REPO_ROOT, "scripts", "das_loadgen.py")]
    t = float(args.leg_timeout_s)
    giant_ks = args.giant_ks

    plan = [
        _leg("parts", "bench", bench,
             {"BENCH_MODE": "parts", "BENCH_K": "512"}, t,
             "autotuner decomposition: do rs_xor / rs_dense_pl / "
             "fused_epi take seats on this chip?"),
        _leg("stream", "bench", bench,
             {"BENCH_MODE": "stream", "BENCH_K": "512"}, t,
             "persistent-ring streaming; emits the b{1,2,4} batched "
             "rows in this one leg"),
        _leg("repair", "bench", bench,
             {"BENCH_MODE": "repair", "BENCH_K": "512"}, t,
             "grouped decode sweeps — past the 2.38x CPU figure?"),
    ]
    for k in giant_ks:
        plan.append(_leg(
            f"compute_sharded_k{k}", "bench", bench,
            {"BENCH_MODE": "compute_sharded", "BENCH_K": str(k),
             "BENCH_SHARDS": args.shards}, t,
            "multi-chip sharded-panel extend: the XOR all-reduce on "
            "real ICI instead of shard_map emulation"))
    for k in giant_ks:
        plan.append(_leg(
            f"panel_k{k}", "bench", bench,
            {"BENCH_MODE": "compute", "BENCH_K": str(k),
             "CELESTIA_PIPE_PANEL": "on"}, t,
            "panel-streamed giant square: never materializes the EDS"))
    plan += [
        _leg("das_shard_sweep", "das",
             das + ["--shard-sweep", args.shards,
                    "--clients", str(args.das_clients),
                    "--round-out", "__LEGDIR__/DAS_sweep.json"],
             {}, t,
             "proof-serving shard sweep: does the r02 CPU inversion "
             "flip — proofs/sec scaling with HBM bandwidth?"),
        _leg("mempool", "bench", bench,
             {"BENCH_MODE": "mempool",
              "BENCH_THREADS": str(args.mempool_threads)}, t,
             "sharded-vs-global admission A/B on a many-core host "
             "(2 cores bounded the 2.02x)"),
        _leg("withhold_heal", "das",
             das + ["--withhold-frac", "0.125", "--heal",
                    "--round-out", "__LEGDIR__/DAS_heal.json"],
             {}, t,
             "the adversarial drills' repair leg: withhold then heal, "
             "detect -> gather -> batched repair -> readmit on-chip"),
        _leg("hbm_k512", "bench", bench,
             {"BENCH_MODE": "compute", "BENCH_K": "512"}, t,
             "the k=512 HBM high-water recipe: the leg's /device "
             "snapshot carries the allocator-attributed ownership "
             "table, replacing the RSS proxy"),
    ]
    if args.legs:
        wanted = {w.strip() for w in args.legs.split(",") if w.strip()}
        unknown = wanted - {leg["name"] for leg in plan}
        if unknown:
            raise SystemExit(
                f"chip_sweep: unknown legs {sorted(unknown)}; "
                f"known: {[leg['name'] for leg in plan]}")
        plan = [leg for leg in plan if leg["name"] in wanted]
    return plan


# --- backend preflight (bench.py's probe contract) ---------------------------

def probe_backend(timeout_s: float) -> str | None:
    """Default-backend platform string, or None if unusable.  Subprocess
    + SIGTERM (then SIGKILL) on timeout — the parent stays jax-free."""
    code = ("import jax; "
            "print(jax.devices()[0].platform)")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            cwd=REPO_ROOT,
        )
    except OSError:
        return None
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        if proc.returncode == 0 and out.strip():
            return out.strip().splitlines()[-1]
        return None
    except subprocess.TimeoutExpired:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        return None


# --- journal -----------------------------------------------------------------

def next_round_path(out_dir: str) -> str:
    taken = []
    for p in glob.glob(os.path.join(out_dir, "SWEEP_r*.json")):
        m = re.match(r"SWEEP_r(\d+)\.json$", os.path.basename(p))
        if m:
            taken.append(int(m.group(1)))
    return os.path.join(out_dir, f"SWEEP_r{max(taken, default=0) + 1:02d}.json")


def write_journal(path: str, journal: dict) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(journal, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def _load_device_snapshot(path: str) -> dict | None:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# --- leg runner --------------------------------------------------------------

def run_leg(leg: dict, leg_dir: str) -> dict:
    """One leg, one subprocess, one hard timeout.  Returns the journal
    record; never raises (a leg failure is a row, not an abort)."""
    os.makedirs(leg_dir, exist_ok=True)
    snap_path = os.path.join(leg_dir, "device.json")
    env = dict(os.environ)
    env.update(leg["env"])
    env["CELESTIA_DEVICE_SNAPSHOT"] = snap_path
    argv = [a.replace("__LEGDIR__", leg_dir) for a in leg["argv"]]

    rec: dict = {
        "argv": argv, "env": leg["env"], "note": leg["note"],
        "status": "error", "seconds": 0.0,
    }
    t0 = time.monotonic()
    stdout_path = os.path.join(leg_dir, "stdout.log")
    try:
        with open(stdout_path, "w", encoding="utf-8") as out:
            proc = subprocess.Popen(
                argv, stdout=out, stderr=subprocess.STDOUT,
                env=env, cwd=REPO_ROOT,
            )
            try:
                proc.wait(timeout=leg["timeout_s"])
                rec["status"] = "ok" if proc.returncode == 0 else "error"
                rec["returncode"] = proc.returncode
            except subprocess.TimeoutExpired:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
                rec["status"] = "timeout"
    except OSError as e:
        rec["error"] = str(e)
    rec["seconds"] = round(time.monotonic() - t0, 3)

    # bench legs print ONE summary JSON line last; keep it in the journal.
    try:
        with open(stdout_path, encoding="utf-8") as f:
            tail = [ln for ln in f.read().splitlines() if ln.strip()]
        for ln in reversed(tail):
            try:
                rec["summary"] = json.loads(ln)
                break
            except ValueError:
                continue
    except OSError:
        pass
    dev = _load_device_snapshot(snap_path)
    if dev is not None:
        rec["device"] = dev
    for extra in ("DAS_sweep.json", "DAS_heal.json"):
        p = os.path.join(leg_dir, extra)
        loaded = _load_device_snapshot(p)
        if loaded is not None:
            rec.setdefault("artifacts", {})[extra] = loaded
    return rec


# --- entry -------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dryrun", action="store_true",
                    help="resolve + journal every leg without spawning "
                         "anything (no jax import anywhere)")
    ap.add_argument("--resume", metavar="SWEEP_rNN.json", default=None,
                    help="reuse an interrupted round's journal; legs "
                         "already ok are skipped")
    ap.add_argument("--legs", default=None,
                    help="comma list restricting the plan (default: all)")
    ap.add_argument("--out-dir", default=REPO_ROOT,
                    help="where SWEEP_rNN.json and per-leg dirs land")
    ap.add_argument("--leg-timeout-s", type=float, default=1800.0,
                    help="hard per-leg timeout (default 1800)")
    ap.add_argument("--probe-timeout-s", type=float, default=120.0,
                    help="backend preflight timeout (default 120, the "
                         "bench.py figure)")
    ap.add_argument("--require-device", action="store_true",
                    help="abort the sitting if the preflight lands on "
                         "CPU — a chip sweep on a fallback is the debt "
                         "it exists to retire")
    ap.add_argument("--shards", default="1,8",
                    help="shard counts for the sharded/das legs")
    ap.add_argument("--giant-ks", type=lambda s: tuple(
                        int(x) for x in s.split(",") if x.strip()),
                    default=GIANT_KS,
                    help="square sizes for the sharded/panel legs")
    ap.add_argument("--das-clients", type=int, default=1000,
                    help="swarm size for the das legs")
    ap.add_argument("--mempool-threads", type=int, default=8)
    args = ap.parse_args(argv)

    plan = build_plan(args)

    if args.resume:
        round_path = args.resume
        try:
            with open(round_path, encoding="utf-8") as f:
                journal = json.load(f)
        except (OSError, ValueError) as e:
            print(f"chip_sweep: cannot resume {round_path}: {e}",
                  file=sys.stderr)
            return 2
    else:
        round_path = next_round_path(args.out_dir)
        journal = {
            "schema": SWEEP_SCHEMA,
            "round": int(re.search(r"r(\d+)\.json$", round_path).group(1)),
            "plan": [leg["name"] for leg in plan],
            "legs": {},
        }

    if args.dryrun:
        journal["dryrun"] = True
        journal["platform"] = "unprobed"
        for leg in plan:
            journal["legs"][leg["name"]] = {
                "status": "planned",
                "argv": leg["argv"],
                "env": leg["env"],
                "timeout_s": leg["timeout_s"],
                "note": leg["note"],
            }
        write_journal(round_path, journal)
        print(json.dumps({
            "round": round_path,
            "dryrun": True,
            "legs": [leg["name"] for leg in plan],
        }))
        return 0

    platform = probe_backend(args.probe_timeout_s)
    if platform is None:
        print("chip_sweep: backend preflight failed; legs will fall "
              "back per bench.py's own probe", file=sys.stderr)
    journal["platform"] = platform or "unusable"
    if args.require_device and platform in (None, "cpu"):
        print(f"chip_sweep: --require-device but preflight says "
              f"{journal['platform']}; refusing to burn the sitting",
              file=sys.stderr)
        write_journal(round_path, journal)
        return 3

    base = os.path.splitext(round_path)[0]
    for leg in plan:
        prior = journal["legs"].get(leg["name"])
        if prior and prior.get("status") == "ok":
            print(f"chip_sweep: {leg['name']}: already ok, skipping")
            continue
        print(f"chip_sweep: {leg['name']}: starting "
              f"(timeout {leg['timeout_s']:.0f}s)")
        rec = run_leg(leg, os.path.join(base, leg["name"]))
        journal["legs"][leg["name"]] = rec
        write_journal(round_path, journal)  # after EVERY leg: resumable
        print(f"chip_sweep: {leg['name']}: {rec['status']} "
              f"in {rec['seconds']:.1f}s")

    ok = sum(1 for r in journal["legs"].values() if r.get("status") == "ok")
    print(json.dumps({
        "round": round_path,
        "platform": journal["platform"],
        "ok": ok,
        "total": len(plan),
    }))
    return 0 if ok == len(plan) else 1


if __name__ == "__main__":
    sys.exit(main())
