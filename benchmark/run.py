"""The benchmark: one cell per process.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from BENCHMARK.json: the cell's configuration
file, its traffic file (benchmark/traffic/<traffic>.json), its own
parameters (benchmark/cells/<cell>.json, if any), and one reader per metric
(benchmark/metrics/<metric>.py, a `read(ctx)` that returns a number or
None; `<name>.<suffix>` falls back to `<name>.py`).  With --trace 0 the
line carries the cell's end-to-end metrics, with --trace 1 its per-layer
metrics from a short profiled window.

The last stdout line is the result object; the numbers compared to decide
`correct`, each with its limit, are the last stderr lines and the result's
last key.  With no TPU, or fewer chips than the cell asks for, the run
exits 3 and prints no result.  `--control` swaps the plain reference's
control into the program's place (never run by the driver).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration, traffic and own parameters."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    config["max_square_size"] = min(config["gov_max_square_size"],
                                    config["square_size_upper_bound"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    own = os.path.join(HERE, "cells", name + ".json")
    params = load_json(own) if os.path.exists(own) else {}
    return {"bench": bench, "cell": cell, "config": config, "traffic": traffic,
            "params": params}


def metric_entries(bench: dict, cell: str, trace: bool) -> list[dict]:
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def read_metric(name: str, ctx: dict):
    """The metric's own reader, else that of its name without the last
    `.suffix` (`device_idle.das` -> `device_idle.py`)."""
    base = name
    path = os.path.join(HERE, "metrics", base + ".py")
    while not os.path.exists(path) and "." in base:
        base = base.rsplit(".", 1)[0]
        path = os.path.join(HERE, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def place_compile_cache() -> None:
    """JAX's persistent compile cache lives at a fixed path in this checkout,
    a directory of the benchmark's own (a machine may bring a cache of its
    own to `.jax_cache`), keeps every program and evicts none, so only a
    checkout's first run compiles.  The program takes the directory from
    $JAX_COMPILATION_CACHE_DIR; JAX reads these variables when imported."""
    cache = os.path.join(ROOT, ".bench_jax_cache")
    os.makedirs(cache, exist_ok=True)
    settings = {"jax_compilation_cache_dir": cache,
                "jax_compilation_cache_max_size": -1,
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    for name, value in settings.items():
        os.environ[name.upper()] = str(value)
    if "jax" in sys.modules:  # imported already (tests): JAX read the env before
        import jax

        for name, value in settings.items():
            jax.config.update(name, value)


class CompileCounter:
    """Counts backend compiles (persistent-cache misses) while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and "backend_compile" in event:
            self.count += 1


def make_driver(loaded: dict, seed: int):
    config, traffic, params = loaded["config"], loaded["traffic"], loaded["params"]
    if traffic["kind"] == "propose":
        from benchmark.propose import ProposeCell

        return ProposeCell(config, traffic, seed)
    if traffic["kind"] == "das":
        from benchmark.das import DasCell

        setup = load_json(HERE, "traffic", traffic["setup_traffic"] + ".json")
        return DasCell(config, traffic, seed, params["rate_rounds_per_s"], setup)
    raise SystemExit(f"bench: unknown traffic kind {traffic['kind']!r}")


def run(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description="one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", default="",
                    help="DAS only: comma-separated round rates, one window "
                         "each after one set-up; prints a line per rate and no result")
    ap.add_argument("--workers", default="",
                    help="with --sweep: comma-separated worker counts, each swept")
    args = ap.parse_args(argv)

    try:
        import celestia_app_tpu  # noqa: F401
    except ImportError:
        print("bench: the program (celestia_app_tpu) is not in this checkout",
              file=sys.stderr)
        return 2
    loaded = load_cell(args.workload)
    cell, traffic = loaded["cell"], loaded["traffic"]
    place_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX finds no device: {e}", file=sys.stderr)
        return 3
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"bench: JAX's device is {dev.platform!r}, not a TPU; nothing was run",
              file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    counter = CompileCounter()

    driver = make_driver(loaded, args.seed)
    if args.workers:  # a sweep warms every batch size its largest pool can make
        driver.workers = max(int(w) for w in args.workers.split(","))
    if traffic["kind"] == "propose":
        driver.setup(args.seconds)
    else:
        driver.setup()
    setup_s = time.perf_counter() - T0
    print(f"bench: set-up {setup_s:.3f} s", file=sys.stderr, flush=True)

    if args.sweep:
        workers = [int(w) for w in args.workers.split(",") if w] or [driver.workers]
        return sweep(driver, [float(r) for r in args.sweep.split(",")], workers,
                     args.seconds)
    counter.armed = True
    from celestia_app_tpu.trace.tracer import traced

    wall0 = time.time_ns()
    window = profile = None
    if args.trace:
        from benchmark import profile as prof

        with prof.Window() as w:
            if traffic["kind"] == "propose":
                start, end = driver.window(args.seconds, traffic["trace_heights"])
            else:
                start, end = driver.window(min(args.seconds, traffic["trace_seconds"]))
        red = prof.reduce(w.path)
        w.cleanup()
        mods = sorted(((n, len(d), sum(hi - lo for lo, hi in d) / 1e9)
                       for dev_ in red["devices"] for n, d in dev_["modules"].items()),
                      key=lambda x: -x[2])
        print("bench: device programs in the trace: " + ", ".join(
            f"{n} x{c} {t:.4f} s" for n, c, t in mods[:8]), file=sys.stderr)
        for name, count, _ in mods[:2]:
            if count <= 16:  # each execution: offset, seconds, host annotation
                ev = sorted(red["devices"][0]["modules"].get(name, []))
                print(f"bench: {name} events: " + ", ".join(
                    f"+{(lo - ev[0][0]) / 1e9:.4f} {(hi - lo) / 1e9:.4f} "
                    f"{prof.covering(red, (lo + hi) // 2)}" for lo, hi in ev),
                    file=sys.stderr)
        print("bench: device trace lines: " + ", ".join(
            f"{dev_['name']}:{ln}" for dev_ in red["devices"] for ln in dev_["lines"]),
            file=sys.stderr)
        window = w.seconds
        profile = red
    else:
        start, end = driver.window(args.seconds)
    wall1 = time.time_ns()
    counter.armed = False
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    spans = {t: [r for r in traced().table(t) if wall0 <= r.get("ts_ns", 0) <= wall1]
             for t in ("square_pipeline", "proof_serve", "block_journal")}
    if args.trace:
        print("bench: extend dispatches the host counted: " + str(sum(
            1 for r in spans["block_journal"] if r.get("source") == "compute")),
            file=sys.stderr)
    peaks_row = None
    peaks = load_json(HERE, "peaks.json")
    if dev.platform == "tpu":
        if dev.device_kind not in peaks:
            raise SystemExit(f"bench: no peaks for device kind {dev.device_kind!r}")
        peaks_row = peaks[dev.device_kind]
    records = getattr(driver, "records", None)
    rounds = getattr(driver, "rounds", None)
    driver.free()
    gc.collect()

    checks, checked = driver.check()
    if args.control:
        program = checks
        checks, _ = driver.check(control=True)
        for name, c in program.items():
            print(f"program {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    ctx = {"cell": cell["name"], "kind": traffic["kind"], "config": loaded["config"],
           "traffic": traffic, "setup_s": setup_s, "start": start, "end": end,
           "records": records, "rounds": rounds, "spans": spans, "profile": profile,
           "trace_seconds": window, "peaks": peaks_row,
           "k": loaded["config"]["max_square_size"]}
    metrics = {}
    for m in metric_entries(loaded["bench"], cell["name"], bool(args.trace)):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values())}
    if traffic["kind"] == "propose":
        out["attempted"] = len(records)
        out["failed"] = sum(not r["accepted"] for r in records)
    else:
        out["attempted"] = sum(traffic["samples"] for _ in rounds)
        out["failed"] = sum(r["failed"] for r in rounds)
    out["metrics"] = metrics
    if args.trace:
        from benchmark import profile as prof

        busy = prof.busy_seconds(profile)
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = window
        out["device"] = device
        out["breakdown"] = prof.breakdown(profile)
    else:
        out["device"] = device
    out["checks"] = checks
    print(f"bench: {len(records or rounds)} {'heights' if records else 'rounds'} in "
          f"{end - start:.3f} s; compiles in the window: {counter.count}; "
          f"checked: {checked}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def sweep(driver, rates: list[float], workers: list[int], seconds: float) -> int:
    """The knee sweep: one window per worker count and offered rate."""
    for w, rate in [(w, r) for w in workers for r in rates]:
        driver.workers, driver.rate = w, rate
        start, end = driver.window(seconds)
        ctx = {"kind": "das", "rounds": driver.rounds, "start": start, "end": end}
        row = {"workers": w, "rate_rounds_per_s": rate,
               "offered_proofs_per_s": rate * driver.traffic["samples"],
               **{m: read_metric(m, ctx) for m in
                  ("das_proofs_per_s", "das_p95_ms", "das_gen_lag_ms", "das_serve_ms")},
               "failed": sum(r["failed"] for r in driver.rounds),
               "p50_ms": sorted((r["done"] or end + 1e3) * 1e3 - r["due_abs"] * 1e3
                                for r in driver.rounds)[len(driver.rounds) // 2]}
        print("sweep " + json.dumps(row), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
