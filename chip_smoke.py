#!/usr/bin/env python3
"""Run the node's main path once on one TPU chip and check what comes out.

The path is the one a validator runs: App.prepare_proposal -> square ->
extend + NMT + DAH -> process_proposal -> finalize/commit, at the
reference's big-block manifest (GovMaxSquareSize 512,
test/e2e/benchmark/throughput.go:15-54): three blocks at >= 90 % of
MaxBlockBytes through testutil/benchmark.run_throughput, so every square
is k=512 (134 MB ODS, 537 MB EDS).  Then DAS proofs are served from the
device for the last height, and one block runs at the protocol hard cap
(k=128) against the full host reference.  Everything runs in this one
process: a chip belongs to one process at a time.

Checks (any failure exits non-zero and prints no "ok" line):
  * each k=512 block: a seeded sample of 8 EDS rows and 8 columns read
    back from the chip — RS parity against the numpy codec, NMT root
    against hashlib — and all 4k roots rehashed to the block's data root;
  * the k=128 block: row roots, column roots and data root bit for bit
    against testutil/reference.host_dah (numpy GF + hashlib);
  * DAS: >= 32 seeded samples per axis over all four quadrants, served
    by ProofSampler.sample_batch from a ForestCache entry; every proof
    verifies on the host, and serve.verify.verify_proofs on the chip
    returns the same vector (one tampered proof as a negative control);
  * nothing stepped down: after every phase the pipeline mode of every k
    run equals the env's base mode, no celestia_recoveries_total sample
    says outcome="degraded", and the parity sentinel (if one ran) saw
    no mismatch.

Options (the driver passes none):
  --hard-cap       only the k=128 phase — the run that shows a non-default
                   lowering on the chip, e.g. with
                   CELESTIA_PIPE_FUSED=epi CELESTIA_RS_XOR=on
  --chips 4        only the sharded path on a 4-device mesh:
                   parallel/sharded_eds's full and DAH-only programs bit
                   for bit against the single-chip fused program on the
                   same ODS, then one sharded-serve gather
                   (CELESTIA_SERVE_SHARDS=4) verified on the host
  --cpu-rehearsal  the same phases at tiny sizes on the CPU (Pallas in
                   interpret mode); prints "platform: cpu" and never "ok"

The last stdout line on success is
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
Seconds printed on earlier lines are one smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

T0 = time.perf_counter()


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.1f}s] {msg}", flush=True)


def need(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --- checks shared by every phase --------------------------------------------


def check_no_step_down(ks) -> None:
    """The ladder stayed where the env seated it, and no seam degraded."""
    from celestia_app_tpu.kernels.fused import (
        env_base_mode_for_k,
        pipeline_mode_for_k,
    )
    from celestia_app_tpu.trace.metrics import registry

    for k in sorted(set(ks)):
        mode, base = pipeline_mode_for_k(k), env_base_mode_for_k(k)
        need(mode == base, f"k={k} stepped down the ladder: {base} -> {mode}")
    rec = registry().get("celestia_recoveries_total")
    degraded = [
        labels for labels, value in (rec.samples() if rec else [])
        if labels.get("outcome") == "degraded" and value
    ]
    need(not degraded, f"degraded recoveries: {degraded}")
    par = registry().get("celestia_parity_checks_total")
    mismatches = sum(
        value for labels, value in (par.samples() if par else [])
        if labels.get("result") == "mismatch"
    )
    need(not mismatches, f"parity sentinel saw {mismatches} mismatch(es)")


def counter_total(name: str, **match) -> float:
    from celestia_app_tpu.trace.metrics import registry

    rec = registry().get(name)
    return sum(
        value for labels, value in (rec.samples() if rec else [])
        if all(labels.get(k) == v for k, v in match.items())
    )


def on_device(arr, platform: str) -> bool:
    return all(d.platform == platform for d in arr.devices())


def check_block_sample(eds, data_root: bytes, k: int, rng, platform: str,
                       lines: int = 8) -> None:
    """Seeded rows and columns of a chip EDS against the host: RS parity
    (numpy codec), NMT roots (hashlib), and all 4k roots -> data root."""
    import numpy as np

    from celestia_app_tpu.gf import codec_for_width
    from celestia_app_tpu.merkle import hash_from_byte_slices
    from celestia_app_tpu.testutil.reference import line_root

    need(eds.k == k, f"EDS is k={eds.k}, block says k={k}")
    need(on_device(eds._eds, platform), "EDS is not on the chip")
    codec = codec_for_width(k)
    row_roots, col_roots = eds.row_roots(), eds.col_roots()
    half = max(1, lines // 2)

    def pick():  # both halves: data and parity lines
        return np.concatenate([
            rng.choice(k, min(half, k), replace=False),
            k + rng.choice(k, min(half, k), replace=False),
        ])

    for axis, idxs, roots in (("row", pick(), row_roots),
                              ("col", pick(), col_roots)):
        for i in idxs:
            i = int(i)
            line = eds.row(i) if axis == "row" else eds.col(i)
            need(np.array_equal(codec.encode(line[:k]), line[k:]),
                 f"{axis} {i}: RS parity differs from the host codec")
            need(line_root(line, i, axis, k) == roots[i],
                 f"{axis} {i}: NMT root differs from the host")
    need(hash_from_byte_slices(row_roots + col_roots) == data_root,
         "row + column roots do not hash to the block's data root")
    need(eds.data_root() == data_root, "EDS data root differs from block")


def run_blocks(gov: int, blocks: int, blob_size: int, min_fill: float,
               on_block=None):
    """`blocks` saturated blocks through App at GovMaxSquareSize `gov`;
    returns (app, ThroughputResult, [BlockData])."""
    from celestia_app_tpu.app import App
    from celestia_app_tpu.state.dec import Dec
    from celestia_app_tpu.testutil import (
        TestNode,
        deterministic_genesis,
        funded_keys,
    )
    from celestia_app_tpu.testutil.benchmark import run_throughput

    keys = funded_keys(2)
    app = App(node_min_gas_price=Dec.from_str("0.000001"),
              square_size_upper_bound=max(gov, 128))
    app.init_chain(deterministic_genesis(keys, gov_max_square_size=gov))
    node = TestNode(keys=keys, app=app)
    seen = []

    def hook(data):
        seen.append(data)
        need(data.square_size == gov,
             f"block {len(seen)} is k={data.square_size}, want {gov}")
        if on_block is not None:
            on_block(app, data)

    res = run_throughput(node, blocks=blocks, blob_size=blob_size,
                         target_fill=min_fill, on_block=hook)
    need(res.sustained(min_fill),
         f"fill below {min_fill}: {[round(f, 4) for f in res.fills]}")
    return app, res, seen


# --- phases ------------------------------------------------------------------


def big_block_phase(k: int, blob_size: int, min_fill: float, seed: int,
                    platform: str):
    import numpy as np

    rng = np.random.default_rng(seed)

    def check(app, data):
        eds = app.last_eds_for_root(data.hash)
        need(eds is not None, "the app kept no EDS for the block's root")
        check_block_sample(eds, data.hash, k, rng, platform)
        log(f"  block at height {app.height}: k={k} sample rows/cols and "
            "data root match the host")

    app, res, blocks = run_blocks(k, 3, blob_size, min_fill, check)
    secs = ", ".join(f"{b:.3f}" for b in res.block_seconds)
    log(f"gov-{k}: 3 blocks, fills {[round(f, 4) for f in res.fills]}, "
        f"seconds per block (one smoke run, not a benchmark): {secs}")
    eds = app.last_eds_for_root(blocks[-1].hash)
    return app, eds


def das_phase(eds, height: int, seed: int, per_quadrant: int = 8) -> None:
    import numpy as np

    from celestia_app_tpu.serve.cache import ForestCache
    from celestia_app_tpu.serve.sampler import ProofSampler
    from celestia_app_tpu.serve.verify import verify_proofs

    rng = np.random.default_rng(seed)
    k = eds.k
    cache = ForestCache(heights=1, spill=0)
    entry = cache.put(height, eds)
    need(entry is not None and entry.device_resident,
         "ForestCache did not retain the height on the device")
    sampler = ProofSampler()
    root = eds.data_root()
    proofs = []
    for axis in ("row", "col"):
        coords = [
            (qr * k + int(rng.integers(k)), qc * k + int(rng.integers(k)))
            for qr in (0, 1) for qc in (0, 1) for _ in range(per_quadrant)
        ]
        proofs += sampler.sample_batch(entry, coords, axis=axis)
    host = [p.verify(root) for p in proofs]
    need(all(host), f"{host.count(False)} of {len(proofs)} proofs fail "
         "host verification")
    bad = bytearray(proofs[0].data[0])
    bad[200] ^= 1
    tampered = dataclasses.replace(
        proofs[0], data=(bytes(bad),) + tuple(proofs[0].data[1:])
    )
    queue = proofs + [tampered]
    want = host + [tampered.verify(root)]
    need(want[-1] is False, "the tampered proof verified on the host")
    before = counter_total("celestia_verified_samples_total", mode="batched")
    got = verify_proofs(queue, root)
    batched = counter_total(
        "celestia_verified_samples_total", mode="batched") - before
    need(list(got) == want, "verify_proofs on the chip disagrees with host")
    need(batched == len(queue),
         f"only {batched:.0f} of {len(queue)} proofs verified on the device")
    log(f"DAS k={k}: {len(proofs)} proofs over 4 quadrants x 2 axes verify "
        f"on the host; verify_proofs agrees on {len(queue)} (1 tampered)")
    cache.reset_for_tests()


def hard_cap_phase(k: int, blob_size: int, min_fill: float,
                   platform: str) -> None:
    import numpy as np

    from celestia_app_tpu.constants import SHARE_SIZE
    from celestia_app_tpu.square import builder as square
    from celestia_app_tpu.testutil.reference import host_dah

    app, res, blocks = run_blocks(k, 1, blob_size, min_fill)
    data = blocks[0]
    eds = app.last_eds_for_root(data.hash)
    need(eds is not None, "the app kept no EDS for the block's root")
    need(on_device(eds._eds, platform), "EDS is not on the chip")
    sq = square.construct(list(data.txs), app.max_effective_square_size())
    ods = np.frombuffer(b"".join(sq.share_bytes()), dtype=np.uint8)
    t = time.perf_counter()
    rows, cols, root = host_dah(ods.reshape(k, k, SHARE_SIZE))
    need(eds.row_roots() == rows, "row roots differ from the host reference")
    need(eds.col_roots() == cols, "col roots differ from the host reference")
    need(root == data.hash, "data root differs from the host reference")
    log(f"hard cap k={k}: block fill {res.fills[0]:.4f}, DAH bit-identical "
        f"to the numpy/hashlib reference ({time.perf_counter() - t:.1f}s "
        f"host), data root {data.hash.hex()[:16]}...")


def seeded_ods(k: int, seed: int):
    """A (k, k, S) ODS with namespace-sorted random shares."""
    import numpy as np

    from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE

    rng = np.random.default_rng(seed)
    ods = rng.integers(0, 256, (k, k, SHARE_SIZE), dtype=np.uint8)
    ids = np.arange(k * k, dtype=">u8").view(np.uint8).reshape(k * k, 8)
    ods[..., :NAMESPACE_SIZE] = 0
    ods[..., NAMESPACE_SIZE - 8:NAMESPACE_SIZE] = ids.reshape(k, k, 8)
    return ods


def four_chip_phase(k: int, seed: int, platform: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from celestia_app_tpu.da.eds import ExtendedDataSquare
    from celestia_app_tpu.kernels.fused import jit_extend_and_dah
    from celestia_app_tpu.parallel.sharded_eds import (
        make_sharded_dah_pipeline,
        make_sharded_pipeline,
    )

    devs = jax.devices()
    need(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("data",))
    ods = seeded_ods(k, seed)
    sh = NamedSharding(mesh, P("data", None, None))
    single_fn = jit_extend_and_dah(k)
    full_fn = make_sharded_pipeline(k, mesh)
    dah_fn = make_sharded_dah_pipeline(k, mesh)
    one = jax.ShapeDtypeStruct(ods.shape, np.uint8,
                               sharding=jax.sharding.SingleDeviceSharding(devs[0]))
    spread = jax.ShapeDtypeStruct(ods.shape, np.uint8, sharding=sh)
    # The three programs share nothing: compile them side by side (XLA
    # compiles without the GIL), then run each compiled executable.
    from concurrent.futures import ThreadPoolExecutor

    t = time.perf_counter()
    with ThreadPoolExecutor(3) as pool:
        single, full, dah = pool.map(
            lambda fa: fa[0].lower(fa[1]).compile(),
            [(single_fn, one), (full_fn, spread), (dah_fn, spread)],
        )
    log(f"4-chip: compiled fused, sharded and sharded-DAH k={k} in "
        f"{time.perf_counter() - t:.1f}s (side by side)")
    ref = [np.asarray(x) for x in single(jax.device_put(ods, devs[0]))]
    out = full(jax.device_put(ods, sh))
    eds_dev = out[0]
    homes = {s.device for s in eds_dev.addressable_shards}
    need(len(homes) == 4, f"EDS shards sit on {len(homes)} device(s), want 4")
    need(all(d.platform == platform for d in homes), "EDS is not on the chips")
    names = ("eds", "row_roots", "col_roots", "data_root")
    for name, got, want in zip(names, out, ref):
        need(np.array_equal(np.asarray(got), want),
             f"sharded {name} differs from the single-chip fused program")
    log(f"4-chip: sharded extend+DAH k={k} bit-identical to one chip; EDS "
        f"shards on {sorted(d.id for d in homes)}")
    for name, got, want in zip(names[1:], dah(jax.device_put(ods, sh)),
                               ref[1:]):
        need(np.array_equal(np.asarray(got), want),
             f"sharded DAH-only {name} differs from the single-chip program")
    log(f"4-chip: sharded DAH-only k={k} bit-identical to one chip")

    os.environ["CELESTIA_SERVE_SHARDS"] = "4"
    from celestia_app_tpu.serve.cache import ForestCache
    from celestia_app_tpu.serve.sampler import ProofSampler
    from celestia_app_tpu.serve.shard import ShardedCachedForest

    eds = ExtendedDataSquare(eds_dev, out[1], out[2], out[3], k)
    cache = ForestCache(heights=1, spill=0)
    entry = cache.put(1, eds)
    need(isinstance(entry, ShardedCachedForest) and entry.shards == 4,
         "the serve plane did not shard the forest over 4 devices")
    need(entry.share_shards == 4, "the retained EDS is not 4-way sharded")
    rng = np.random.default_rng(seed)
    root = bytes(ref[3])
    proofs = []
    for axis in ("row", "col"):
        coords = [
            (qr * k + int(rng.integers(k)), qc * k + int(rng.integers(k)))
            for qr in (0, 1) for qc in (0, 1) for _ in range(8)
        ]
        proofs += ProofSampler().sample_batch(entry, coords, axis=axis)
    need(all(p.verify(root) for p in proofs),
         "a sharded-serve proof fails host verification")
    forest_rows = counter_total("celestia_serve_shard_gathers_total")
    share_rows = counter_total("celestia_serve_share_gathers_total")
    need(forest_rows > 0 and share_rows > 0,
         "the sharded forest/share gathers never ran")
    log(f"4-chip: sharded serve k={k}: {len(proofs)} proofs verify on the "
        f"host ({forest_rows:.0f} forest rows, {share_rows:.0f} shares "
        "gathered by shard)")
    cache.reset_for_tests()


# --- reporting ---------------------------------------------------------------


def report_device(dev) -> None:
    from celestia_app_tpu.trace.device_ledger import snapshot

    per_family: dict[str, float] = {}
    lowerings = set()
    for row in snapshot()["programs"]:
        per_family[row["family"]] = (
            per_family.get(row["family"], 0.0) + row["compile_s"]
        )
        lowerings.add(f"{row['family']} k={row['k']} "
                      f"{row['construction']}/{row['mode']}")
    for family, secs in sorted(per_family.items()):
        log(f"  first-dispatch (trace+compile) seconds, {family}: {secs:.3f}")
    log(f"  programs run (family k construction/mode): {sorted(lowerings)}")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"  peak_bytes_in_use: "
        f"{peak if peak is not None else 'not reported by this backend'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--hard-cap", action="store_true")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()
    try:
        from celestia_app_tpu.compile_cache import enable_compile_cache
    except ImportError:
        print("chip_smoke: run from the repository root (celestia_app_tpu "
              "is not importable here)", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX finds no device: {e}", file=sys.stderr)
        return 3
    dev = devs[0]
    platform = dev.platform
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want:
        print(f"chip_smoke: JAX's first device is {platform!r}, need "
              f"{want!r}; nothing was run", file=sys.stderr)
        return 3
    print(f"platform: {platform}")
    log(f"device_kind: {dev.device_kind}; device count: {len(devs)}")

    tiny = args.cpu_rehearsal
    big_k, cap_k = (8, 4) if tiny else (512, 128)
    min_fill = 0.5 if tiny else 0.9
    ks: list[int] = []
    try:
        if args.chips == 4:
            ks.append(big_k)
            four_chip_phase(big_k, args.seed, platform)
            check_no_step_down(ks)
        else:
            if not args.hard_cap:
                ks.append(big_k)
                app, eds = big_block_phase(
                    big_k, 2_000 if tiny else 1_000_000, min_fill,
                    args.seed, platform,
                )
                check_no_step_down(ks)
                report_device(dev)
                das_phase(eds, app.height, args.seed)
                check_no_step_down(ks)
                del app, eds
                import gc

                gc.collect()
            ks.append(cap_k)
            hard_cap_phase(cap_k, 500 if tiny else 500_000, min_fill,
                           platform)
            check_no_step_down(ks)
        report_device(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": dev.device_kind,
              "count": len(devs)}
    log("all phases passed")
    if tiny:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
