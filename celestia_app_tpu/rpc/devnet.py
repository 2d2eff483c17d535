"""Multi-process devnet: N validators exchanging proposals over sockets.

The reference's testnode Network starts real nodes with RPC/gRPC servers on
random ports (test/util/testnode/network.go:20-43); its multi-validator
tier runs containers. This devnet is the socket tier for this framework:
each validator is its OWN PROCESS serving JSON-RPC, block production
rotates by height, and every block is replicated over HTTP with app-hash /
data-root equality enforced (ReplicationDivergence otherwise).

Run one validator:   python -m celestia_app_tpu.rpc.devnet --index 0 --n 3 \
                        --base-port 26800 [--block-interval-ms 300]
Spawn a whole devnet in-code (tests): `spawn_devnet(n=3)`.

All validators derive the identical deterministic genesis from the shared
seed set (testutil.testnode.deterministic_genesis), so chain state agrees
from height 0 without any genesis-distribution step.
"""

from __future__ import annotations

import argparse
import subprocess
import sys
import time

from celestia_app_tpu.rpc.client import RemoteNode
from celestia_app_tpu.rpc.server import ServingNode, serve
from celestia_app_tpu.testutil.testnode import deterministic_genesis, funded_keys


def _url(base_port: int, i: int) -> str:
    return f"http://127.0.0.1:{base_port + i}"


def run_validator(
    index: int,
    n: int,
    base_port: int,
    block_interval_ms: int = 300,
    n_accounts: int = 4,
    mode: str = "gossip",
    peer_indices: list[int] | None = None,
    wal_dir: str | None = None,
) -> None:
    """Serve validator `index` of `n`; blocks until killed.

    mode="gossip" (default): the multi-round Tendermint machine over p2p
    flood gossip (rpc/gossip.py) — survives proposer crashes via round
    changes.  mode="push": the legacy proposer-push round (one round per
    height, the round-1/2 plane).  `peer_indices` restricts this node's
    peer list (partial topologies, e.g. a ring, to exercise multi-hop
    relay); default is fully connected.  `wal_dir` enables the
    double-sign WAL (one file per validator index).
    """
    keys = funded_keys(n_accounts)
    if peer_indices is None:
        peer_indices = [j for j in range(n) if j != index]
    node = ServingNode(
        genesis=deterministic_genesis(keys, n_validators=n),
        keys=keys,
        validator_index=index,
        n_validators=n,
        peers=[_url(base_port, j) for j in peer_indices],
    )
    driver = None
    if mode == "gossip":
        import os as _os

        driver = node.enable_gossip_consensus(
            interval_s=block_interval_ms / 1000.0,
            wal_path=(
                _os.path.join(wal_dir, f"wal-{index}.jsonl")
                if wal_dir else None
            ),
        )
    server = serve(node, port=base_port + index, block_interval_s=None)
    print(f"validator {index}/{n} serving on {server.url} ({mode})", flush=True)

    # AOT warmup BEFORE consensus starts (SURVEY §7 hard part 4: compiles
    # must never sit on the block path — a first-block compile under the
    # node lock stalls every round timeout).  Small sizes cover empty/
    # near-empty devnet blocks; bigger squares hit the persistent compile
    # cache (compile_cache.py, enabled when the node's App was built).
    from celestia_app_tpu.da.eds import warmup

    warmup([1, 2, 4])
    print(f"validator {index} warmed", flush=True)

    # Startup barrier: wait for every peer to serve before proposing.
    for peer_url in node.peer_urls:
        peer = RemoteNode(peer_url, defer_status=True, timeout=2.0)
        deadline = time.monotonic() + 60
        while True:
            try:
                peer.status()
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise TimeoutError(f"peer {peer_url} never came up")
                time.sleep(0.1)
    print(f"validator {index} peers up", flush=True)

    if driver is not None:
        driver.start()
        while True:
            time.sleep(60)  # the driver's timers run the chain

    interval = block_interval_ms / 1000.0
    while True:
        time.sleep(interval)
        try:
            if node.is_proposer(node.app.height + 1):
                node.produce_block()
        except Exception as e:  # noqa: BLE001 — keep serving; surface the fault
            print(f"validator {index} produce error: {e}", file=sys.stderr, flush=True)


class Devnet:
    """Handle to spawned validator processes."""

    def __init__(self, procs: list[subprocess.Popen], urls: list[str]):
        self.procs = procs
        self.urls = urls

    def client(self, i: int = 0) -> RemoteNode:
        return RemoteNode(self.urls[i])

    def stop(self) -> None:
        for p in self.procs:
            p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def spawn_devnet(
    n: int = 3,
    base_port: int = 26800,
    block_interval_ms: int = 300,
    wait_s: float = 120.0,
    env: dict | None = None,
    mode: str = "gossip",
    topology: dict[int, list[int]] | None = None,
) -> Devnet:
    """Launch n validator processes; returns once all serve their RPC.

    `topology` maps validator index -> peer indices (partial meshes, e.g.
    a ring for multi-hop relay tests); default fully connected.
    """
    import os

    procs = []
    child_env = dict(os.environ if env is None else env)
    # n validators on one host is a consensus test, not a device path, and
    # n processes cannot share one chip: pin every child to the CPU unless
    # the caller's env names a platform itself.
    if env is None or not env.get("JAX_PLATFORMS"):
        child_env["JAX_PLATFORMS"] = "cpu"
    # Pre-warm the persistent cache (compile_cache.py) ONCE before
    # spawning: n validators compiling the same pipelines concurrently on
    # a small host serializes onto the cores and multiplies the startup
    # time by n; after this one-shot, every child's own warmup is a fast
    # cache deserialization.
    subprocess.run(
        [sys.executable, "-c",
         "from celestia_app_tpu.compile_cache import enable_compile_cache; "
         "enable_compile_cache(); "
         "from celestia_app_tpu.da.eds import warmup; warmup([1, 2, 4])"],
        env=child_env, timeout=600,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        check=False,
    )
    for i in range(n):
        cmd = [
            sys.executable, "-m", "celestia_app_tpu.rpc.devnet",
            "--index", str(i), "--n", str(n),
            "--base-port", str(base_port),
            "--block-interval-ms", str(block_interval_ms),
            "--mode", mode,
        ]
        if topology is not None:
            cmd += ["--peers", ",".join(str(j) for j in topology[i])]
        procs.append(
            subprocess.Popen(
                cmd,
                env=child_env,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
        )
    urls = [_url(base_port, i) for i in range(n)]
    net = Devnet(procs, urls)
    deadline = time.monotonic() + wait_s
    try:
        for u in urls:
            peer = RemoteNode(u, defer_status=True, timeout=2.0)
            while True:
                try:
                    peer.status()
                    break
                except Exception:
                    if time.monotonic() > deadline:
                        raise TimeoutError(f"validator at {u} never served")
                    time.sleep(0.2)
    except Exception:
        net.stop()
        raise
    return net


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="celestia-tpu devnet validator")
    ap.add_argument("--index", type=int, required=True)
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--base-port", type=int, default=26800)
    ap.add_argument("--block-interval-ms", type=int, default=300)
    ap.add_argument("--mode", choices=["gossip", "push"], default="gossip")
    ap.add_argument("--peers", default=None,
                    help="comma-separated peer indices (default: all others)")
    ap.add_argument("--wal-dir", default=None,
                    help="directory for the double-sign WAL (off if unset)")
    args = ap.parse_args(argv)
    peer_indices = (
        [int(x) for x in args.peers.split(",") if x != ""]
        if args.peers is not None
        else None
    )
    run_validator(
        args.index, args.n, args.base_port, args.block_interval_ms,
        mode=args.mode, peer_indices=peer_indices, wal_dir=args.wal_dir,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
