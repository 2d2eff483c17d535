"""Driver for `das` traffic: an open loop of light-client sampling rounds.

Set-up produces `retained_heights` heights of the configuration's square
through a proposer App (the setup traffic file's blocks) and retains each
committed height as the ServingNode commit hook does: the App's own EDS
handle for the committed data root goes into the serve plane's ForestCache
(program defaults: 4 heights on the device, 8 spilled to the host).  In the
window no block is produced.  Rounds fall due on a fixed schedule; at its
due instant a round's samples join the queue of a pool of `workers`
threads, each calling DasProvider.share_proof_payload (what the RPC planes
call, without the socket).  The pool stands in for the gRPC plane's
(`rpc/grpc_plane.serve_grpc`, 16 workers by default), so the queue in
front of it is the server's queue.  When the window closes, every sample
still queued is waited for: it comes late, not wrong.
"""

from __future__ import annotations

import hashlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import loadgen
from benchmark.propose import ProposeCell, note
from benchmark.reference import dah as ref_dah
from benchmark.reference import square as ref_square


class DasCell:
    def __init__(self, config: dict, traffic: dict, seed: int, rate: float,
                 setup_traffic: dict):
        self.config, self.traffic, self.seed, self.rate = config, traffic, seed, rate
        self.workers = traffic["workers"]
        self.producer = ProposeCell(config, setup_traffic, seed)
        self.rounds: list[dict] = []

    def setup(self) -> None:
        from celestia_app_tpu.serve.api import DasProvider

        n = self.config["retained_heights"]
        p = self.producer
        self.provider = DasProvider()
        self.heights: list[int] = []
        self.txs: dict[int, list[bytes]] = {}
        p.setup(seconds=0.0, validate=False)  # produces the first height
        self._retain()
        for h in range(1, n):
            p.pool.append(p._sign(h))
            p._height(h)
            self._retain()
        self.k = p.blocks[-1][1].square_size
        note(f"{n} heights produced and retained")
        self._warm()
        note("gather shapes warmed")

    def _retain(self) -> None:
        """The commit hook's retention: the App's EDS handle for the
        committed root goes into the ForestCache."""
        p = self.producer
        data = p.blocks[-1][1]
        height = p.proposer.height
        eds = p.proposer.last_eds_for_root(data.hash)
        if eds is None:
            raise RuntimeError(f"the App kept no EDS for height {height}")
        self.provider.cache.put(height, eds)
        self.heights.append(height)
        self.txs[height] = list(data.txs)

    def _warm(self) -> None:
        """Every batch size up to the worker pool on a device height and a
        spilled one, so no gather shape compiles in the window."""
        sampler, cache = self.provider.sampler, self.provider.cache
        for height in (self.heights[-1], self.heights[0]):
            entry, _ = cache.get(height)
            for b in range(1, self.workers + 1):
                sampler.sample_batch(entry, [(i % (2 * self.k), 0) for i in range(b)])
        self.provider.share_proof_payload(self.heights[-1], 0, 0)

    def window(self, seconds: float) -> tuple[float, float]:
        import jax

        sched = loadgen.das_schedule(self.traffic, self.rate, seconds, self.seed,
                                     self.heights, self.k)
        self.sched = sched
        lock = threading.Lock()
        self.rounds = [{"due": float(d), "height": h, "left": self.traffic["samples"],
                        "done": None, "failed": 0, "issued": None, "proofs": []}
                       for d, h in zip(sched["due"], sched["heights"])]
        provider = self.provider

        def sample(r: dict, row: int, col: int) -> None:
            began = time.perf_counter()
            try:
                payload = provider.share_proof_payload(r["height"], row, col)
            except Exception as e:  # noqa: BLE001 - a failed sample is counted
                payload = None
                err = repr(e)
            t = time.perf_counter()
            with lock:
                if payload is None:
                    r["failed"] += 1
                    r.setdefault("errors", []).append(err)
                else:
                    r["proofs"].append((row, col, payload, began, t))
                r["left"] -= 1
                if r["left"] == 0:
                    r["done"] = t

        pool = ThreadPoolExecutor(self.workers, thread_name_prefix="das")
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("das_window"):
                for r, coords in zip(self.rounds, sched["coords"]):
                    due = start + r["due"]
                    wait = due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    r["issued"] = time.perf_counter()
                    r["due_abs"] = due
                    for row, col in coords:
                        pool.submit(sample, r, int(row), int(col))
                end = start + seconds
                wait = end - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
        finally:
            pool.shutdown(wait=True, cancel_futures=False)
        self.start, self.end = start, end
        return start, end

    def free(self) -> None:
        self.provider = None
        self.producer = None

    def check(self, control: bool = False) -> tuple[dict, int]:
        """A seeded sample of the returned proofs against the reference
        data root of their height, recomputed from the height's txs; and
        how many proofs were verified."""
        roots: dict[int, bytes] = {}
        with ref_dah.hashing_pool(self.k) as pool:
            for height, txs in self.txs.items():
                ods = ref_square.ods_from_txs(txs, self.config["max_square_size"])
                roots[height] = ref_dah.data_root(ods, pool=pool)
        proofs = [(r["height"], row, col, payload) for r in self.rounds
                  for row, col, payload, *_ in r["proofs"]]
        rng = np.random.default_rng([self.seed, 11])
        n = min(len(proofs), self.traffic["check_proofs"])
        picks = rng.choice(len(proofs), n, replace=False) if proofs else []
        bad = 0
        for i in picks:
            height, row, col, payload = proofs[int(i)]
            ok = verify_payload(payload, height, row, col, roots[height], self.k,
                                control=control)
            bad += int(not ok)
        failed = sum(r["failed"] for r in self.rounds)
        return {"bad_proofs": {"value": bad, "limit": 0},
                "failed_samples": {"value": failed, "limit": 0}}, n


def _leaf(ns: bytes, share: bytes) -> bytes:
    return ns + ns + hashlib.sha256(b"\x00" + ns + share).digest()


def verify_payload(payload: dict, height: int, row: int, col: int, root: bytes,
                   k: int, control: bool = False) -> bool:
    """A share_proof payload proves EDS share (row, col) of `height` against
    the reference data root: NMT path to the row root, RFC 6962 path to
    the data root.  The control hashes a parity leaf under its own bytes'
    namespace."""
    try:
        if (payload["height"], payload["row"], payload["col"]) != (height, row, col):
            return False
        proof = payload["proof"]
        share = bytes.fromhex(proof["data"][0])
        sp = proof["share_proofs"][0]
        nodes = [bytes.fromhex(n) for n in sp["nodes"]]
        n = 2 * k
        if (sp["start"], sp["end"], sp["total"]) != (col, col + 1, n):
            return False
        q0 = row < k and col < k
        ns = share[:ref_square.NS] if (q0 or control) else ref_square.PARITY_NS
        it = iter(nodes)

        def walk(lo: int, hi: int) -> bytes:
            if hi <= col or lo >= col + 1:
                return next(it)
            if hi - lo == 1:
                return _leaf(ns, share)
            mid = (lo + hi) // 2
            return ref_dah._node(walk(lo, mid), walk(mid, hi))

        row_root = walk(0, n)
        if next(it, None) is not None:
            return False
        rp = proof["row_proof"]
        if (rp["start_row"], rp["end_row"], rp["total"]) != (row, row + 1, 2 * n):
            return False
        if bytes.fromhex(rp["row_roots"][0]) != row_root:
            return False
        h = hashlib.sha256(b"\x00" + row_root).digest()
        index = row
        for sib in rp["proofs"][0]:
            s = bytes.fromhex(sib)
            h = hashlib.sha256(b"\x01" + (h + s if index % 2 == 0 else s + h)).digest()
            index //= 2
        return h == root and index == 0
    except (KeyError, IndexError, ValueError, StopIteration, TypeError):
        return False
