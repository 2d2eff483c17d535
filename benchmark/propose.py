"""Driver for `propose` traffic: a closed loop of heights, proposer and validator.

One height: the proposer App runs prepare_proposal on that height's txs; a
second App, the validator, which has never seen the square, runs
process_proposal on the same BlockData (it rebuilds the square and
recomputes the DAH: no own-root memo can hit); then both finalize and commit.
The window ends at the end of the first height that finishes after
`seconds`.  Both Apps share this process's compiled programs and the
module-level blob-commitment memo, which two machines would not.
"""

from __future__ import annotations

import dataclasses
import math
import sys
import time


from benchmark import loadgen
from benchmark.reference import dah as ref_dah
from benchmark.reference import square as ref_square

GAS_PRICE = 0.00001  # utia/gas: 10x the node minimum, as the e2e saturator pays
NODE_MIN_GAS_PRICE = "0.000001"


def note(msg: str) -> None:
    """A set-up phase on stderr, stamped with the process clock."""
    print(f"bench: [{time.perf_counter():9.3f}] {msg}", file=sys.stderr, flush=True)


class ProposeCell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.plans: list[list[list[int]]] = []
        self.pool: list[list[bytes]] = []
        self.records: list[dict] = []
        self.blocks: list = []

    # --- set-up ---------------------------------------------------------------
    def _plan(self, n: int) -> None:
        while len(self.plans) < n:
            self.plans.append(loadgen.plan_height(
                self.traffic, self.config, self.seed, len(self.plans)))

    def _new_app(self, genesis):
        from celestia_app_tpu.app import App
        from celestia_app_tpu.state.dec import Dec

        app = App(node_min_gas_price=Dec.from_str(NODE_MIN_GAS_PRICE),
                  square_size_upper_bound=self.config["square_size_upper_bound"])
        app.init_chain(genesis)
        return app

    def _sign(self, height: int) -> list[bytes]:
        from celestia_app_tpu.modules.blob.types import estimate_gas
        from celestia_app_tpu.shares.namespace import Namespace
        from celestia_app_tpu.shares.sparse import Blob

        txs, used = [], []
        for i, sizes in enumerate(self.plans[height]):
            addr = self.addrs[i]
            self.signer.set_sequence(addr, self.sequences[i])
            blobs = [Blob(Namespace.from_bytes(ns), data) for ns, data in
                     loadgen.blobs_of(self.traffic, self.seed, height, i, sizes)]
            gas = estimate_gas(sizes)
            txs.append(self.signer.create_pay_for_blobs(
                addr, blobs, gas, int(gas * GAS_PRICE) + 1))
            used.append(i)
        while txs and not loadgen.fits(txs, self.config):
            txs.pop()
            used.pop()
        fill = sum(len(t) for t in txs) / self.config["max_block_bytes"]
        if fill < self.traffic["fill_min"]:
            raise RuntimeError(f"traffic fills height {height} to {fill:.4f} "
                               f"< {self.traffic['fill_min']}")
        for i in used:
            self.sequences[i] += 1
        return txs

    def setup(self, seconds: float, validate: bool = True) -> None:
        from celestia_app_tpu.crypto import PrivateKey
        from celestia_app_tpu.state.accounts import AuthKeeper
        from celestia_app_tpu.testutil import deterministic_genesis
        from celestia_app_tpu.user import Signer

        self._plan(self.traffic["plan_heights"])
        n_acc = max(len(p) for p in self.plans)
        keys = [PrivateKey.from_seed(f"bench-account-{i}".encode())
                for i in range(n_acc)]
        genesis = dataclasses.replace(
            deterministic_genesis(
                keys, gov_max_square_size=self.config["gov_max_square_size"]),
            block_max_bytes=self.config["max_block_bytes"],
        )
        self.proposer = self._new_app(genesis)
        self.validator = self._new_app(genesis) if validate else None
        self.signer = Signer(self.proposer.chain_id)
        auth = AuthKeeper(self.proposer.cms.working)
        for key in keys:
            acc = auth.get_account(key.public_key().address())
            self.signer.add_account(key, acc.account_number, acc.sequence)
        self.addrs = [k.public_key().address() for k in keys]
        self.sequences = [0] * n_acc
        note(f"genesis with {n_acc} accounts, two Apps")
        # Warm-up height: compiles (first run) or loads every program the
        # window uses, and times a steady validator recompute.
        self.pool.append(self._sign(0))
        warm = self._height(0)
        if not warm["accepted"]:
            raise RuntimeError("the validator rejected the warm-up height")
        self.warm = warm
        note(f"warm-up height: prepare {warm['prepare_s']:.3f} s, process "
             f"{warm['process_s']:.3f} s, commit {warm['commit_s']:.3f} s")
        if not validate:
            return
        est = max(1e-3, 2 * warm["process_s"] + warm["commit_s"])
        need = 1 + math.ceil(seconds / est * self.traffic["pool_margin"]) + 2
        need = min(need, self.traffic["plan_heights"])
        self.records.clear()
        self.blocks.clear()
        for h in range(1, need):
            self.pool.append(self._sign(h))
        note(f"pool of {need - 1} heights signed")

    # --- the window -------------------------------------------------------------
    def _height(self, h: int) -> dict:
        import jax

        txs = self.pool[h]
        with jax.profiler.TraceAnnotation("prepare_proposal"):
            t0 = time.perf_counter()
            data = self.proposer.prepare_proposal(txs)
            t1 = time.perf_counter()
        apps = [a for a in (self.proposer, self.validator) if a is not None]
        with jax.profiler.TraceAnnotation("process_proposal"):
            ok = apps[-1].process_proposal(data)
            t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("finalize_commit"):
            when = self.proposer.last_block_time_ns + 15 * 10**9
            for app in apps:
                app.finalize_block(when, list(data.txs))
                app.commit()
            t3 = time.perf_counter()
        rec = {"height": h, "t0": t0, "t3": t3, "prepare_s": t1 - t0,
               "process_s": t2 - t1, "commit_s": t3 - t2, "accepted": bool(ok),
               "offered": len(txs), "kept": len(data.txs), "k": data.square_size,
               "fill": sum(len(t) for t in data.txs) / self.config["max_block_bytes"]}
        self.records.append(rec)
        self.blocks.append((h, data))
        return rec

    def window(self, seconds: float, max_heights: int | None = None) -> tuple[float, float]:
        """Runs heights until `seconds` have passed (or `max_heights` ran);
        returns (start, end) on the host clock."""
        start = time.perf_counter()
        for h in range(1, len(self.pool)):
            rec = self._height(h)
            if rec["t3"] - start >= seconds:
                break
            if max_heights is not None and len(self.records) >= max_heights:
                break
        else:
            print(f"bench: the pool of {len(self.pool) - 1} heights ran out before "
                  f"{seconds} s", flush=True)
        return start, self.records[-1]["t3"]

    def free(self) -> None:
        self.proposer = self.validator = None
        self.pool = []

    # --- correctness --------------------------------------------------------------
    def check(self, control: bool = False) -> tuple[dict, int]:
        """The numbers compared with the plain reference, each with its
        limit, and how many heights were rebuilt by the reference: every
        height of the window."""
        t0 = time.perf_counter()
        mismatch = 0
        with ref_dah.hashing_pool(self.config["max_square_size"]) as pool:
            for _, data in self.blocks:
                ods = ref_square.ods_from_txs(list(data.txs),
                                              self.config["max_square_size"])
                eds = ref_dah.extend(ods)
                want = ref_dah.dah(eds, pool=pool)[2]
                got = ref_dah.dah(eds, True, pool)[2] if control else data.hash
                mismatch += int(got != want or data.square_size != ods.shape[0])
        note(f"reference rebuilt {len(self.blocks)} heights in "
             f"{time.perf_counter() - t0:.3f} s")
        rejected = sum(not r["accepted"] for r in self.records)
        dropped = sum(r["offered"] - r["kept"] for r in self.records)
        return {
            "root_mismatch": {"value": mismatch, "limit": 0},
            "rejected": {"value": rejected, "limit": 0},
            "dropped_txs": {"value": dropped, "limit": 0},
        }, len(self.blocks)
