"""App-layer tests: the real state machine driven over its ABCI surface.

Tier-2 of the reference test strategy (SURVEY §4: app/test/*): a real App on
an in-memory store, no consensus, ABCI methods called directly.
"""

import numpy as np
import pytest

from celestia_app_tpu.app import App, BlockData
from celestia_app_tpu.constants import PFB_GAS_FIXED_COST
from celestia_app_tpu.crypto import PrivateKey
from celestia_app_tpu.modules.blob.types import estimate_gas, new_msg_pay_for_blobs
from celestia_app_tpu.shares.namespace import Namespace
from celestia_app_tpu.shares.sparse import Blob
from celestia_app_tpu.state.dec import Dec
from celestia_app_tpu.testutil import TestNode, deterministic_genesis, funded_keys
from celestia_app_tpu.tx.envelopes import BlobTx
from celestia_app_tpu.tx.messages import Coin, MsgSend
from celestia_app_tpu.tx.sign import Fee, build_and_sign

RNG = np.random.default_rng(31)


def rand_bytes(n: int) -> bytes:
    return RNG.integers(0, 256, n, dtype=np.uint8).tobytes()


def user_ns(tag: int) -> Namespace:
    return Namespace.v0(bytes([tag]) * 10)


@pytest.fixture()
def node() -> TestNode:
    return TestNode()


def pfb_tx(node: TestNode, key: PrivateKey, blobs, seq: int, gas=None, fee_utia=None):
    addr = key.public_key().address()
    msg = new_msg_pay_for_blobs(addr, list(blobs))
    gas = gas or estimate_gas([len(b.data) for b in blobs])
    fee = Fee((Coin("utia", fee_utia if fee_utia is not None else gas),), gas)
    acct = _account(node, addr)
    raw_tx = build_and_sign([msg], key, node.chain_id, acct.account_number, seq, fee)
    return BlobTx(raw_tx, tuple(blobs)).marshal()


def send_tx(node: TestNode, key: PrivateKey, to: str, amount: int, seq: int):
    addr = key.public_key().address()
    msg = MsgSend(addr, to, (Coin("utia", amount),))
    fee = Fee((Coin("utia", 20_000),), 100_000)
    acct = _account(node, addr)
    return build_and_sign([msg], key, node.chain_id, acct.account_number, seq, fee)


def _account(node: TestNode, addr: str):
    from celestia_app_tpu.state.accounts import AuthKeeper

    return AuthKeeper(node.app.cms.working).get_account(addr)


class TestLifecycle:
    def test_empty_block(self, node):
        data, results = node.produce_block()
        assert data.square_size == 1
        assert results == []
        assert node.app.height == 1

    def test_pfb_end_to_end(self, node):
        key = node.keys[0]
        blobs = (Blob(user_ns(7), rand_bytes(20_000)),)
        res = node.broadcast(pfb_tx(node, key, blobs, seq=0))
        assert res.code == 0, res.log
        data, results = node.produce_block()
        assert len(data.txs) == 1
        assert data.square_size > 1
        [r] = results
        assert r.code == 0, r.log
        assert r.gas_used > 0
        assert any(e[0].endswith("EventPayForBlobs") for e in r.events)

    def test_send_and_balances(self, node):
        a, b = node.keys[0], node.keys[1]
        from celestia_app_tpu.state.accounts import BankKeeper

        addr_b = b.public_key().address()
        before = BankKeeper(node.app.cms.working).balance(addr_b)
        node.broadcast(send_tx(node, a, addr_b, 5000, seq=0))
        _, results = node.produce_block()
        assert results[0].code == 0, results[0].log
        after = BankKeeper(node.app.cms.working).balance(addr_b)
        assert after - before == 5000

    def test_multiple_txs_same_signer(self, node):
        key = node.keys[0]
        to = node.keys[1].public_key().address()
        node.broadcast(send_tx(node, key, to, 100, seq=0))
        node.broadcast(send_tx(node, key, to, 200, seq=1))
        _, results = node.produce_block()
        assert [r.code for r in results] == [0, 0]

    def test_app_hash_deterministic(self):
        hashes = []
        for _ in range(2):
            node = TestNode()
            key = node.keys[0]
            blobs = (Blob(user_ns(3), b"\x42" * 5000),)
            node.broadcast(pfb_tx(node, key, blobs, seq=0))
            node.produce_block()
            hashes.append(node.app.cms.last_app_hash)
        assert hashes[0] == hashes[1]

    def test_fee_deducted(self, node):
        from celestia_app_tpu.state.accounts import BankKeeper, FEE_COLLECTOR

        key = node.keys[0]
        addr = key.public_key().address()
        bank = BankKeeper(node.app.cms.working)
        before = bank.balance(addr)
        blobs = (Blob(user_ns(1), rand_bytes(100)),)
        gas = estimate_gas([100])
        node.broadcast(pfb_tx(node, key, blobs, seq=0, gas=gas, fee_utia=gas))
        node.produce_block()
        bank2 = BankKeeper(node.app.cms.working)
        assert bank2.balance(addr) == before - gas
        assert bank2.balance(FEE_COLLECTOR) >= gas


class TestCheckTx:
    def test_rejects_bad_sequence(self, node):
        key = node.keys[0]
        to = node.keys[1].public_key().address()
        assert node.broadcast(send_tx(node, key, to, 1, seq=5)).code != 0

    def test_rejects_low_fee(self, node):
        key = node.keys[0]
        blobs = (Blob(user_ns(1), rand_bytes(100)),)
        res = node.broadcast(pfb_tx(node, key, blobs, seq=0, fee_utia=0))
        assert res.code != 0

    def test_rejects_insufficient_pfb_gas(self, node):
        key = node.keys[0]
        blobs = (Blob(user_ns(1), rand_bytes(100_000)),)
        res = node.broadcast(pfb_tx(node, key, blobs, seq=0, gas=80_000, fee_utia=80_000))
        assert res.code != 0

    def test_rejects_tampered_blob(self, node):
        key = node.keys[0]
        blob = Blob(user_ns(1), rand_bytes(500))
        raw = pfb_tx(node, key, (blob,), seq=0)
        from celestia_app_tpu.tx.envelopes import unmarshal_blob_tx

        btx = unmarshal_blob_tx(raw)
        evil = BlobTx(btx.tx, (Blob(blob.namespace, blob.data[:-1] + b"\x00"),)).marshal()
        assert node.broadcast(evil).code != 0


class TestProcessProposal:
    def _valid_proposal(self, node):
        key = node.keys[0]
        blobs = (Blob(user_ns(5), rand_bytes(3000)),)
        node.broadcast(pfb_tx(node, key, blobs, seq=0))
        return node.app.prepare_proposal(node.mempool.reap())

    def test_accepts_own_proposal(self, node):
        data = self._valid_proposal(node)
        assert node.app.process_proposal(data)

    def test_rejects_wrong_data_hash(self, node):
        data = self._valid_proposal(node)
        bad = BlockData(data.txs, data.square_size, bytes(32))
        assert not node.app.process_proposal(bad)

    def test_own_root_memo_skips_pipeline_but_still_validates(self, node, monkeypatch):
        """Process on bytes this node just prepared must NOT re-run the
        device pipeline (the round-5 own-root memo), yet a wrong claimed
        hash over those same bytes is still rejected — the memo serves
        OUR computed root for comparison, never the proposer's claim."""
        from celestia_app_tpu.app import app as app_mod

        data = self._valid_proposal(node)  # prepare warmed the memo
        calls = []
        orig = app_mod.extend_shares
        monkeypatch.setattr(
            app_mod, "extend_shares",
            lambda shares: calls.append(len(shares)) or orig(shares),
        )
        assert node.app.process_proposal(data)
        assert calls == [], "memo hit must skip the device pipeline"
        bad = BlockData(data.txs, data.square_size, b"\x13" * 32)
        assert not node.app.process_proposal(bad)
        assert calls == [], "rejection rides the same memoized root"

    def test_rejects_wrong_square_size(self, node):
        data = self._valid_proposal(node)
        bad = BlockData(data.txs, data.square_size * 2, data.hash)
        assert not node.app.process_proposal(bad)

    def test_rejects_tampered_blob(self, node):
        from celestia_app_tpu.tx.envelopes import unmarshal_blob_tx

        data = self._valid_proposal(node)
        btx = unmarshal_blob_tx(data.txs[0])
        evil_blob = Blob(btx.blobs[0].namespace, btx.blobs[0].data[:-1] + b"\x99")
        evil = BlobTx(btx.tx, (evil_blob,)).marshal()
        bad = BlockData((evil,), data.square_size, data.hash)
        assert not node.app.process_proposal(bad)

    def test_rejects_unsigned_injected_tx(self, node):
        data = self._valid_proposal(node)
        other = PrivateKey.from_seed(b"mallory")
        msg = MsgSend(
            other.public_key().address(), other.public_key().address(), (Coin("utia", 1),)
        )
        fake = build_and_sign([msg], other, node.chain_id, 99, 0, Fee((Coin("utia", 9000),), 90_000))
        bad = BlockData((fake,) + data.txs, data.square_size, data.hash)
        assert not node.app.process_proposal(bad)


class TestFilterTxs:
    def test_drops_invalid_keeps_valid(self, node):
        key = node.keys[0]
        to = node.keys[1].public_key().address()
        good = send_tx(node, key, to, 100, seq=0)
        bad_sig = good[:-10] + rand_bytes(10)
        data = node.app.prepare_proposal([bad_sig, good, rand_bytes(80)])
        assert data.txs == (good,)


class TestMultiSend:
    """MsgMultiSend (sdk bank): single input fanned to many outputs in one
    tx; sum mismatches and multi-input msgs reject statelessly (the
    single-input rule — this chain's ante admits one signer per tx)."""

    def _submit(self, node, key, msg, seq):
        addr = key.public_key().address()
        acct = _account(node, addr)
        raw = build_and_sign(
            [msg], key, node.chain_id, acct.account_number, seq,
            Fee((Coin("utia", 20_000),), 200_000),
        )
        return node.broadcast(raw), raw

    def test_multisend_fans_out_one_block(self, node):
        from celestia_app_tpu.state.accounts import BankKeeper
        from celestia_app_tpu.tx.messages import BankIO, MsgMultiSend

        key = node.keys[0]
        src = key.public_key().address()
        a = node.keys[1].public_key().address()
        b = PrivateKey.from_seed(b"fresh-multisend").public_key().address()
        msg = MsgMultiSend(
            inputs=(BankIO(src, (Coin("utia", 1_000),)),),
            outputs=(
                BankIO(a, (Coin("utia", 700),)),
                BankIO(b, (Coin("utia", 300),)),
            ),
        )
        bank0 = BankKeeper(node.app.cms.working)
        bal_a = bank0.balance(a)
        res, _ = self._submit(node, key, msg, seq=0)
        assert res.code == 0, res.log
        node.produce_block()
        bank = BankKeeper(node.app.cms.working)
        assert bank.balance(a) == bal_a + 700
        assert bank.balance(b) == 300
        # The fresh recipient exists as an account (create-on-receive).
        from celestia_app_tpu.state.accounts import AuthKeeper

        assert AuthKeeper(node.app.cms.working).get_account(b) is not None

    def test_multisend_rejections(self, node):
        from celestia_app_tpu.tx.messages import BankIO, MsgMultiSend

        key = node.keys[0]
        src = key.public_key().address()
        to = node.keys[1].public_key().address()
        mismatch = MsgMultiSend(
            inputs=(BankIO(src, (Coin("utia", 10),)),),
            outputs=(BankIO(to, (Coin("utia", 9),)),),
        )
        res, _ = self._submit(node, key, mismatch, seq=0)
        assert res.code != 0 and "sum inputs" in res.log

        two_senders = MsgMultiSend(
            inputs=(
                BankIO(src, (Coin("utia", 5),)),
                BankIO(to, (Coin("utia", 5),)),
            ),
            outputs=(BankIO(to, (Coin("utia", 10),)),),
        )
        res, _ = self._submit(node, key, two_senders, seq=0)
        assert res.code != 0 and "multiple senders" in res.log


class TestLayerSpans:
    """A height's host phases as spans: a proposer App prepares, a second
    App (a validator that never saw the square) processes, both finalize
    and commit.  Every span row carries the height and the side."""

    CHILDREN = ("share_pack", "square_digest", "ods_upload",
                "extend_dispatch", "roots_wait")

    since = 0  # rows of earlier tests (same heights) are left out

    def _rows(self, name: str, height: int,
              phase: str | None = None) -> list[dict]:
        from celestia_app_tpu.trace.tracer import traced

        return [r for r in traced().table(name) if r.get("height") == height
                and r.get("start_ns", r["ts_ns"]) >= self.since
                and (phase is None or r.get("phase") == phase)]

    def test_prepare_and_process_write_every_layer_span(self, tmp_path):
        import time

        import jax

        self.since = time.time_ns()
        proposer, validator = TestNode(), TestNode()
        key = proposer.keys[0]
        blobs = (Blob(user_ns(9), rand_bytes(6000)),
                 Blob(user_ns(10), rand_bytes(900)))
        raw = pfb_tx(proposer, key, blobs, seq=0)
        # Under a profiler session, as a traced run: rows carry cpu_ms.
        jax.profiler.start_trace(str(tmp_path))
        try:
            data = proposer.app.prepare_proposal([raw])
            assert 1 < data.square_size <= 8
            assert validator.app.process_proposal(data)
            height = proposer.app.height + 1
            for app in (proposer.app, validator.app):
                app.finalize_block(proposer.app.last_block_time_ns + 10**9,
                                   list(data.txs))
                app.commit()
        finally:
            jax.profiler.stop_trace()

        for phase in ("prepare", "process"):
            for name in ("ante", "blob_validate", "square_pipeline",
                         *self.CHILDREN):
                rows = self._rows(name, height, phase)
                assert rows, (name, phase)
                for r in rows:
                    assert 0 <= r["cpu_ms"] <= r["duration_ms"] + 1e-3
            [pipe] = self._rows("square_pipeline", height, phase)
            assert pipe["memo"] == "miss"
            children = sum(r["duration_ms"] for name in self.CHILDREN
                           for r in self._rows(name, height, phase))
            assert 0 < children <= pipe["duration_ms"]
            # Every child lies inside the parent's interval.
            for name in self.CHILDREN:
                for r in self._rows(name, height, phase):
                    assert pipe["start_ns"] <= r["start_ns"] <= r["end_ns"] \
                        <= pipe["end_ns"]
        assert self._rows("square_build", height, "prepare")
        assert self._rows("square_construct", height, "process")
        [proposed] = self._rows("blob_validate", height, "prepare")
        [checked] = self._rows("blob_validate", height, "process")
        assert proposed["n_blobs"] == checked["n_blobs"] == 2
        # The validator's commitments come from the process-wide memo the
        # proposer filled (both Apps share one process).
        assert checked["memo_hits"] == 2
        assert len(self._rows("finalize_block", height)) == 2
        assert len(self._rows("commit", height)) == 2
        # The journal's upload/dispatch timings are the spans' own.
        from celestia_app_tpu.trace.tracer import traced

        journal = [r for r in traced().table("block_journal")
                   if r.get("height") == height and r["source"] == "compute"
                   and r["ts_ns"] >= self.since]
        uploads = sorted(r["duration_ms"]
                         for r in self._rows("ods_upload", height))
        assert sorted(r["upload_ms"] for r in journal) == uploads

    def test_own_root_memo_hit_is_marked(self, node):
        import time

        self.since = time.time_ns()
        key = node.keys[0]
        data = node.app.prepare_proposal(
            [pfb_tx(node, key, (Blob(user_ns(11), rand_bytes(700)),), seq=0)])
        assert node.app.process_proposal(data)
        [pipe] = self._rows("square_pipeline", node.app.height + 1, "process")
        assert pipe["memo"] == "hit"
