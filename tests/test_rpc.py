"""Serving plane: wire clients, served txsim, multi-process devnet.

Reference parity targets:
  * real servers around the app even in tests
    (test/util/testnode/network.go:38-43, app/app.go:712-735);
  * TxClient speaking to a node over the wire (pkg/user over gRPC);
  * txsim filling blocks against a served node it did not construct;
  * multi-validator block exchange over sockets with app-hash equality.
"""

from __future__ import annotations

import pytest

from celestia_app_tpu.rpc.client import RemoteNode, RPCError
from celestia_app_tpu.rpc.server import ReplicationDivergence, ServingNode, serve
from celestia_app_tpu.shares.namespace import Namespace
from celestia_app_tpu.shares.sparse import Blob
from celestia_app_tpu.state import smt
from celestia_app_tpu.testutil.testnode import deterministic_genesis, funded_keys
from celestia_app_tpu.user.tx_client import TxClient


@pytest.fixture(scope="module")
def served():
    keys = funded_keys(4)
    node = ServingNode(genesis=deterministic_genesis(keys), keys=keys)
    server = serve(node, port=0, block_interval_s=0.1)
    yield node, server, keys
    server.stop()


@pytest.fixture()
def remote(served):
    _, server, _ = served
    return RemoteNode(server.url)


class TestWireBasics:
    def test_status(self, served, remote):
        node, _, _ = served
        st = remote.status()
        assert st["chain_id"] == node.chain_id
        assert st["height"] >= 0

    def test_account_query(self, served, remote):
        _, _, keys = served
        acc = remote.query_account(keys[0].public_key().address())
        assert acc is not None and acc.account_number >= 0
        assert remote.query_account("celestia1unknown") is None

    def test_unknown_method_is_clean_error(self, remote):
        with pytest.raises(RPCError):
            remote.call("no_such_method")

    def test_validators(self, remote):
        vals = remote.validators()
        assert len(vals) == 3 and all(v["power"] == 100 for v in vals)


class TestWireTxClient:
    def test_pfb_over_the_wire(self, served, remote):
        _, _, keys = served
        client = TxClient(remote, [keys[0]])
        blob = Blob(Namespace.v0(b"\x01" * 10), b"wire blob " * 40)
        resp = client.submit_pay_for_blob([blob])
        assert resp.code == 0 and resp.height >= 1

        # The blob's tx is fetchable and provable over the wire.
        block = remote.block(resp.height)
        assert block["square_size"] >= 1
        proof, data_root = remote.tx_inclusion_proof(
            resp.height, len(block["txs"]) - 1
        )
        assert bytes.fromhex(block["data_hash"]) == data_root
        assert proof.verify(data_root)

    def test_send_over_the_wire(self, served, remote):
        from celestia_app_tpu.tx.messages import Coin, MsgSend

        _, _, keys = served
        client = TxClient(remote, [keys[1]])
        to = keys[2].public_key().address()
        resp = client.submit_tx(
            [MsgSend(client.default_address, to, (Coin("utia", 777),))]
        )
        assert resp.code == 0 and resp.height >= 1

    def test_state_proof_over_the_wire(self, served, remote):
        _, _, keys = served
        # Any committed account key must be provable against the app hash.
        proof, app_hash = remote.state_proof(b"nonexistent-key")
        assert proof.value is None
        assert smt.verify(proof, app_hash)


class TestReplication:
    def test_two_served_validators_stay_identical(self):
        keys = funded_keys(4)
        genesis = deterministic_genesis(keys, n_validators=2)
        v1 = ServingNode(genesis=genesis, keys=keys, validator_index=1,
                         n_validators=2)
        s1 = serve(v1, port=0, block_interval_s=None)
        v0 = ServingNode(genesis=genesis, keys=keys, validator_index=0,
                         n_validators=2, peers=[s1.url])
        s0 = serve(v0, port=0, block_interval_s=None)
        try:
            client = TxClient(RemoteNode(s0.url), [keys[0]])
            blob = Blob(Namespace.v0(b"\x02" * 10), b"replicated " * 30)
            with client._lock:
                resp = client._broadcast_pfb([blob], client.default_address)
            for _ in range(3):
                v0.produce_block()
            status = v0.tx_status(resp.tx_hash)
            assert status is not None and status[1] == 0, status
            assert v0.app.height == v1.app.height == 3
            assert v0.app.cms.last_app_hash == v1.app.cms.last_app_hash
            assert [b.hash for b in v0.blocks] == [b.hash for b in v1.blocks]
        finally:
            s0.stop()
            s1.stop()

    def test_lagging_peer_catches_up(self):
        """A peer that missed earlier blocks fetches them from whoever
        serves them before applying the new one (no permanent wedge)."""
        keys = funded_keys(2)
        genesis = deterministic_genesis(keys, n_validators=2)
        v0 = ServingNode(genesis=genesis, keys=keys, validator_index=0,
                         n_validators=2)
        s0 = serve(v0, port=0, block_interval_s=None)
        v1 = ServingNode(genesis=genesis, keys=keys, validator_index=1,
                         n_validators=2, peers=[s0.url])
        s1 = serve(v1, port=0, block_interval_s=None)
        try:
            for _ in range(3):  # v0 advances alone; v1 hears nothing
                v0.produce_block()
            assert v1.app.height == 0
            # Now v1 receives block 4 out of order and must catch up 1-3.
            # Replication carries the proposer's LastCommitInfo (x/slashing
            # input) with the block, exactly as finalize_commit ships it.
            data4, _ = v0.produce_block()
            b4 = v0.rpc_block(4)
            signers = b4["last_commit_signers"]
            reply = v1.apply_block(
                4, b4["time_ns"], data4,
                last_commit_signers=set(signers) if signers is not None else None,
                evidence=v1._parse_evidence(b4["evidence"] or []),
            )
            assert v1.app.height == 4
            assert bytes.fromhex(reply["app_hash"]) == v0.app.cms.last_app_hash
        finally:
            s0.stop()
            s1.stop()

    def test_divergent_peer_detected(self):
        keys = funded_keys(2)
        genesis = deterministic_genesis(keys, n_validators=2)
        v1 = ServingNode(genesis=genesis, keys=keys, validator_index=1,
                         n_validators=2)
        s1 = serve(v1, port=0, block_interval_s=None)
        # Corrupt the replica's state: its app hash must differ.
        v1.app.cms.working.set(b"corrupt", b"state")
        v0 = ServingNode(genesis=genesis, keys=keys, validator_index=0,
                         n_validators=2, peers=[s1.url])
        s0 = serve(v0, port=0, block_interval_s=None)
        try:
            with pytest.raises(ReplicationDivergence):
                v0.produce_block()
        finally:
            s0.stop()
            s1.stop()


@pytest.mark.slow
class TestServedTxsim:
    def test_txsim_fills_blocks_against_foreign_process(self, tmp_path):
        """The VERDICT #5 'done' criterion: txsim drives a node that lives
        in another PROCESS (spawned devnet), reached only over the socket."""
        import os

        from celestia_app_tpu.rpc.devnet import spawn_devnet
        from celestia_app_tpu.txsim.run import BlobSequence, SendSequence, run

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # a devnet is a consensus test
        net = spawn_devnet(n=1, base_port=26930, block_interval_ms=200, env=env)
        try:
            remote = net.client(0)
            keys = funded_keys(4)
            stats = run(
                remote,
                keys[:2],
                [BlobSequence(blob_size=(2_000, 20_000), blobs_per_pfb=(1, 2)),
                 SendSequence()],
                blocks=3,
            )
            assert stats["submitted"] >= 4
            assert stats["failed"] == 0
            st = remote.status()
            assert st["height"] >= 3
            # Blocks actually carry the blobs: a recent block isn't empty.
            found_tx = any(
                remote.block(h)["txs"]
                for h in range(1, st["height"] + 1)
            )
            assert found_tx
        finally:
            net.stop()

    def test_three_validator_devnet_over_sockets(self):
        import os

        from celestia_app_tpu.rpc.devnet import spawn_devnet

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # a devnet is a consensus test
        net = spawn_devnet(n=3, base_port=26940, block_interval_ms=300, env=env)
        try:
            c0 = net.client(0)
            c0.wait_for_height(4, timeout_s=90)
            statuses = [net.client(i).status() for i in range(3)]
            h = min(s["height"] for s in statuses)
            assert h >= 4
            # All validators committed identical chains up to h.
            blocks = [
                [net.client(i).block(j)["data_hash"] for j in range(1, h + 1)]
                for i in range(3)
            ]
            assert blocks[0] == blocks[1] == blocks[2]
            # App hash equality at a common height is enforced by the
            # proposer (ReplicationDivergence), and rotation means every
            # validator proposed at least once by height 4.
        finally:
            net.stop()


class TestSubscribeTx:
    """JSON-RPC long-poll subscription (the websocket /subscribe analog):
    RemoteNode.wait_tx parks server-side on the commit event."""

    def test_subscribe_roundtrip_and_timeout(self, served, remote):
        import time as _time

        node, _, keys = served
        from celestia_app_tpu.tx import tx_hash as compute_hash
        from celestia_app_tpu.tx.messages import Coin, MsgSend
        from celestia_app_tpu.tx.sign import Fee, build_and_sign

        acc = remote.query_account(keys[0].public_key().address())
        raw = build_and_sign(
            [MsgSend(
                keys[0].public_key().address(),
                keys[1].public_key().address(),
                (Coin("utia", 31),),
            )],
            keys[0], node.chain_id, acc.account_number, acc.sequence,
            Fee((Coin("utia", 200_000),), 200_000),
        )
        res = remote.broadcast(raw)
        assert res.code == 0, res.log
        status = remote.wait_tx(compute_hash(raw), timeout_s=30.0)
        assert status is not None and status[1] == 0 and status[0] >= 1

        t0 = _time.monotonic()
        assert remote.wait_tx(b"\x02" * 32, timeout_s=1.2) is None
        assert _time.monotonic() - t0 >= 1.0, "server must park the waiter"
