"""Gossip consensus end-to-end: the round machine over the p2p flood.

VERDICT r2 "Done" criteria these tests pin:
  * item 2 (multi-round BFT): a devnet that loses the height-H proposer
    still commits H, in round >= 1, and keeps going;
  * item 3 (gossip, not push): a tx submitted to a NON-proposer lands in
    a block with the submitter never talking to the proposer; votes reach
    quorum with no proposer HTTP push anywhere (there is no push path in
    gossip mode at all); multi-hop relay crosses a ring topology where
    most peers are not directly connected.

In-process variant (fast, deterministic-ish): ServingNodes with
ConsensusDriver in one process.  Process-level variants (kill -9 the
proposer) live in TestDevnetGossip and are marked slow.
"""

from __future__ import annotations

import time

import pytest

from celestia_app_tpu.rpc.client import RemoteNode
from celestia_app_tpu.rpc.server import ServingNode, serve
from celestia_app_tpu.testutil.testnode import deterministic_genesis, funded_keys


def _gossip_cluster(n_live: int, n_validators: int, interval_s: float = 0.1,
                    topology: dict[int, list[int]] | None = None):
    """n_live served gossip validators of an n_validators genesis."""
    keys = funded_keys(3)
    nodes, servers = [], []
    for i in range(n_live):
        node = ServingNode(
            genesis=deterministic_genesis(keys, n_validators=n_validators),
            keys=keys,
            validator_index=i,
            n_validators=n_validators,
        )
        node.enable_gossip_consensus(interval_s=interval_s)
        servers.append(serve(node, port=0, block_interval_s=None))
        nodes.append(node)
    for i, node in enumerate(nodes):
        if topology is None:
            node.peer_urls = [s.url for j, s in enumerate(servers) if j != i]
        else:
            node.peer_urls = [servers[j].url for j in topology[i]]
    return keys, nodes, servers


def _wait_height(nodes, h: int, timeout_s: float = 30.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(n.app.height >= h for n in nodes):
            return
        time.sleep(0.05)
    raise TimeoutError(
        f"heights {[n.app.height for n in nodes]} never all reached {h}"
    )


class TestGossipRounds:
    def test_full_mesh_advances_and_agrees(self):
        keys, nodes, servers = _gossip_cluster(3, 3)
        try:
            for n in nodes:
                n.consensus_driver.start()
            _wait_height(nodes, 4)
            h = min(n.app.height for n in nodes)
            assert len({n.app.cms.app_hash_at(h) for n in nodes}) == 1
            # Commit records verify against the validator set and carry
            # the attested block time.
            rec = nodes[0]._commits[h]
            assert rec.time_ns > 0
            from celestia_app_tpu.consensus import verify_commit

            vals = nodes[0]._validator_set()
            assert verify_commit(vals, nodes[0].chain_id, rec)
        finally:
            for s in servers:
                s.stop()

    def test_dead_proposer_height_commits_in_later_round(self):
        """4-validator genesis, 3 live: every 4th height's round-0
        proposer is the dead validator, so those heights MUST commit in a
        round >= 1 — the property the single-round plane could not
        provide (a crashed proposer halted the chain)."""
        keys, nodes, servers = _gossip_cluster(3, 4)
        try:
            for n in nodes:
                n.consensus_driver.start()
            _wait_height(nodes, 5, timeout_s=60.0)
            # Identify heights whose ROUND-0 proposer was the dead
            # validator (index 3): rotation order is sorted(addresses)
            # shifted by height-1.
            later_round = [
                h for h, rec in sorted(nodes[0]._commits.items())
                if rec.round >= 1
            ]
            assert later_round, (
                "expected at least one height to commit in round >= 1 "
                f"(rounds: {[(h, r.round) for h, r in sorted(nodes[0]._commits.items())]})"
            )
            # And agreement held throughout.
            h = min(n.app.height for n in nodes)
            assert len({n.app.cms.app_hash_at(h) for n in nodes}) == 1
        finally:
            for s in servers:
                s.stop()

    def test_ring_topology_multi_hop_relay(self):
        """A ring (each node peers ONLY with its two neighbors): proposals
        and votes must cross multiple hops to reach quorum; a tx submitted
        to one node must reach proposers it is not connected to."""
        keys, nodes, servers = _gossip_cluster(
            4, 4, topology={0: [1, 3], 1: [0, 2], 2: [1, 3], 3: [2, 0]}
        )
        try:
            for n in nodes:
                n.consensus_driver.start()
            _wait_height(nodes, 3, timeout_s=60.0)
            # Submit a tx to node 2 only; node 2's peers are {1, 3} — the
            # height rotation guarantees some proposer is NOT among them.
            from celestia_app_tpu.state.accounts import AuthKeeper
            from celestia_app_tpu.tx.messages import Coin, MsgSend
            from celestia_app_tpu.tx.sign import Fee, build_and_sign

            sender = keys[0]
            addr = sender.public_key().address()
            with nodes[2].lock:
                acct = AuthKeeper(nodes[2].app.cms.working).get_account(addr)
            raw = build_and_sign(
                [MsgSend(addr, keys[1].public_key().address(),
                         (Coin("utia", 17),))],
                sender, nodes[2].chain_id, acct.account_number, acct.sequence,
                Fee((Coin("utia", 20_000),), 200_000),
            )
            res = nodes[2].broadcast(raw)
            assert res.code == 0, res.log
            from celestia_app_tpu.tx import tx_hash

            want = tx_hash(raw)
            deadline = time.monotonic() + 30
            status = None
            while time.monotonic() < deadline and status is None:
                with nodes[0].lock:
                    status = nodes[0].tx_status(want)
                time.sleep(0.05)
            assert status is not None, "tx never committed via ring relay"
            assert status[1] == 0, status
        finally:
            for s in servers:
                s.stop()

    def test_divergent_node_cannot_reach_quorum_but_honest_majority_advances(self):
        """A node whose state silently diverged computes different block
        ids: its votes never join the honest vote sets.  With 3 honest of
        4 total, the chain still advances — without the divergent node's
        signatures in the commits."""
        keys, nodes, servers = _gossip_cluster(4, 4)
        try:
            # Corrupt node 3's state before the chain starts.
            with nodes[3].lock:
                nodes[3].app.cms.working.set(b"evil/divergence", b"\x01")
            for n in nodes:
                n.consensus_driver.start()
            _wait_height(nodes[:3], 3, timeout_s=60.0)
            honest = {nodes[i].app.cms.app_hash_at(2) for i in range(3)}
            assert len(honest) == 1
            # The divergent node's operator address appears in no commit.
            div_addr = nodes[3]._operator_address()
            for h, rec in nodes[0]._commits.items():
                assert all(v.validator != div_addr for v in rec.precommits), h
        finally:
            for s in servers:
                s.stop()


@pytest.mark.slow
class TestDevnetGossip:
    def test_kill_proposer_devnet_recovers(self, tmp_path):
        """Process-level proposer failure: SIGKILL one devnet validator of
        four; the remaining three keep committing (the dead validator's
        proposer heights commit in later rounds)."""
        import os
        import signal

        from celestia_app_tpu.rpc.devnet import spawn_devnet

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        net = spawn_devnet(
            n=4, base_port=27210, block_interval_ms=150, mode="gossip", env=env
        )
        try:
            c0 = RemoteNode(net.urls[0], defer_status=True)
            c0.wait_for_height(2, timeout_s=90.0)
            # Kill validator 3's PROCESS outright (not a graceful stop).
            net.procs[3].send_signal(signal.SIGKILL)
            net.procs[3].wait(timeout=10)
            h0 = c0.status()["height"]
            # The chain must advance AT LEAST 5 more heights without it —
            # including heights where the dead node was round-0 proposer.
            c0.wait_for_height(h0 + 5, timeout_s=120.0)
            # All survivors agree.
            hts = []
            for u in net.urls[:3]:
                st = RemoteNode(u, defer_status=True).status()
                hts.append((st["height"], st["app_hash"]))
            target = min(h for h, _ in hts)
            hashes = set()
            for u in net.urls[:3]:
                b = RemoteNode(u, defer_status=True).call("block", height=target)
                hashes.add(b["data_hash"])
            assert len(hashes) == 1
        finally:
            net.stop()
