"""The C bridge wired into a served validator (VERDICT r4 next #6).

$CELESTIA_SQUARE_BACKEND=bridge routes every block's square extension
through the C ABI worker (the reference's pkg/wrapper/nmt_wrapper.go:73-86
host-language seam); the device pipeline is the fallback. Pinned here:

  * a served validator under the bridge backend commits byte-identical
    app hashes and data roots to one on the device backend;
  * SIGKILLing the worker mid-run costs one in-flight call, not the
    chain — the faulted block rides the device fallback, the next block
    re-spawns a fresh worker, and hashes still match the device chain.
"""

from __future__ import annotations

import os
import signal
import subprocess

import pytest

from celestia_app_tpu.da import eds as eds_mod
from celestia_app_tpu.shares import Blob, Namespace
from celestia_app_tpu.rpc.server import ServingNode, serve
from celestia_app_tpu.testutil import deterministic_genesis, funded_keys
from celestia_app_tpu.user import TxClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD_DIR = os.path.join(REPO, "bridge", "build")

pytestmark = pytest.mark.slow  # spawns workers + two served chains


@pytest.fixture(scope="module")
def bridge_lib() -> str:
    subprocess.run(
        ["cmake", "-S", os.path.join(REPO, "bridge"), "-B", BUILD_DIR],
        check=True, capture_output=True,
    )
    subprocess.run(
        ["cmake", "--build", BUILD_DIR], check=True, capture_output=True
    )
    return os.path.join(BUILD_DIR, "libcelestia_square_bridge.so")


def _worker_pids() -> list[int]:
    out = subprocess.run(
        ["pgrep", "-f", "celestia_app_tpu.bridge.worker"],
        capture_output=True, text=True,
    )
    return [int(p) for p in out.stdout.split()]


def _run_chain(keys, n_blocks: int) -> tuple[list[bytes], list]:
    """Serve a validator, push one PFB per block; returns (app hashes,
    committed BlockData) per height.

    Since round 5's RFC 6979 deterministic signing, tx bytes — hence
    data roots — are byte-identical across runs too, so both app hashes
    AND block data hashes are cross-run comparison quantities; bridge
    output is additionally pinned by device-recomputation from each
    run's own committed txs."""
    node = ServingNode(
        genesis=deterministic_genesis(keys, n_validators=1),
        keys=keys, validator_index=0, n_validators=1,
    )
    node.peer_urls = []
    server = serve(node, port=0, block_interval_s=None)  # we drive blocks
    try:
        client = TxClient(node, keys[:1])
        hashes, blocks = [], []
        for i in range(n_blocks):
            resp = client.submit_pay_for_blob(
                [Blob(Namespace.v0(bytes([1 + i]) * 10), b"payload-%d" % i * 64)]
            )
            assert resp.code == 0, resp.log
            hashes.append(node.app.cms.last_app_hash)
            blocks.append(node.blocks[-1])
        return hashes, blocks
    finally:
        server.stop()


def _recompute_data_roots_on_device(blocks) -> None:
    """Every committed block's data root must equal a device-path
    recomputation from its own txs (bridge output == device output)."""
    from celestia_app_tpu.app.extend_block import extend_block
    from celestia_app_tpu.da.dah import DataAvailabilityHeader

    assert eds_mod.square_backend() == "device"
    for data in blocks:
        eds = extend_block(list(data.txs))
        assert eds is not None
        assert DataAvailabilityHeader.from_eds(eds).hash() == data.hash


def test_bridge_backend_matches_device_and_survives_worker_kill(
    bridge_lib, monkeypatch
):
    keys = funded_keys(2)

    # --- reference chain on the device backend ---
    monkeypatch.delenv("CELESTIA_SQUARE_BACKEND", raising=False)
    device_hashes, device_blocks = _run_chain(keys, 4)

    # --- same chain under the bridge backend, with a mid-run worker kill ---
    monkeypatch.setenv("CELESTIA_SQUARE_BACKEND", "bridge")
    monkeypatch.setenv("CELESTIA_BRIDGE_LIB", bridge_lib)
    eds_mod._reset_bridge()
    before = set(_worker_pids())

    node = ServingNode(
        genesis=deterministic_genesis(keys, n_validators=1),
        keys=keys, validator_index=0, n_validators=1,
    )
    node.peer_urls = []
    server = serve(node, port=0, block_interval_s=None)
    bridge_hashes, bridge_blocks = [], []
    try:
        client = TxClient(node, keys[:1])
        for i in range(4):
            if i == 2:
                # SIGKILL the worker mid-run: the in-flight extension must
                # fall back to the device pipeline, the chain must keep
                # committing, and a fresh worker must serve later blocks.
                pids = [p for p in _worker_pids() if p not in before]
                assert pids, "bridge backend never spawned a worker"
                for p in pids:
                    os.kill(p, signal.SIGKILL)
            resp = client.submit_pay_for_blob(
                [Blob(Namespace.v0(bytes([1 + i]) * 10), b"payload-%d" % i * 64)]
            )
            assert resp.code == 0, resp.log
            bridge_hashes.append(node.app.cms.last_app_hash)
            bridge_blocks.append(node.blocks[-1])
        # The worker served blocks 0-1, died at 2, and a fresh one must
        # exist by the final block (the reset-retry contract).
        assert [p for p in _worker_pids() if p not in before], \
            "bridge client never re-spawned a worker after the kill"
    finally:
        server.stop()
        eds_mod._reset_bridge()

    assert bridge_hashes == device_hashes, (
        "bridge-backed chain's app hashes diverged from the device chain"
    )
    # Deterministic signing makes data roots cross-run comparable too:
    # the bridge chain's committed blocks must be byte-identical to the
    # device chain's.
    assert [b.hash for b in bridge_blocks] == [b.hash for b in device_blocks]
    # Bridge-produced data roots must be device-identical for the actual
    # committed squares (including the fallback block at i=2).
    monkeypatch.delenv("CELESTIA_SQUARE_BACKEND")
    _recompute_data_roots_on_device(bridge_blocks)


def test_bridge_fault_falls_back_within_one_call(bridge_lib, monkeypatch):
    """A bridge pointed at a nonexistent lib must cost nothing but a
    stderr line: extend_shares returns the device result immediately."""
    import numpy as np

    from celestia_app_tpu.constants import SHARE_SIZE

    monkeypatch.setenv("CELESTIA_SQUARE_BACKEND", "bridge")
    monkeypatch.setenv("CELESTIA_BRIDGE_LIB", "/nonexistent/lib.so")
    eds_mod._reset_bridge()
    rng = np.random.default_rng(3)
    shares = [
        bytes(rng.integers(0, 256, SHARE_SIZE, dtype=np.uint8))
        for _ in range(4)
    ]
    got = eds_mod.extend_shares(shares)
    monkeypatch.delenv("CELESTIA_SQUARE_BACKEND")
    want = eds_mod.extend_shares(shares)
    assert got.row_roots() == want.row_roots()
    assert got.data_root() == want.data_root()


def test_worker_honors_bridge_platform_pin(bridge_lib, monkeypatch):
    """CELESTIA_BRIDGE_PLATFORM overrides the platform the worker
    inherits (it takes JAX's default otherwise —
    celestia_app_tpu/bridge/worker.py). Regression guard: with the pin
    lost, the worker asks for the inherited TPU platform, which this
    host lacks, and extend_and_dah below fails (ping alone never
    touches a backend)."""
    import numpy as np

    from celestia_app_tpu.bridge.client import BridgeClient
    from celestia_app_tpu.constants import SHARE_SIZE

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("CELESTIA_BRIDGE_PLATFORM", "cpu")
    client = BridgeClient(bridge_lib)
    try:
        assert client.ping()
        rng = np.random.default_rng(2)
        ods = rng.integers(0, 256, (2, 2, SHARE_SIZE), dtype=np.uint8)
        eds, _, _, droot = client.extend_and_dah(ods)
        assert eds.shape == (4, 4, SHARE_SIZE) and len(droot) == 32
    finally:
        client.shutdown()
