"""DAS proof round-trips: golden-pinned ShareProofs over the whole
extended square, both RS constructions, batched-vs-host bit identity.

The proof-serving plane's correctness surface (serve/ + proof/):

  * every EDS coordinate — all four quadrants, parity included — proves
    against the committed DAH data root via the existing
    ShareProof.verify, at k in {2, 8, 32} under BOTH RS constructions;
  * namespace-ranged proofs spanning row boundaries verify and reject
    tampering;
  * the batched forest-gather lowering and the pure-host rebuild produce
    byte-identical proof bytes (the serve plane's exactness seam);
  * canonical payload bytes are GOLDEN-pinned for a deterministic square
    so a silent change to proof layout, digest semantics, or the wire
    codec fails loudly;
  * the indexing twins (merkle.path_from_levels vs merkle.proof;
    nmt.range_proof_node_coords vs the prove_range walk) are pinned
    byte-identical — the equivalence everything above leans on.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from celestia_app_tpu import merkle
from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
from celestia_app_tpu.da.eds import ExtendedDataSquare
from celestia_app_tpu.proof.share_proof import (
    new_namespace_proof,
    new_share_sample_proof,
    ods_namespace_range,
)
from celestia_app_tpu.rpc.codec import share_proof_from_json, to_jsonable
from celestia_app_tpu.serve.api import render
from celestia_app_tpu.serve.cache import ForestCache
from celestia_app_tpu.serve.sampler import ProofSampler


def det_square(k: int, seed: int = 1) -> np.ndarray:
    """Deterministic namespace-ordered ODS (the loadgen/soak shape)."""
    rng = np.random.default_rng(seed)
    ns = np.sort(rng.integers(0, 128, k * k).astype(np.uint8))
    ods = rng.integers(0, 256, (k * k, SHARE_SIZE), dtype=np.uint8)
    ods[:, :NAMESPACE_SIZE] = 0
    ods[:, NAMESPACE_SIZE - 1] = ns
    return ods.reshape(k, k, SHARE_SIZE)


CONSTRUCTIONS = ("vandermonde", "leopard")


_SQUARES: dict = {}


@pytest.fixture(scope="module")
def squares():
    """Lazy {(k, construction): eds} factory — k=32 compiles only when a
    slow-marked test asks for it, keeping the fast tier inside budget."""

    def get(k: int, construction: str):
        key = (k, construction)
        if key not in _SQUARES:
            _SQUARES[key] = ExtendedDataSquare.compute(
                det_square(k), construction
            )
        return _SQUARES[key]

    return get


def _quadrant_roundtrip(eds, k: int, construction: str) -> None:
    root = eds.data_root()
    n = 2 * k
    # One coordinate per quadrant plus the square's corners.
    coords = {
        (0, 0), (k - 1, k - 1),          # Q0
        (0, n - 1), (k - 1, k),           # Q1 (row parity)
        (n - 1, 0), (k, k - 1),           # Q2 (col parity)
        (n - 1, n - 1), (k, k),           # Q3 (parity of parity)
    }
    for row, col in coords:
        proof = new_share_sample_proof(eds, row, col)
        assert proof.verify(root), (k, construction, row, col)
        # Wire round-trip: the reconstructed dataclass verifies too
        # (the light-client contract).
        wired = share_proof_from_json(to_jsonable(proof))
        assert wired.verify(root)
        assert wired == proof


class TestSampleRoundTrips:
    @pytest.mark.parametrize("k", [2, 8])
    @pytest.mark.parametrize("construction", CONSTRUCTIONS)
    def test_every_quadrant_proves_to_the_data_root(
        self, squares, k, construction
    ):
        eds = squares(k, construction)
        _quadrant_roundtrip(eds, k, construction)

    @pytest.mark.slow
    @pytest.mark.parametrize("construction", CONSTRUCTIONS)
    def test_k32_round_trips(self, squares, construction):
        """The k=32 leg of the {2, 8, 32} matrix: round-trips AND the
        batched-vs-host seam (slow: two k=32 pipeline compiles)."""
        eds = squares(32, construction)
        _quadrant_roundtrip(eds, 32, construction)
        cache = ForestCache(heights=8, spill=8)
        entry = cache.put(("k32", construction), eds)
        sampler = ProofSampler()
        rng = np.random.default_rng(32)
        coords = sorted({
            (int(rng.integers(0, 64)), int(rng.integers(0, 64)))
            for _ in range(12)
        })
        root = eds.data_root()
        for (row, col), proof in zip(
            coords, sampler.sample_batch(entry, coords)
        ):
            assert proof == sampler.host_proof(entry, row, col)
            assert proof.verify(root)

    @pytest.mark.parametrize("construction", CONSTRUCTIONS)
    def test_column_axis_round_trips(self, squares, construction):
        """axis="col": the share proves through its COLUMN tree, whose
        root is a second-half leaf of the data-root tree — same verifier,
        and batched/host stay bit-identical on the column forest too."""
        eds = squares(8, construction)
        root = eds.data_root()
        coords = [(0, 0), (3, 11), (12, 5), (15, 15)]
        for row, col in coords:
            proof = new_share_sample_proof(eds, row, col, axis="col")
            assert proof.verify(root), (construction, row, col)
            assert proof.row_proof.start_row == 16 + col  # col-root leaf
            wired = share_proof_from_json(to_jsonable(proof))
            assert wired.verify(root) and wired == proof
        cache = ForestCache(heights=4, spill=4)
        entry = cache.put(("colaxis", construction), eds)
        sampler = ProofSampler()
        for (row, col), proof in zip(
            coords, sampler.sample_batch(entry, coords, axis="col")
        ):
            assert proof == sampler.host_proof(entry, row, col, axis="col")
            assert proof.verify(root)

    def test_bad_axis_raises(self, squares):
        eds = squares(2, "vandermonde")
        with pytest.raises(ValueError):
            new_share_sample_proof(eds, 0, 0, axis="diagonal")

    def test_wrong_root_and_tampered_share_fail(self, squares):
        eds = squares(8, "vandermonde")
        proof = new_share_sample_proof(eds, 9, 3)  # a parity coordinate
        assert not proof.verify(b"\x00" * 32)
        from dataclasses import replace

        bad = replace(
            proof, data=(proof.data[0][:100] + b"\x5a" + proof.data[0][101:],)
        )
        assert not bad.verify(eds.data_root())

    def test_out_of_square_coordinates_raise(self, squares):
        eds = squares(2, "vandermonde")
        with pytest.raises(ValueError):
            new_share_sample_proof(eds, 4, 0)
        with pytest.raises(ValueError):
            new_share_sample_proof(eds, 0, -1)


class TestNamespaceRanges:
    @pytest.mark.parametrize("construction", CONSTRUCTIONS)
    def test_ranges_spanning_row_boundaries_verify(self, construction):
        # One namespace repeated often enough to cross several rows.
        k = 8
        ods = det_square(k, seed=3).reshape(k * k, SHARE_SIZE)
        ods[10:40, NAMESPACE_SIZE - 1] = 200  # 30 shares (draws stay < 128)
        ods[:, NAMESPACE_SIZE - 1] = np.sort(ods[:, NAMESPACE_SIZE - 1])
        eds = ExtendedDataSquare.compute(
            ods.reshape(k, k, SHARE_SIZE), construction
        )
        ns = bytes(28) + b"\xc8"  # namespace 200
        rng = ods_namespace_range(eds, ns)
        assert rng is not None and rng[1] - rng[0] == 30
        assert rng[0] // k != (rng[1] - 1) // k  # genuinely multi-row
        proof = new_namespace_proof(eds, ns)
        assert len(proof.share_proofs) >= 3  # one NMT proof per row
        assert proof.verify(eds.data_root())

    def test_absent_namespace_returns_none(self, squares):
        eds = squares(8, "vandermonde")
        assert new_namespace_proof(eds, b"\xee" * NAMESPACE_SIZE) is None

    def test_range_memoizes_row_trees_on_the_handle(self, squares):
        # An m-row range pays at most m tree builds per HANDLE: repeat
        # queries hit the memo (satellite: not m x shares, not per call).
        eds = ExtendedDataSquare.compute(det_square(8, seed=4))
        before = len(eds._tree_memo)
        ns = bytes(eds.ods_namespaces()[20].tobytes())
        new_namespace_proof(eds, ns)
        after_first = len(eds._tree_memo)
        assert after_first > before
        new_namespace_proof(eds, ns)
        assert len(eds._tree_memo) == after_first  # second query: all memo


#: (k, construction, batch, axis): the 12-draw sets on both axes, then
#: every gather bucket up to 16 samples and both edges of its padding.
_BATCHED_CASES = [
    pytest.param(k, c, None, axis,
                 id=f"{c}-{k}" + ("" if axis == "row" else "-col"))
    for axis in ("row", "col") for c in CONSTRUCTIONS for k in (2, 8)
] + [
    pytest.param(8, c, batch, axis, id=f"{c}-8-b{batch}-{axis}")
    for c in CONSTRUCTIONS for batch in (1, 2, 7, 8, 9, 16)
    for axis in ("row", "col")
]


class TestBatchedHostIdentity:
    """The serve plane's exactness seam: forest gathers vs host rebuild."""

    @pytest.mark.parametrize("k, construction, batch, axis", _BATCHED_CASES)
    def test_batched_equals_host_bit_for_bit(
        self, squares, k, construction, batch, axis
    ):
        eds = squares(k, construction)
        cache = ForestCache(heights=8, spill=8)
        entry = cache.put((k, CONSTRUCTIONS.index(construction)), eds)
        sampler = ProofSampler()
        n = 2 * k
        if batch is None:
            rng = np.random.default_rng(k)
            coords = sorted({
                (int(rng.integers(0, n)), int(rng.integers(0, n)))
                for _ in range(12)
            })
        else:
            picks = np.random.default_rng([k, batch]).choice(
                n * n, batch, replace=False
            )
            coords = [(int(i) // n, int(i) % n) for i in picks]
        batched = sampler.sample_batch(entry, coords, axis=axis)
        assert len(batched) == len(coords)
        root = eds.data_root()
        for (row, col), proof in zip(coords, batched):
            host = sampler.host_proof(entry, row, col, axis)
            assert proof == host, (k, construction, row, col)
            assert render(to_jsonable(proof)) == render(to_jsonable(host))
            assert proof.verify(root)

    def test_spilled_entry_serves_identical_bytes(self):
        eds = ExtendedDataSquare.compute(det_square(8, seed=6))
        cache = ForestCache(heights=1, spill=2)
        entry = cache.put(1, eds)
        sampler = ProofSampler()
        device_proofs = sampler.sample_batch(entry, [(0, 0), (9, 13)])
        # Evict height 1 to the host tier; same entry object, numpy arrays.
        cache.put(2, ExtendedDataSquare.compute(det_square(8, seed=7)))
        spilled, tier = cache.get(1)
        assert tier == "host" and spilled is entry
        assert not entry.device_resident
        host_tier_proofs = sampler.sample_batch(entry, [(0, 0), (9, 13)])
        assert host_tier_proofs == device_proofs


def _serve_entry(spilled: bool):
    """A k=8 entry on the device tier, or spilled to the host tier, and
    its square."""
    eds = ExtendedDataSquare.compute(det_square(8, seed=12))
    cache = ForestCache(heights=1, spill=2)
    entry = cache.put(1, eds)
    if spilled:
        cache.put(2, ExtendedDataSquare.compute(det_square(8, seed=13)))
        assert cache.get(1) == (entry, "host")
    return entry, eds


class TestServeGather:
    """The compiled serve gather: what every reader of a retained height
    gets back, and what one sampled batch dispatches."""

    @pytest.mark.parametrize("tier", ["device", "host"])
    def test_gathers_return_the_resident_bytes(self, tier):
        entry, eds = _serve_entry(spilled=tier == "host")
        assert entry.device_resident == (tier == "device")
        n = 2 * entry.k
        square = np.asarray(eds._eds)
        for axis in ("row", "col"):
            forest = np.asarray(entry._flat(axis))[:, :90]
            for count in (0, 1, 7, 8, 9, 2 * n - 1):
                idx = [(i * 37) % forest.shape[0] for i in range(count)]
                got = entry.gather(axis, idx)
                assert got.shape == (count, 90) and got.dtype == np.uint8
                assert np.array_equal(got, forest[idx])
            for tree in (0, n - 1):
                host = (eds.row_tree(tree, host=True) if axis == "row"
                        else eds.col_tree(tree, host=True)).levels()
                assert entry.line_levels(axis, tree) == host
                leaves = entry.gather(axis, [
                    entry.flat_index(tree, 0, i) for i in range(n)
                ])
                assert [bytes(x.tobytes()) for x in leaves] == host[0]
        for count in (0, 1, 3, 16, 17):
            coords = [((i * 5) % n, (i * 11) % n) for i in range(count)]
            got = entry.gather_shares(coords)
            assert got.shape == (count, SHARE_SIZE)
            want = np.asarray([square[r, c] for r, c in coords], np.uint8)
            assert np.array_equal(got, want.reshape(count, SHARE_SIZE))
        nodes, shares = entry.gather_proof("col", [3, 5], [(1, 2)])
        assert np.array_equal(nodes, np.asarray(entry.col_flat)[[3, 5], :90])
        assert np.array_equal(shares, square[[1], [2]])

    def test_one_program_per_batch_and_no_recompile_in_a_bucket(
        self, monkeypatch
    ):
        import time

        from celestia_app_tpu.serve import cache as cache_mod
        from celestia_app_tpu.trace.tracer import traced

        built = cache_mod.take_fn
        calls: list[tuple] = []

        def counting(*key):
            fn = built(*key)

            def dispatch(*args):
                calls.append(key)
                return fn(*args)

            return dispatch

        monkeypatch.setattr(cache_mod, "take_fn", counting)
        entry, _ = _serve_entry(spilled=False)
        sampler = ProofSampler()
        since = time.time_ns()
        sampler.sample_batch(entry, [(0, 1), (2, 3), (4, 5)])
        assert len(calls) == 1
        key = calls[0]
        assert key[1:3] == (4 * 4, 4)  # 4 levels x bucket 4, bucket 4
        compiled = built(*key)._cache_size()
        for coords in ([(6, 7), (8, 9), (1, 1), (15, 0)], [(3, 3)] * 3):
            sampler.sample_batch(entry, coords, axis="col")
        assert calls == [key] * 3
        assert built(*key)._cache_size() == compiled
        rows = [r for r in traced().table("proof_gather")
                if r.get("start_ns", r["ts_ns"]) >= since]
        assert [(r["tier"], r["programs"]) for r in rows] == [("device", 1)] * 3
        spilled, _ = _serve_entry(spilled=True)
        sampler.sample_batch(spilled, [(0, 1)])
        assert len(calls) == 3  # the host tier dispatches nothing
        assert traced().table("proof_gather")[-1]["programs"] == 0


class TestGoldenPins:
    """Canonical payload bytes pinned for the deterministic k=8 square —
    any silent change to proof layout, NMT digest semantics, the merkle
    audit path, or the wire codec moves these digests."""

    ROOTS = {
        "vandermonde":
            "1383e9f9ad9f7b01e37f9f0928087136ca4dcd254779f6d47c91a5a0720f3626",
        "leopard":
            "1d689b0e786d39dcd1e7a7c52ba20fbd16c33dbacbf7965b7cdde2d13b1657f5",
    }
    SAMPLE_3_11 = {
        "vandermonde":
            "43147e47f167ac87c90e408127e212d601e856397dc673d2e265824194fcbd04",
        "leopard":
            "c9b208db2f8f23623b4d9c47b5079b3099c840587935152f386c91bb9d8dee0d",
    }
    NS_PROOF = {
        "vandermonde":
            "3fc7f5be55807dc4fc7bc2dad9cb88444de4c0ccce56ceb6d20999b849b85e0d",
        "leopard":
            "cd1c091c5ea3604cd2ebf49e0e2251a4f3e76e36b16bf38da5a2d0fa241c5ff2",
    }

    @pytest.mark.parametrize("construction", CONSTRUCTIONS)
    def test_golden_sample_and_namespace_payloads(self, squares, construction):
        eds = squares(8, construction)
        assert eds.data_root().hex() == self.ROOTS[construction]
        sample = new_share_sample_proof(eds, 3, 11)
        assert (
            hashlib.sha256(render(to_jsonable(sample))).hexdigest()
            == self.SAMPLE_3_11[construction]
        )
        ns = bytes(28) + b"\x25"
        nsp = new_namespace_proof(eds, ns)
        assert (
            hashlib.sha256(render(to_jsonable(nsp))).hexdigest()
            == self.NS_PROOF[construction]
        )

    def test_batched_path_reproduces_the_golden_bytes(self, squares):
        # The pins above were produced by the HOST constructors; the
        # batched sampler must land on the same bytes.
        eds = squares(8, "vandermonde")
        entry = ForestCache(heights=1, spill=1).put(1, eds)
        proof = ProofSampler().sample_batch(entry, [(3, 11)])[0]
        assert (
            hashlib.sha256(render(to_jsonable(proof))).hexdigest()
            == self.SAMPLE_3_11["vandermonde"]
        )


class TestIndexingTwins:
    """The aligned-indexing equivalences the batched path is built on."""

    def test_merkle_path_from_levels_matches_recursive_proof(self):
        items = [bytes([i]) * 90 for i in range(32)]
        levels = merkle.levels_from_leaves(items)
        for i in range(32):
            assert merkle.path_from_levels(levels, i) == merkle.proof(items, i)
        assert levels[-1][0] == merkle.hash_from_byte_slices(items)

    def test_merkle_levels_reject_non_power_of_two(self):
        with pytest.raises(ValueError):
            merkle.levels_from_leaves([b"x"] * 3)

    def test_range_proof_coords_match_prove_range_walk(self):
        from celestia_app_tpu.nmt.proof import (
            prove_range,
            prove_range_from_levels,
            range_proof_node_coords,
        )
        from celestia_app_tpu.nmt.tree import NamespacedMerkleTree

        leaves = [
            bytes([0] * 28 + [i // 2]) + bytes([i]) * 20 for i in range(16)
        ]
        tree = NamespacedMerkleTree()
        for leaf in leaves:
            tree.push(leaf)
        levels = tree.levels()
        for start in range(16):
            for end in range(start + 1, 17):
                walk = prove_range(tree, start, end)
                indexed = prove_range_from_levels(levels, start, end)
                assert walk == indexed, (start, end)
                coords = range_proof_node_coords(16, start, end)
                assert len(coords) == len(walk.nodes)

    def test_coords_require_power_of_two(self):
        from celestia_app_tpu.nmt.proof import range_proof_node_coords

        with pytest.raises(ValueError):
            range_proof_node_coords(12, 0, 1)
