"""Fused extend_and_dah == staged path, bit for bit.

The fused single-dispatch lowering (kernels/fused) must reproduce the
staged extend-then-hash composition (da/eds._pipeline) exactly — roots,
data root, and EDS bytes — on golden vectors and random squares, across
the donated-buffer path and the multi-chip DAH-only path.  These pins are
what make the bench autotuner's fused/staged seat a pure perf choice.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
from celestia_app_tpu.da.eds import ExtendedDataSquare, _pipeline, extend_shares
from celestia_app_tpu.gf.rs import active_construction
from celestia_app_tpu.kernels.fused import jit_extend_and_dah, pipeline_mode

# Reference golden DAH hashes (pkg/da/data_availability_header_test.go;
# same constants as tests/test_golden_vectors.py — the fused path must
# reproduce them through its own lowering).
K2_HASH = bytes.fromhex(
    "b56e4d251ac266f4b91cc5464b3fc7efcbdc888064647496d13133f0dc65ac25"
)
K128_HASH = bytes.fromhex(
    "0bd3abeeacfbb0b92dfbdac4a154868e3c4e79666f7fcf6c620bb90dd3a0dcf0"
)


def _golden_share() -> bytes:
    ns = bytes([0x00]) + bytes(18) + bytes([0x01]) * 10
    assert len(ns) == NAMESPACE_SIZE
    return ns + b"\xff" * (SHARE_SIZE - NAMESPACE_SIZE)


def random_ods(k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ods = rng.integers(0, 256, size=(k, k, SHARE_SIZE), dtype=np.uint8)
    ods[..., 0] = 0  # namespaces below the parity namespace
    return ods


_STAGED_JITS: dict = {}


def _staged(k: int, ods: np.ndarray):
    # One jit wrapper per (k, construction) for the whole module: a
    # fresh jax.jit around a fresh _pipeline closure per call compiled
    # the SAME staged program again for every parity test (~4 duplicate
    # k∈{2,8} compiles, tens of seconds of tier-1 budget).
    key = (k, active_construction())
    fn = _STAGED_JITS.get(key)
    if fn is None:
        fn = _STAGED_JITS[key] = jax.jit(_pipeline(*key))
    return [np.asarray(x) for x in fn(jnp.asarray(ods, dtype=jnp.uint8))]


class TestFusedParity:
    # k=128 is covered by the slow golden-vector test below (same
    # compile); the random-content sweep stays small enough for the CPU
    # image.  The k=32 leg is slow-marked (tier-1 budget): it compiles
    # fused AND staged k=32 programs nothing else in the fast tier
    # uses, and the k in {2, 8} legs already pin the parity seam.
    @pytest.mark.parametrize(
        "k", [2, 8, pytest.param(32, marks=pytest.mark.slow)]
    )
    def test_fused_matches_staged(self, k):
        ods = random_ods(k, seed=k * 13 + 1)
        ref = _staged(k, ods)
        got = jit_extend_and_dah(k)(jnp.asarray(ods, dtype=jnp.uint8))
        for name, a, b in zip(("eds", "row_roots", "col_roots", "droot"),
                              ref, got):
            assert np.array_equal(a, np.asarray(b)), (k, name)

    @pytest.mark.parametrize("k", [2, 8])
    def test_donated_buffer_path(self, k):
        """donate=True must not change a byte; the input buffer is consumed
        on backends that honor donation and silently kept elsewhere."""
        ods = random_ods(k, seed=k * 17 + 2)
        ref = _staged(k, ods)
        x = jnp.asarray(ods, dtype=jnp.uint8)
        got = jit_extend_and_dah(k, donate=True)(x)
        for name, a, b in zip(("eds", "row_roots", "col_roots", "droot"),
                              ref, got):
            assert np.array_equal(a, np.asarray(b)), (k, name)

    # roots_only has no production caller yet (the DAH-only variant for
    # header-service callers): its k=8 program is a ~20 s compile
    # nothing else in the fast tier dispatches, so that leg rides the
    # slow tier and k=2 keeps the lowering pinned (tier-1 budget).
    @pytest.mark.parametrize(
        "k", [2, pytest.param(8, marks=pytest.mark.slow)]
    )
    def test_roots_only_lowering(self, k):
        ods = random_ods(k, seed=k * 19 + 3)
        _, rr, cr, droot = _staged(k, ods)
        got = jit_extend_and_dah(k, roots_only=True)(
            jnp.asarray(ods, dtype=jnp.uint8)
        )
        assert np.array_equal(rr, np.asarray(got[0])), k
        assert np.array_equal(cr, np.asarray(got[1])), k
        assert np.array_equal(droot, np.asarray(got[2])), k

    def _golden_through_fused(self, k: int, want: bytes) -> None:
        from celestia_app_tpu.da.dah import DataAvailabilityHeader

        shares = [_golden_share()] * (k * k)
        ods = np.frombuffer(b"".join(shares), dtype=np.uint8).reshape(
            k, k, SHARE_SIZE
        )
        _, rr, cr, _ = jit_extend_and_dah(k, donate=True)(
            jnp.asarray(ods, dtype=jnp.uint8)
        )
        dah = DataAvailabilityHeader(
            row_roots=[bytes(r) for r in np.asarray(rr)],
            column_roots=[bytes(r) for r in np.asarray(cr)],
        )
        assert dah.hash() == want, k

    def test_golden_vectors_through_fused(self):
        """The reference golden DAH hash via an explicitly-fused, donated
        dispatch (k=2; the k=128 reference size is the slow twin below —
        its DONATED compile is ~40 s on a 1-core CPU; the default-path
        k=128 golden is pinned in the fast tier by
        test_golden_vectors.py::test_k128_dah_golden)."""
        self._golden_through_fused(2, K2_HASH)

    @pytest.mark.slow
    def test_golden_vectors_through_fused_k128(self):
        self._golden_through_fused(128, K128_HASH)

    def test_default_route_is_fused_and_env_flips_it(self, monkeypatch):
        """ExtendedDataSquare.compute rides the seam: default fused,
        $CELESTIA_PIPE_FUSED=off forces staged, outputs byte-identical."""
        monkeypatch.delenv("CELESTIA_PIPE_FUSED", raising=False)
        assert pipeline_mode() == "fused"
        k = 8
        ods = random_ods(k, seed=99)
        fused = ExtendedDataSquare.compute(ods)
        monkeypatch.setenv("CELESTIA_PIPE_FUSED", "off")
        assert pipeline_mode() == "staged"
        staged = ExtendedDataSquare.compute(ods)
        assert fused.data_root() == staged.data_root()
        assert fused.row_roots() == staged.row_roots()
        assert fused.col_roots() == staged.col_roots()
        np.testing.assert_array_equal(fused.squared(), staged.squared())

    def test_golden_vectors_unaffected_by_tracing(self, monkeypatch):
        """Observability regression pin: the golden DAH hash is identical
        with tracing explicitly enabled and disabled — spans/journal rows
        must never perturb the device pipeline's bytes."""
        from celestia_app_tpu.da.dah import DataAvailabilityHeader
        from celestia_app_tpu.trace import journal, traced

        k = 2
        shares = [_golden_share()] * (k * k)
        ods = np.frombuffer(b"".join(shares), dtype=np.uint8).reshape(
            k, k, SHARE_SIZE
        )
        for gate in ("on", "off"):
            monkeypatch.setenv("CELESTIA_TRACE", gate)
            before = len(traced().table(journal.TABLE))
            eds = ExtendedDataSquare.compute(ods.copy())
            dah = DataAvailabilityHeader(
                row_roots=eds.row_roots(), column_roots=eds.col_roots()
            )
            assert dah.hash() == K2_HASH, gate
            journaled = len(traced().table(journal.TABLE)) - before
            assert journaled == (1 if gate == "on" else 0)

    def test_extend_shares_construction_pin(self):
        """The construction seam threads through extend_shares: pinning the
        active construction explicitly must be byte-identical to default
        resolution."""
        k = 2
        shares = [_golden_share()] * (k * k)
        a = extend_shares(shares)
        b = extend_shares(shares, active_construction())
        assert a.data_root() == b.data_root()


class TestFusedEpilogue:
    """The leaf-hash-epilogue variant (pipeline mode "fused_epi": the
    column-phase extend feeds the bottom half's parity-namespace leaf
    digests before anything lands in HBM on TPU; the same ops staged
    through XLA off-chip) must be bit-identical to the staged path —
    roots, data root, and EDS bytes — so the bench autotuner's three-way
    pipe seat stays a pure perf choice."""

    # The k=8 leg is slow-marked (tier-1 budget): no other fast-tier
    # test dispatches the epi-k=8 program, and k=2 pins the parity seam
    # (the golden + roots_only + env-routing tests below keep the
    # epilogue's full contract in tier-1 at k=2).
    @pytest.mark.parametrize(
        "k", [2, pytest.param(8, marks=pytest.mark.slow)]
    )
    def test_epilogue_matches_staged(self, k):
        ods = random_ods(k, seed=k * 23 + 5)
        ref = _staged(k, ods)
        got = jit_extend_and_dah(k, epilogue=True)(
            jnp.asarray(ods, dtype=jnp.uint8)
        )
        for name, a, b in zip(("eds", "row_roots", "col_roots", "droot"),
                              ref, got):
            assert np.array_equal(a, np.asarray(b)), (k, name)

    def test_golden_vectors_through_epilogue(self):
        """The reference golden DAH hash (k=2) via the epilogue lowering,
        donated like a block-production dispatch would be."""
        from celestia_app_tpu.da.dah import DataAvailabilityHeader

        k, want = 2, K2_HASH
        shares = [_golden_share()] * (k * k)
        ods = np.frombuffer(b"".join(shares), dtype=np.uint8).reshape(
            k, k, SHARE_SIZE
        )
        _, rr, cr, _ = jit_extend_and_dah(k, donate=True, epilogue=True)(
            jnp.asarray(ods, dtype=jnp.uint8)
        )
        dah = DataAvailabilityHeader(
            row_roots=[bytes(r) for r in np.asarray(rr)],
            column_roots=[bytes(r) for r in np.asarray(cr)],
        )
        assert dah.hash() == want

    def test_env_epi_routes_whole_stack(self, monkeypatch):
        """$CELESTIA_PIPE_FUSED=epi flips pipeline_mode to fused_epi and
        ExtendedDataSquare.compute rides it, byte-identical to staged."""
        from celestia_app_tpu.kernels.fused import env_base_mode

        k = 8
        ods = random_ods(k, seed=77)
        monkeypatch.setenv("CELESTIA_PIPE_FUSED", "off")
        staged = ExtendedDataSquare.compute(ods)
        monkeypatch.setenv("CELESTIA_PIPE_FUSED", "epi")
        assert env_base_mode() == "fused_epi"
        assert pipeline_mode() == "fused_epi"
        epi = ExtendedDataSquare.compute(ods)
        assert epi.data_root() == staged.data_root()
        assert epi.row_roots() == staged.row_roots()
        assert epi.col_roots() == staged.col_roots()
        np.testing.assert_array_equal(epi.squared(), staged.squared())

    def test_roots_only_epilogue_lowering(self):
        k = 4
        ods = random_ods(k, seed=41)
        _, rr, cr, droot = _staged(k, ods)
        got = jit_extend_and_dah(k, roots_only=True, epilogue=True)(
            jnp.asarray(ods, dtype=jnp.uint8)
        )
        assert np.array_equal(rr, np.asarray(got[0]))
        assert np.array_equal(cr, np.asarray(got[1]))
        assert np.array_equal(droot, np.asarray(got[2]))


class TestFusedMultiChip:
    """Multi-chip paths under the conftest 8-device CPU mesh: the DAH-only
    pipeline all-gathers only 90-byte roots (never shares) and must stay
    bit-identical to the single-chip fused program."""

    # (16, 8) compiles a sharded program only this leg uses (~15 s);
    # (8, 4) and (4, 2) keep the collective topology pinned in tier-1
    # and the full 8-device width is covered by the MULTICHIP dryruns.
    @pytest.mark.parametrize(
        "k,n",
        [(8, 4), (4, 2), pytest.param(16, 8, marks=pytest.mark.slow)],
    )
    def test_sharded_dah_only_matches(self, k, n):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from celestia_app_tpu.parallel import (
            default_mesh,
            make_sharded_dah_pipeline,
        )

        assert len(jax.devices()) >= n, "conftest must provide 8 devices"
        mesh = default_mesh(n)
        ods = random_ods(k, seed=k * 5 + n)
        ref = ExtendedDataSquare.compute(ods)
        fn = make_sharded_dah_pipeline(k, mesh)
        sh = NamedSharding(mesh, P("data", None, None))
        rr, cr, droot = fn(jax.device_put(jnp.asarray(ods), sh))
        assert [bytes(r) for r in np.asarray(rr)] == ref.row_roots()
        assert [bytes(r) for r in np.asarray(cr)] == ref.col_roots()
        assert np.asarray(droot).tobytes() == ref.data_root()

    def test_dah_pipeline_rejects_indivisible_mesh(self):
        from celestia_app_tpu.parallel import (
            default_mesh,
            make_sharded_dah_pipeline,
        )

        with pytest.raises(ValueError):
            make_sharded_dah_pipeline(4, default_mesh(8))
