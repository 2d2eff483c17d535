"""Bitsliced XOR RS lowering: bit-identity with the dense path.

kernels/rs_xor.py re-expresses the mod-2 generator matmul as uint32
XOR/AND-parity planes (arXiv 2108.02692's schedule on TPU register
shapes); its contract is byte-for-byte equality with kernels/rs.encode_axis
across every square size and BOTH RS constructions — that identity is what
lets the bench autotuner seat it as a pure perf choice.  Off-TPU the
kernel runs in interpret mode; hardware timing is bench.py's job (the
rs_xor parts candidate).
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from celestia_app_tpu.constants import (
    NAMESPACE_SIZE,
    PARITY_NAMESPACE_BYTES,
    SHARE_SIZE,
)
from celestia_app_tpu.gf.rs import RSCodec
from celestia_app_tpu.kernels.rs import encode_axis
from celestia_app_tpu.kernels.rs_xor import (
    encode_axis_xor,
    pack_data_words,
    pack_generator_words,
    xor_supported,
)


@pytest.mark.parametrize("construction", ["vandermonde", "leopard"])
@pytest.mark.parametrize("k", [2, 4, 16, 64, 128])
def test_bit_identity_both_axes(k, construction):
    """The ISSUE's golden matrix: every k the reference pins, both
    constructions, both contraction axes, against the dense lowering."""
    codec = RSCodec(k, construction)
    m = codec.field.m
    assert xor_supported(k, m)
    G_bits = jnp.asarray(codec.generator_bits())
    G_words = jnp.asarray(pack_generator_words(codec.generator_bits()))
    rng = np.random.default_rng(k * 7 + 1)
    data = jnp.asarray(rng.integers(0, 256, (3, k, 16), dtype=np.uint8))
    for axis in (0, 1):
        d = jnp.moveaxis(data, 1, axis)
        want = encode_axis(d, G_bits, m, axis)
        got = encode_axis_xor(d, G_words, m, axis, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want)), (
            k, construction, axis)


def test_unaligned_cols_are_padded():
    """cols not a multiple of the lane tile: padded in, sliced out."""
    k = 16
    codec = RSCodec(k, "vandermonde")
    m = codec.field.m
    G_bits = jnp.asarray(codec.generator_bits())
    G_words = jnp.asarray(pack_generator_words(codec.generator_bits()))
    rng = np.random.default_rng(5)
    # batch=1, width 72 -> cols = 72, far below the 256-lane tile
    data = jnp.asarray(rng.integers(0, 256, (1, k, 72), dtype=np.uint8))
    want = encode_axis(data, G_bits, m, 1)
    got = encode_axis_xor(data, G_words, m, 1, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_generator_packing_bit_order():
    """Word w bit u of packed row i == G_bits[i, 32w + u] — the exact
    contraction order pack_data_words uses, else every parity is wrong."""
    codec = RSCodec(4, "vandermonde")
    G = codec.generator_bits()  # (32, 32)
    W = pack_generator_words(G)  # (1, 32)
    for i in range(G.shape[0]):
        for u in range(G.shape[1]):
            assert (int(W[u // 32, i]) >> (u % 32)) & 1 == int(G[i, u])


def test_data_packing_matches_unpack_order():
    """pack_data_words' uint32 bit 8q+t must hold the same contraction
    row the dense path's byte->bit unpack produces (j*m + 8b + t)."""
    rng = np.random.default_rng(9)
    n, bps, cols = 2, 2, 3  # m = 16
    x = jnp.asarray(rng.integers(0, 256, (n, bps, cols), dtype=np.uint8))
    words = np.asarray(pack_data_words(x))  # (1, cols)
    bits = np.asarray(
        (x[:, :, None, :] >> jnp.arange(8, dtype=jnp.uint8)[None, None, :, None])
        & 1
    ).reshape(n * bps * 8, cols)
    for c in range(cols):
        for r in range(n * bps * 8):
            assert (int(words[r // 32, c]) >> (r % 32)) & 1 == bits[r, c]


def test_encode_fn_env_seam(monkeypatch):
    """$CELESTIA_RS_XOR=on routes the library encode through the XOR
    kernel (interpret mode off-TPU) and the extension stays byte-exact."""
    from celestia_app_tpu.kernels.rs import extend_square_fn

    k = 4
    rng = np.random.default_rng(11)
    ods = rng.integers(0, 256, (k, k, 64), dtype=np.uint8)
    monkeypatch.delenv("CELESTIA_RS_XOR", raising=False)
    want = np.asarray(extend_square_fn(k)(jnp.asarray(ods)))
    monkeypatch.setenv("CELESTIA_RS_XOR", "on")
    got = np.asarray(extend_square_fn(k)(jnp.asarray(ods)))
    assert np.array_equal(got, want)


@pytest.mark.slow
def test_epilogue_kernel_extends_and_hashes(k=2):
    """The fused leaf-hash epilogue: the Pallas kernel's bottom shares
    AND their parity-namespace leaf digests match the staged composition
    (interpret mode — ~90 s of unrolled SHA rounds, hence the slow tier;
    the fast tier pins the library fused_epi mode's composition path in
    tests/test_fused_pipeline.py)."""
    from celestia_app_tpu.kernels.nmt import leaf_digests
    from celestia_app_tpu.kernels.rs_xor import extend_leaf_digests

    codec = RSCodec(k, "vandermonde")
    m = codec.field.m
    G_bits = jnp.asarray(codec.generator_bits())
    G_words = jnp.asarray(pack_generator_words(codec.generator_bits()))
    rng = np.random.default_rng(13)
    ods = jnp.asarray(
        rng.integers(0, 256, (k, k, SHARE_SIZE), dtype=np.uint8)
    )
    top = jnp.concatenate([ods, encode_axis(ods, G_bits, m, 1)], axis=1)
    want_bottom = encode_axis(top, G_bits, m, 0)
    parity = jnp.frombuffer(PARITY_NAMESPACE_BYTES, dtype=jnp.uint8)
    par_ns = jnp.broadcast_to(parity, (k, 2 * k, NAMESPACE_SIZE))
    _, _, want_hashes = leaf_digests(par_ns, want_bottom)
    bottom, hashes = extend_leaf_digests(top, G_words, m, interpret=True)
    assert np.array_equal(np.asarray(bottom), np.asarray(want_bottom))
    assert np.array_equal(np.asarray(hashes), np.asarray(want_hashes))


def test_byte_planes_match_packed_rows():
    """The epilogue's bit rows -> byte planes glue (shares onto lanes)
    agrees with the packed byte rows the kernel writes out, for the
    GF(2^16) two-plane layout the k >= 256 squares use."""
    from celestia_app_tpu.kernels.rs_xor import _byte_planes, _pack_bit_rows

    tn, m, c = 4, 16, 8
    rng = np.random.default_rng(5)
    bits = jnp.asarray(rng.integers(0, 2, (tn * m, c), dtype=np.uint32))
    by = np.asarray(_pack_bit_rows(bits)).reshape(tn, m // 8, c)
    planes = _byte_planes(bits, tn, m)
    assert len(planes) == 2
    for b, plane in enumerate(planes):
        assert np.array_equal(np.asarray(plane), by[:, b, :].T)
