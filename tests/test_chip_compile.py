"""Every Pallas kernel of the block path compiles for a v5e chip.

Ahead-of-time compiles at real width (the k=512 square) against a
DESCRIBED v5e topology — no chip attached, the TPU compiler refuses here
whatever it would refuse on the chip (an unsupported Mosaic shape cast,
an unsigned reduction, a block shape off the (8, 128) tiling).  Results
and times need the chip (chip_smoke.py); this file guards compilation
only, at no chip time.  Whole fused programs (tens of seconds each) stay
out of this tier.
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

K = 512  # the gov-512 square: 4k^2 = 1M NMT leaves
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on chip 0 of a described v5e:2x2, with the
    persistent compilation cache off (a compile for a described device
    cannot be read back without one)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < HBM_BYTES, temp
    return compiled


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _codec_words():
    from celestia_app_tpu.gf import codec_for_width
    from celestia_app_tpu.kernels.rs_xor import pack_generator_words

    codec = codec_for_width(K)
    bits = codec.generator_bits()
    return codec.field.m, bits.shape, pack_generator_words(bits).shape


def test_sha256_pallas_leaf_batch(one_chip, monkeypatch):
    """The dispatcher's TPU branch at the k=512 leaf batch (542-byte
    NMT leaves): what `auto` selects on the chip."""
    from celestia_app_tpu.kernels.sha256 import sha256

    monkeypatch.setenv("CELESTIA_SHA_PALLAS", "on")
    _compile(sha256, _spec(one_chip, (4 * K * K, 542), jnp.uint8))


def test_sha256_leaves_pallas(one_chip):
    from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
    from celestia_app_tpu.kernels.sha256 import sha256_leaves_pallas

    n = 4 * K * K
    _compile(
        sha256_leaves_pallas,
        _spec(one_chip, (n, NAMESPACE_SIZE), jnp.uint8),
        _spec(one_chip, (n, SHARE_SIZE), jnp.uint8),
    )


def test_rs_pallas_dense_matmul(one_chip):
    from celestia_app_tpu.kernels.rs_pallas import mod2_matmul_planes_pallas

    m, g_shape, _ = _codec_words()
    bps = m // 8
    _compile(
        lambda g, x: mod2_matmul_planes_pallas(g, x, m),
        _spec(one_chip, g_shape, jnp.int8),
        _spec(one_chip, (K, bps, 2 * K * 512 // bps), jnp.uint8),
    )


def test_rs_xor_matmul(one_chip):
    from celestia_app_tpu.kernels.rs_xor import mod2_matmul_planes_xor

    m, _, w_shape = _codec_words()
    bps = m // 8
    _compile(
        lambda g, x: mod2_matmul_planes_xor(g, x, m, interpret=False),
        _spec(one_chip, w_shape, jnp.uint32),
        _spec(one_chip, (K, bps, 2 * K * 512 // bps), jnp.uint8),
    )


def test_rs_xor_extend_leaf_digests(one_chip):
    """The fused_epi rung's kernel: column extend + parity leaf digests."""
    from celestia_app_tpu.constants import SHARE_SIZE
    from celestia_app_tpu.kernels.rs_xor import extend_leaf_digests

    m, _, w_shape = _codec_words()
    compiled = _compile(
        lambda t, g: extend_leaf_digests(t, g, m, interpret=False),
        _spec(one_chip, (K, 2 * K, SHARE_SIZE), jnp.uint8),
        _spec(one_chip, w_shape, jnp.uint32),
    )
    out = compiled.out_info
    assert [tuple(o.shape) for o in out] == [
        (K, 2 * K, SHARE_SIZE), (K, 2 * K, 32)
    ]
    assert np.dtype(out[1].dtype) == np.uint8


@pytest.mark.parametrize("nodes, shares", [(8, 1), (128, 16), (512, 0), (0, 16)])
def test_serve_gather_reads_the_resident_arrays(one_chip, nodes, shares):
    """The DAS serve gather at the hard-cap square (k=128), over the
    resident forest and square in their default layouts: no op copies
    or prefetches the whole EDS or the whole forest — each dispatch
    reads only its rows."""
    from celestia_app_tpu.constants import SHARE_SIZE
    from celestia_app_tpu.serve.cache import FOREST_ROW, take_fn

    k, n = 128, 256
    forest = (n * (2 * n - 1), FOREST_ROW)
    square = (n, n, SHARE_SIZE)
    compiled = take_fn(k, nodes, shares, "tpu").lower(
        _spec(one_chip, forest, jnp.uint8) if nodes else None,
        _spec(one_chip, square, jnp.uint8) if shares else None,
        _spec(one_chip, (nodes + 2 * shares,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "cross_program_prefetch" not in text
    big = [f"u8[{','.join(map(str, s))}]" for s in (forest, square)]
    whole = []
    for line in text.splitlines():
        _, eq, rhs = line.partition(" = ")
        op = re.search(r"\s([a-z][\w-]*)\(", rhs)
        if eq and op and op.group(1) != "parameter" and any(
            b in rhs[:op.start()] for b in big
        ):
            whole.append(line.strip())
    assert not whole, whole[:2]
    assert [tuple(o.shape) for o in [compiled.out_info]] == [
        (nodes * 90 + shares * SHARE_SIZE,)
    ]
