"""Fused Pallas lowering of the dense RS bit-matmul.

The XLA dense path (kernels/rs.py `_mod2_matmul_planes`) ran at ~9% of the
MXU's int8 peak in its round-3 chip measurement (0.255 s at k=512 against
a ~25 ms roofline): the matmul itself is MXU-shaped, but the byte->bit
unpack before it and the bit->byte pack after it are separate HBM-visible
passes over 8x-inflated bit planes — HBM traffic, not MACs, sets the rate.

This kernel fuses the whole contraction into one Pallas program so the bit
planes NEVER exist in HBM:

    grid (col_tiles, row_tiles), row fastest;
    per col tile, on the first row step, the byte planes (n, bps, TC) are
    unpacked once into a VMEM scratch of {0,1} int8 (n*m, TC);
    every row step then runs one (128, n*m) @ (n*m, TC) int8 MXU matmul
    from scratch and packs its 128 output bit-rows back to bytes in-regs
    before the (16, TC) uint8 tile leaves for HBM.

HBM traffic: bytes in + bytes out + G once per col tile — the 8x bit
inflation stays on-chip. Bit order matches gf/field.expand_bit_matrix
(symbol-major, byte-then-bit within a symbol), so the kernel is
bit-identical to `encode_axis` (pinned by tests/test_rs_pallas.py).

Reference seam: rsmt2d.ComputeExtendedDataSquare's codec.Encode
(/root/reference/pkg/da/data_availability_header.go:74) — this is the
same linear map as kernels/rs.py, only the schedule differs.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_OT = 128  # output bit-rows per grid step: one MXU row tile
_TC = 256  # symbol-columns per grid step (lane axis)


def _kernel(n: int, m: int, bps: int, tc: int):
    def kernel(x_ref, g_ref, out_ref, bits_ref):
        # Unpack the col tile's byte planes once per col tile (row step 0):
        # (n, bps, TC) uint8 -> {0,1} int8 (n*m, TC), symbol-major rows.
        @jax.named_scope("unpack")
        def unpack():
            x = x_ref[...].astype(jnp.int32)  # (n, bps, TC)
            shifts = jnp.arange(8, dtype=jnp.int32)[None, None, :, None]
            bits = (x[:, :, None, :] >> shifts) & 1  # (n, bps, 8, TC)
            bits_ref[...] = bits.astype(jnp.int8).reshape(n * m, tc)

        @pl.when(pl.program_id(1) == 0)
        def _():
            unpack()

        acc = lax.dot_general(
            g_ref[...],
            bits_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (OT, TC)
        nsym_t = _OT // m
        pb = (acc & 1).reshape(nsym_t, bps, 8, tc)
        weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, None, :, None]
        out_ref[...] = (pb * weights).sum(axis=2).astype(jnp.uint8).reshape(
            _OT // 8, tc
        )

    return kernel


def mod2_matmul_planes_pallas(
    G_bits: jnp.ndarray, x: jnp.ndarray, m: int, interpret: bool = False
) -> jnp.ndarray:
    """Drop-in for kernels/rs._mod2_matmul_planes on the fused kernel.

    G_bits: (P*m, n*m) 0/1; x: (n, bps, cols) uint8 byte planes.
    Returns (P, bps, cols) uint8 parity planes. Requires P*m and n*m to be
    multiples of 128 (MXU tiling) — callers fall back below that.
    """
    n, bps, cols = x.shape
    Pm, nm = G_bits.shape
    assert nm == n * m and Pm % _OT == 0, (G_bits.shape, x.shape, m)
    pad = (-cols) % _TC
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    total = cols + pad
    out = pl.pallas_call(
        _kernel(n, m, bps, _TC),
        grid=(total // _TC, Pm // _OT),
        in_specs=[
            pl.BlockSpec((n, bps, _TC), lambda c, r: (0, 0, c)),
            pl.BlockSpec((_OT, nm), lambda c, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((_OT // 8, _TC), lambda c, r: (r, c)),
        out_shape=jax.ShapeDtypeStruct((Pm // 8, total), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((nm, _TC), jnp.int8)],
        interpret=interpret,
    )(x, G_bits.astype(jnp.int8))
    P = Pm // m
    return out[:, :cols].reshape(P, bps, cols)


def encode_axis_pallas(
    data: jnp.ndarray,
    G_bits: jnp.ndarray,
    m: int,
    contract_axis: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """kernels/rs.encode_axis with the fused Pallas core (same byte moves)."""
    bps = m // 8
    x = jnp.moveaxis(data, contract_axis, 0)
    n, batch, S = x.shape
    nsym = S // bps
    cols = batch * nsym
    planes = jnp.moveaxis(x.reshape(n, batch, nsym, bps), 3, 1)
    out = mod2_matmul_planes_pallas(
        G_bits, planes.reshape(n, bps, cols), m, interpret=interpret
    )
    P = out.shape[0]
    by = jnp.moveaxis(out.reshape(P, bps, batch, nsym), 1, 3)
    return jnp.moveaxis(by.reshape(P, batch, S), 0, contract_axis)


@lru_cache(maxsize=None)
def pallas_supported(k: int, m: int) -> bool:
    """MXU tiling wants both matmul dims in 128-multiples."""
    return (k * m) % 128 == 0
