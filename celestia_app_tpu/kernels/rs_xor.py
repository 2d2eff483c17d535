"""Bitsliced XOR lowering of the RS bit-matmul + fused leaf-hash epilogue.

"Accelerating XOR-based Erasure Coding using Program Optimization
Techniques" (arXiv 2108.02692) re-expresses GF(2^8) encode as scheduled
XOR planes.  On TPU that maps to this kernel: the mod-2 matmul

    parity_bits = (G_bits @ data_bits) mod 2      (kernels/rs.py)

never touches the MXU, the int32 accumulator, or the `& 1` reduction.
Instead the CONTRACTION axis is packed 32 bits per uint32 word, and
because bit-parity is GF(2)-linear — parity(a ^ b) = parity(a) ^
parity(b) — the whole row-times-column dot collapses to

    acc[i, c]    = XOR_w ( G_words[w, i] & B_words[w, c] )
    parity[i, c] = 5-step xor-fold of acc[i, c]'s 32 bits

i.e. NW = ceil(n*m/32) AND+XOR vector ops per (output-row, column) tile
plus one fold.  Nothing is ever inflated 8x: the packed words are
byte-for-byte the size of the input shares (4 uint8 byte-planes -> 1
uint32), the fold and the bit->byte repack happen in vregs, and HBM sees
only shares in and parity bytes out.

Bit order matches gf/field.expand_bit_matrix (symbol-major, byte-then-bit
within a symbol; bit t of byte b is LSB-first), so the kernel is
bit-identical to `kernels/rs.encode_axis` — pinned across k and both RS
constructions by tests/test_rs_xor.py.

Second kernel, the fused LEAF-HASH EPILOGUE: the column phase of the
square extension produces only parity shares (namespace = the constant
parity namespace), so their NMT leaf digests depend on nothing but the
extend output itself.  `extend_leaf_digests` computes the column-phase
extend tile and feeds it straight into kernels/sha256._leaf_tile_compute
while it is still in VMEM — the bottom half of the EDS lands in HBM once
(as output) instead of being written, re-read, and re-materialized as 542
-byte padded messages before hashing.  kernels/fused.extend_and_dah_fn's
`epilogue=True` variant rides it (pipeline mode "fused_epi", seated by
the bench autotuner like every other lowering).

Both kernels run under interpret mode off-TPU (`interpret=None` resolves
by platform), so the library paths are CPU-runnable — slowly, which is
fine: CPU carries the tests; the chip carries the bench.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from celestia_app_tpu.constants import NAMESPACE_SIZE, PARITY_NAMESPACE_BYTES

_TC = 256  # symbol-columns per grid step (lane axis), standalone kernel
_OT_MAX = 128  # output bit-rows per grid step, standalone kernel
_EPI_OT_MAX = 1024  # output bit-rows per grid step, epilogue kernel

def _default_interpret() -> bool:
    """Interpret mode on the CPU only; everywhere else the kernel compiles
    (a backend error surfaces instead of turning into a slow path)."""
    return jax.devices()[0].platform == "cpu"


def pack_generator_words(G_bits: np.ndarray) -> np.ndarray:
    """(P*m, n*m) 0/1 generator -> (NW, P*m) uint32, contraction packed.

    Word w, output-row i holds contraction bits [32w, 32w+32) of G's row i
    (LSB first).  Transposed so the kernel's per-word read G_words[w] is a
    contiguous row.  The contraction axis is zero-padded to a multiple of
    32 — AND with a 0 bit contributes nothing, so padding never changes a
    parity.  Host-side, once per (k, construction): G is a constant.
    """
    Pm, nm = G_bits.shape
    pad = (-nm) % 32
    if pad:
        G_bits = np.concatenate(
            [G_bits, np.zeros((Pm, pad), dtype=G_bits.dtype)], axis=1
        )
    nw = (nm + pad) // 32
    w = G_bits.reshape(Pm, nw, 32).astype(np.uint64)
    words = (w << np.arange(32, dtype=np.uint64)).sum(axis=2)
    return np.ascontiguousarray(words.astype(np.uint32).T)  # (NW, Pm)


def pack_data_words(x: jnp.ndarray) -> jnp.ndarray:
    """(n, bps, cols) uint8 byte planes -> (NW, cols) uint32.

    Contraction row j*m + 8*b + t (share j, byte b, bit t — the
    encode_axis unpack order) lands on bit 8*q + t of word w where the
    flat byte row j*bps + b = 4*w + q: packing 4 consecutive byte rows
    little-endian IS the bit order the generator packing uses.  Byte rows
    are zero-padded to a multiple of 4 (see pack_generator_words).
    """
    n, bps, cols = x.shape
    rows = n * bps
    flat = x.reshape(rows, cols)
    pad = (-rows) % 4
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad, cols), dtype=jnp.uint8)], axis=0
        )
    w = flat.reshape((rows + pad) // 4, 4, cols).astype(jnp.uint32)
    return (
        w[:, 0]
        | (w[:, 1] << np.uint32(8))
        | (w[:, 2] << np.uint32(16))
        | (w[:, 3] << np.uint32(24))
    )  # (NW, cols)


def _fold_parity(v: jnp.ndarray) -> jnp.ndarray:
    """Per-element parity of a uint32: 5 xor-folds, result in bit 0."""
    v = v ^ (v >> np.uint32(16))
    v = v ^ (v >> np.uint32(8))
    v = v ^ (v >> np.uint32(4))
    v = v ^ (v >> np.uint32(2))
    v = v ^ (v >> np.uint32(1))
    return v & np.uint32(1)


def _pack_bit_rows(bits: jnp.ndarray) -> jnp.ndarray:
    """(R, C) 0/1 uint32 bit rows -> (R/8, C) uint8, LSB-first within a
    byte — the encode_axis repack order."""
    r, c = bits.shape
    # Mosaic reduces no unsigned integers: fold the same bits as int32.
    pb = jax.lax.bitcast_convert_type(bits, jnp.int32).reshape(r // 8, 8, c)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    return (pb * weights).sum(axis=1).astype(jnp.uint8)


def _byte_planes(bits: jnp.ndarray, tn: int, m: int) -> tuple:
    """(tn*m, C) 0/1 uint32 bit rows (row p*m + 8*b + t: share p, byte
    b, bit t) -> m/8 byte planes of (C, tn) int32, plane b holding byte
    b of every symbol with the shares on lanes — the layout
    kernels/sha256._leaf_tile_compute assembles message words from.
    Split by 8 along sublanes, then a tile-local transpose: no byte-axis
    reshape, which Mosaic refuses."""
    c = bits.shape[1]
    grouped = jax.lax.bitcast_convert_type(bits, jnp.int32).reshape(tn, m, c)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    return tuple(
        (grouped[:, 8 * b:8 * b + 8, :] * weights).sum(axis=1).T
        for b in range(m // 8)
    )


def _xor_kernel(nw: int, ot: int, tc: int):
    """b_ref (NW, TC) + g_ref (NW, OT) uint32 -> out_ref (OT/8, TC) uint8."""

    def kernel(b_ref, g_ref, out_ref):
        def step(w, acc):
            return acc ^ (g_ref[w][:, None] & b_ref[w][None, :])

        acc = jax.lax.fori_loop(
            0, nw, step, jnp.zeros((ot, tc), dtype=jnp.uint32)
        )
        out_ref[...] = _pack_bit_rows(_fold_parity(acc))

    return kernel


def _out_tile(Pm: int, cap: int) -> int:
    """Output bit-rows per grid step: Pm is k*m (a power of two >= 16 for
    every supported field), so min(cap, Pm) always divides it."""
    return min(cap, Pm)


def mod2_matmul_planes_xor(
    G_words: jnp.ndarray, x: jnp.ndarray, m: int, interpret: bool | None = None
) -> jnp.ndarray:
    """Drop-in for kernels/rs._mod2_matmul_planes on the XOR schedule.

    G_words: (NW, P*m) uint32 from pack_generator_words; x: (n, bps, cols)
    uint8 byte planes.  Returns (P, bps, cols) uint8 parity planes.
    """
    n, bps, cols = x.shape
    nw, Pm = G_words.shape
    assert nw == (n * m + 31) // 32 and Pm % 8 == 0, (G_words.shape, x.shape, m)
    if interpret is None:
        interpret = _default_interpret()
    ot = _out_tile(Pm, _OT_MAX)
    B = pack_data_words(x)
    pad = (-cols) % _TC
    if pad:
        B = jnp.pad(B, ((0, 0), (0, pad)))
    total = cols + pad
    out = pl.pallas_call(
        _xor_kernel(nw, ot, _TC),
        grid=(total // _TC, Pm // ot),
        in_specs=[
            pl.BlockSpec((nw, _TC), lambda c, r: (0, c)),
            pl.BlockSpec((nw, ot), lambda c, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((ot // 8, _TC), lambda c, r: (r, c)),
        out_shape=jax.ShapeDtypeStruct((Pm // 8, total), jnp.uint8),
        interpret=interpret,
    )(B, G_words)
    P = Pm // m
    return out[:, :cols].reshape(P, bps, cols)


def encode_axis_xor(
    data: jnp.ndarray,
    G_words: jnp.ndarray,
    m: int,
    contract_axis: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """kernels/rs.encode_axis with the bitsliced XOR core (same byte moves)."""
    bps = m // 8
    x = jnp.moveaxis(data, contract_axis, 0)
    n, batch, S = x.shape
    nsym = S // bps
    cols = batch * nsym
    planes = jnp.moveaxis(x.reshape(n, batch, nsym, bps), 3, 1)
    out = mod2_matmul_planes_xor(
        G_words, planes.reshape(n, bps, cols), m, interpret=interpret
    )
    P = out.shape[0]
    by = jnp.moveaxis(out.reshape(P, bps, batch, nsym), 1, 3)
    return jnp.moveaxis(by.reshape(P, batch, S), 0, contract_axis)


@lru_cache(maxsize=None)
def xor_supported(k: int, m: int) -> bool:
    """Byte-granular fields only (m a multiple of 8 — every construction
    in gf/ qualifies); the padding inside the packers removes every other
    alignment constraint, so unlike the dense Pallas kernel this one has
    no MXU-tile floor."""
    return m % 8 == 0


# --------------------------------------------------------------------------
# Fused leaf-hash epilogue: column-phase extend feeds the NMT leaf rounds
# straight from VMEM
# --------------------------------------------------------------------------


def _epi_kernel(nw: int, ot: int, nsym: int, m: int):
    """One batch-column's worth of bottom shares AND their leaf digests.

    b_ref (NW, nsym) + g_ref (NW, OT) uint32 ->
      shares_ref (OT/8, nsym) uint8   (the packed byte planes, the same
                                       layout the standalone kernel emits)
      dig_ref    (8, OT/m)    uint32  (one digest lane per share)

    Every bottom-half leaf carries the constant parity namespace, so its
    message is 0x00 || 0xFF^29 || share — nothing but the extend output,
    which is exactly why the hash can ride the extend tile without ever
    seeing HBM.  _leaf_tile_compute is the SAME per-tile function the
    fused-leaf SHA kernel wraps, so digest bytes cannot fork between the
    two fused paths.
    """
    from celestia_app_tpu.kernels.sha256 import _leaf_tile_compute

    tn = ot // m
    parity = [int(v) for v in PARITY_NAMESPACE_BYTES]

    def kernel(b_ref, g_ref, shares_ref, dig_ref):
        def step(w, acc):
            return acc ^ (g_ref[w][:, None] & b_ref[w][None, :])

        acc = jax.lax.fori_loop(
            0, nw, step, jnp.zeros((ot, nsym), dtype=jnp.uint32)
        )
        bits = _fold_parity(acc)  # (tn*m, nsym): row p*m + 8*b + t
        shares_ref[...] = _pack_bit_rows(bits)
        # The same bits as byte planes, shares on lanes, straight into the
        # leaf rounds — never an HBM round trip.
        dig_ref[...] = _leaf_tile_compute(parity, _byte_planes(bits, tn, m), tn)

    return kernel


def extend_leaf_digests(
    top: jnp.ndarray,
    G_words: jnp.ndarray,
    m: int,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Column-phase extend + bottom-half NMT leaf digests, one program.

    top: (k, 2k, S) uint8 — the row-extended top half; contraction runs
    over axis 0 (the transpose-free column phase).  Returns
    (bottom (k, 2k, S) uint8, leaf_hashes (k, 2k, 32) uint8) with bottom
    bit-identical to encode(top, 0) and hashes bit-identical to
    sha256(0x00 || parity_ns || share) — tests/test_rs_xor.py pins both.
    """
    from celestia_app_tpu.constants import SHARE_SIZE
    from celestia_app_tpu.kernels.sha256 import _digest_bytes

    k, n2, S = top.shape
    assert S == SHARE_SIZE, top.shape  # _leaf_tile_compute is share-shaped
    bps = m // 8
    nsym = S // bps
    nw, Pm = G_words.shape
    ot = _out_tile(Pm, _EPI_OT_MAX)
    row_tiles = Pm // ot
    tn = ot // m
    if interpret is None:
        interpret = _default_interpret()
    planes = jnp.moveaxis(top.reshape(k, n2, nsym, bps), 3, 1)  # (k,bps,n2,nsym)
    B = pack_data_words(planes.reshape(k, bps, n2 * nsym))
    shares, dig = pl.pallas_call(
        _epi_kernel(nw, ot, nsym, m),
        grid=(n2, row_tiles),  # row tiles fastest; B block constant per b
        in_specs=[
            pl.BlockSpec((nw, nsym), lambda b, r: (0, b)),
            pl.BlockSpec((nw, ot), lambda b, r: (0, r)),
        ],
        out_specs=[
            pl.BlockSpec((ot // 8, nsym), lambda b, r: (r, b)),
            # One (8, tn) digest slab per grid step: a leading squeezed
            # axis keeps the block's last two dims whole for any tn.
            pl.BlockSpec((None, 8, tn), lambda b, r: (b * row_tiles + r, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Pm // 8, n2 * nsym), jnp.uint8),
            jax.ShapeDtypeStruct((n2 * row_tiles, 8, tn), jnp.uint32),
        ],
        interpret=interpret,
    )(B, G_words)
    P = Pm // m  # == k for the square generator
    by = jnp.moveaxis(shares.reshape(P, bps, n2, nsym), 1, 3)
    bottom = by.reshape(P, n2, S)
    # Slab (b * row_tiles + r) holds shares r*tn .. r*tn+tn-1 of batch
    # column b: back to the (row, col) grid of the bottom half.
    d = dig.reshape(n2, row_tiles, 8, tn).transpose(1, 3, 0, 2)  # (rt,tn,n2,8)
    hashes = _digest_bytes(d.reshape(P * n2, 8)).reshape(P, n2, 32)
    return bottom, hashes


def _use_epilogue_kernel(k: int, m: int) -> bool:
    """The compiled epilogue kernel runs on the chip; everywhere else the
    fused_epi mode rides the XLA composition below (same ops, same bytes
    — interpret mode cannot execute the ~7k-op unrolled SHA rounds at
    square scale in reasonable time, the same reason the fused-leaf SHA
    tests jit _leaf_tile_compute directly)."""
    return xor_supported(k, m) and jax.devices()[0].platform == "tpu"


def bottom_leaf_fn(k: int, construction: str | None = None, *,
                   fallback_encode=None):
    """f(top) -> (bottom, leaf_hashes) for the fused_epi pipeline.

    On TPU: the fused Pallas epilogue (extend tile -> leaf rounds in
    VMEM).  Elsewhere: the staged XLA composition through the SEATED
    encode lowering (`fallback_encode`, required — the caller already
    built it, and the epilogue mode must not silently fork the RS seat
    off-chip).  Both branches are bit-identical; the mode choice is a
    perf detail, never a correctness hazard.
    """
    from celestia_app_tpu.gf.rs import codec_for_width

    codec = codec_for_width(k, construction)
    m = codec.field.m
    if _use_epilogue_kernel(k, m):
        G_words = jnp.asarray(pack_generator_words(codec.generator_bits()))

        def fn(top: jnp.ndarray):
            return extend_leaf_digests(top, G_words, m)

        return fn

    assert fallback_encode is not None, "off-TPU epilogue needs the seat's encode"
    from celestia_app_tpu.kernels.nmt import leaf_digests

    def fn(top: jnp.ndarray):
        bottom = fallback_encode(top, 0)
        parity = jnp.frombuffer(PARITY_NAMESPACE_BYTES, dtype=jnp.uint8)
        par_ns = jnp.broadcast_to(parity, (k, 2 * k, NAMESPACE_SIZE))
        _, _, hashes = leaf_digests(par_ns, bottom)
        return bottom, hashes

    return fn
