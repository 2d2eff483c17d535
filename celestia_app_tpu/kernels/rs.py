"""Reed-Solomon square extension as MXU bit-matmuls.

TPU-first lowering of the rsmt2d encode (reference
pkg/da/data_availability_header.go:74 -> rsmt2d.ComputeExtendedDataSquare):
GF(2^m) arithmetic never reaches the device as table lookups.  Multiplication
by a field constant is GF(2)-linear on the symbol's bit vector, so the whole
systematic generator G (gf/rs.py) bit-expands to a constant 0/1 matrix G_bits
of shape (k*m, k*m), and

    parity_bits = (G_bits @ data_bits) mod 2

is one dense matmul per axis phase - exactly the shape the MXU wants.  The
mod-2 is a final `& 1` on the int32 accumulator (max k*m = 8192 partial
products, far below 2^31).

Layout discipline (measured on v5e: uint8 relayouts are ~50x the matmul
cost, so they decide everything):

  * all transposes happen on BYTE arrays, never on the 8x larger bit
    planes;
  * bit unpack/pack keep the huge batch axis (R*nsym) as the trailing
    lane dimension and put the 8-wide bit axis in the middle;
  * `encode_axis` contracts over a caller-chosen axis, so the column
    phase of the square extension consumes the row-extended top half
    with NO transpose at all - its parity lands directly as the bottom
    rows.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from celestia_app_tpu.gf.rs import active_construction, codec_for_width

# int8 feeds the MXU's integer path on TPU; exactness: 0/1 products
# accumulated mod 256 (int8 wraparound) keep bit 0 — the only bit the
# mod-2 result reads — exact at any contraction depth.
_DOT_DTYPE = jnp.int8


def _mod2_matmul_planes(G_bits: jnp.ndarray, x: jnp.ndarray, m: int) -> jnp.ndarray:
    """Core bit-sliced product: bytes (n, bps, cols) -> bytes (P, bps, cols).

    `x` holds the contraction-axis shares as byte planes: x[j, b, c] is
    byte b of symbol-column c of share j.  Unpacks to {0,1} int8 with the
    bit axis in the middle, runs ONE dense (P*m, n*m) x (n*m, cols) int8
    matmul, and repacks.  cols is the flattened (batch x symbol) axis and
    stays the innermost lane dimension throughout.
    """
    n, bps, cols = x.shape
    bits = (x[:, :, None, :] >> jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]) & 1
    B = bits.reshape(n * m, cols).astype(_DOT_DTYPE)
    # int32 accumulation: int8 accumulation would be exact too (parity
    # survives mod-256 wraparound) but measured ~100x slower on the TPU
    # in round 3 — XLA has no fast int8-accumulate MXU path there.
    acc = lax.dot_general(
        G_bits.astype(_DOT_DTYPE),
        B,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (P*m, cols)
    P = acc.shape[0] // m
    pb = (acc & 1).astype(jnp.uint32).reshape(P, bps, 8, cols)
    weights = (jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32))[None, None, :, None]
    return (pb * weights).sum(axis=2).astype(jnp.uint8)  # (P, bps, cols)


def encode_axis(
    data: jnp.ndarray, G_bits: jnp.ndarray, m: int, contract_axis: int = 1
) -> jnp.ndarray:
    """Systematic encode contracting over `contract_axis` of (A, B, S) bytes.

    Returns parity with the contracted axis replaced by P = G rows / m at
    the same position; the other two axes are untouched.  contract_axis=0
    runs with zero byte transposes (the square extension's column phase).
    """
    bps = m // 8
    x = jnp.moveaxis(data, contract_axis, 0)  # (n, batch, S)
    n, batch, S = x.shape
    nsym = S // bps
    cols = batch * nsym
    planes = jnp.moveaxis(x.reshape(n, batch, nsym, bps), 3, 1)  # (n, bps, batch, nsym)
    out = _mod2_matmul_planes(G_bits, planes.reshape(n, bps, cols), m)
    P = out.shape[0]
    by = jnp.moveaxis(out.reshape(P, bps, batch, nsym), 1, 3)  # (P, batch, nsym, bps)
    return jnp.moveaxis(by.reshape(P, batch, S), 0, contract_axis)


def _fft_choice(k: int) -> tuple[bool, bool | None]:
    """(use_fft, force_md) for size k.

    $CELESTIA_RS_FFT: "on" / "off" / "auto" (default). "on" honors
    $CELESTIA_RS_FFT_MD as before (force_md None = env-controlled).

    Auto is platform- and size-aware, from measurement:
      * TPU — dense everywhere: the grouped butterflies measured 0.359 s
        vs 0.255 s dense at k=512 (r3); the transpose-free md variant is
        unmeasured on the chip, so it stays an autotune candidate
        (bench parts row) rather than the default;
      * elsewhere — the md FFT at k >= 512, where dense's O(k^3) MACs
        overwhelm a CPU: measured 60.4 s vs 138.1 s dense steady-state
        at k=512 (2026-07-31, this image), dead heat at k=256 (11.7 vs
        11.6 s), dense faster below.
    Both paths produce identical bytes (tests/test_fft.py pins it), so a
    stale cached choice is a perf detail, never a correctness hazard —
    caches key on (k, construction) only.
    """
    import os

    mode = os.environ.get("CELESTIA_RS_FFT", "auto")
    if mode == "on":
        return True, None
    if mode != "auto":
        return False, None
    try:
        platform = jax.devices()[0].platform
    except Exception:  # chaos-ok: no backend: tracing only
        return False, None
    if platform == "cpu" and k >= 512:
        # Only CPU was measured; other accelerators stay on dense until
        # a measurement says otherwise (GPUs in particular excel at the
        # dense matmul the FFT avoids).
        return True, True
    return False, None


def _use_pallas_rs(k: int, m: int) -> bool:
    """$CELESTIA_RS_PALLAS: "on" / "off" (default).  The fused Pallas
    dense kernel (kernels/rs_pallas.py) keeps the 8x bit planes in VMEM —
    unmeasured on hardware yet, so it is opt-in until a chip run (the
    bench autotuner measures it as the rs_dense_pl candidate and flips
    the env for the rows it wins). Requires MXU-tileable dims."""
    import os

    if os.environ.get("CELESTIA_RS_PALLAS", "off") != "on":
        return False
    from celestia_app_tpu.kernels.rs_pallas import pallas_supported

    return pallas_supported(k, m)


def _use_xor_rs(k: int, m: int) -> bool:
    """$CELESTIA_RS_XOR: "on" / "off" (default).  The bitsliced XOR/AND-
    popcount Pallas lowering (kernels/rs_xor.py): no MXU, no int32
    accumulator, no 8x bit inflation — the arXiv 2108.02692 schedule.
    Opt-in until a chip run; the bench autotuner measures it as the
    rs_xor parts candidate and flips this env for the rows it wins.
    Off-TPU the kernel runs in interpret mode (slow but correct), so the
    seam is CPU-runnable."""
    import os

    if os.environ.get("CELESTIA_RS_XOR", "off") != "on":
        return False
    from celestia_app_tpu.kernels.rs_xor import xor_supported

    return xor_supported(k, m)


def encode_fn(k: int, construction: str | None = None):
    """The encode-path selector: f(data, contract_axis) -> parity shares.

    ONE owner for the FFT-vs-dense-vs-pallas-vs-xor policy — both the
    single-chip square extension and the sharded pipeline build their
    encode through here, so the selection (and any future threshold/env
    change) cannot diverge between them.  Auto picks per platform and
    size (see _fft_choice for the measured rationale: dense on TPU,
    md-FFT on other platforms at k >= 512); CELESTIA_RS_FFT=on forces
    the additive-FFT butterflies, CELESTIA_RS_PALLAS=on the fused Pallas
    dense kernel, and CELESTIA_RS_XOR=on the bitsliced XOR schedule
    (kernels/rs_xor.py) — identical bytes any way.
    """
    from celestia_app_tpu.gf.rs import active_construction as _active

    codec = codec_for_width(k, construction)
    m = codec.field.m
    resolved = construction or _active()

    use_fft, force_md = _fft_choice(k)
    if use_fft:
        from celestia_app_tpu.kernels.fft import encode_axis_fft

        def encode(data: jnp.ndarray, contract_axis: int = 1) -> jnp.ndarray:
            return encode_axis_fft(data, k, resolved, contract_axis,
                                   md=force_md)
    elif _use_pallas_rs(k, m):
        from celestia_app_tpu.kernels.rs_pallas import encode_axis_pallas

        G_bits_pl = jnp.asarray(codec.generator_bits())

        def encode(data: jnp.ndarray, contract_axis: int = 1) -> jnp.ndarray:
            return encode_axis_pallas(data, G_bits_pl, m, contract_axis)
    elif _use_xor_rs(k, m):
        from celestia_app_tpu.kernels.rs_xor import (
            encode_axis_xor,
            pack_generator_words,
        )

        G_words = jnp.asarray(pack_generator_words(codec.generator_bits()))

        def encode(data: jnp.ndarray, contract_axis: int = 1) -> jnp.ndarray:
            return encode_axis_xor(data, G_words, m, contract_axis)
    else:
        G_bits = jnp.asarray(codec.generator_bits())

        def encode(data: jnp.ndarray, contract_axis: int = 1) -> jnp.ndarray:
            return encode_axis(data, G_bits, m, contract_axis)

    return encode


def extend_square_fn(k: int, construction: str | None = None):
    """Returns eds = f(ods) for a fixed square size k.

    ods: (k, k, SHARE_SIZE) uint8 -> eds: (2k, 2k, SHARE_SIZE) uint8 with
    quadrants [[Q0, Q1], [Q2, Q3]] (row-parity right, column-parity below),
    matching rsmt2d's quadrant layout.  The RS construction is resolved at
    build time; callers caching the result must key on it.
    """
    encode = encode_fn(k, construction)

    def extend(ods: jnp.ndarray) -> jnp.ndarray:
        # Row phase: each of the k rows is a codeword batch along cols.
        q1 = encode(ods, 1)  # (k, k, S)
        top = jnp.concatenate([ods, q1], axis=1)  # (k, 2k, S)
        # Column phase: contract over the row axis directly - Q2 and Q3
        # arrive as the bottom rows with no transpose (row/col encodes
        # commute: EDS = [[Q0, Q0 G^T], [G Q0, G Q0 G^T]]).
        bottom = encode(top, 0)  # (k, 2k, S)
        return jnp.concatenate([top, bottom], axis=0)  # (2k, 2k, S)

    return extend


@lru_cache(maxsize=None)
def _jit_extend_square(k: int, construction: str):
    from celestia_app_tpu.trace.device_ledger import track

    return track(
        jax.jit(extend_square_fn(k, construction)),
        "extend_square", k=k, construction=construction,
    )


def jit_extend_square(k: int):
    """Cached jitted extension for square size k (one compile per
    (k, active RS construction))."""
    return _jit_extend_square(k, active_construction())


def extend_square(ods: np.ndarray) -> np.ndarray:
    """Host convenience: numpy ODS (k, k, S) -> numpy EDS (2k, 2k, S)."""
    k = ods.shape[0]
    assert ods.shape[1] == k, ods.shape
    return np.asarray(jit_extend_square(k)(jnp.asarray(ods, dtype=jnp.uint8)))


def decode_axis_fn(k: int, construction: str | None = None):
    """Erasure decode along an axis as a constant matmul.

    Returns f(shares, R_bits) where shares is (R, k, S) holding the k known
    shares (already gathered) and R_bits the bit-expanded (2k*m, k*m) recovery
    matrix from RSCodec.recover_matrix - output is the full (R, 2k, S).
    """
    codec = codec_for_width(k, construction)
    m = codec.field.m

    def decode(known: jnp.ndarray, R_bits: jnp.ndarray) -> jnp.ndarray:
        return encode_axis(known, R_bits, m, contract_axis=1)

    from celestia_app_tpu.trace.device_ledger import track

    return track(
        jax.jit(decode),
        "rs_decode_axis", k=k, construction=construction,
    )
