"""Batched fixed-shape SHA-256 on the VPU.

The DA pipeline's hash workload (reference hot loop (2), SURVEY 3.2: 4k NMT
builds x 2k leaves at k=512 ~ 4.2M compressions per block) is thousands of
*independent* fixed-length messages - ideal for lane-parallel execution: one
uint32 lane per message, rounds unrolled, message lengths static so padding
is a compile-time constant concat.

Replaces Go's crypto/sha256 assembly behind appconsts.NewBaseHashFunc
(reference pkg/appconsts/global_consts.go:86).  All message shapes used by
the square pipeline are fixed:

    NMT leaf   0x00 || ns(29) || share(512)        = 542 B -> 9 blocks
    NMT node   0x01 || left(90) || right(90)       = 181 B -> 3 blocks
    merkle leaf 0x00 || row-or-col root(90)        =  91 B -> 2 blocks
    merkle node 0x01 || h(32) || h(32)             =  65 B -> 2 blocks
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
        0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
        0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
        0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
        0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
        0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_H0 = np.array(
    [0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
     0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19],
    dtype=np.uint32,
)


def _rotr(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _compress(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """One SHA-256 compression. state: (N, 8) uint32; block: (N, 16) uint32.

    Graph-size-conscious: a fori_loop over 4 chunks of 16 rounds each.
    Within a chunk every schedule index is static (round r uses w[r]), so the
    VPU sees straight-line vector code; across chunks the 16-word schedule
    window is recomputed in place.  ~16x smaller HLO than full unrolling,
    which keeps AOT warmup of all square sizes off the critical path
    (SURVEY hard part 4).

    Layout: the 16 schedule words ride the carry as SEPARATE (N,) vectors —
    the batch axis N is the only array axis anywhere in the loop, so every
    op is a full-lane VPU op with no strided (N, 16) column slicing.
    """
    k_chunks = jnp.asarray(_K.reshape(4, 16))

    def chunk(c, carry):
        a, b, cc, d, e, f, g, h = carry[:8]
        ws = list(carry[8:])  # 16 x (N,)
        kc = k_chunks[c]  # (16,) uint32
        for r in range(16):
            s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + s1 + ch + kc[r] + ws[r]
            s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
            maj = (a & b) ^ (a & cc) ^ (b & cc)
            t2 = s0 + maj
            h, g, f, e, d, cc, b, a = g, f, e, d + t1, cc, b, a, t1 + t2
        # next 16 schedule words: w'[r] = w[r] + s0(w[r+1]) + w[r+9] + s1(w[r+14])
        # (indices >= 16 refer to already-updated entries, handled by ordering)
        for r in range(16):
            x15 = ws[(r + 1) % 16]
            x2 = ws[(r + 14) % 16]
            s0 = _rotr(x15, 7) ^ _rotr(x15, 18) ^ (x15 >> np.uint32(3))
            s1 = _rotr(x2, 17) ^ _rotr(x2, 19) ^ (x2 >> np.uint32(10))
            ws[r] = ws[r] + s0 + ws[(r + 9) % 16] + s1
        return (a, b, cc, d, e, f, g, h, *ws)

    init = tuple(state[:, i] for i in range(8)) + tuple(
        block[:, r] for r in range(16)
    )
    out = jax.lax.fori_loop(0, 4, chunk, init)
    return state + jnp.stack(out[:8], axis=1)


def _pad_tail(length: int) -> np.ndarray:
    """The constant SHA-256 padding appended to every length-`length` message."""
    padded = ((length + 9 + 63) // 64) * 64
    tail = np.zeros(padded - length, dtype=np.uint8)
    tail[0] = 0x80
    tail[-8:] = np.frombuffer((length * 8).to_bytes(8, "big"), dtype=np.uint8)
    return tail


def _message_words(msgs: jnp.ndarray) -> jnp.ndarray:
    """(N, L) uint8 messages -> (N, nblocks, 16) big-endian uint32 words
    with the constant SHA-256 padding appended."""
    n, length = msgs.shape
    tail = _pad_tail(length)
    full = jnp.concatenate(
        [msgs, jnp.broadcast_to(jnp.asarray(tail), (n, len(tail)))], axis=1
    )
    nblocks = full.shape[1] // 64
    words = full.reshape(n, nblocks, 16, 4).astype(jnp.uint32)
    return (
        (words[..., 0] << np.uint32(24))
        | (words[..., 1] << np.uint32(16))
        | (words[..., 2] << np.uint32(8))
        | words[..., 3]
    )  # (N, nblocks, 16)


def _digest_bytes(out: jnp.ndarray) -> jnp.ndarray:
    """(N, 8) uint32 state -> (N, 32) big-endian digest bytes."""
    shifts = np.uint32(8) * np.arange(3, -1, -1, dtype=np.uint32)
    by = (out[..., None] >> shifts) & np.uint32(0xFF)
    return by.astype(jnp.uint8).reshape(out.shape[0], 32)


def _sha256_jnp(msgs: jnp.ndarray) -> jnp.ndarray:
    """The XLA-fused reference path (every platform)."""
    n = msgs.shape[0]
    words = _message_words(msgs)
    nblocks = words.shape[1]
    state = jnp.broadcast_to(jnp.asarray(_H0), (n, 8))
    if nblocks == 1:
        out = _compress(state, words[:, 0])
    else:
        # scan over blocks: graph size independent of message length
        out, _ = jax.lax.scan(
            lambda s, blk: (_compress(s, blk), None),
            state,
            words.transpose(1, 0, 2),
        )
    return _digest_bytes(out)


# --------------------------------------------------------------------------
# Pallas path: messages ride the LANES, all 64 rounds live in vregs
# --------------------------------------------------------------------------

_LANE_TILE = 1024  # messages per grid step: 8 sublanes x 128 lanes


def _pallas_kernel(nblocks: int):
    """words_ref: (nblocks, 16, TN) uint32 -> out_ref: (8, TN) uint32.

    One kernel instance hashes TN messages in lock-step: every round is a
    full-lane VPU op on (TN,) vectors held in vector registers — the
    schedule window (16 words) + state (8) never round-trip through HBM,
    which is where the jnp path loses ~6x (measured 161 ms for the k=512
    NMT phase at ~16% of VPU int32 peak).
    """
    k_chunks = _K.reshape(4, 16)

    def kernel(words_ref, out_ref):
        state = tuple(
            jnp.full((out_ref.shape[1],), h, dtype=jnp.uint32) for h in _H0
        )

        def block_step(b, st):
            ws0 = words_ref[b]  # (16, TN)
            a, bb, cc, d, e, f, g, h = st
            ws = [ws0[r] for r in range(16)]
            # 4 chunks x 16 rounds, statically unrolled: round constants
            # stay python scalars (a captured K array would have to be a
            # pallas input) and every op is a full-lane vreg op.
            for c in range(4):
                kc = k_chunks[c]
                for r in range(16):
                    s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
                    ch = (e & f) ^ (~e & g)
                    t1 = h + s1 + ch + np.uint32(kc[r]) + ws[r]
                    s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
                    maj = (a & bb) ^ (a & cc) ^ (bb & cc)
                    t2 = s0 + maj
                    h, g, f, e, d, cc, bb, a = g, f, e, d + t1, cc, bb, a, t1 + t2
                if c < 3:
                    for r in range(16):
                        x15 = ws[(r + 1) % 16]
                        x2 = ws[(r + 14) % 16]
                        s0 = _rotr(x15, 7) ^ _rotr(x15, 18) ^ (x15 >> np.uint32(3))
                        s1 = _rotr(x2, 17) ^ _rotr(x2, 19) ^ (x2 >> np.uint32(10))
                        ws[r] = ws[r] + s0 + ws[(r + 9) % 16] + s1
            out = (a, bb, cc, d, e, f, g, h)
            return tuple(s + o for s, o in zip(st, out))

        final = jax.lax.fori_loop(0, nblocks, block_step, state)
        for i in range(8):
            out_ref[i] = final[i]

    return kernel


def _sha256_pallas(msgs: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    from jax.experimental import pallas as pl

    n = msgs.shape[0]
    words = _message_words(msgs)  # (N, nblocks, 16)
    nblocks = words.shape[1]
    pad = (-n) % _LANE_TILE
    if pad:
        words = jnp.concatenate(
            [words, jnp.zeros((pad, nblocks, 16), jnp.uint32)], axis=0
        )
    total = n + pad
    words_t = words.transpose(1, 2, 0)  # (nblocks, 16, N) — lanes = messages
    out = pl.pallas_call(
        _pallas_kernel(nblocks),
        grid=(total // _LANE_TILE,),
        in_specs=[
            pl.BlockSpec((nblocks, 16, _LANE_TILE), lambda i: (0, 0, i))
        ],
        out_specs=pl.BlockSpec((8, _LANE_TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, total), jnp.uint32),
        interpret=interpret,
    )(words_t)
    return _digest_bytes(out.T[:n])


# --------------------------------------------------------------------------
# Fused NMT-leaf kernel: message construction + padding + packing in VMEM
# --------------------------------------------------------------------------

from celestia_app_tpu.constants import NAMESPACE_SIZE as _NS, SHARE_SIZE as _SS

_LEAF_LEN = 1 + _NS + _SS  # 0x00 || ns(29) || share(512) = 542
_LEAF_BLOCKS = 9  # padded to 576 bytes


def _leaf_tile_compute(ns_rows, share_planes, tn: int):
    """The fused per-tile computation: (8, TN) uint32 digest words of
    0x00 || ns || share for TN leaves laid out LANE-MAJOR (one lane per
    leaf, one row per byte), so every message word is assembled from
    whole rows with shifts — no in-kernel reshape of the byte axis,
    which Mosaic refuses (`unsupported shape cast`).

    ns_rows: (29, TN) byte rows, or 29 python ints (a namespace shared
    by every leaf of the tile, folded into the words as immediates).
    share_planes: bps arrays of (512 / bps, TN) — byte b of symbol s of
    every leaf's share is share_planes[b][s] (bps = 1: plain share
    bytes; the extend epilogue hands its GF(2^16) byte planes as-is).

    Pure jnp — the pallas kernels wrap exactly this function, and the
    off-TPU tests run it directly (interpret mode cannot execute the
    ~7k-op unrolled round structure in reasonable time).

    SEAM: kernels/rs_xor._epi_kernel (the extend+leaf-hash epilogue,
    pipeline mode "fused_epi") also wraps this function, feeding it
    column-phase extend tiles straight from VMEM — keep the signature
    and digest semantics stable or both fused paths fork at once (the
    shared function is what makes their digests provably identical)."""
    k_chunks = _K.reshape(4, 16)
    bps = len(share_planes)
    planes = [p.astype(jnp.uint32) for p in share_planes]
    if isinstance(ns_rows, (list, tuple)):
        ns = [int(v) for v in ns_rows]
    else:
        ns_u32 = ns_rows.astype(jnp.uint32)
        ns = [ns_u32[i] for i in range(_NS)]
    # 34 tail bytes (0x80, zeros, bit length) as python ints: a captured
    # constant ARRAY would have to be a pallas input.
    tail = [int(v) for v in _pad_tail(_LEAF_LEN)]

    def byte(j: int):
        """Message byte j: a (TN,) uint32 row, or a python int."""
        if j == 0:
            return 0
        if j <= _NS:
            return ns[j - 1]
        if j < _LEAF_LEN:
            i = j - 1 - _NS
            return planes[i % bps][i // bps]
        return tail[j - _LEAF_LEN]

    def word(w: int) -> jnp.ndarray:
        """Big-endian message word w as a (TN,) uint32 lane vector."""
        const, acc = 0, None
        for q in range(4):
            v, shift = byte(4 * w + q), 24 - 8 * q
            if isinstance(v, int):
                const |= v << shift
                continue
            v = v << np.uint32(shift) if shift else v
            acc = v if acc is None else acc | v
        if acc is None:
            return jnp.full((tn,), const, dtype=jnp.uint32)
        return acc | np.uint32(const) if const else acc

    a, bb, cc, d, e, f, g, h = (
        jnp.full((tn,), v, dtype=jnp.uint32) for v in _H0
    )
    for b in range(_LEAF_BLOCKS):  # static: shapes fixed per block
        sa, sb, sc, sd, se, sf, sg, sh = a, bb, cc, d, e, f, g, h
        ws = [word(16 * b + r) for r in range(16)]
        for c in range(4):
            kc = k_chunks[c]
            for r in range(16):
                s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
                ch = (e & f) ^ (~e & g)
                t1 = h + s1 + ch + np.uint32(kc[r]) + ws[r]
                s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
                maj = (a & bb) ^ (a & cc) ^ (bb & cc)
                t2 = s0 + maj
                h, g, f, e, d, cc, bb, a = g, f, e, d + t1, cc, bb, a, t1 + t2
            if c < 3:
                for r in range(16):
                    x15 = ws[(r + 1) % 16]
                    x2 = ws[(r + 14) % 16]
                    s0 = _rotr(x15, 7) ^ _rotr(x15, 18) ^ (x15 >> np.uint32(3))
                    s1 = _rotr(x2, 17) ^ _rotr(x2, 19) ^ (x2 >> np.uint32(10))
                    ws[r] = ws[r] + s0 + ws[(r + 9) % 16] + s1
        a, bb, cc, d = sa + a, sb + bb, sc + cc, sd + d
        e, f, g, h = se + e, sf + f, sg + g, sh + h
    return jnp.stack((a, bb, cc, d, e, f, g, h), axis=0)


def _leaf_kernel(tn: int):
    """ns_ref (29, TN) + share_ref (TN, 512) uint8 -> out_ref (8, TN).

    The unfused path materializes every leaf's padded 576-byte message
    AND its lane-major transpose in HBM (~2.3 GB each way at k=512)
    before the rounds read them; here the share tile is widened and
    transposed tile-locally (leaves onto lanes) and each message word
    is assembled from its byte rows in VMEM, so HBM sees only the raw
    shares (plus the 29-byte namespaces, lane-major) in and 32-byte
    digests out.
    """

    def kernel(ns_ref, share_ref, out_ref):
        share_t = share_ref[...].astype(jnp.uint32).T  # (512, TN)
        out_ref[...] = _leaf_tile_compute(ns_ref[...], (share_t,), tn)

    return kernel


def sha256_leaves_pallas(
    ns: jnp.ndarray,
    shares: jnp.ndarray,
    interpret: bool = False,
    tile: int = _LANE_TILE,
) -> jnp.ndarray:
    """NMT leaf digests with fused message construction.

    ns: (N, 29) uint8, shares: (N, 512) uint8 -> (N, 32) digests of
    0x00 || ns || share. Bit-identical to sha256(concat(...)) — pinned
    by tests/test_sha_fused.py.
    """
    from jax.experimental import pallas as pl

    from celestia_app_tpu.constants import NAMESPACE_SIZE

    n = shares.shape[0]
    assert ns.shape == (n, NAMESPACE_SIZE) and shares.shape[1] == 512, (
        ns.shape, shares.shape)
    pad = (-n) % tile
    if pad:
        ns = jnp.concatenate(
            [ns, jnp.zeros((pad, NAMESPACE_SIZE), jnp.uint8)], axis=0)
        shares = jnp.concatenate(
            [shares, jnp.zeros((pad, 512), jnp.uint8)], axis=0)
    total = n + pad
    out = pl.pallas_call(
        _leaf_kernel(tile),
        grid=(total // tile,),
        in_specs=[
            pl.BlockSpec((NAMESPACE_SIZE, tile), lambda i: (0, i)),
            pl.BlockSpec((tile, 512), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((8, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, total), jnp.uint32),
        interpret=interpret,
    )(ns.T, shares)
    return _digest_bytes(out.T[:n])


def _use_pallas_fused_leaves(n: int) -> bool:
    """$CELESTIA_SHA_FUSED: on / off / auto (default). Auto keeps it OFF
    everywhere — unmeasured on hardware; the bench parts stage measures
    it as the nmt_dah_plf candidate and flips this env for the rows it
    wins. Even when on, tiny batches stay on the jnp path (same
    4-tile gate as _use_pallas: a near-empty lane tile wastes the
    kernel)."""
    import os

    mode = os.environ.get("CELESTIA_SHA_FUSED", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return n >= 4 * _LANE_TILE
    return False


def _use_pallas(n: int) -> bool:
    """$CELESTIA_SHA_PALLAS: on / off / auto (default).  Auto uses the
    Pallas kernel on TPU for batches big enough to fill the lane tiles;
    tiny batches (top merkle levels, host conveniences) stay on the
    fused-jnp path everywhere."""
    import os

    mode = os.environ.get("CELESTIA_SHA_PALLAS", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return True
    return jax.devices()[0].platform == "tpu" and n >= 4 * _LANE_TILE


def sha256(msgs: jnp.ndarray) -> jnp.ndarray:
    """Batched SHA-256 over same-length messages: (N, L) uint8 -> (N, 32) uint8.

    L is static (trace-time constant), so padding is a constant-tail concat
    and the block loop fully unrolls.  Large batches on TPU run the Pallas
    lane-parallel kernel; identical digests either way (tests pin it).
    """
    if _use_pallas(msgs.shape[0]):
        return _sha256_pallas(msgs)
    return _sha256_jnp(msgs)


def sha256_bytes(data: bytes) -> bytes:
    """Single-message host convenience (used by tests/tools, not hot paths)."""
    out = sha256(jnp.frombuffer(data, dtype=jnp.uint8).reshape(1, -1))
    return bytes(np.asarray(out)[0])
