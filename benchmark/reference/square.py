"""Plain reference: the original data square of a block, from its raw txs.

Written from the share and layout specs (celestia specs `shares.md`,
`data_square_layout.md`, the IndexWrapper table of `data_structures.md`)
and imports nothing of the program.  It is slow where the program is fast
and simple where the program is clever: one layout fixpoint over the whole
block, shares written into one numpy array.

Layout, in order: normal txs (compact shares, TRANSACTION namespace), PFB
txs wrapped as IndexWrappers (compact shares, PAY_FOR_BLOB namespace),
primary-reserved padding, blobs sorted by namespace (stable in tx order) at
subtree-aligned starts with namespace padding between them, tail padding
to k*k.  k is the smallest power of two that holds the content.
"""

from __future__ import annotations

import math

import numpy as np

SHARE = 512
NS = 29
FIRST_SPARSE = SHARE - NS - 1 - 4  # 478
CONT_SPARSE = SHARE - NS - 1  # 482
FIRST_COMPACT = SHARE - NS - 1 - 4 - 4  # 474
CONT_COMPACT = SHARE - NS - 1 - 4  # 478
SUBTREE_ROOT_THRESHOLD = 64


def _primary(last: int) -> bytes:
    return bytes(NS - 1) + bytes([last])


TX_NS = _primary(0x01)
PFB_NS = _primary(0x04)
RESERVED_PADDING_NS = _primary(0xFF)
TAIL_PADDING_NS = bytes([0xFF]) + bytes([0xFF] * 27) + bytes([0xFE])
PARITY_NS = bytes([0xFF]) * NS


# --- protobuf, as little as the envelopes need ------------------------------


def uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _read_uvarint(buf: bytes, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes):
    pos = 0
    while pos < len(buf):
        key, pos = _read_uvarint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _read_uvarint(buf, pos)
        elif wire == 2:
            ln, pos = _read_uvarint(buf, pos)
            val, pos = buf[pos:pos + ln], pos + ln
        else:
            raise ValueError(f"unexpected wire type {wire}")
        yield num, wire, val


def parse_blob_tx(raw: bytes):
    """(inner tx, [(namespace 29 B, data)]) of a BlobTx, or None."""
    try:
        tx, blobs, type_id = b"", [], b""
        for num, wire, val in _fields(raw):
            if num == 1 and wire == 2:
                tx = val
            elif num == 2 and wire == 2:
                ns_id, data, ns_version = b"", b"", 0
                for n2, w2, v2 in _fields(val):
                    if n2 == 1 and w2 == 2:
                        ns_id = v2
                    elif n2 == 2 and w2 == 2:
                        data = v2
                    elif n2 == 4 and w2 == 0:
                        ns_version = v2
                blobs.append((bytes([ns_version]) + ns_id, data))
            elif num == 3 and wire == 2:
                type_id = val
    except (IndexError, ValueError):
        return None
    if type_id != b"BLOB" or not blobs:
        return None
    return tx, blobs


def index_wrapper(tx: bytes, starts: list[int]) -> bytes:
    packed = b"".join(uvarint(s) for s in starts)
    return (b"\x0a" + uvarint(len(tx)) + tx
            + b"\x12" + uvarint(len(packed)) + packed
            + b"\x1a\x04INDX")


# --- share arithmetic --------------------------------------------------------


def _needed(n: int, first: int, cont: int) -> int:
    if n == 0:
        return 0
    if n <= first:
        return 1
    return 1 + -(-(n - first) // cont)


def sparse_shares(n: int) -> int:
    return _needed(n, FIRST_SPARSE, CONT_SPARSE)


def compact_shares(units: list[bytes]) -> int:
    return _needed(sum(len(uvarint(len(u))) + len(u) for u in units),
                   FIRST_COMPACT, CONT_COMPACT)


def _pow2_ceil(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def subtree_width(count: int, threshold: int = SUBTREE_ROOT_THRESHOLD) -> int:
    min_square = _pow2_ceil(math.isqrt(max(count, 1) - 1) + 1)
    return min(_pow2_ceil(-(-count // threshold)), min_square)


# --- layout and shares -------------------------------------------------------


def layout(normal: list[bytes], pfbs: list[tuple[bytes, list]],
           max_square: int):
    """(k, wrapped PFB txs, [(start, tx index, blob index)] in placement
    order, tx share count, pfb share count) by the fixpoint on blob starts."""
    tx_shares = compact_shares(normal)
    blobs = [(ti, bi, ns, data) for ti, (_, bl) in enumerate(pfbs)
             for bi, (ns, data) in enumerate(bl)]
    order = sorted(range(len(blobs)), key=lambda i: blobs[i][2])
    starts = {(ti, bi): max_square * max_square for ti, bi, _, _ in blobs}
    for _ in range(64):
        wrapped = [index_wrapper(tx, [starts[(ti, bi)] for bi in range(len(bl))])
                   for ti, (tx, bl) in enumerate(pfbs)]
        pfb_shares = compact_shares(wrapped)
        cursor = tx_shares + pfb_shares
        new, placed = {}, []
        for i in order:
            ti, bi, _, data = blobs[i]
            count = sparse_shares(len(data))
            width = subtree_width(count)
            start = -(-cursor // width) * width
            new[(ti, bi)] = start
            placed.append((start, ti, bi))
            cursor = start + count
        if new == starts:
            break
        starts = new
    else:
        raise RuntimeError("layout did not converge")
    k = max(1, _pow2_ceil(math.isqrt(max(cursor - 1, 0)) + 1))
    if k > max_square:
        raise ValueError(f"block needs k={k} > {max_square}")
    return k, wrapped, placed, tx_shares, pfb_shares


def _write_compact(out: np.ndarray, at: int, units: list[bytes], ns: bytes) -> int:
    if not units:
        return at
    data = bytearray()
    unit_starts = []
    for u in units:
        unit_starts.append(len(data))
        data += uvarint(len(u)) + u
    seq_len, pos, i = len(data), 0, 0
    while pos < seq_len:
        first = i == 0
        size = FIRST_COMPACT if first else CONT_COMPACT
        head = bytearray(ns) + bytes([1 if first else 0])
        if first:
            head += seq_len.to_bytes(4, "big")
        off = len(head) + 4
        inside = [s for s in unit_starts if pos <= s < pos + size]
        reserved = off + (inside[0] - pos) if inside else 0
        head += reserved.to_bytes(4, "big")
        chunk = bytes(head) + bytes(data[pos:pos + size])
        out[at, :len(chunk)] = np.frombuffer(chunk, np.uint8)
        at, pos, i = at + 1, pos + size, i + 1
    return at


def _padding(ns: bytes) -> np.ndarray:
    row = np.zeros(SHARE, np.uint8)
    row[:NS] = np.frombuffer(ns, np.uint8)
    row[NS] = 1  # sequence start, version 0; sequence length 0
    return row


def _write_blob(out: np.ndarray, at: int, ns: bytes, data: bytes) -> int:
    n = len(data)
    count = sparse_shares(n)
    nsv = np.frombuffer(ns, np.uint8)
    out[at:at + count, :NS] = nsv
    out[at, NS] = 1
    out[at, NS + 1:NS + 5] = np.frombuffer(n.to_bytes(4, "big"), np.uint8)
    body = np.frombuffer(data, np.uint8)
    head = min(n, FIRST_SPARSE)
    out[at, NS + 5:NS + 5 + head] = body[:head]
    rest = body[head:]
    if rest.size:
        pad = np.zeros((count - 1) * CONT_SPARSE, np.uint8)
        pad[:rest.size] = rest
        out[at + 1:at + count, NS + 1:] = pad.reshape(count - 1, CONT_SPARSE)
    return at + count


def ods_from_txs(raw_txs: list[bytes], max_square: int) -> np.ndarray:
    """(k, k, 512) uint8: the square a validator must build from `raw_txs`."""
    normal, pfbs = [], []
    for raw in raw_txs:
        parsed = parse_blob_tx(raw)
        if parsed is None:
            normal.append(raw)
        else:
            pfbs.append(parsed)
    k, wrapped, placed, tx_n, pfb_n = layout(normal, pfbs, max_square)
    out = np.zeros((k * k, SHARE), np.uint8)
    at = _write_compact(out, 0, normal, TX_NS)
    at = _write_compact(out, at, wrapped, PFB_NS)
    assert at == tx_n + pfb_n
    last_ns = None
    for start, ti, bi in placed:
        pad_ns = RESERVED_PADDING_NS if last_ns is None else last_ns
        out[at:start] = _padding(pad_ns)
        ns, data = pfbs[ti][1][bi]
        at = _write_blob(out, start, ns, data)
        last_ns = ns
    out[at:] = _padding(TAIL_PADDING_NS)
    return out.reshape(k, k, SHARE)
