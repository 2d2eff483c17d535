"""Plain reference: RS extension, NMT row and column roots, DAH data root.

Written from the codec's specification and the NMT / RFC 6962 hashing rules;
imports nothing of the program.

Codec: systematic Reed-Solomon over GF(2^8) (poly 0x11D) for k <= 128 and
GF(2^16) (poly 0x1100B, symbols are little-endian byte pairs) above.
Data share j is the codeword at point j, parity share p the codeword at
point k+p, of the degree-<k polynomial through the data: the parity
generator is the Lagrange basis, G[p, j] = prod_{m != j} (k+p ^ m) /
(j ^ m).  The extension runs on the default JAX device as a plain binary
matmul over the bit-expanded G, in blocks of lines (int8 in, int32 out,
exact): rows first, then every column of the row-extended half.

NMT: leaf = ns || ns || sha256(0x00 || ns || share), where ns is the share's
own namespace in the original quadrant and the parity namespace elsewhere;
node = min || max || sha256(0x01 || left || right), max ignoring a right
child whose min is the parity namespace.  Data root: RFC 6962 over the 2k
row roots then the 2k column roots.

`parity_leaves_own_ns=True` is the control: parity leaves hashed under
their own bytes' namespace, the shortcut that skips the namespace select.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ProcessPoolExecutor
from contextlib import contextmanager
from functools import lru_cache
from multiprocessing import get_context

import numpy as np

from benchmark.reference.square import NS, PARITY_NS, SHARE

POLY = {8: 0x11D, 16: 0x1100B}


def _mul(a: int, b: int, m: int) -> int:
    out, top, poly = 0, 1 << m, POLY[m]
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & top:
            a ^= poly
        b >>= 1
    return out


@lru_cache(maxsize=None)
def field(m: int) -> tuple[np.ndarray, np.ndarray]:
    """(exp, log) tables of GF(2^m) built on its smallest generator."""
    q = 1 << m
    for g in range(2, q):
        exp = np.zeros(2 * (q - 1), np.int64)
        x = 1
        for i in range(q - 1):
            exp[i] = x
            x = _mul(x, g, m)
        if x == 1 and len(np.unique(exp[:q - 1])) == q - 1:
            exp[q - 1:] = exp[:q - 1]
            log = np.zeros(q, np.int64)
            log[exp[:q - 1]] = np.arange(q - 1)
            return exp, log
    raise ValueError(f"no generator for m={m}")


def field_bits(k: int) -> int:
    return 8 if 2 * k <= 256 else 16


@lru_cache(maxsize=4)
def generator_bits(k: int) -> np.ndarray:
    """(k*m, k*m) int8: bit-expanded parity generator, rows (p, out bit),
    columns (j, in bit)."""
    m = field_bits(k)
    exp, log = field(m)
    order = (1 << m) - 1
    data = np.arange(k)
    parity = np.arange(k, 2 * k)
    a = log[parity[:, None] ^ data[None, :]]  # (k, k) log(x_p - x_m)
    diff = data[:, None] ^ data[None, :]
    np.fill_diagonal(diff, 1)  # log 1 = 0 drops m == j from the sum
    d = log[diff]  # (k, k) log(x_j - x_m)
    num = a.sum(1)[:, None] - a  # prod over m != j of (x_p - x_m)
    den = d.sum(1)[None, :]
    g_log = (num - den) % order  # (k, k) log G[p, j]
    # bit b' of G[p, j] * 2^b
    prod = exp[(g_log[:, :, None] + np.arange(m)[None, None, :]) % order]
    bits = (prod[:, :, :, None] >> np.arange(m)) & 1  # (p, j, b, b')
    return bits.transpose(0, 3, 1, 2).reshape(k * m, k * m).astype(np.int8)


@lru_cache(maxsize=4)
def _encode_fn(k: int, lines: int):
    import jax
    import jax.numpy as jnp

    m = field_bits(k)

    def encode(gb, block):  # (lines, k, 512) uint8 -> parity (lines, k, 512)
        x = block.astype(jnp.int32)
        if m == 16:
            x = x[..., 0::2] | (x[..., 1::2] << 8)
        s = x.shape[-1]
        bits = (x[..., None] >> jnp.arange(m)) & 1  # (L, k, s, m)
        bits = bits.transpose(1, 3, 0, 2).reshape(k * m, lines * s)
        out = jax.lax.dot_general(
            gb, bits.astype(jnp.int8), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ) & 1
        out = out.reshape(k, m, lines, s).transpose(2, 0, 3, 1)
        sym = (out << jnp.arange(m)).sum(-1)
        if m == 16:
            sym = jnp.stack([sym & 0xFF, sym >> 8], -1).reshape(lines, k, 2 * s)
        return sym.astype(jnp.uint8)

    return jax.jit(encode)


def encode_lines(lines: np.ndarray) -> np.ndarray:
    """(n, k, 512) -> (n, k, 512) parity, on the default device in blocks."""
    n, k, _ = lines.shape
    block = max(1, min(n, (1 << 15) // k))  # ~2^27 bits in flight
    fn = _encode_fn(k, block)
    import jax.numpy as jnp

    gb = jnp.asarray(generator_bits(k))
    out = np.empty_like(lines)
    for lo in range(0, n, block):
        part = lines[lo:lo + block]
        if len(part) < block:
            part = np.concatenate(
                [part, np.zeros((block - len(part),) + part.shape[1:], np.uint8)])
        out[lo:lo + block] = np.asarray(fn(gb, part))[:min(block, n - lo)]
    return out


def extend(ods: np.ndarray) -> np.ndarray:
    """(k, k, 512) -> (2k, 2k, 512) EDS."""
    top = np.concatenate([ods, encode_lines(ods)], axis=1)  # (k, 2k, S)
    bottom = encode_lines(np.ascontiguousarray(top.transpose(1, 0, 2)))
    return np.concatenate([top, bottom.transpose(1, 0, 2)], axis=0)


def _node(left: bytes, right: bytes) -> bytes:
    r_min = right[:NS]
    top = left[NS:2 * NS] if r_min == PARITY_NS else right[NS:2 * NS]
    return left[:NS] + top + hashlib.sha256(b"\x01" + left + right).digest()


def _root(leaves: list[bytes]) -> bytes:
    while len(leaves) > 1:
        leaves = [_node(leaves[i], leaves[i + 1]) for i in range(0, len(leaves), 2)]
    return leaves[0]


def merkle_root(items: list[bytes]) -> bytes:
    """RFC 6962 root of a power-of-two list."""
    level = [hashlib.sha256(b"\x00" + it).digest() for it in items]
    while len(level) > 1:
        level = [hashlib.sha256(b"\x01" + level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    return level[0]


def _line_roots(job: tuple) -> list[bytes]:
    """NMT roots of lines lo.. of a (lines, 2k, 512) block of rows, or of
    columns laid out as rows: share j of line i is original iff i < k and
    j < k, whichever the axis."""
    block, lo, k, parity_leaves_own_ns = job
    sha = hashlib.sha256
    roots = []
    for r, line in enumerate(block):
        raw = line.tobytes()
        leaves = []
        for j in range(line.shape[0]):
            share = raw[j * SHARE:(j + 1) * SHARE]
            own = (lo + r < k and j < k) or parity_leaves_own_ns
            ns = share[:NS] if own else PARITY_NS
            leaves.append(ns + ns + sha(b"\x00" + ns + share).digest())
        roots.append(_root(leaves))
    return roots


@contextmanager
def hashing_pool(k: int):
    """Worker processes for the NMT hashing of a big square, or None: the
    hashing is split by lines and runs after the window, outside set-up.
    Spawned, so no worker inherits the parent's hold on the chip."""
    workers = min(12, len(os.sched_getaffinity(0)) - 1)
    if k < 64 or workers < 2:
        yield None
        return
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as pool:
        yield pool


def dah(eds: np.ndarray, parity_leaves_own_ns: bool = False, pool=None):
    """(row_roots, col_roots, data_root) of a (2k, 2k, 512) EDS."""
    n = eds.shape[0]
    k = n // 2
    step = max(1, n // 64)
    cols = np.ascontiguousarray(eds.transpose(1, 0, 2))
    jobs = [(axis[lo:lo + step], lo, k, parity_leaves_own_ns)
            for axis in (eds, cols) for lo in range(0, n, step)]
    roots = [r for part in (pool.map if pool else map)(_line_roots, jobs) for r in part]
    return roots[:n], roots[n:], merkle_root(roots)


def data_root(ods: np.ndarray, parity_leaves_own_ns: bool = False, pool=None) -> bytes:
    return dah(extend(ods), parity_leaves_own_ns, pool)[2]
