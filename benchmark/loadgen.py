"""The one general traffic generator: reads a traffic file's parameters.

Two kinds of traffic, named by the file's `kind`:

  propose  heights of PFBs.  Each height draws PFBs of `blobs_per_pfb`
           blobs, each of `blob_size` bytes, uniform in the given closed
           ranges (the txsim BlobSequence shape), until the next PFB would
           break the block's byte cap or the square's share budget, so the
           proposer keeps every tx it is offered and fill stays >= the
           traffic's `fill_min`.  The sizes come from the traffic's own
           `plan_seed`, so every run seed does the same work; the run seed
           draws the bytes, the namespaces and the order of the PFBs in a
           height.  Account i signs the i-th PFB of a height at its next
           sequence: since nothing is dropped, no sequence ever gaps.
  das      light-client rounds: a fixed count (rate x seconds) of rounds at
           sorted uniform instants (a Poisson process conditioned on its
           count), on the newest height with probability `newest_share`,
           else uniform over the others; instants and heights come from the
           traffic's `plan_seed`, so every run seed offers the same arrivals.
           The run seed draws each round's `samples` row-axis coordinates,
           uniform over the 2k x 2k EDS.

Everything is drawn from (seed, height) or (seed, "das"), so the same seed
gives the same bytes.  The program sees only the signed txs and the
coordinates.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import square as ref

# Upper bounds on what signing adds to the blobs (auth info, fee, the PFB
# message with its commitments, protobuf framing): only used to stop
# drawing; the exact bytes are checked after signing.
TX_BASE_BYTES = 600
TX_PER_BLOB_BYTES = 120


def _rng(seed: int, *salt) -> np.random.Generator:
    return np.random.default_rng([seed, *[hash_salt(s) for s in salt]])


def hash_salt(s) -> int:
    if isinstance(s, int):
        return s
    return int.from_bytes(str(s).encode()[:8].ljust(8, b"\0"), "little")


def plan_height(traffic: dict, config: dict, seed: int, height: int) -> list[list[int]]:
    """Blob sizes of each PFB of one height, drawn until the block is full,
    in an order drawn from the run seed."""
    rng = _rng(traffic["plan_seed"], height)
    (bmin, bmax), (smin, smax) = traffic["blobs_per_pfb"], traffic["blob_size"]
    k = config["max_square_size"]
    share_budget = int(k * k * (1 - traffic["share_margin"]))
    pfbs: list[list[int]] = []
    raw_bytes = compact_bytes = blob_shares = 0
    while True:
        n = int(rng.integers(bmin, bmax + 1))
        sizes = [int(s) for s in rng.integers(smin, smax + 1, n)]
        tx = TX_BASE_BYTES + TX_PER_BLOB_BYTES * n
        counts = [ref.sparse_shares(s) for s in sizes]
        shares = sum(c + ref.subtree_width(c) - 1 for c in counts)
        compact = ref._needed(compact_bytes + tx + 6 * n + 4,
                              ref.FIRST_COMPACT, ref.CONT_COMPACT)
        if (raw_bytes + sum(sizes) + tx > config["max_block_bytes"]
                or compact + blob_shares + shares > share_budget):
            order = _rng(seed, height, "order").permutation(len(pfbs))
            return [pfbs[i] for i in order]
        pfbs.append(sizes)
        raw_bytes += sum(sizes) + tx
        compact_bytes += tx + 6 * n + 4
        blob_shares += shares


def blobs_of(traffic: dict, seed: int, height: int, pfb: int, sizes: list[int]):
    """[(namespace 29 B, data)] of one PFB, namespaces sorted as txsim does."""
    rng = _rng(seed, height, pfb, "blobs")
    out = []
    for size in sizes:
        sub = rng.integers(1, 256, 10, dtype=np.uint8).tobytes()
        out.append((bytes(19) + sub, rng.bytes(size)))
    return sorted(out, key=lambda b: b[0])


def fits(raw_txs: list[bytes], config: dict) -> bool:
    """The whole list fits the byte cap and the largest square."""
    if sum(len(t) for t in raw_txs) > config["max_block_bytes"]:
        return False
    pfbs = [ref.parse_blob_tx(t) for t in raw_txs]
    try:
        ref.layout([], pfbs, config["max_square_size"])
    except ValueError:
        return False
    return True


def das_schedule(traffic: dict, rate: float, seconds: float, seed: int,
                 heights: list[int], k: int) -> dict:
    """Due instants (s from window start), height and coordinates per round."""
    plan = _rng(traffic["plan_seed"], "das")
    n = max(1, int(round(rate * seconds)))
    due = np.sort(plan.uniform(0.0, seconds, n))
    newest, older = heights[-1], heights[:-1]
    pick_new = plan.uniform(size=n) < traffic["newest_share"]
    pick_old = plan.integers(0, max(1, len(older)), n)
    hs = [newest if new or not older else older[i] for new, i in zip(pick_new, pick_old)]
    coords = _rng(seed, "das").integers(0, 2 * k, (n, traffic["samples"], 2))
    return {"due": due, "heights": hs, "coords": coords}
