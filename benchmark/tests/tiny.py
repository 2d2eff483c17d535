"""Tiny stand-ins for the cells, for CPU tests of the whole run path: the
same files, with the square cut to k=8 and the traffic to small blobs."""

from __future__ import annotations

from benchmark import run

TINY_K = 8
TINY_PROPOSE = {"blob_size": [100, 1500], "blobs_per_pfb": [1, 2],
                "plan_heights": 8, "fill_min": 0.5}


def patch(monkeypatch) -> None:
    real_cell, real_json = run.load_cell, run.load_json

    def load_json(*parts):
        d = real_json(*parts)
        if d.get("kind") == "propose":
            d.update(TINY_PROPOSE)
        return d

    def load_cell(name):
        loaded = real_cell(name)
        loaded["config"].update(
            gov_max_square_size=TINY_K, square_size_upper_bound=TINY_K,
            max_square_size=TINY_K, max_block_bytes=TINY_K * TINY_K * 482,
            retained_heights=3)
        t = loaded["traffic"]
        if t["kind"] == "propose":
            t.update(TINY_PROPOSE)
        else:
            t.update(workers=4, trace_seconds=1, check_proofs=200)
            loaded["params"] = {"rate_rounds_per_s": 5}
        return loaded

    monkeypatch.setattr(run, "load_json", load_json)
    monkeypatch.setattr(run, "load_cell", load_cell)
