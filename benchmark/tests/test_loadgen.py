"""The generator at tiny sizes of each traffic mix: the same seed gives the
same work, every seed the same amount of it."""

import numpy as np

from benchmark import loadgen, run

CONFIG = {"max_square_size": 8, "max_block_bytes": 8 * 8 * 482}


def _traffic(name, **kw):
    t = run.load_json(run.HERE, "traffic", name + ".json")
    t.update(kw)
    return t


def test_propose_plans_are_seeded_and_fill_the_square():
    t = _traffic("propose-txsim", blob_size=[100, 1500], blobs_per_pfb=[1, 2])
    a = loadgen.plan_height(t, CONFIG, 2**33 + 1, 3)
    assert a == loadgen.plan_height(t, CONFIG, 2**33 + 1, 3)
    assert a != loadgen.plan_height(t, CONFIG, 2**33 + 2, 3)
    assert sum(map(sum, a)) > 0.4 * CONFIG["max_block_bytes"]


def test_manifest_blobs_are_distinct_and_sorted():
    t = _traffic("propose-manifest")
    blobs = loadgen.blobs_of(t, 5, 0, 0, [200_000] * 6)
    assert [b[0] for b in blobs] == sorted(b[0] for b in blobs)
    assert len({b[1] for b in blobs}) == 6 and all(len(b[1]) == 200_000 for b in blobs)


def test_das_schedule_is_fixed_work():
    t = _traffic("das-swarm")
    a = loadgen.das_schedule(t, 50, 2.0, 7, [1, 2, 3], 8)
    b = loadgen.das_schedule(t, 50, 2.0, 8, [1, 2, 3], 8)
    assert len(a["due"]) == len(b["due"]) == 100
    assert np.all(np.diff(a["due"]) >= 0) and a["due"][-1] < 2.0
    assert a["coords"].shape == (100, 16, 2) and a["coords"].max() < 16
    newest = np.mean([h == 3 for h in a["heights"]])
    assert 0.5 < newest < 0.9
    # every seed offers the same arrivals on the same heights, at its own coordinates
    assert np.array_equal(a["due"], b["due"]) and a["heights"] == b["heights"]
    assert not np.array_equal(a["coords"], b["coords"])
    again = loadgen.das_schedule(t, 50, 2.0, 7, [1, 2, 3], 8)
    assert np.array_equal(a["coords"], again["coords"])
