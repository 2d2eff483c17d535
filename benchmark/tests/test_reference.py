"""The plain reference against the program at tiny sizes of each traffic
mix: the same square from the same txs, the same data root."""

import numpy as np
import pytest

from benchmark import run
from benchmark.propose import ProposeCell
from benchmark.reference import dah, square
from benchmark.tests import tiny


@pytest.mark.parametrize("traffic", ["propose-manifest", "propose-txsim"])
def test_square_and_root_match_the_program(monkeypatch, traffic):
    from celestia_app_tpu.square import builder
    from celestia_app_tpu.testutil.reference import host_dah

    tiny.patch(monkeypatch)
    loaded = run.load_cell("k512-propose")
    t = run.load_json(run.HERE, "traffic", traffic + ".json")
    cell = ProposeCell(loaded["config"], t, seed=2**31 + 5)
    cell.setup(seconds=0.0, validate=False)
    txs = cell.pool[0]
    k = loaded["config"]["max_square_size"]
    ours = square.ods_from_txs(txs, k)
    theirs = builder.construct(txs, k)
    want = np.frombuffer(b"".join(theirs.share_bytes()), np.uint8)
    assert ours.reshape(-1).tobytes() == want.tobytes()
    rows, cols, root = host_dah(ours.copy())
    assert dah.dah(dah.extend(ours)) == (rows, cols, root)
    assert root == cell.blocks[-1][1].hash


def test_generator_matches_the_spec_at_every_field():
    from celestia_app_tpu.gf import codec_for_width

    for k in (2, 16, 256):
        assert (dah.generator_bits(k) == codec_for_width(k).generator_bits()).all()


def test_control_changes_the_root():
    rng = np.random.default_rng(1)
    ods = rng.integers(0, 256, (4, 4, 512), dtype=np.uint8)
    ods[..., :29] = 0
    assert dah.data_root(ods) != dah.data_root(ods, parity_leaves_own_ns=True)
