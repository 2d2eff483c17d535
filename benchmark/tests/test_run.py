"""The whole run path on the CPU at k=8: each cell proves correct, its
control and each fault the cell can have make `correct` false, and a run
without a TPU prints no result."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.tests import tiny

SEED = 3_000_000_123  # larger than 32 signed bits hold


def _run(monkeypatch, capsys, cell, *extra, trace=0):
    tiny.patch(monkeypatch)
    rc = run.run(["--workload", cell, "--seed", str(SEED), "--seconds", "2",
                  "--trace", str(trace), *extra], require_tpu=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("cell", ["k512-propose", "k128-das-over"])
def test_cell_is_correct(monkeypatch, capsys, cell):
    out = _run(monkeypatch, capsys, cell)
    assert out["correct"], out["checks"]
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", ["k512-propose", "k128-das-over"])
def test_control_is_not_correct(monkeypatch, capsys, cell):
    out = _run(monkeypatch, capsys, cell, "--control")
    assert not out["correct"], out["checks"]


def _answer_altered(monkeypatch):
    """The data root altered where the proposer produces it."""
    from celestia_app_tpu.app import App

    real = App.prepare_proposal

    def prepare(self, txs):
        data = real(self, txs)
        return dataclasses.replace(data, hash=bytes([data.hash[0] ^ 1]) + data.hash[1:])

    monkeypatch.setattr(App, "prepare_proposal", prepare)


def _half_batch(monkeypatch):
    """The proposer leaves out half of the txs it is offered."""
    from celestia_app_tpu.app import App

    real = App.prepare_proposal
    monkeypatch.setattr(App, "prepare_proposal",
                        lambda self, txs: real(self, txs[: len(txs) // 2]))


def _state_unchanged(monkeypatch):
    """Finalize returns the state unchanged: nothing is executed."""
    from celestia_app_tpu.app import App

    monkeypatch.setattr(App, "finalize_block", lambda self, t, txs, **kw: [])


def _proof_altered(monkeypatch):
    """A served share altered where the sampler assembles its proof."""
    from celestia_app_tpu.serve.sampler import ProofSampler

    real = ProofSampler._batched

    def batched(self, entry, coords, axis="row"):
        proofs = real(self, entry, coords, axis)
        p = proofs[0]
        share = bytearray(p.data[0])
        share[300] ^= 1
        proofs[0] = dataclasses.replace(p, data=(bytes(share),))
        return proofs

    monkeypatch.setattr(ProofSampler, "_batched", batched)


@pytest.mark.parametrize("cell,fault", [
    ("k512-propose", _answer_altered),
    ("k512-propose", _half_batch),
    ("k512-propose", _state_unchanged),
    ("k128-das-over", _proof_altered),
])
def test_fault_is_not_correct(monkeypatch, capsys, cell, fault):
    tiny.patch(monkeypatch)
    if cell == "k128-das-over":
        real_setup = run.make_driver

        def make(loaded, seed):  # plant the fault after set-up's warm-up
            d = real_setup(loaded, seed)
            setup = d.setup

            def planted():
                setup()
                fault(monkeypatch)
            d.setup = planted
            return d
        monkeypatch.setattr(run, "make_driver", make)
    else:
        fault(monkeypatch)
    try:
        rc = run.run(["--workload", cell, "--seed", str(SEED), "--seconds", "2",
                      "--trace", "0"], require_tpu=False)
    except RuntimeError:
        return  # the fault stopped the run before any result: not correct
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not out["correct"], out["checks"]


def test_trace_run_reports_per_layer(monkeypatch, capsys):
    out = _run(monkeypatch, capsys, "k512-propose", trace=1)
    assert {"prepare_s", "process_s", "dispatch_s"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0


def test_no_tpu_prints_no_result(capsys):
    rc = run.run(["--workload", "k512-propose", "--seed", "1", "--seconds", "1"])
    assert rc == 3
    assert capsys.readouterr().out.strip() == ""


def test_without_the_program_fails(tmp_path):
    root = os.path.dirname(run.HERE)
    shutil.copytree(run.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "k512-propose",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": ""})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
