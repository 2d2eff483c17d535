"""Cross-height continuous batching: the batched path is bit-identical
to the unbatched fused pipeline, the persistent buffer ring never
aliases a retained square, and the batched jit cache keys per
(k, batch, mode).

Crypto-free (no TestNode import) so the whole module runs in this image.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from celestia_app_tpu.constants import NAMESPACE_SIZE, SHARE_SIZE
from celestia_app_tpu.da.eds import (
    ExtendedDataSquare,
    _batched_pipeline_for_mode,
    jit_pipeline_batched,
)
from celestia_app_tpu.kernels.fused import (
    batched_is_built,
    jit_extend_and_dah,
    jit_extend_and_dah_batched,
)
from celestia_app_tpu.parallel.pipeline import (
    BlockPipeline,
    _BufferRing,
    env_batch,
    stream_blocks,
)

CONSTRUCTIONS = ("vandermonde", "leopard")

# Reference golden DAH hash (pkg/da/data_availability_header_test.go) —
# the batched program must reproduce it square-for-square.
K2_HASH = bytes.fromhex(
    "b56e4d251ac266f4b91cc5464b3fc7efcbdc888064647496d13133f0dc65ac25"
)


def _golden_share() -> bytes:
    ns = bytes([0x00]) + bytes(18) + bytes([0x01]) * 10
    return ns + b"\xff" * (SHARE_SIZE - NAMESPACE_SIZE)


def random_ods(k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n = k * k
    ns = np.sort(rng.integers(0, 128, n).astype(np.uint8))
    ods = rng.integers(0, 256, (n, SHARE_SIZE), dtype=np.uint8)
    ods[:, :NAMESPACE_SIZE] = 0
    ods[:, NAMESPACE_SIZE - 1] = ns
    return ods.reshape(k, k, SHARE_SIZE)


def _batched_outputs(k: int, odss: np.ndarray, construction: str):
    fn = jit_extend_and_dah_batched(k, odss.shape[0], construction)
    return fn(jnp.asarray(odss, dtype=jnp.uint8))


class TestBatchedParity:
    """The vmapped multi-square program must equal B independent fused
    dispatches byte for byte — the whole reason the dispatcher may
    coalesce without a correctness argument."""

    def _assert_batched_matches(self, k, batch, construction):
        odss = np.stack(
            [random_ods(k, seed=100 * k + b) for b in range(batch)]
        )
        out = _batched_outputs(k, odss, construction)
        single = jit_extend_and_dah(k, construction)
        for b in range(batch):
            ref = single(jnp.asarray(odss[b], dtype=jnp.uint8))
            for name, got_arr, want_arr in zip(
                ("eds", "row_roots", "col_roots", "droot"),
                (o[b] for o in out), ref,
            ):
                assert np.array_equal(
                    np.asarray(got_arr), np.asarray(want_arr)
                ), (k, construction, b, name)

    # The full k ∈ {2,8,32} × both-constructions matrix is pinned; the
    # fast tier carries the cheap-compile corner of it and the rest is
    # slow-marked (one vmap compile per (k, batch, construction) on this
    # 1-core image is tens of seconds — the test_das_proofs precedent).
    @pytest.mark.parametrize("k,batch,construction", [
        (2, 3, "vandermonde"), (2, 3, "leopard"),
    ])
    def test_batched_matches_unbatched(self, k, batch, construction):
        self._assert_batched_matches(k, batch, construction)

    @pytest.mark.slow
    @pytest.mark.parametrize("k,batch,construction", [
        (8, 2, "vandermonde"), (8, 2, "leopard"),
        (32, 2, "vandermonde"), (32, 2, "leopard"),
    ])
    def test_batched_matches_unbatched_slow(self, k, batch, construction):
        self._assert_batched_matches(k, batch, construction)

    def test_golden_vector_through_batched_program(self):
        """The reference golden DAH hash, every square of the batch."""
        from celestia_app_tpu.da.dah import DataAvailabilityHeader

        k, batch = 2, 2
        shares = [_golden_share()] * (k * k)
        ods = np.frombuffer(b"".join(shares), dtype=np.uint8).reshape(
            k, k, SHARE_SIZE
        )
        out = _batched_outputs(k, np.stack([ods] * batch), "vandermonde")
        for b in range(batch):
            dah = DataAvailabilityHeader(
                row_roots=[bytes(r) for r in np.asarray(out[1][b])],
                column_roots=[bytes(r) for r in np.asarray(out[2][b])],
            )
            assert dah.hash() == K2_HASH, b

    def test_batched_stream_matches_serial(self):
        """The whole pipeline leg: coalesced stream == serial computes."""
        k = 2
        blocks = [(i, random_ods(k, seed=40 + i)) for i in range(5)]
        ref = [
            ExtendedDataSquare.compute(o).data_root() for _, o in blocks
        ]
        out = list(stream_blocks(iter(blocks), k, depth=2, batch=2))
        assert [t for t, _ in out] == [0, 1, 2, 3, 4]
        assert [e.data_root() for _, e in out] == ref

    # Two extra whole-pipeline variants to compile (~24 s) for a parity
    # that the fused leg already pins every run — slow tier.
    @pytest.mark.slow
    def test_batched_staged_mode_matches(self, monkeypatch):
        """The staged rung's batched twin (what a degraded pipeline
        dispatches) is bit-identical too."""
        k, batch = 2, 2
        odss = np.stack([random_ods(k, seed=60 + b) for b in range(batch)])
        fused = _batched_outputs(k, odss, "vandermonde")
        staged = _batched_pipeline_for_mode(
            "staged", k, batch, "vandermonde"
        )(jnp.asarray(odss, dtype=jnp.uint8))
        host = _batched_pipeline_for_mode(
            "host", k, batch, "vandermonde"
        )(jnp.asarray(odss, dtype=jnp.uint8))
        for got in (staged, host):
            for a, b_arr in zip(fused, got):
                assert np.array_equal(np.asarray(a), np.asarray(b_arr))


class TestBatchedJitKeying:
    """One executable per (k, batch, mode, construction) — never a stale
    or cross-shape cache hit."""

    def test_same_key_same_callable(self):
        a = jit_extend_and_dah_batched(2, 2, "vandermonde")
        b = jit_extend_and_dah_batched(2, 2, "vandermonde")
        assert a is b

    def test_distinct_keys_distinct_callables(self):
        base = jit_extend_and_dah_batched(2, 2, "vandermonde")
        assert jit_extend_and_dah_batched(2, 3, "vandermonde") is not base
        assert jit_extend_and_dah_batched(4, 2, "vandermonde") is not base
        assert jit_extend_and_dah_batched(2, 2, "leopard") is not base
        assert (
            jit_extend_and_dah_batched(2, 2, "vandermonde", donate=True)
            is not base
        )

    def test_mode_routes_to_distinct_pipelines(self):
        fused = _batched_pipeline_for_mode("fused", 2, 2, "vandermonde")
        staged = _batched_pipeline_for_mode("staged", 2, 2, "vandermonde")
        host = _batched_pipeline_for_mode("host", 2, 2, "vandermonde")
        assert fused is not staged and staged is not host
        # fused_epi folds into the fused batched program (the epilogue is
        # a per-square tile schedule) — same executable, by design.
        assert _batched_pipeline_for_mode("fused_epi", 2, 2, "vandermonde") is fused

    def test_jit_pipeline_batched_routes_by_env(self, monkeypatch):
        """The active-mode entry rides the $CELESTIA_PIPE_FUSED seam like
        its unbatched twin."""
        monkeypatch.delenv("CELESTIA_PIPE_FUSED", raising=False)
        fused = jit_pipeline_batched(2, 2)
        assert fused is jit_extend_and_dah_batched(2, 2)
        monkeypatch.setenv("CELESTIA_PIPE_FUSED", "off")
        assert jit_pipeline_batched(2, 2) is not fused

    def test_built_registry_tracks_batched_keys(self):
        jit_extend_and_dah_batched(2, 2, "vandermonde")
        assert batched_is_built(2, 2, "vandermonde")
        assert not batched_is_built(2, 64, "vandermonde")

    def test_batch_below_one_rejected(self):
        with pytest.raises(ValueError):
            jit_extend_and_dah_batched(2, 0, "vandermonde")

    def test_env_batch_parse(self, monkeypatch):
        monkeypatch.delenv("CELESTIA_PIPE_BATCH", raising=False)
        assert env_batch() == 1
        for raw, want in (("0", 1), ("1", 1), ("off", 1), ("4", 4),
                          ("junk", 1), ("-3", 1)):
            monkeypatch.setenv("CELESTIA_PIPE_BATCH", raw)
            assert env_batch() == want, raw

    def test_env_batch_auto_follows_occupancy_signal(self, monkeypatch):
        """`auto` batches exactly when the square journal says traffic is
        producing small, under-filled squares."""
        from celestia_app_tpu.trace import square_journal

        monkeypatch.setenv("CELESTIA_PIPE_BATCH", "auto")
        monkeypatch.setattr(
            square_journal, "_LAST", {"occupancy": 0.9, "k": 8}
        )
        assert env_batch() == 1
        monkeypatch.setattr(
            square_journal, "_LAST", {"occupancy": 0.2, "k": 8}
        )
        assert env_batch() == 4
        # 0.0 is a REAL signal (an empty square), not a missing one.
        monkeypatch.setattr(
            square_journal, "_LAST", {"occupancy": 0.0, "k": 8}
        )
        assert env_batch() == 4
        monkeypatch.setattr(square_journal, "_LAST", None)
        assert env_batch() == 1  # no signal yet: stay unbatched

    def test_env_batch_cap_is_the_warmup_ceiling(self, monkeypatch):
        """auto's cap is the auto batch even before any traffic — what a
        server warms at startup must cover what auto may later run."""
        from celestia_app_tpu.parallel.pipeline import env_batch_cap
        from celestia_app_tpu.trace import square_journal

        monkeypatch.setattr(square_journal, "_LAST", None)
        monkeypatch.setenv("CELESTIA_PIPE_BATCH", "auto")
        assert env_batch() == 1  # no signal yet...
        assert env_batch_cap() == 4  # ...but the ceiling is the warm target
        monkeypatch.setenv("CELESTIA_PIPE_BATCH", "3")
        assert env_batch_cap() == 3
        monkeypatch.delenv("CELESTIA_PIPE_BATCH")
        assert env_batch_cap() == 1

    def test_late_pin_is_counted_not_silent(self):
        """A pin landing after the slot was re-acquired (retention past
        the fence window) must be observable."""
        ring = _BufferRing(2, slots=1, batch=1)
        sid = ring.acquire(1.0)
        gen = ring.generation(sid)
        ring.release(sid)
        ring.acquire(1.0)  # re-acquired: the fence window has passed
        ring.pin(sid, gen)
        assert ring.late_pins == 1
        # An in-window pin is not a late pin.
        ring2 = _BufferRing(2, slots=1, batch=1)
        s2 = ring2.acquire(1.0)
        ring2.pin(s2, ring2.generation(s2))
        assert ring2.late_pins == 0


class TestBufferRing:
    """The persistent staging ring: recycled across blocks, never
    aliasing anything retained downstream."""

    def test_acquire_release_cycle_reuses_buffers(self):
        ring = _BufferRing(2, slots=2, batch=1)
        a = ring.acquire(1.0)
        b = ring.acquire(1.0)
        assert {a, b} == {0, 1}
        assert ring.acquire(0.05) is None  # exhausted: bounded wait
        before = ring.host(a)
        ring.release(a)
        c = ring.acquire(1.0)
        assert c == a and ring.host(c) is before  # recycled, not realloc'd
        assert ring.swaps == 0

    def test_pinned_slot_swaps_fresh_buffer(self):
        """Write-after-retain must be a fresh slot: pinning marks the
        buffer as retained downstream and the next acquire swaps it."""
        ring = _BufferRing(2, slots=1, batch=1)
        sid = ring.acquire(1.0)
        retained = ring.host(sid)
        retained[:] = 7  # the bytes a retained square would alias
        ring.release(sid)
        ring.pin(sid)
        again = ring.acquire(1.0)
        assert again == sid
        assert ring.host(again) is not retained  # fresh backing buffer
        assert (retained == 7).all()  # the retained bytes are untouched
        assert ring.swaps == 1
        assert ring.states()["pinned"] == 0  # pin consumed by the swap

    def test_pin_after_release_still_protects(self):
        """Retention lands at commit, usually after the drain released
        the slot — pin must work at any point in the lifecycle."""
        ring = _BufferRing(2, slots=2, batch=2)
        sid = ring.acquire(1.0)
        buf = ring.host(sid)
        ring.release(sid)
        ring.pin(sid)  # post-release, like ForestCache.put at commit
        got = {ring.acquire(1.0), ring.acquire(1.0)}
        assert got == {0, 1}
        assert ring.host(sid) is not buf

    def test_recycled_slot_never_aliases_forest_retained_eds(self):
        """The regression the ring exists to prevent: stream squares
        through one pipeline, retain one in the serve plane's
        ForestCache, keep streaming until every ring slot has been
        recycled — the retained square's proofs and root must be
        byte-identical throughout, and the retention must have pinned
        (then swapped) its feeding slot."""
        from celestia_app_tpu.serve.cache import ForestCache

        k = 2
        blocks = [(i, random_ods(k, seed=70 + i)) for i in range(8)]
        ref_roots = {
            i: ExtendedDataSquare.compute(o).data_root() for i, o in blocks
        }
        cache = ForestCache(heights=2, spill=2)
        pipe = BlockPipeline(k, depth=2, batch=1)
        retained = {}
        try:
            submitted = 0
            for tag, ods in blocks:
                pipe.submit(ods, tag)
                submitted += 1
                if submitted <= 2:
                    continue  # prime the overlap window
                got_tag, eds = pipe._drain_one()
                if got_tag == 0:
                    # Retain mid-stream, while later blocks keep
                    # recycling the ring behind it.
                    entry = cache.put(got_tag, eds)
                    retained[got_tag] = (entry, eds)
            for got_tag, eds in pipe.drain():
                pass
        finally:
            pipe.close()
        assert retained, "retention never happened"
        assert pipe._ring._pinned or pipe._ring.swaps, (
            "retention must pin (or have swapped) the feeding slot"
        )
        entry, eds = retained[0]
        # The retained square still serves the exact committed bytes.
        assert eds.data_root() == ref_roots[0]
        line = entry.line_levels("row", 0)
        host_tree = eds.row_tree(0, host=True)
        assert line == host_tree.levels()

    def test_stream_recycles_instead_of_allocating(self):
        """More blocks than ring slots through one pipeline: the ring's
        backing buffers must be reused (no per-height allocation, no
        swaps when nothing is retained)."""
        k = 2
        blocks = [(i, random_ods(k, seed=90 + i)) for i in range(6)]
        pipe = BlockPipeline(k, depth=1, batch=1)
        ids_before = {id(h) for h in pipe._ring._hosts}
        out = []
        try:
            submitted = 0
            for tag, ods in blocks:
                pipe.submit(ods, tag)
                submitted += 1
                if submitted > 1:
                    out.append(pipe._drain_one())
            out.extend(pipe.drain())
        finally:
            pipe.close()
        assert len(out) == 6
        ids_after = {id(h) for h in pipe._ring._hosts}
        assert ids_after == ids_before  # nothing was swapped or realloc'd
        assert pipe._ring.swaps == 0


class TestBatchedFaultFallback:
    """A batched-dispatch fault must fall to the unbatched rung and on
    down the ladder, with roots bit-identical (the chaos drill's tier-1
    twin, small and fixed-seed)."""

    def test_batched_fault_falls_to_unbatched_then_ladder(self):
        from celestia_app_tpu import chaos
        from celestia_app_tpu.chaos import degrade
        from celestia_app_tpu.trace.metrics import registry

        k = 2
        blocks = [(i, random_ods(k, seed=200 + i)) for i in range(4)]
        chaos.install("")
        degrade.reset_for_tests()
        baseline = {
            t: e.data_root()
            for t, e in stream_blocks(iter(blocks), k, depth=2, batch=1)
        }

        def falls():
            for labels, v in registry().counter(
                "celestia_recoveries_total", ""
            ).samples():
                if (labels.get("seam") == "device.dispatch"
                        and labels.get("outcome") == "unbatched"):
                    return v
            return 0.0

        before = falls()
        chaos.install("seed=17,dispatch_fail=1.0")
        try:
            chaotic = {
                t: e.data_root()
                for t, e in stream_blocks(iter(blocks), k, depth=2, batch=2)
            }
        finally:
            chaos.uninstall()
            degrade.reset_for_tests()
        assert chaotic == baseline
        assert falls() > before
