"""Pipelined block streaming (SURVEY §2.4 P5, BASELINE config 5).

The reference processes blocks serially per height; the mainnet-replay
benchmark config instead streams consecutive blocks through the device.
Three overlaps compose here:

  * device-side: JAX dispatch is asynchronous, so the fused
    extend/NMT/DAH program for block i+1 queues behind block i without
    host involvement;
  * host-side: the host->device share transfer is driven by a dedicated
    uploader thread, so block i+1's ODS streams in WHILE block i computes.
    This is the part async dispatch alone cannot give: `device_put` of a
    fresh buffer blocks the calling thread for the full transfer, so
    without the uploader the pipeline degrades to transfer+compute
    serial time;
  * upload/dispatch split: transfer and program dispatch run on SEPARATE
    threads (double-buffered hand-off through a bounded queue), so the
    uploader starts block i+1's transfer the moment its slot frees instead
    of first waiting out block i's dispatch call, so a dispatch
    round-trip no longer sits between two transfers.

Cross-height continuous batching (this file's third era) adds two legs:

  * persistent donated buffers: a small ring of staging buffers
    (`_BufferRing`, depth+1 slots) is allocated ONCE and recycled across
    blocks — the uploader copies height h+1's shares into a free slot
    while height h is still dispatching, instead of allocating a fresh
    contiguous buffer per height.  A slot is recycled only after its
    batch's drain sync confirms the device consumed it, and a slot whose
    square the serve plane RETAINED (serve/cache.ForestCache — donation
    may alias the upload into the retained EDS) is pinned: the next
    acquire swaps in a fresh backing buffer instead of overwriting bytes
    a proof plane may still be serving.
  * vmap'd multi-square dispatch: with `$CELESTIA_PIPE_BATCH` > 1 (or
    `auto`, driven by the square journal's occupancy signal) the uploader
    coalesces queued same-k squares into one (B, k, k, S) staging slot
    and the dispatcher runs ONE vmapped fused program
    (da/eds._batched_pipeline_for_mode) instead of paying B dispatch
    latencies.  A batched-dispatch fault degrades to per-square dispatch
    through the normal guarded ladder (batched -> unbatched fused ->
    staged -> host), ticking celestia_recoveries_total{outcome=unbatched}.

Every drained block writes one `block_journal` row (trace/journal.py):
upload/dispatch/drain ms plus the two queue stalls (uploader blocked on
the depth-bounded hand-off, dispatcher starved of staged uploads) and the
dispatch's `batch_size`, all host perf_counter deltas around calls the
pipeline already makes — the only device sync remains the drain's
existing block_until_ready.

`BlockPipeline` bounds in-flight blocks (double buffering by default) so
HBM holds at most `depth` extended batches.  When the fused lowering is
active (kernels/fused.pipeline_mode), each uploaded ODS buffer is DONATED
to its dispatch — the pipeline owns the upload, nothing re-reads it, and
XLA may reuse it as extension scratch, which is what keeps depth>1
affordable at k=512 (one 134 MB scratch saved per in-flight block).
"""

from __future__ import annotations

import os
import queue
import threading
import time
import weakref
from dataclasses import dataclass, field

import jax
import numpy as np

from celestia_app_tpu.constants import SHARE_SIZE
from celestia_app_tpu.da.eds import (
    ExtendedDataSquare,
    _batched_pipeline_for_mode,
    _pipeline_for_mode,
    pipeline_cache_state,
)
from celestia_app_tpu.gf.rs import active_construction
from celestia_app_tpu.trace import journal

_SENTINEL = object()

#: Transient-upload retry budget (chaos upload_fail / a flaky transfer
#: link): attempts per block before the pipeline declares the feeder dead.
_UPLOAD_RETRIES = 2
#: Poll interval for the deadline-aware queue waits: every bounded put/get
#: wakes this often to check worker liveness, so a dead stage is reported
#: instead of wedging the caller forever.
_POLL_S = 0.1
#: close()'s inactivity window before a still-alive worker is declared
#: wedged: long enough for a cold large-k jit compile to finish (the
#: slow-but-healthy case), short enough that an abandoned process isn't
#: parked behind a dead device forever.
_CLOSE_STALL_S = 60.0
#: The coalescing ceiling $CELESTIA_PIPE_BATCH=auto resolves to when the
#: square journal's occupancy signal says traffic is producing small,
#: under-filled squares (the regime where dispatch latency dominates).
_AUTO_BATCH = 4
#: Occupancy below which `auto` batching engages: a square less than half
#: full at the current k means the proposer is cutting small squares.
_AUTO_OCCUPANCY = 0.5


def env_batch() -> int:
    """$CELESTIA_PIPE_BATCH: how many queued same-k squares one dispatch
    may coalesce.  ""/unset/"0"/"1" = off (every square its own
    dispatch); an integer N > 1 = coalesce up to N; "auto" = consult the
    square journal's occupancy signal — when the last exported square ran
    under 50% occupancy (0.0, an empty square, very much included),
    traffic is producing many small squares and the dispatcher batches up
    to 4, otherwise it stays unbatched."""
    val = os.environ.get("CELESTIA_PIPE_BATCH", "").strip().lower()
    if val in ("", "0", "1", "off"):
        return 1
    if val == "auto":
        from celestia_app_tpu.trace.square_journal import last_square

        last = last_square()
        if last is None:
            return 1  # no traffic signal yet: stay unbatched
        occupancy = last.get("occupancy")
        if occupancy is not None and occupancy < _AUTO_OCCUPANCY:
            return _AUTO_BATCH
        return 1
    try:
        return max(1, int(val))
    except ValueError:
        return 1


def env_batch_cap() -> int:
    """The CEILING $CELESTIA_PIPE_BATCH may ever resolve to — what a
    server's warmup must compile for.  Unlike env_batch() this ignores
    the instantaneous occupancy signal: "auto" at startup sees no
    traffic and env_batch() says 1, but the moment small squares arrive
    it will say _AUTO_BATCH, and THAT first coalesced dispatch must not
    pay a compile on the block path."""
    val = os.environ.get("CELESTIA_PIPE_BATCH", "").strip().lower()
    if val == "auto":
        return _AUTO_BATCH
    return env_batch()


def _queue_depth_gauge():
    from celestia_app_tpu.trace.metrics import registry

    return registry().gauge(
        "celestia_pipeline_queue_depth",
        "blocks resident per block-pipeline hand-off queue",
    )


def _close_leak_counter():
    from celestia_app_tpu.trace.metrics import registry

    return registry().counter(
        "celestia_pipeline_close_leaked_total",
        "pipeline worker threads still alive after close()'s join timeout",
    )


def _ring_occupancy_gauge():
    from celestia_app_tpu.trace.metrics import registry

    return registry().gauge(
        "celestia_pipeline_ring_occupancy",
        "buffer-ring slots by state (free / in_use / pinned-for-swap)",
    )


def _batch_size_histogram():
    from celestia_app_tpu.trace.metrics import registry

    return registry().histogram(
        "celestia_pipeline_batch_size",
        "same-k squares coalesced into one pipeline dispatch",
        buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0),
    )


def _rings_owned_bytes() -> int:
    """Staging bytes held by every live buffer ring's CURRENT backing
    arrays.  A pinned slot swapped out for a fresh buffer stops being
    counted here — its old bytes live exactly as long as the retained
    square, whose owner (serve_forest_cache) already reports them."""
    return sum(
        int(h.nbytes) for ring in list(_ALL_RINGS) for h in ring._hosts
    )


_ALL_RINGS: "weakref.WeakSet[_BufferRing]" = weakref.WeakSet()

from celestia_app_tpu.trace.device_ledger import (  # noqa: E402
    register_owner as _register_ring_owner,
)

_register_ring_owner("pipeline_buffer_ring", _rings_owned_bytes)


class _BufferRing:
    """Persistent staging buffers recycled across blocks.

    `slots` host arrays of shape (batch, k, k, SHARE_SIZE), allocated once
    at pipeline construction: the uploader copies each height's shares
    into a free slot (a memcpy into memory the allocator already owns —
    no per-height allocation, and on pinned-memory backends the transfer
    engine reads straight out of it) and `device_put`s the filled rows.

    Recycling contract:

      * a slot frees only when its batch's DRAIN confirmed the device
        consumed the upload (`release` after the batch's last
        block_until_ready) — `device_put` may be zero-copy on CPU, so
        overwriting a slot whose program hasn't executed yet would
        corrupt an in-flight square;
      * a slot whose square was RETAINED by the serve plane
        (ForestCache.put -> eds.attach_forest -> `pin`) is never
        overwritten while pinned: the next `acquire` of a pinned slot
        swaps in a FRESH backing array (write-after-retain is a fresh
        slot) and the old buffer lives exactly as long as the retained
        square does.

    Why the pin is belt-and-braces rather than load-bearing today: the
    retained EDS holds program OUTPUTS, and XLA only aliases an input
    buffer into an output via donation — which it refuses for buffers it
    does not own.  A zero-copy `device_put` (CPU) leaves the buffer
    externally owned, so donation is "not usable" there (the filtered
    warning), and a copying `device_put` (TPU) means the device buffer
    is jax-owned HBM that never references these staging bytes.  Either
    way no current backend can make a retained EDS alias a ring slot.
    The pin exists for a future unified-memory backend where that
    reasoning breaks — and because retention (at commit) can land after
    the drain already released the slot, `pin` takes the slot GENERATION
    its square was staged under: a pin that arrives after the slot was
    re-acquired is counted on `late_pins` (the fence fired after the
    window on a hypothetical aliasing backend — observable, not silent)
    and still pins forward.
    """

    def __init__(self, k: int, slots: int, batch: int):
        self.k = k
        self.batch = batch
        self._cond = threading.Condition()
        self._hosts = [
            np.zeros((batch, k, k, SHARE_SIZE), dtype=np.uint8)
            for _ in range(slots)
        ]
        self._free: list[int] = list(range(slots))
        self._pinned: set[int] = set()
        self._gen = [0] * slots  # bumped per acquire: late-pin detection
        self.swaps = 0  # pinned slots replaced with a fresh buffer
        self.late_pins = 0  # pins that arrived after the slot was reused
        _ALL_RINGS.add(self)

    def acquire(self, timeout_s: float) -> int | None:
        """A free slot id (its buffer safe to overwrite), or None on
        timeout so the caller can re-check liveness.  A pinned slot is
        swapped for a fresh buffer here — the retained square keeps the
        old bytes for its own lifetime."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while not self._free:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._cond.wait(remaining)
            sid = self._free.pop()
            if sid in self._pinned:
                self._hosts[sid] = np.zeros_like(self._hosts[sid])
                self._pinned.discard(sid)
                self.swaps += 1
            self._gen[sid] += 1
            return sid

    def generation(self, sid: int) -> int:
        with self._cond:
            return self._gen[sid]

    def host(self, sid: int) -> np.ndarray:
        return self._hosts[sid]

    def release(self, sid: int) -> None:
        with self._cond:
            self._free.append(sid)
            self._cond.notify()

    def pin(self, sid: int, gen: int | None = None) -> None:
        """Mark a slot's current buffer as retained downstream: it will
        be swapped, not overwritten, on its next acquire.  `gen` is the
        generation the retained square was staged under (see the class
        docstring): a pin landing after the slot was already re-acquired
        is counted on `late_pins` — on every current backend that is
        harmless (outputs never alias staging bytes), and counting it
        keeps the fence's coverage observable instead of silently
        assumed."""
        with self._cond:
            if gen is not None and self._gen[sid] != gen:
                self.late_pins += 1
            self._pinned.add(sid)

    def states(self) -> dict[str, int]:
        with self._cond:
            free = len(self._free)
            pinned = len(self._pinned)
        return {
            "free": free,
            "in_use": len(self._hosts) - free,
            "pinned": pinned,
        }


@dataclass
class _InFlight:
    tag: object
    outputs: tuple  # (eds, row_roots, col_roots, droot) device arrays
    k: int
    meta: dict = field(default_factory=dict)  # stage timings for the journal
    mode: str | None = None  # the lowering THIS square actually ran
    slot: tuple | None = None  # (ring, sid, refcount-list, generation)

    def release_slot(self) -> None:
        if self.slot is None:
            return
        ring, sid, ref, _gen = self.slot
        ref[0] -= 1
        if ref[0] == 0:
            ring.release(sid)
        self.slot = None


class BlockPipeline:
    """Bounded-depth asynchronous square pipeline with a transfer uploader
    and a separate dispatcher (double-buffered upload/compute overlap),
    optionally coalescing queued same-k squares into one vmapped dispatch
    (`batch` / $CELESTIA_PIPE_BATCH)."""

    def __init__(self, k: int, depth: int = 2, batch: int | None = None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.k = k
        self.depth = depth
        self.batch = max(1, batch if batch is not None else env_batch())
        # Panel streaming ($CELESTIA_PIPE_PANEL, kernels/panel.py): when
        # the seam engages at this k, the staging slot is consumed
        # PANEL-granularly — the uploader skips the whole-ODS device_put
        # and the dispatcher's panel runner uploads one row panel at a
        # time out of the persistent host slot, so the device never
        # stages a giant square whole next to the pipeline's working
        # set.  Panel squares are giant by definition and never coalesce
        # (the vmapped batched program would materialize B full EDSes),
        # so batching is forced off.  The multi-chip sharded rung
        # ($CELESTIA_EXTEND_SHARDS, kernels/panel_sharded.py) rides the
        # same staging: it engages only where the panel seam does, and
        # its runner consumes the host slot one mesh-wide panel step at
        # a time.
        from celestia_app_tpu.kernels.panel import panel_rows

        self._panel = panel_rows(k)
        if self._panel:
            self.batch = 1
        # A pipeline is bound to the RS construction active at creation:
        # every block it streams uses this one generator, even if
        # $CELESTIA_RS_CONSTRUCTION flips while blocks are in flight.
        self.construction = active_construction()
        # Journal context: pipeline mode + whether this (k, construction)
        # pays a jit build, both pinned before the wrapper is built.  The
        # first journaled block carries the init-time compile state; every
        # later row is by definition a hit.
        from celestia_app_tpu.kernels.fused import pipeline_mode_for_k

        self._mode = pipeline_mode_for_k(k)
        self._compile_state = pipeline_cache_state(
            k, self.construction, owned=True
        )
        # The pipeline owns each uploaded buffer and uses it exactly once,
        # so it rides the owned-input entry: the donating fused program by
        # default, the staged jit when the seam says staged.  Resolved per
        # MODE so the dispatcher can follow the degradation ladder
        # mid-stream (chaos/degrade.guarded_dispatch re-resolves after a
        # breaker trip).
        self._pipe_mode = self._mode
        self._pipe = _pipeline_for_mode(
            self._mode, k, self.construction, owned=True
        )
        # One persistent staging buffer per in-flight batch plus one being
        # filled: the uploader writes height h+1 into a free slot while
        # height h is still dispatching, and nothing allocates per block.
        self._ring = _BufferRing(k, slots=depth + 1, batch=self.batch)
        # submit -> _tasks -> [uploader: stage + device_put] -> _staged
        #        -> [dispatcher: program dispatch] -> _done
        # _tasks/_done bounded by depth: at most `depth` batches in flight
        # on the device and `depth` host squares waiting to transfer.
        # _staged is a SINGLE-slot hand-off — dispatch is a cheap async
        # enqueue, so one transferred-but-undispatched batch is all the
        # overlap needs, and the device high-water mark stays at the
        # documented `depth` batches instead of depth + staged uploads.
        self._tasks: queue.Queue = queue.Queue(maxsize=max(depth, self.batch))
        self._staged: queue.Queue = queue.Queue(maxsize=1)
        self._done: queue.Queue = queue.Queue(maxsize=depth * self.batch)
        self._error: BaseException | None = None
        self._stopping = False
        self._closed = False
        self._finished = False  # a _done sentinel has been consumed
        self._uploader = threading.Thread(target=self._upload, daemon=True)
        self._dispatcher = threading.Thread(target=self._dispatch, daemon=True)
        self._uploader.start()
        self._dispatcher.start()

    def _upload(self) -> None:
        """Uploader thread body.  The inner loop handles per-block faults
        (store the error, forward the sentinel); the outer wrap catches
        anything that escapes the loop itself, so a worker can die wedged
        but never die SILENT — submit()/drain() raise the stored
        exception instead of hanging behind a thread that no longer
        exists."""
        try:
            self._upload_loop()
        except BaseException as e:  # chaos-ok: worker death must be loud
            if self._error is None:
                self._error = e
            self._force_sentinel(self._staged)
            self._note_death("uploader", e)

    def _coalesce(self, first) -> tuple[list, bool]:
        """Greedy non-blocking batch fill: `first` plus up to batch-1 more
        queued tasks — the moment the intake runs dry the batch closes
        (the occupancy signal: coalescing trades nothing for latency, it
        only merges dispatches that were ALREADY queued behind each
        other).  Returns (items, sentinel_seen)."""
        items = [first]
        sentinel_seen = False
        while len(items) < self.batch:
            try:
                nxt = self._tasks.get_nowait()
            except queue.Empty:
                break
            if nxt is _SENTINEL:
                sentinel_seen = True
                break
            items.append(nxt)
        return items, sentinel_seen

    def _upload_loop(self) -> None:
        from celestia_app_tpu import chaos
        from celestia_app_tpu.chaos.degrade import recoveries

        failed = False
        while True:
            item = self._tasks.get()
            if item is _SENTINEL:
                self._staged.put(_SENTINEL)
                return
            if failed or self._stopping:
                continue  # keep consuming so no producer blocks forever
            items, sentinel_seen = self._coalesce(item)
            try:
                t0 = time.perf_counter()
                # A free persistent slot (recycled from a drained batch);
                # bounded waits so a stopping/dying pipeline never parks
                # this thread on a ring nobody will drain.  A close() in
                # progress just discards the batch (dropping queued work
                # is close()'s contract, not a death); a DEAD dispatcher
                # is a real failure to propagate.
                sid = None
                while True:
                    sid = self._ring.acquire(_POLL_S)
                    if sid is not None or self._stopping:
                        break
                    if not self._dispatcher.is_alive():
                        raise RuntimeError(
                            "dispatcher died; no staging slot will free"
                        )
                if sid is None:  # stopping: discard, keep consuming
                    if sentinel_seen:
                        self._staged.put(_SENTINEL)
                        return
                    continue
                host = self._ring.host(sid)
                for i, (ods, _tag, _t_enq) in enumerate(items):
                    np.copyto(host[i], ods)
                for attempt in range(_UPLOAD_RETRIES + 1):
                    try:
                        chaos.device_upload()  # injected stall/failure
                        if self._panel:
                            # Panel-granular staging: hand the host slot
                            # through whole — the dispatcher's panel
                            # runner uploads one row panel at a time out
                            # of it, so device staging residency is one
                            # panel, never the giant square.
                            x = host[0]
                        else:
                            x = jax.device_put(
                                host[0] if len(items) == 1
                                else host[: len(items)]
                            )
                        break
                    except Exception:  # chaos-ok: bounded upload retry
                        if attempt == _UPLOAD_RETRIES:
                            raise
                        time.sleep(0.002 * (2 ** attempt))
                if attempt:
                    recoveries().inc(seam="device.upload", outcome="retried")
                t1 = time.perf_counter()
            except BaseException as e:  # chaos-ok: stored, surfaced on the next drain
                self._error = e
                self._staged.put(_SENTINEL)
                self._note_death("uploader", e)
                failed = True
                continue
            # Stage timings ride the hand-off in `meta`; the put-stall
            # (uploader blocked because `depth` batches are already in
            # flight downstream) is written the instant put() returns.
            # The consolidated journal row is built at drain time, a full
            # dispatch later, so the read always sees the value in
            # practice — and the row falls back to 0.0, never a missing
            # field, if this thread were descheduled that whole time.
            # The slot id rides along so a failed DONATED dispatch can
            # re-upload from the persistent staging bytes
            # (guarded_dispatch's refresh) and the drain can recycle it.
            meta = {
                "upload_ms": (t1 - t0) * 1e3,
                # Head-of-line intake wait: how long the batch's OLDEST
                # block sat in _tasks before the uploader picked it up
                # (back-pressure/occupancy queue time, a gap — not work).
                "intake_wait_ms": max(
                    0.0,
                    (t0 - min(t_enq for _ods, _tag, t_enq in items)) * 1e3,
                ),
            }
            tags = [tag for _ods, tag, _t_enq in items]
            self._staged.put((x, tags, meta, sid))
            meta["upload_stall_ms"] = (time.perf_counter() - t1) * 1e3
            if sentinel_seen:
                self._staged.put(_SENTINEL)
                return

    def _dispatch(self) -> None:
        try:
            self._dispatch_loop()
        except BaseException as e:  # chaos-ok: worker death must be loud
            if self._error is None:
                self._error = e
            self._force_sentinel(self._done)
            self._note_death("dispatcher", e)

    def _note_death(self, stage: str, err: BaseException) -> None:
        """Black-box a pipeline-fatal stage failure: the journal rows
        around the death are the forensic record and the ring buffer is
        still warm.  ALWAYS called after the death sentinel is delivered
        — capture serializes table tails and probes /healthz, and a
        consumer blocked on the queue must not wait behind forensics.
        note_trigger rate-limits and never raises."""
        from celestia_app_tpu.trace.flight_recorder import note_trigger

        note_trigger(
            "worker_death", stage=stage, k=self.k, depth=self.depth,
            mode=self._mode, error=f"{type(err).__name__}: {err}"[:300],
        )

    @staticmethod
    def _force_sentinel(q: queue.Queue) -> None:
        """Deliver a death sentinel even against a full queue, by evicting
        one staged item per lap.  Dropping in-flight work on a DYING
        pipeline is correct — results past the failure are void — whereas
        a dropped sentinel would starve the downstream consumer into the
        silent wedge this propagation machinery exists to kill.  (This
        thread is the queue's only producer, so the evict-then-put race
        only ever runs against consumers, and converges.)"""
        while True:
            try:
                q.put(_SENTINEL, timeout=0.5)
                return
            except queue.Full:
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass

    def _resolve_pipe(self, mode: str):
        """The owned-input pipeline for `mode`, swapping lowerings when
        the degradation ladder moved it mid-stream (journal rows from then
        on carry the mode blocks actually ran)."""
        if mode != self._pipe_mode:
            self._pipe = _pipeline_for_mode(
                mode, self.k, self.construction, owned=True
            )
            self._pipe_mode = self._mode = mode
        return self._pipe

    def _dispatch_batched(self, x, sid: int, n: int) -> list[tuple[str, tuple]]:
        """One vmapped dispatch for n coalesced squares; any batched fault
        falls down to n unbatched dispatches through the normal guarded
        ladder (batched -> unbatched fused -> staged -> host), so a fault
        in the batching machinery costs latency, never a block.  Returns
        [(mode, (eds, rr, cr, droot)), ...] per square, in order."""
        from celestia_app_tpu import chaos
        from celestia_app_tpu.chaos.degrade import guarded_dispatch, recoveries
        from celestia_app_tpu.kernels.fused import pipeline_mode

        mode = pipeline_mode()
        try:
            chaos.device_dispatch(mode)
            out = _batched_pipeline_for_mode(
                mode, self.k, n, self.construction, owned=True
            )(x)
            ran = "fused" if mode == "fused_epi" else mode
            return [
                (ran, (out[0][b], out[1][b], out[2][b], out[3][b]))
                for b in range(n)
            ]
        except Exception:  # chaos-ok: batched fault -> unbatched rung
            recoveries().inc(seam="device.dispatch", outcome="unbatched")
            host = self._ring.host(sid)
            results = []
            for b in range(n):
                # The donated batch may be consumed; re-upload each square
                # from the persistent staging bytes and ride the ladder.
                xb = jax.device_put(host[b])
                results.append(
                    guarded_dispatch(
                        self._resolve_pipe, xb,
                        refresh=lambda b=b: jax.device_put(
                            np.ascontiguousarray(host[b])
                        ),
                        k=self.k,
                    )
                )
            return results

    def _dispatch_loop(self) -> None:
        from celestia_app_tpu.chaos.degrade import guarded_dispatch

        failed = False
        while True:
            t0 = time.perf_counter()
            item = self._staged.get()
            starve_ms = (time.perf_counter() - t0) * 1e3
            if item is _SENTINEL:
                self._done.put(_SENTINEL)
                return
            if failed or self._stopping:
                self._ring.release(item[3])  # keep the ring whole
                continue
            x, tags, meta, sid = item
            n = len(tags)
            try:
                t1 = time.perf_counter()
                # Async enqueue with retry + ladder fallback; no sync here.
                if n == 1:
                    host = self._ring.host(sid)
                    mode, out = guarded_dispatch(
                        self._resolve_pipe, x,
                        refresh=lambda: jax.device_put(
                            np.ascontiguousarray(host[0])
                        ),
                        k=self.k,
                    )
                    per_square = [(mode, out)]
                    # One owner for the panel/sharded journal extras —
                    # da/eds._panel_fields — so this row can never
                    # disagree with compute()'s for the same dispatch.
                    from celestia_app_tpu.da.eds import _panel_fields

                    meta.update(_panel_fields(mode, self.k))
                else:
                    per_square = self._dispatch_batched(x, sid, n)
                meta["dispatch_ms"] = (time.perf_counter() - t1) * 1e3
                meta["dispatch_starve_ms"] = starve_ms
                meta["batch_size"] = n
                _batch_size_histogram().observe(float(n), k=str(self.k))
            except BaseException as e:  # chaos-ok: stored, surfaced on the next drain
                self._error = e
                self._ring.release(sid)
                self._done.put(_SENTINEL)
                self._note_death("dispatcher", e)
                failed = True
                continue
            ref = [n]  # the slot recycles when the whole batch drained
            gen = self._ring.generation(sid)  # still held: stable here
            for tag, (mode, out) in zip(tags, per_square):
                self._done.put(_InFlight(
                    tag, out, self.k, meta, mode=mode,
                    slot=(self._ring, sid, ref, gen),
                ))

    def _materialize(self, inflight: _InFlight) -> tuple[object, ExtendedDataSquare]:
        eds, rr, cr, droot = inflight.outputs
        t0 = time.perf_counter()
        try:
            jax.block_until_ready(droot)  # the pipeline's one existing sync
        except Exception:  # chaos-ok: deferred fault -> breaker, then surface
            # Async dispatch defers real execution faults to THIS sync,
            # past guarded_dispatch's reach: this block is lost (the
            # caller sees the error), but the breaker still learns, so a
            # persistent fault steps the ladder for the blocks after it.
            from celestia_app_tpu.chaos.degrade import note_async_device_failure
            from celestia_app_tpu.kernels.fused import env_base_mode_for_k

            inflight.release_slot()
            note_async_device_failure(self._mode,
                                      base=env_base_mode_for_k(self.k))
            raise
        meta = inflight.meta
        journal.record(
            "stream", inflight.k, mode=inflight.mode or self._mode,
            compile=self._compile_state, tag=str(inflight.tag),
            depth=self.depth,
            batch_size=meta.get("batch_size", 1),
            **({"panels": meta["panels"]} if "panels" in meta else {}),
            **({"shards": meta["shards"]} if "shards" in meta else {}),
            intake_wait_ms=meta.get("intake_wait_ms", 0.0),
            upload_ms=meta.get("upload_ms", 0.0),
            upload_stall_ms=meta.get("upload_stall_ms", 0.0),
            dispatch_ms=meta.get("dispatch_ms", 0.0),
            dispatch_starve_ms=meta.get("dispatch_starve_ms", 0.0),
            drain_ms=(time.perf_counter() - t0) * 1e3,
        )
        self._compile_state = "hit"  # paid (or confirmed) on the first row
        result = ExtendedDataSquare(eds, rr, cr, droot, inflight.k)
        if inflight.slot is not None:
            # Serve-plane retention (ForestCache.put -> attach_forest)
            # pins the feeding slot: its buffer is swapped, not recycled.
            # The staged-under generation rides along so a pin landing
            # after the slot's next acquire is detected (ring.late_pins).
            ring, sid, _ref, gen = inflight.slot
            result._retain_cb = lambda: ring.pin(sid, gen)
        inflight.release_slot()
        gauge = _queue_depth_gauge()
        for name, q in (("tasks", self._tasks), ("staged", self._staged),
                        ("done", self._done)):
            gauge.set(q.qsize(), queue=name)
        ring_gauge = _ring_occupancy_gauge()
        for state, count in self._ring.states().items():
            ring_gauge.set(count, state=state)
        return inflight.tag, result

    def _raise_worker_death(self, stage: str) -> None:
        err = self._error
        msg = f"pipeline {stage} thread died"
        if err is not None:
            raise RuntimeError(msg) from err
        raise RuntimeError(msg)

    def submit(self, ods: np.ndarray, tag: object = None,
               timeout_s: float | None = None) -> None:
        """Enqueue one block; blocks the host only when `depth` batches are
        already in flight (back-pressure).

        Deadline-aware: the bounded put wakes periodically to check the
        workers, so a dead uploader raises the stored exception here
        instead of wedging the caller behind a queue nobody drains; with
        `timeout_s` set, sustained back-pressure past the deadline raises
        TimeoutError (the caller's load-shedding hook)."""
        if self._closed:
            raise RuntimeError("pipeline already closed")
        if self._error is not None:
            raise RuntimeError("pipeline feeder failed") from self._error
        deadline = (
            time.monotonic() + timeout_s if timeout_s is not None else None
        )
        while True:
            try:
                # The enqueue stamp rides the task so the uploader can
                # report the head-of-line intake wait (time queued before
                # any stage touched the block) — the timeline's first gap.
                self._tasks.put((ods, tag, time.perf_counter()),
                                timeout=_POLL_S)
                return
            except queue.Full:
                if self._error is not None:
                    raise RuntimeError(
                        "pipeline feeder failed"
                    ) from self._error
                if not self._uploader.is_alive():
                    self._raise_worker_death("uploader")
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"pipeline back-pressure: no intake slot within "
                        f"{timeout_s}s (depth={self.depth})"
                    ) from None

    def _get_done(self):
        """One _done item, with the wedge check: a dispatcher that died
        without managing to forward a sentinel leaves the queue silent
        forever — detect it and raise the stored error instead."""
        while True:
            try:
                return self._done.get(timeout=_POLL_S)
            except queue.Empty:
                if not self._dispatcher.is_alive() and self._done.empty():
                    # Leave _finished unset: the caller's close() still
                    # owes the uploader an unblock + leak report.
                    self._raise_worker_death("dispatcher")

    def _drain_one(self) -> tuple[object, ExtendedDataSquare]:
        inflight = self._get_done()
        if inflight is _SENTINEL:
            self._finished = True
            if self._error is not None:
                raise RuntimeError("pipeline feeder failed") from self._error
            raise RuntimeError("pipeline is closed")
        return self._materialize(inflight)

    def drain(self):
        """Close the intake and yield (tag, ExtendedDataSquare) for every
        remaining block, in order.  Blocks computed before a mid-stream
        failure still come out; the stored exception raises at the
        failure point (the sentinel) rather than hanging."""
        self._closed = True
        # A LIVE pipeline always consumes the intake (even post-failure
        # the uploader drains and discards), so the sentinel lands; with
        # EITHER worker dead it may never free — a dead uploader reads
        # nothing, and a dead dispatcher leaves the uploader wedged on the
        # _staged hand-off — so skip the intake rather than blocking on a
        # queue nobody will drain (the death wrappers already force-fed
        # the downstream sentinel that _get_done below will surface).
        while True:
            try:
                self._tasks.put(_SENTINEL, timeout=_POLL_S)
                break
            except queue.Full:
                if (not self._uploader.is_alive()
                        or not self._dispatcher.is_alive()):
                    break
        while True:
            inflight = self._get_done()
            if inflight is _SENTINEL:
                self._finished = True
                if self._error is not None:
                    raise RuntimeError("pipeline feeder failed") from self._error
                return
            yield self._materialize(inflight)

    def close(self) -> None:
        """Abandon the pipeline: stop both stages and drop pending results
        (early-exit path — device buffers held by _done are released).

        Keyed on _finished, NOT _closed: abandoning a drain() mid-stream
        leaves _closed set with results still queued, and an early return
        there would strand the dispatcher blocked on a full _done holding
        `depth` extended batches for the process lifetime.

        Worker death is REPORTED, never swallowed: a stage that outlives
        its join timeout (a genuine wedge — the error-propagation paths
        above cover everything else) logs and ticks
        `celestia_pipeline_close_leaked_total{stage}`."""
        if self._finished:
            return
        self._stopping = True  # stages discard anything still queued
        sentinel_needed = not self._closed
        self._closed = True
        # Unblock the stages if their output queues are full, and drop
        # held outputs.  Bounded waits everywhere: the intake sentinel is
        # offered NON-blocking inside the drain loop — with every queue
        # full and _done undrained, a blocking put here would deadlock
        # against the very back-pressure chain this method exists to
        # unwind — and a dispatcher that died without a sentinel (or
        # wedged outright) must not wedge close() itself.  The deadline
        # measures INACTIVITY (re-armed on every drained item), not total
        # wall clock: an abandoned stream whose first dispatch is mid-
        # jit-compile is slow-but-healthy, not a leak to report.
        deadline = time.monotonic() + _CLOSE_STALL_S
        while time.monotonic() < deadline:
            if sentinel_needed:
                try:
                    self._tasks.put_nowait(_SENTINEL)
                    sentinel_needed = False
                except queue.Full:
                    pass  # a drain below frees the chain; retry next lap
            try:
                item = self._done.get(timeout=_POLL_S)
            except queue.Empty:
                if not sentinel_needed and not self._dispatcher.is_alive():
                    break
                continue
            if item is _SENTINEL:
                break
            if isinstance(item, _InFlight):
                item.release_slot()  # keep the ring whole for the workers
            deadline = time.monotonic() + _CLOSE_STALL_S  # progress: re-arm
        self._finished = True
        self._uploader.join(timeout=5)
        self._dispatcher.join(timeout=5)
        for stage, thread in (("uploader", self._uploader),
                              ("dispatcher", self._dispatcher)):
            if thread.is_alive():
                import sys

                print(f"BlockPipeline.close: {stage} thread leaked past "
                      f"join timeout (k={self.k})", file=sys.stderr)
                _close_leak_counter().inc(stage=stage)


def stream_blocks(ods_iter, k: int, depth: int = 2, batch: int | None = None):
    """Stream squares through the device with `depth`-deep overlap.

    Yields (tag, ExtendedDataSquare) in submission order; with depth=2 the
    uploader transfers block i+1 while the device computes block i and the
    caller consumes block i-1 (the v5e-4 double-buffering shape of
    BASELINE config 5).  `batch` (default $CELESTIA_PIPE_BATCH) lets the
    dispatcher coalesce queued same-k squares into one vmapped dispatch.
    Abandoning the generator early stops the stages and releases in-flight
    device buffers."""
    pipe = BlockPipeline(k, depth, batch=batch)
    finished = False
    try:
        submitted = drained = 0
        window = max(depth, pipe.batch)
        for tag, ods in ods_iter:
            # Keep the intake primed without over-filling HBM: drain once
            # we have more than a window of submissions outstanding (the
            # window widens with the batch so coalescing has squares to
            # merge).
            while submitted - drained > window:
                yield pipe._drain_one()
                drained += 1
            pipe.submit(ods, tag)
            submitted += 1
        for item in pipe.drain():
            yield item
        finished = True
    finally:
        if not finished:
            pipe.close()
