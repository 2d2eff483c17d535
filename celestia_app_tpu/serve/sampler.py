"""ProofSampler: queued DAS sample requests, answered a whole batch per
dispatch.

The read-side twin of the fused->staged seam: two lowerings of "prove
share (row, col) against the committed DAH root", pinned byte-identical:

  batched (default)  the index plan for every queued request is computed
                     host-side (range_proof_node_coords — pure int math),
                     then the whole batch's proof nodes and shares come
                     off the cached forest and square in ONE compiled
                     gather program and one readback
                     (serve/cache.CachedForest.gather_proof), and RowProof
                     audit paths are indexed out of the memoized
                     data-root tree levels.  Zero hashing per request.
  host (fallback)    rebuild the touched row's NMT from the retained
                     shares (eds.row_tree(host=True)) and re-derive the
                     audit path recursively (merkle.proof) — no forest,
                     no gather, no batch machinery.  Slower, independent,
                     bit-identical.

$CELESTIA_SERVE_MODE pins the lowering ("batched" / "host"); the chaos
seam `proof.serve` ($CELESTIA_CHAOS proof_fail / proof_slow_ms) injects
failures into the batched dispatch, which the sampler absorbs by
answering the SAME batch on the host path — ticking
celestia_recoveries_total{seam="proof.serve"} — so an injected fault
costs latency, never a wrong or missing proof.

Queueing: concurrent `share_proof` callers park on a shared queue; the
first arrival becomes the batch leader, waits $CELESTIA_SERVE_BATCH_MS
(default 0: drain whatever queued), and answers everyone in one
dispatch.  Latency lands on celestia_proof_latency_seconds{phase="total"}
per sample; each batch group's `proof_gather` and `proof_assemble` spans
(trace/context.trace_span: rows, `celestia_proof_<step>_seconds`, and a
profiler annotation each) time its steps, and the group's `proof_serve`
row sums its samples' queue wait (`queue_wait_ms`).

Adversary detection (chaos/adversary.py — the ISSUE-10 attack model):

  * a sample landing on a share the WITHHOLDING PROPOSER hid raises
    ShareWithheld — the failed sample IS the light client's detection
    signal (celestia_da_detections_total{kind="withheld"} + the
    `withholding_detected` flight trigger);
  * when an adversary TAMPERS with the served square (malform_shares /
    wrong_root), every assembled proof passes a VERIFICATION GATE
    against the committed data root before leaving the sampler: a proof
    that does not verify raises BadProofDetected
    (kind="bad_proof" + the `root_mismatch` flight trigger) — a
    malformed share or forged root is detected, never served as a valid
    proof.  $CELESTIA_SERVE_VERIFY=1 arms the gate unconditionally
    (paranoid mode); with no adversary configured the gate costs one
    attr read per batch.
"""

from __future__ import annotations

import os
import threading
import time
from functools import lru_cache

from celestia_app_tpu.proof.share_proof import RowProof, ShareProof
from celestia_app_tpu.constants import NAMESPACE_SIZE, PARITY_NAMESPACE_BYTES
from celestia_app_tpu.nmt.proof import (
    NmtRangeProof,
    prove_range_from_levels,
    range_proof_node_coords,
)


class ShareWithheld(LookupError):
    """The sampled share is being withheld from the serve path (a
    data-withholding attack detected by this very sample)."""

    def __init__(self, height: int, row: int, col: int):
        super().__init__(
            f"share ({row},{col}) at height {height} is withheld "
            "(data-availability attack detected)"
        )
        self.height = height
        self.row = row
        self.col = col


class BadProofDetected(ValueError):
    """An assembled proof failed verification against the committed data
    root — a malformed square or wrong-root attack, detected at the
    sampler before any client saw a "valid" proof."""


def serve_mode() -> str:
    """$CELESTIA_SERVE_MODE: "batched" (default) or "host"."""
    return (
        "host"
        if os.environ.get("CELESTIA_SERVE_MODE", "") == "host"
        else "batched"
    )


def batch_window_s() -> float:
    """$CELESTIA_SERVE_BATCH_MS: how long the batch leader waits for more
    requests to coalesce before dispatching (0 = drain what queued)."""
    try:
        return max(
            float(os.environ.get("CELESTIA_SERVE_BATCH_MS", "0") or 0), 0.0
        ) / 1e3
    except ValueError:
        return 0.0


@lru_cache(maxsize=4096)
def _sample_coords(total: int, col: int) -> tuple[tuple[int, int], ...]:
    """(level, index) plan for a single-leaf range [col, col+1) — shared
    by every request sampling that column of a same-k square."""
    return tuple(range_proof_node_coords(total, col, col + 1))


def _latency():
    from celestia_app_tpu.trace.metrics import DEVICE_SECONDS_BUCKETS, registry

    return registry().histogram(
        "celestia_proof_latency_seconds",
        "DAS proof serving latency (phase=total, per served sample, "
        "labeled with the served share's capped namespace)",
        buckets=DEVICE_SECONDS_BUCKETS,
    )


def _shard_label(p: "_Pending") -> str:
    """Bounded `shard` label of one sample: the serve shard owning the
    sampled coordinate's leaf node (serve/shard.py routing math) — "0"
    on the single-device plane (one getattr, no layout math)."""
    leaf_shard = getattr(p.entry, "leaf_shard", None)
    if leaf_shard is None:
        return "0"
    return str(leaf_shard(p.row, p.col, p.axis))


def _proof_namespace_label(proof) -> str:
    """Capped per-tenant label of one served proof — the PR 4 accounting
    plane's cardinality contract applied to the read path (parity shares
    and failed samples fold into the reserved `other` bucket)."""
    from celestia_app_tpu.trace.square_journal import (
        OTHER_LABEL,
        capped_namespace_label,
        namespace_label,
    )

    ns = getattr(proof, "namespace", None)
    if not isinstance(ns, bytes) or ns == PARITY_NAMESPACE_BYTES:
        return OTHER_LABEL
    return capped_namespace_label(namespace_label(ns))


class _Pending:
    __slots__ = ("entry", "row", "col", "axis", "event", "proof", "error",
                 "t_submit")

    def __init__(self, entry, row: int, col: int, axis: str):
        self.entry = entry
        self.row = row
        self.col = col
        self.axis = axis
        self.event = threading.Event()
        self.proof: ShareProof | None = None
        self.error: Exception | None = None
        self.t_submit = time.perf_counter()


def _check_withheld(entry, coords) -> None:
    """The withholding intercept: raise ShareWithheld on the FIRST
    sampled coordinate the adversary hides — ticking the detection
    counter and black-boxing through the rate-limited
    `withholding_detected` trigger.  No adversary configured = one
    injector read, nothing else."""
    from celestia_app_tpu import chaos

    adv = chaos.active_adversary()
    if adv is None or adv.withhold_frac <= 0:
        return
    if getattr(entry, "healed", False):
        # A healed height serves from this node's own recovered,
        # root-verified store — the withholding proposer no longer sits
        # between the node and these bytes (serve/heal.py).
        return
    height = getattr(entry, "height", 0)
    n = 2 * entry.k
    for row, col in coords:
        if adv.withholds(height, n, row, col):
            from celestia_app_tpu.chaos.adversary import detections
            from celestia_app_tpu.serve import heal
            from celestia_app_tpu.trace.flight_recorder import note_trigger

            adv.count_injection("adversary.withhold", "withhold_frac")
            detections().inc(kind="withheld")
            note_trigger(
                "withholding_detected",
                height=height, row=int(row), col=int(col),
                withhold_frac=adv.withhold_frac,
            )
            # The detect -> act wire: a registered HealingEngine turns
            # this very detection into a repair + re-admit; the failed
            # sample itself still answers the terminal 410.
            heal.note_detection("withheld", height, entry=entry)
            raise ShareWithheld(height, int(row), int(col))


def _qos_gate_sample(entry, row: int, col: int) -> None:
    """The read-path per-tenant proof-rate gate (qos.py), resolved from
    the sampled coordinate's OWN namespace bytes pre-gather.  One cached
    env compare when enforcement is off; parity quadrants never carry a
    tenant."""
    from celestia_app_tpu import qos

    enf = qos.enforcer()
    if enf is None or row >= entry.k or col >= entry.k:
        return
    # One memoized device read per HANDLE (ods_namespaces), then a pure
    # host index per request: refusing an over-limit tenant must cost
    # less than the gather it sheds, or throttling is no protection.
    ns = bytes(entry.eds.ods_namespaces()[row * entry.k + col].tobytes())
    if ns == PARITY_NAMESPACE_BYTES:
        return
    from celestia_app_tpu.trace.square_journal import (
        capped_namespace_label,
        namespace_label,
    )

    enf.admit_proof(capped_namespace_label(namespace_label(ns)))


def _verify_gate_armed(entry) -> bool:
    """Proof verification before serving: armed when an adversary is
    tampering with served state, or unconditionally via
    $CELESTIA_SERVE_VERIFY=1."""
    if os.environ.get("CELESTIA_SERVE_VERIFY", "") == "1":
        return True
    from celestia_app_tpu import chaos

    adv = chaos.active_adversary()
    return adv is not None and adv.tampers()


class ProofSampler:
    """Batching sampler over ForestCache entries (serve/cache.py)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._queue: list[_Pending] = []
        self._leader_active = False

    # --- the queued entry point --------------------------------------------
    def share_proof(self, entry, row: int, col: int, axis: str = "row",
                    timeout_s: float = 30.0) -> ShareProof:
        """One sample through the batch queue: enqueue, and either lead
        the next batch dispatch or park until a leader answers."""
        # Per-sample withholding check BEFORE enqueue: one caller's
        # withheld coordinate must fail that caller, never its
        # batch-mates (a real server refuses one share, not the batch).
        _check_withheld(entry, [(row, col)])
        # Read-path QoS ($CELESTIA_QOS <tenant>.proof_rate) BEFORE the
        # gather: the tenant is the sampled share's own namespace (one
        # 29-byte read off the entry — the PR 10 label, resolved early),
        # so an over-limit spammer is refused at share-read cost instead
        # of after a full proof build it would make everyone else queue
        # behind.  Parity-quadrant coordinates carry no tenant and are
        # never throttled (uniform DAS sampling is protocol traffic).
        _qos_gate_sample(entry, row, col)
        p = _Pending(entry, row, col, axis)
        with self._lock:
            self._queue.append(p)
            lead = not self._leader_active
            if lead:
                self._leader_active = True
        if lead:
            window = batch_window_s()
            if window:
                time.sleep(window)
            with self._lock:
                batch, self._queue = self._queue, []
                self._leader_active = False
            self._serve_batch(batch)
        elif not p.event.wait(timeout_s):
            raise TimeoutError(
                f"proof sample ({row},{col}) not served within {timeout_s}s"
            )
        if p.error is not None:
            raise p.error
        assert p.proof is not None
        return p.proof

    def _serve_batch(self, batch: list[_Pending]) -> None:
        lat = _latency()
        t0 = time.perf_counter()
        by_entry: dict[tuple, list[_Pending]] = {}
        for p in batch:
            by_entry.setdefault((id(p.entry), p.axis), []).append(p)
        from celestia_app_tpu.trace.tracer import traced

        # One row per (entry, axis) group, each stamped with the group's
        # height — a batched dispatch serving three heights writes three
        # rows, so the height timeline (trace/timeline.py) never has to
        # guess which heights a batch touched.  `heights` still carries
        # the batch-wide group count on every row (the coalescing fact).
        tracer = traced()
        for group in by_entry.values():
            entry = group[0].entry
            tracer.write(
                "proof_serve", batch=len(group), heights=len(by_entry),
                queue_wait_ms=sum(t0 - p.t_submit for p in group) * 1e3,
                height=getattr(entry, "height", None),
                mode=serve_mode(),
                shards=getattr(entry, "shards", 0),
                # The extend plane's share partition
                # (kernels/panel_sharded): independent of the forest mesh
                # above, so the row carries both — a
                # sharded-forest/unsharded-share plane and its inverse
                # are distinguishable from one trace table.
                share_shards=getattr(entry, "share_shards", 0),
            )
        for group in by_entry.values():
            entry = group[0].entry
            coords = [(p.row, p.col) for p in group]
            try:
                proofs = self.sample_batch(entry, coords, axis=group[0].axis)
                for p, proof in zip(group, proofs):
                    p.proof = proof
            except Exception as e:  # noqa: BLE001 — parked callers must wake
                for p in group:
                    p.error = e
            finally:
                for p in group:
                    # Per-sample total carries the served share's capped
                    # namespace — the read path's per-tenant latency view
                    # (the group's gather/assemble spans stay unlabeled:
                    # one dispatch serves many tenants).
                    lat.observe(
                        time.perf_counter() - p.t_submit, phase="total",
                        namespace=_proof_namespace_label(p.proof),
                        shard=_shard_label(p),
                    )
                    p.event.set()

    # --- the two lowerings --------------------------------------------------
    def sample_batch(self, entry, coords, axis: str = "row") -> list[ShareProof]:
        """Answer [(row, col), ...] against one cached height on one
        sampling axis; routes the $CELESTIA_SERVE_MODE seam and absorbs
        injected/real batched-path faults by re-answering on the host
        path (bit-identical)."""
        from celestia_app_tpu import chaos
        from celestia_app_tpu.chaos.degrade import recoveries

        n = 2 * entry.k
        if axis not in ("row", "col"):
            raise ValueError(f"axis must be 'row' or 'col', got {axis!r}")
        for row, col in coords:
            if not (0 <= row < n and 0 <= col < n):
                raise ValueError(f"coordinate ({row},{col}) outside {n}x{n}")
        # Direct callers (drills, loadgen) get the same withholding
        # intercept the queued path applies per sample.
        _check_withheld(entry, coords)
        if serve_mode() == "host":
            return self._gate(entry, self._host_batch(entry, coords, axis))
        try:
            chaos.proof_serve()
            proofs = self._batched(entry, coords, axis)
        except Exception:  # noqa: BLE001 — the host path is the answer
            proofs = self._host_batch(entry, coords, axis)
            recoveries().inc(seam="proof.serve", outcome="degraded")
        return self._gate(entry, proofs)

    @staticmethod
    def _gate(entry, proofs: list[ShareProof]) -> list[ShareProof]:
        """The verification gate: when armed (adversarial tampering or
        $CELESTIA_SERVE_VERIFY=1), every proof must verify against the
        entry's committed data root before it leaves the sampler.  A
        failure is an attack detection (malformed square / wrong root):
        counted, black-boxed, and raised — never served as valid."""
        if not _verify_gate_armed(entry):
            return proofs
        # One batched device program decides the whole queue
        # (serve/verify.py); bit-identical to per-proof host verify,
        # host fallback on any batched fault via the proof.verify seam.
        from celestia_app_tpu.serve.verify import verify_proofs

        for ok in verify_proofs(proofs, entry.data_root):
            if ok:
                continue
            from celestia_app_tpu.chaos.adversary import detections
            from celestia_app_tpu.serve import heal
            from celestia_app_tpu.trace.flight_recorder import note_trigger

            detections().inc(kind="bad_proof")
            note_trigger(
                "root_mismatch",
                reason="serve_verification",
                height=getattr(entry, "height", 0),
            )
            heal.note_detection(
                "bad_proof", getattr(entry, "height", None), entry=entry
            )
            raise BadProofDetected(
                "assembled proof does not verify against the committed "
                f"data root at height {getattr(entry, 'height', 0)} "
                "(malformed square or wrong root)"
            )
        return proofs

    def _batched(self, entry, coords, axis: str = "row") -> list[ShareProof]:
        from celestia_app_tpu.trace.context import trace_span

        n = 2 * entry.k
        tier = "device" if getattr(entry, "device_resident", True) else "host"
        with trace_span("proof_gather", root=False, layer="serve",
                        batch=len(coords), tier=tier,
                        programs=entry.gather_programs):
            # Row sampling proves leaf `col` of tree `row`; column
            # sampling the transpose — leaf `row` of column tree `col`,
            # whose root is data-root leaf 2k + col.
            if axis == "col":
                plans = [_sample_coords(n, row) for row, _ in coords]
                trees = [col for _, col in coords]
            else:
                plans = [_sample_coords(n, col) for _, col in coords]
                trees = [row for row, _ in coords]
            node_idx: list[int] = []
            for tree, plan in zip(trees, plans):
                node_idx.extend(
                    entry.flat_index(tree, lvl, i) for lvl, i in plan
                )
            nodes, shares = entry.gather_proof(axis, node_idx, coords)

        from celestia_app_tpu import merkle

        with trace_span("proof_assemble", root=False, layer="serve",
                        batch=len(coords)):
            all_roots = entry.row_roots + entry.col_roots
            out: list[ShareProof] = []
            pos = 0
            for (row, col), plan, share_row in zip(coords, plans, shares):
                share = bytes(share_row.tobytes())
                nmt_nodes = tuple(
                    bytes(nodes[pos + i].tobytes()) for i in range(len(plan))
                )
                pos += len(plan)
                ns = (
                    share[:NAMESPACE_SIZE]
                    if row < entry.k and col < entry.k
                    else PARITY_NAMESPACE_BYTES
                )
                if axis == "col":
                    leaf, root_index = row, n + col
                else:
                    leaf, root_index = col, row
                out.append(ShareProof(
                    data=(share,),
                    share_proofs=(NmtRangeProof(leaf, leaf + 1, nmt_nodes, n),),
                    namespace=ns,
                    row_proof=RowProof(
                        row_roots=(all_roots[root_index],),
                        proofs=(tuple(
                            merkle.path_from_levels(
                                entry.root_levels, root_index
                            )
                        ),),
                        start_row=root_index,
                        end_row=root_index + 1,
                        total=2 * n,
                    ),
                ))
        return out

    def _host_batch(self, entry, coords, axis: str = "row") -> list[ShareProof]:
        return [self.host_proof(entry, row, col, axis) for row, col in coords]

    @staticmethod
    def host_proof(entry, row: int, col: int, axis: str = "row") -> ShareProof:
        """The pure-host lowering: rebuild the row tree from the shares,
        re-derive the data-root audit path recursively.  MUST stay
        byte-identical to _batched (the serve plane's exactness seam,
        pinned by tests/test_das_proofs.py and the chaos soak's sampling
        drill)."""
        import numpy as np

        from celestia_app_tpu import merkle

        eds = entry.eds
        n = 2 * entry.k
        share = bytes(np.asarray(eds._eds[row, col]).tobytes())
        if axis == "col":
            tree = eds.col_tree(col, host=True)
            proof = prove_range_from_levels(tree.levels(), row, row + 1)
            root_index = n + col
        else:
            tree = eds.row_tree(row, host=True)
            proof = prove_range_from_levels(tree.levels(), col, col + 1)
            root_index = row
        all_roots = entry.row_roots + entry.col_roots
        ns = (
            share[:NAMESPACE_SIZE]
            if row < entry.k and col < entry.k
            else PARITY_NAMESPACE_BYTES
        )
        return ShareProof(
            data=(share,),
            share_proofs=(proof,),
            namespace=ns,
            row_proof=RowProof(
                row_roots=(all_roots[root_index],),
                proofs=(tuple(merkle.proof(all_roots, root_index)),),
                start_row=root_index,
                end_row=root_index + 1,
                total=len(all_roots),
            ),
        )
