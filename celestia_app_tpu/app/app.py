"""The ABCI application: proposal construction, validation, and execution.

Behavioral parity with the reference app package:

  PrepareProposal  app/prepare_proposal.go:22-91   filter -> build square ->
                                                   RS-extend -> DAH -> root
  ProcessProposal  app/process_proposal.go:24-158  decode/validate every tx,
                                                   reconstruct, compare root
  CheckTx          app/check_tx.go:16-54           BlobTx unwrap + ante
  Finalize/Commit  app/app.go:446-480              mint BeginBlock, tx
                                                   execution, signal-driven
                                                   upgrades, state commit

The square pipeline below FilterTxs runs on the TPU via the fused
extend+NMT+DAH program (da/eds.py) — the offload target of SURVEY §3.2.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from celestia_app_tpu.constants import (
    DEFAULT_GOV_MAX_SQUARE_SIZE,
    LATEST_VERSION,
    MAX_CODEC_SQUARE_SIZE,
    SQUARE_SIZE_UPPER_BOUND,
)
from celestia_app_tpu.app.ante import AnteError, run_ante
from celestia_app_tpu.app.gas import OutOfGas
from celestia_app_tpu.da import DataAvailabilityHeader, extend_shares, min_data_availability_header
from celestia_app_tpu.modules.blob.types import (
    BlobTxError,
    gas_to_consume,
    validate_blob_tx,
    validate_blob_txs_batched,
)
from celestia_app_tpu.modules.minfee import MinFeeKeeper
from celestia_app_tpu.modules.mint.minter import Minter
from celestia_app_tpu.modules.signal.keeper import SignalError, SignalKeeper
from celestia_app_tpu.square import SquareOverflow
from celestia_app_tpu.square import builder as square
from celestia_app_tpu.state.accounts import AuthKeeper, BankKeeper, FEE_COLLECTOR
from celestia_app_tpu.state.dec import Dec
from celestia_app_tpu.state.staking import StakingKeeper, Validator
from celestia_app_tpu.state.store import CommitStore, KVStore
from celestia_app_tpu.tx.envelopes import unmarshal_blob_tx
from celestia_app_tpu.tx.messages import (
    MsgAcknowledgement,
    MsgAuthzExec,
    MsgAuthzGrant,
    MsgAuthzRevoke,
    MsgBeginRedelegate,
    MsgCancelUnbondingDelegation,
    MsgCreatePeriodicVestingAccount,
    MsgCreatePermanentLockedAccount,
    MsgCreateVestingAccount,
    MsgDepositV1,
    MsgMultiSend,
    MsgSubmitEvidence,
    MsgSubmitProposalV1,
    MsgVerifyInvariant,
    MsgVoteV1,
    MsgVoteWeightedV1,
    MsgCreateValidator,
    MsgDelegate,
    MsgDeposit,
    MsgEditValidator,
    MsgGrantAllowance,
    MsgPayForBlobs,
    MsgRecvPacket,
    MsgRevokeAllowance,
    MsgFundCommunityPool,
    MsgSend,
    MsgSetWithdrawAddress,
    MsgSignalVersion,
    MsgSubmitProposal,
    MsgTimeout,
    MsgTransfer,
    MsgTryUpgrade,
    MsgUndelegate,
    MsgUnjail,
    MsgVote,
    MsgVoteWeighted,
    MsgWithdrawDelegatorReward,
    MsgWithdrawValidatorCommission,
)
from celestia_app_tpu.trace import trace_span, traced
from celestia_app_tpu.tx.sign import Tx


@dataclass(frozen=True)
class GenesisAccount:
    address: str
    balance: int  # utia
    pubkey: bytes = b""
    # Optional vesting schedule (x/auth/vesting; celestia mainnet genesis
    # carries vesting accounts): type 1 = continuous, 2 = delayed.
    vesting_type: int = 0
    original_vesting: int = 0
    vesting_start_ns: int = 0
    vesting_end_ns: int = 0


@dataclass(frozen=True)
class Genesis:
    chain_id: str
    genesis_time_ns: int
    accounts: tuple[GenesisAccount, ...] = ()
    validators: tuple[Validator, ...] = ()
    app_version: int = LATEST_VERSION
    gov_max_square_size: int = DEFAULT_GOV_MAX_SQUARE_SIZE
    # x/blobstream DataCommitmentWindow (types/genesis.go:29); 0 = default 400.
    data_commitment_window: int = 0
    # Consensus Block.MaxBytes; 0 derives gov_max_square_size^2 x 478 (the
    # reference's DefaultMaxBytes formula, initial_consts.go:10-14 — its
    # big-block e2e manifests raise this alongside the square cap).
    block_max_bytes: int = 0


@dataclass(frozen=True)
class BlockData:
    """PrepareProposal response payload (celestia-core BlockData fork fields,
    app/prepare_proposal.go:84-90)."""

    txs: tuple[bytes, ...]
    square_size: int
    hash: bytes  # the DAH data root


@dataclass
class TxResult:
    code: int  # 0 = ok
    log: str = ""
    gas_wanted: int = 0
    gas_used: int = 0
    events: list = field(default_factory=list)


class Ctx:
    """A branched state view for one proposal / tx / block."""

    def __init__(self, store: KVStore, height: int, time_ns: int, app_version: int):
        self.store = store
        self.height = height
        self.time_ns = time_ns
        self.app_version = app_version
        self.auth = AuthKeeper(store)
        self.bank = BankKeeper(store)
        self.staking = StakingKeeper(store)

    def branch(self) -> "Ctx":
        return Ctx(self.store.branch(), self.height, self.time_ns, self.app_version)

    def with_store(self, store) -> "Ctx":
        """Same coordinates over a different store view (e.g. gas-metered)."""
        return Ctx(store, self.height, self.time_ns, self.app_version)

    def send_spendable(self, sender: str, recipient: str, amount: int) -> None:
        """Transfer that cannot dip into still-vesting tokens."""
        from celestia_app_tpu.state.accounts import send_spendable

        send_spendable(self.auth, self.bank, sender, recipient, amount, self.time_ns)

    def assert_spendable(self, sender: str, amount: int) -> None:
        from celestia_app_tpu.state.accounts import assert_spendable

        assert_spendable(self.auth, self.bank, sender, amount, self.time_ns)


class App:
    """The celestia state machine with a TPU square pipeline."""

    def __init__(
        self,
        node_min_gas_price: Dec | None = None,
        v2_upgrade_height: int | None = None,
        ibc_token_filter: bool = True,
        square_size_upper_bound: int | None = None,
    ):
        from celestia_app_tpu.compile_cache import enable_compile_cache

        enable_compile_cache()  # before this node's first compile
        self.cms = CommitStore()
        self.chain_id = ""
        self.app_version = LATEST_VERSION
        # Height-based v1->v2 upgrade (reference --v2-upgrade-height,
        # cmd/celestia-appd/cmd/root.go:40,142 consumed at app/app.go:458-470).
        self.v2_upgrade_height = v2_upgrade_height
        self.height = 0
        self.genesis_time_ns = 0
        self.last_block_time_ns = 0
        self.node_min_gas_price = node_min_gas_price or Dec.from_str("0.002")
        self.minter = Minter.default()
        # False models a non-celestia counterparty chain (the reference's
        # test/pfm/simapp.go) in IBC tests; celestia itself always filters.
        self.ibc_token_filter = ibc_token_filter
        # The versioned protocol hard cap (128 for v1/v2).  The reference's
        # big-block benchmark manifests override MaxSquareSize up to 512
        # (test/e2e/benchmark/throughput.go:15-54); this knob is that
        # override, clamped to what the DA kernels support.
        self.square_size_upper_bound = min(
            square_size_upper_bound or SQUARE_SIZE_UPPER_BOUND,
            MAX_CODEC_SQUARE_SIZE,
        )
        self._check_state: KVStore | None = None
        # Own-root memo: (square_size, sha256(square bytes)) -> DAH hash.
        # A data root is a pure function of the square bytes, and this
        # node recomputes the SAME square's root up to twice per block
        # (PrepareProposal, then ProcessProposal rebuilding the square
        # from the txs itself). Only self-computed results enter the memo
        # and Process still rebuilds the square from the raw txs, so the
        # proposer's claims are never trusted — identical bytes simply
        # skip the identical device pipeline. Bounded FIFO.
        self._own_roots: dict[tuple[int, bytes], bytes] = {}

    # --- keeper views over committed state ---------------------------------
    @property
    def minfee(self) -> MinFeeKeeper:
        return MinFeeKeeper(self.cms.working)

    @property
    def gov_max_square_size(self) -> int:
        """On-chain x/blob param (read at square_size.go:20-22)."""
        from celestia_app_tpu.modules.blob.params import BlobParamsKeeper

        return BlobParamsKeeper(self.cms.working).gov_max_square_size()

    @property
    def gas_per_blob_byte(self) -> int:
        from celestia_app_tpu.modules.blob.params import BlobParamsKeeper

        return BlobParamsKeeper(self.cms.working).gas_per_blob_byte()

    @property
    def signal(self) -> SignalKeeper:
        return SignalKeeper(self.cms.working, StakingKeeper(self.cms.working))

    def max_effective_square_size(self) -> int:
        """min(gov, hard cap) — reference app/square_size.go:9-23."""
        return min(self.gov_max_square_size, self.square_size_upper_bound)

    # --- genesis ------------------------------------------------------------
    def init_chain(self, genesis: Genesis) -> None:
        if self.height != 0:
            raise RuntimeError("chain already initialized")
        self.chain_id = genesis.chain_id
        self.app_version = genesis.app_version
        self.genesis_time_ns = genesis.genesis_time_ns
        self.last_block_time_ns = genesis.genesis_time_ns
        from celestia_app_tpu.modules.blob.params import BlobParamsKeeper

        BlobParamsKeeper(self.cms.working).set_gov_max_square_size(
            genesis.gov_max_square_size
        )
        if genesis.data_commitment_window:
            from celestia_app_tpu.modules.blobstream.keeper import (
                set_data_commitment_window,
            )

            set_data_commitment_window(
                self.cms.working, genesis.data_commitment_window
            )
        from celestia_app_tpu.constants import CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
        from celestia_app_tpu.modules.consensus_params import ConsensusParamsKeeper

        ConsensusParamsKeeper(self.cms.working).set_block_max_bytes(
            genesis.block_max_bytes
            or genesis.gov_max_square_size**2 * CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
        )
        ctx = Ctx(self.cms.working, 0, genesis.genesis_time_ns, self.app_version)
        for acc in genesis.accounts:
            a = ctx.auth.create_account(acc.address, acc.pubkey)
            if acc.vesting_type:
                a.vesting_type = acc.vesting_type
                a.original_vesting = acc.original_vesting
                a.vesting_start_ns = acc.vesting_start_ns or genesis.genesis_time_ns
                a.vesting_end_ns = acc.vesting_end_ns
            ctx.auth.set_account(a)
            if acc.balance:
                ctx.bank.mint(acc.address, acc.balance)
        from celestia_app_tpu.modules.distribution import DistributionKeeper
        from celestia_app_tpu.state.staking import POWER_REDUCTION

        dist = DistributionKeeper(ctx.store)
        for v in genesis.validators:
            ctx.staking.set_validator(v)
            # A genesis validator's declared power is a notional self-bond
            # (no escrowed delegation backs it); register it with
            # distribution so its reward share accrues to the operator.
            dist.set_notional(v.address, v.power * POWER_REDUCTION)
        # x/crisis: genesis invariant assertion (the reference runs module
        # invariants at genesis unless skipGenesisInvariants).
        from celestia_app_tpu.modules.crisis import assert_invariants

        assert_invariants(self.cms.working)
        self.cms.commit(0)
        self._check_state = None

    # --- CheckTx (mempool admission, app/check_tx.go:16-54) ----------------
    def check_tx(self, raw: bytes) -> TxResult:
        if self._check_state is None:
            self._check_state = self.cms.working.branch()
        ctx = Ctx(
            self._check_state, self.height + 1, self.last_block_time_ns, self.app_version
        )
        from celestia_app_tpu.trace.metrics import registry

        checked = registry().counter(
            "celestia_checktx_total", "CheckTx admissions by result"
        )
        btx = unmarshal_blob_tx(raw)
        inner = raw
        if btx is not None:
            try:
                validate_blob_tx(btx)
            except BlobTxError as e:
                checked.inc(result="rejected")
                return TxResult(code=11, log=str(e))
            inner = btx.tx
        try:
            tx = Tx.unmarshal(inner)
            res = run_ante(self, ctx, tx, is_check_tx=True, tx_bytes=inner)
        except OutOfGas as e:
            checked.inc(result="rejected")
            return TxResult(code=11, log=str(e))  # sdk ErrOutOfGas
        except (AnteError, ValueError) as e:
            checked.inc(result="rejected")
            return TxResult(code=1, log=str(e))
        checked.inc(result="accepted")
        return TxResult(code=0, gas_wanted=res.gas_wanted, events=[("priority", res.priority)])

    # --- PrepareProposal (app/prepare_proposal.go:22-91) --------------------
    def prepare_proposal(self, raw_txs: list[bytes]) -> BlockData:
        # telemetry.MeasureSince parity (prepare_proposal.go:23); joins
        # the block's trace when the caller set one (trace/context.py).
        # The height and side ride the baggage: every span below (ante,
        # square build, the pipeline's children) carries them on its row.
        height = self.height + 1
        with trace_span("prepare_proposal", layer="app",
                        height=height, n_txs=len(raw_txs),
                        baggage={"height": height, "phase": "prepare"}):
            raw_txs = self._cap_block_bytes(raw_txs)
            filtered = self._filter_txs(raw_txs)
            sq, kept = square.build(filtered, self.max_effective_square_size())
            if sq.is_empty():
                dah = min_data_availability_header()
                return BlockData(tuple(kept), 1, dah.hash())
            root = self._pipeline_root(sq, "prepare")
            return BlockData(tuple(kept), sq.size, root)

    def _cap_block_bytes(self, raw_txs: list[bytes]) -> list[bytes]:
        """Keep the prefix of candidate txs fitting the on-chain
        Block.MaxBytes consensus param (the reference's celestia-core reaps
        the mempool under this cap before PrepareProposal sees it)."""
        from celestia_app_tpu.modules.consensus_params import ConsensusParamsKeeper

        max_bytes = ConsensusParamsKeeper(self.cms.working).block_max_bytes()
        kept, total = [], 0
        for raw in raw_txs:
            if total + len(raw) > max_bytes:
                break  # prefix semantics: a later small tx must not jump
                # an earlier large one (sequence gaps would drop it anyway)
            total += len(raw)
            kept.append(raw)
        return kept

    def _filter_txs(self, raw_txs: list[bytes]) -> list[bytes]:
        """FilterTxs (app/validate_txs.go:32): separate tx classes, then
        ante-validate in BLOCK order (normal txs before blob txs,
        validate_txs.go:14,31-36) on one branched state, dropping failures.
        Validating in block order matters: a signer's sequence must advance
        in the order txs execute, not the order they arrived."""
        ctx = Ctx(
            self.cms.working.branch(),
            self.height + 1,
            self.last_block_time_ns,
            self.app_version,
        )
        classified = [(raw, unmarshal_blob_tx(raw)) for raw in raw_txs]
        normal: list[bytes] = []
        blob: list[bytes] = []
        with trace_span("ante", layer="app"):
            for raw, btx in classified:
                if btx is not None:
                    continue
                try:
                    tx = Tx.unmarshal(raw)
                    if any(isinstance(m, MsgPayForBlobs) for m in tx.msgs()):
                        continue  # PFB outside a BlobTx is invalid
                    run_ante(self, ctx, tx, is_check_tx=False, tx_bytes=raw)
                    normal.append(raw)
                except (AnteError, ValueError, OutOfGas):
                    continue
        blob_entries = [(raw, btx) for raw, btx in classified if btx is not None]
        validated = validate_blob_txs_batched([b for _, b in blob_entries])
        with trace_span("ante", layer="app"):
            for (raw, btx), v in zip(blob_entries, validated):
                if isinstance(v, BlobTxError):
                    continue
                try:
                    run_ante(
                        self, ctx, Tx.unmarshal(btx.tx), is_check_tx=False,
                        tx_bytes=btx.tx,
                    )
                    blob.append(raw)
                except (AnteError, ValueError, OutOfGas):
                    continue
        return normal + blob

    # --- ProcessProposal (app/process_proposal.go:24-158) -------------------
    def process_proposal(self, data: BlockData) -> bool:
        from celestia_app_tpu.trace.metrics import registry

        outcomes = registry().counter(
            "celestia_process_proposal_total", "ProcessProposal verdicts"
        )
        height = self.height + 1
        with trace_span("process_proposal", layer="app",
                        height=height, n_txs=len(data.txs),
                        baggage={"height": height, "phase": "process"}):
            try:
                ok = self._process_proposal(data)
            except Exception:
                # recover() -> reject (process_proposal.go:29-35); counted like
                # the reference's rejection telemetry (process_proposal.go:32).
                traced().write("process_proposal_rejections", height=self.height + 1)
                outcomes.inc(result="panic_reject")
                return False
            outcomes.inc(result="accepted" if ok else "rejected")
            return ok

    def _process_proposal(self, data: BlockData) -> bool:
        # Block.MaxBytes is consensus law, not proposer advice: an oversize
        # block is rejected validator-side (celestia-core enforces this
        # around the reference app; here the app is the enforcement point).
        from celestia_app_tpu.modules.consensus_params import ConsensusParamsKeeper

        if sum(len(t) for t in data.txs) > ConsensusParamsKeeper(
            self.cms.working
        ).block_max_bytes():
            return False
        ctx = Ctx(
            self.cms.working.branch(),
            self.height + 1,
            self.last_block_time_ns,
            self.app_version,
        )
        classified = [(raw, unmarshal_blob_tx(raw)) for raw in data.txs]
        # Hot loop (3): every blob's commitment recomputed, batched on device.
        validated = iter(
            validate_blob_txs_batched([b for _, b in classified if b is not None])
        )
        with trace_span("ante", layer="app"):
            for raw, btx in classified:
                if btx is None:
                    tx = Tx.unmarshal(raw)
                    if any(isinstance(m, MsgPayForBlobs) for m in tx.msgs()):
                        return False  # PFB must ride in a BlobTx (:77-88)
                    run_ante(self, ctx, tx, is_check_tx=False, tx_bytes=raw)
                else:
                    v = next(validated)
                    if isinstance(v, BlobTxError):
                        raise v
                    run_ante(
                        self, ctx, Tx.unmarshal(btx.tx), is_check_tx=False,
                        tx_bytes=btx.tx,
                    )

        sq = square.construct(list(data.txs), self.max_effective_square_size())
        if sq.size != data.square_size:
            return False  # square-size equality (:133)
        if sq.is_empty():
            return min_data_availability_header().hash() == data.hash
        # Root equality (:152) over the square REBUILT from the raw txs
        # above — the own-root memo only skips re-running the pipeline on
        # bytes this node already extended (its own Prepare, usually).
        return self._pipeline_root(sq, "process") == data.hash

    @staticmethod
    def _square_key(size: int, share_bytes: list[bytes]) -> tuple:
        import hashlib

        digest = hashlib.sha256()
        for s in share_bytes:
            digest.update(s)
        return (size, digest.digest())

    def square_eds(self, size: int, share_bytes: list[bytes]):
        """The extended square for a built square's shares — the serve
        plane's rebuild source (rpc/server._rebuild_eds): when the
        content matches the square this app just extended, the SAME
        device-resident handle comes back with zero extra extensions;
        otherwise (a cache-miss rebuild of an old height) it extends
        fresh.  Deliberately NOT a `_last_eds` writer: that slot belongs
        to the consensus path (_square_root), and a concurrent read-side
        rebuild overwriting it would displace the just-extended square
        right before the commit hook retains it.  The rebuild's caller
        admits the result to the forest cache, so repeats are already
        covered there."""
        key = self._square_key(size, share_bytes)
        last = getattr(self, "_last_eds", None)
        if last is not None and last[0] == key:
            return last[1]
        return extend_shares(share_bytes)

    def last_eds_for_root(self, data_root: bytes):
        """The freshest extended square IF its DAH hash is `data_root` —
        how the serving plane's commit hook retains the just-committed
        height without reconstructing the square (no second layout
        solve, no duplicate square-journal row, no device work)."""
        last = getattr(self, "_last_eds", None)
        if last is not None and last[2] == data_root:
            return last[1]
        return None

    def _pipeline_root(self, sq, phase: str) -> bytes:
        """The built square's DAH hash under the `square_pipeline` span
        (phase=prepare on the proposer, process on a validator), each of
        its host steps a child span: `share_pack` here and in
        extend_shares, `square_digest`, `ods_upload`, `extend_dispatch`,
        `roots_wait`."""
        with trace_span("square_pipeline", layer="device",
                        e2e="dispatch" if phase == "prepare" else None,
                        k=sq.size, phase=phase) as span:
            with trace_span("share_pack", layer="host"):
                shares = sq.share_bytes()
            return self._square_root(sq.size, shares, span)

    def _square_root(self, size: int, share_bytes: list[bytes],
                     span: dict | None = None) -> bytes:
        """DAH hash of a built square, memoized on the square's content;
        `span` (the open square_pipeline span) learns memo=hit|miss."""
        with trace_span("square_digest", layer="host"):
            key = self._square_key(size, share_bytes)
        cached = self._own_roots.get(key)
        if span is not None:
            span["memo"] = "miss" if cached is None else "hit"
        if cached is not None:
            return cached
        eds = extend_shares(share_bytes)
        # The first host read of the roots: waits for the device program.
        with trace_span("roots_wait", layer="device"):
            root = DataAvailabilityHeader.from_eds(eds).hash()
        from celestia_app_tpu.serve import serve_heights

        if serve_heights() > 0:
            # Keep the freshest EDS handle alive for the serve plane's
            # commit-time retention (ONE handle; the forest cache owns
            # longer-term residency).  Gated so a node with serving
            # disabled holds no extra device memory.
            self._last_eds = (key, eds, root)
        while len(self._own_roots) >= 4:
            self._own_roots.pop(next(iter(self._own_roots)))
        self._own_roots[key] = root
        return root

    # --- block execution ----------------------------------------------------
    def finalize_block(
        self,
        time_ns: int,
        txs: list[bytes],
        last_commit_signers: set[str] | None = None,
        evidence: tuple = (),
    ) -> list[TxResult]:
        """Execute one block.  `last_commit_signers` is the set of operator
        addresses whose precommits made the previous block's commit (ABCI
        RequestBeginBlock.LastCommitInfo) — None skips liveness tracking
        (harnesses without a consensus plane).  `evidence` carries
        consensus.votes.Equivocation records (ByzantineValidators)."""
        height = self.height + 1
        with trace_span("finalize_block", layer="app", height=height,
                        n_txs=len(txs), baggage={"height": height}):
            block_store = self.cms.working.branch()
            ctx = Ctx(block_store, height, time_ns, self.app_version)

            self._begin_block(ctx, time_ns, last_commit_signers, evidence)
            results = [self._deliver_tx(ctx, raw) for raw in txs]
            self._end_block(ctx, height)
            from celestia_app_tpu.trace.metrics import registry

            delivered = registry().counter(
                "celestia_txs_delivered_total", "delivered txs by result code"
            )
            for r in results:
                delivered.inc(code=str(r.code))

            self.cms.working.write_back(block_store)
            self.height = height
            self.last_block_time_ns = time_ns
            return results

    def commit(self) -> bytes:
        with trace_span("commit", layer="app", height=self.height,
                        baggage={"height": self.height}):
            app_hash = self.cms.commit(self.height)
            self._check_state = None  # reset mempool check state each block
            from celestia_app_tpu.trace.metrics import registry

            registry().gauge(
                "celestia_block_height", "last committed height"
            ).set(self.height)
            return app_hash

    def _begin_block(
        self,
        ctx: Ctx,
        time_ns: int,
        last_commit_signers: set[str] | None = None,
        evidence: tuple = (),
    ) -> None:
        """x/mint BeginBlocker (x/mint/abci.go:14-20), then x/distribution's
        (sdk begin-block order: mint before distribution, so this block's
        provision and the previous block's tx fees sweep together), then
        x/evidence + x/slashing liveness."""
        supply = ctx.bank.supply()
        self.minter.update(self.genesis_time_ns, time_ns, supply)
        prev = (
            self.minter.previous_block_time_ns
            if self.minter.previous_block_time_ns is not None
            else self.last_block_time_ns
        )
        provision = self.minter.calculate_block_provision(time_ns, prev)
        if provision > 0:
            ctx.bank.mint(FEE_COLLECTOR, provision)
        self.minter.previous_block_time_ns = time_ns
        from celestia_app_tpu.modules.distribution import DistributionKeeper

        dist = DistributionKeeper(ctx.store)
        dist.allocate(ctx.bank, ctx.staking)

        if evidence or last_commit_signers is not None:
            from celestia_app_tpu.modules.slashing import SlashingKeeper

            slashing = SlashingKeeper(ctx.store)
            # x/evidence BeginBlocker: punish equivocations first (sdk
            # begin-block order: evidence before slashing liveness).
            for ev in evidence:
                try:
                    slashing.handle_equivocation(
                        ctx.staking, ctx.bank, dist,
                        self.chain_id, ev.vote_a, ev.vote_b,
                        current_height=ctx.height,
                    )
                except ValueError:
                    continue  # invalid evidence is dropped, not fatal
            if last_commit_signers is not None:
                for v in ctx.staking.bonded_validators():
                    slashing.handle_validator_signature(
                        ctx.staking, ctx.bank, dist,
                        v.address, v.address in last_commit_signers, time_ns,
                    )

    def simulate_tx(self, raw: bytes) -> TxResult:
        """cosmos.tx.v1beta1.Service/Simulate: run the tx (ante + msgs)
        against a throwaway branch of committed state at the next height
        — signature verification and the gas limit are waived (sdk
        Simulate), gas_used is the real metered consumption, and no state
        survives."""
        ctx = Ctx(
            self.cms.working.branch(), self.height + 1,
            self.last_block_time_ns, self.app_version,
        )
        return self._deliver_tx(ctx, raw, simulate=True)

    def _deliver_tx(
        self, block_ctx: Ctx, raw: bytes, simulate: bool = False
    ) -> TxResult:
        # Imported BEFORE the first try: a function-level import makes the
        # name local for the WHOLE function, so the first `except OutOfGas`
        # would otherwise raise UnboundLocalError whenever the ante phase
        # fails (latent until Simulate started feeding garbage txs here).
        from celestia_app_tpu.app.gas import GasKVStore, OutOfGas

        btx = unmarshal_blob_tx(raw)
        inner = btx.tx if btx is not None else raw
        tx_ctx = block_ctx.branch()
        try:
            tx = Tx.unmarshal(inner)
            ante_res = run_ante(
                self, tx_ctx, tx, is_check_tx=False, tx_bytes=inner,
                simulate=simulate,
            )
        except OutOfGas as e:
            return TxResult(code=11, log=str(e))  # sdk ErrOutOfGas, either phase
        except (AnteError, ValueError) as e:
            return TxResult(code=1, log=str(e))

        # The tx's SINGLE gas meter (sdk runTx) carries from the ante chain
        # into execution: store access during message handling is charged
        # the KVStore schedule, and blob gas consumes against the same
        # limit (closes the round-2 store-gas PARITY deviation).
        meter = ante_res.meter
        events: list = []
        # Messages run on their own branch (baseapp runMsgs' cache): a failed
        # execution rolls back msg effects ONLY — the ante effects (fee
        # deduction, sequence bump) stay committed, so a failed tx still pays
        # its fee and cannot be replayed (msCache.Write() precedes runMsgs).
        msg_ctx = tx_ctx.branch()
        exec_ctx = msg_ctx.with_store(GasKVStore(msg_ctx.store, meter))
        try:
            for msg in tx.msgs():
                # Simulate runs on an infinite meter, so consumption can
                # legitimately exceed the fee's nominal gas_wanted — the
                # remaining-gas argument must not go negative there.
                remaining = (
                    (1 << 62) if simulate
                    else ante_res.gas_wanted - meter.consumed
                )
                used, evts = self._handle_msg(exec_ctx, msg, remaining)
                if used:
                    meter.consume(used, "execution")
                events.extend(evts)
        except OutOfGas as e:
            block_ctx.store.write_back(tx_ctx.store)  # ante effects persist
            return TxResult(
                code=11,  # sdk ErrOutOfGas
                log=str(e), gas_wanted=ante_res.gas_wanted,
                gas_used=meter.consumed,
            )
        except Exception as e:
            from celestia_app_tpu.modules.crisis import InvariantBroken

            if isinstance(e, InvariantBroken):
                # x/crisis: a broken invariant HALTS the chain (the sdk
                # panics in the crisis msg server) — converting it into a
                # failed tx would let a corrupted state keep committing.
                raise
            block_ctx.store.write_back(tx_ctx.store)  # ante effects persist
            return TxResult(
                code=2, log=str(e), gas_wanted=ante_res.gas_wanted,
                gas_used=meter.consumed,
            )
        tx_ctx.store.write_back(msg_ctx.store)
        block_ctx.store.write_back(tx_ctx.store)
        return TxResult(
            code=0, gas_wanted=ante_res.gas_wanted, gas_used=meter.consumed,
            events=events,
        )

    def _handle_msg(self, ctx: Ctx, msg, gas_remaining: int):
        if isinstance(msg, MsgSend):
            total = sum(c.amount for c in msg.amount if c.denom == "utia")
            ctx.send_spendable(msg.from_address, msg.to_address, total)
            # The sdk bank keeper creates the recipient account on first
            # receive (x/bank SendCoins -> SetAccount): a freshly funded
            # address — a multisig, say — must exist before it can sign.
            ctx.auth.get_or_create(msg.to_address)
            return 0, [("transfer", msg.from_address, msg.to_address, total)]
        if isinstance(msg, MsgSubmitEvidence):
            # Reference behavior: the evidence keeper has NO router
            # (app/app.go:348-353 never calls SetRouter), so tx-submitted
            # evidence never succeeds — equivocation evidence arrives via
            # ABCI ByzantineValidators, not txs.  Error text follows the
            # sdk's registered ErrNoEvidenceHandlerExists ("unregistered
            # handler for evidence type"); the reference's exact
            # nil-router failure shape is unverifiable in-image.
            raise ValueError(
                "unregistered handler for evidence type: "
                f"{msg.evidence.type_url}"
            )
        if isinstance(msg, MsgVerifyInvariant):
            from celestia_app_tpu.modules.crisis import INVARIANTS

            name = f"{msg.invariant_module_name}/{msg.invariant_route}"
            check = next((c for n, c in INVARIANTS if n == name), None)
            if check is None:
                raise ValueError(f"unknown invariant {name}")
            # ConstantFee: 1000utia to the fee collector (reference
            # default_overrides.go:120) — on-chain invariant checks are
            # priced so they cannot be spammed for free.
            ctx.send_spendable(msg.sender, FEE_COLLECTOR, 1000)
            # On an UNMETERED branch: the sdk runs AssertInvariants under
            # an infinite gas meter (a full-state audit must not die on
            # the tx's gas limit), and some checks settle intermediate
            # state that must not leak into consensus state.  A broken
            # invariant raises InvariantBroken, which deliver()
            # deliberately does NOT convert to a tx error — the chain
            # halts (sdk panic).
            store = (
                ctx.store.unwrap() if hasattr(ctx.store, "unwrap") else ctx.store
            )
            check(store.branch())
            return 0, [(
                "cosmos.crisis.v1beta1.EventInvariantChecked", name,
            )]
        if isinstance(msg, (
            MsgCreateVestingAccount,
            MsgCreatePeriodicVestingAccount,
            MsgCreatePermanentLockedAccount,
        )):
            from celestia_app_tpu.state.accounts import (
                VESTING_CONTINUOUS,
                VESTING_DELAYED,
                VESTING_PERIODIC,
                VESTING_PERMANENT,
            )

            if ctx.auth.get_account(msg.to_address) is not None:
                # sdk vesting msg server: the target must be brand new.
                raise ValueError(f"account {msg.to_address} already exists")
            acc = ctx.auth.get_or_create(msg.to_address)
            if isinstance(msg, MsgCreateVestingAccount):
                total = sum(c.amount for c in msg.amount if c.denom == "utia")
                acc.vesting_type = (
                    VESTING_DELAYED if msg.delayed else VESTING_CONTINUOUS
                )
                # Continuous vesting starts at the block time (sdk
                # NewContinuousVestingAccount with ctx.BlockTime); delayed
                # ignores the start.
                acc.vesting_start_ns = ctx.time_ns
                acc.vesting_end_ns = msg.end_time * 10**9
            elif isinstance(msg, MsgCreatePeriodicVestingAccount):
                total = msg.total()
                acc.vesting_type = VESTING_PERIODIC
                # Periodic vesting starts at the MSG's start_time (sdk
                # NewPeriodicVestingAccount takes it verbatim).
                acc.vesting_start_ns = msg.start_time * 10**9
                acc.vesting_periods = tuple(
                    (p.length * 10**9,
                     sum(c.amount for c in p.amount if c.denom == "utia"))
                    for p in msg.vesting_periods
                )
                acc.vesting_end_ns = acc.vesting_start_ns + sum(
                    length for length, _ in acc.vesting_periods
                )
            else:
                total = sum(c.amount for c in msg.amount if c.denom == "utia")
                acc.vesting_type = VESTING_PERMANENT
            acc.original_vesting = total
            ctx.auth.set_account(acc)
            ctx.send_spendable(msg.from_address, msg.to_address, total)
            return 0, [(
                "cosmos.vesting.v1beta1.EventCreateVestingAccount",
                msg.to_address, total, acc.vesting_type,
            )]
        if isinstance(msg, MsgMultiSend):
            # Single input (enforced by ValidateBasic, see tx/messages.py),
            # fanned out to every output; recipients are created on first
            # receive like the MsgSend path.
            src = msg.inputs[0].address
            events = []
            for out in msg.outputs:
                total = sum(c.amount for c in out.coins if c.denom == "utia")
                ctx.send_spendable(src, out.address, total)
                ctx.auth.get_or_create(out.address)
                events.append(("transfer", src, out.address, total))
            return 0, events
        if isinstance(msg, MsgAuthzExec):
            return self._handle_authz_exec(ctx, msg, gas_remaining)
        if isinstance(msg, (MsgAuthzGrant, MsgAuthzRevoke)):
            from celestia_app_tpu.modules.authz import AuthzError, AuthzKeeper, Grant

            authz = AuthzKeeper(ctx.store)
            try:
                if isinstance(msg, MsgAuthzGrant):
                    authz.grant(
                        msg.granter, msg.grantee,
                        Grant(msg.msg_type_url, msg.spend_limit, msg.expiration_ns),
                    )
                    return 0, [("cosmos.authz.v1beta1.EventGrant",
                                msg.granter, msg.grantee, msg.msg_type_url)]
                authz.revoke(msg.granter, msg.grantee, msg.msg_type_url)
                return 0, [("cosmos.authz.v1beta1.EventRevoke",
                            msg.granter, msg.grantee, msg.msg_type_url)]
            except AuthzError as e:
                raise ValueError(str(e)) from e
        if isinstance(msg, (MsgGrantAllowance, MsgRevokeAllowance)):
            from celestia_app_tpu.modules.feegrant import (
                Allowance,
                FeegrantError,
                FeegrantKeeper,
            )

            feegrant = FeegrantKeeper(ctx.store)
            try:
                if isinstance(msg, MsgGrantAllowance):
                    feegrant.grant(
                        msg.granter, msg.grantee,
                        Allowance(
                            spend_limit=msg.spend_limit,
                            expiration_ns=msg.expiration_ns,
                            allowed_msgs=msg.allowed_msgs,
                        ),
                    )
                    return 0, [("cosmos.feegrant.v1beta1.EventSetFeeGrant",
                                msg.granter, msg.grantee)]
                feegrant.revoke(msg.granter, msg.grantee)
                return 0, [("cosmos.feegrant.v1beta1.EventRevokeFeeGrant",
                            msg.granter, msg.grantee)]
            except FeegrantError as e:
                raise ValueError(str(e)) from e
        if isinstance(msg, MsgPayForBlobs):
            # keeper.PayForBlobs (x/blob/keeper/keeper.go:43-57): consume
            # shares x 512 x gasPerBlobByte, emit the event.
            gas = gas_to_consume(msg.blob_sizes, self.gas_per_blob_byte)
            if gas > gas_remaining:
                raise ValueError(
                    f"out of gas: blob gas {gas} > remaining {gas_remaining}"
                )
            return gas, [("celestia.blob.v1.EventPayForBlobs", msg.signer, msg.blob_sizes)]
        if isinstance(msg, MsgSignalVersion):
            keeper = SignalKeeper(ctx.store, ctx.staking)
            keeper.signal_version(msg.validator_address, msg.version, self.app_version)
            return 0, []
        if isinstance(msg, MsgTryUpgrade):
            keeper = SignalKeeper(ctx.store, ctx.staking)
            keeper.try_upgrade(ctx.height, self.app_version)
            return 0, []
        if isinstance(msg, (MsgTransfer, MsgRecvPacket, MsgAcknowledgement, MsgTimeout)):
            return self._handle_ibc_msg(ctx, msg)
        if isinstance(msg, (MsgCreateValidator, MsgEditValidator)):
            from celestia_app_tpu.modules.distribution import (
                DistributionError,
                DistributionKeeper,
            )
            from celestia_app_tpu.state.dec import Dec as _Dec
            from celestia_app_tpu.state.staking import StakingError

            dist = DistributionKeeper(ctx.store)
            try:
                if isinstance(msg, MsgCreateValidator):
                    self._track_vesting_delegation(
                        ctx, msg.delegator_address, msg.value.amount
                    )
                    ctx.staking.create_validator(
                        ctx.bank, dist, msg.validator_address, msg.pubkey,
                        msg.delegator_address, msg.value.amount,
                        _Dec.from_str(msg.commission_rate or "0").raw,
                        msg.min_self_delegation,
                    )
                    # The bounds the operator declared bind every later edit.
                    dist.set_commission_bounds(
                        msg.validator_address,
                        _Dec.from_str(msg.commission_max_rate or "1"),
                        _Dec.from_str(msg.commission_max_change_rate or "1"),
                    )
                    return 0, [("cosmos.staking.v1beta1.EventCreateValidator",
                                msg.validator_address, msg.value.amount)]
                if not ctx.staking.has_validator(msg.validator_address):
                    raise ValueError(f"no validator {msg.validator_address}")
                if msg.commission_rate:
                    dist.change_commission_rate(
                        msg.validator_address, _Dec.from_str(msg.commission_rate)
                    )
                return 0, [("cosmos.staking.v1beta1.EventEditValidator",
                            msg.validator_address)]
            except (StakingError, DistributionError) as e:
                raise ValueError(str(e)) from e
        if isinstance(msg, (MsgDelegate, MsgUndelegate, MsgBeginRedelegate)):
            if msg.amount.denom != "utia":  # x/staking ErrBadDenom
                raise ValueError(
                    f"invalid bond denom {msg.amount.denom!r}, expected utia"
                )
            amount = msg.amount.amount
            # Settle pending rewards before the stake changes (the sdk's
            # BeforeDelegationSharesModified hook; x/distribution hooks.go).
            from celestia_app_tpu.modules.distribution import DistributionKeeper

            dist = DistributionKeeper(ctx.store)
            dist.settle(ctx.staking, msg.delegator_address, msg.validator_address)
            if isinstance(msg, MsgBeginRedelegate):
                dist.settle(
                    ctx.staking, msg.delegator_address, msg.validator_dst_address
                )
            if isinstance(msg, MsgDelegate):
                self._track_vesting_delegation(ctx, msg.delegator_address, amount)
                ctx.staking.delegate(
                    ctx.bank, msg.delegator_address, msg.validator_address, amount
                )
                return 0, [("cosmos.staking.v1beta1.EventDelegate",
                            msg.validator_address, amount)]
            if isinstance(msg, MsgUndelegate):
                # No vesting bookkeeping here: the tokens return at
                # unbonding COMPLETION (end blocker), and that's when the
                # lock re-encumbers them (sdk TrackUndelegation runs at
                # CompleteUnbonding) — untracking now would freeze the
                # account's liquid funds for the whole unbonding window.
                completion = ctx.staking.undelegate(
                    ctx.bank, msg.delegator_address, msg.validator_address,
                    amount, ctx.time_ns, height=ctx.height,
                )
                # An operator undelegating below its declared
                # min_self_delegation is jailed (sdk Undelegate's
                # jailValidator path): no skin in the game, no vote.
                min_self = ctx.staking.min_self_delegation(msg.validator_address)
                if (
                    msg.delegator_address == msg.validator_address
                    and min_self
                    and ctx.staking.delegation(
                        msg.delegator_address, msg.validator_address
                    ) < min_self
                    and not ctx.staking.is_jailed(msg.validator_address)
                ):
                    ctx.staking.jail(msg.validator_address)
                return 0, [("cosmos.staking.v1beta1.EventUnbond",
                            msg.validator_address, amount, completion)]
            ctx.staking.begin_redelegate(
                msg.delegator_address, msg.validator_address,
                msg.validator_dst_address, amount,
            )
            # Same skin-in-the-game rule as the undelegate path: an operator
            # redelegating its self-bond below min_self_delegation is jailed
            # (sdk BeginRedelegate jails the source validator too).
            min_self = ctx.staking.min_self_delegation(msg.validator_address)
            if (
                msg.delegator_address == msg.validator_address
                and min_self
                and ctx.staking.delegation(
                    msg.delegator_address, msg.validator_address
                ) < min_self
                and not ctx.staking.is_jailed(msg.validator_address)
            ):
                ctx.staking.jail(msg.validator_address)
            return 0, [("cosmos.staking.v1beta1.EventRedelegate",
                        msg.validator_address, msg.validator_dst_address, amount)]
        if isinstance(msg, MsgCancelUnbondingDelegation):
            from celestia_app_tpu.modules.distribution import DistributionKeeper
            from celestia_app_tpu.state.staking import StakingError

            # Settle pending rewards before shares change (the same
            # BeforeDelegationSharesModified hook the delegate path runs).
            DistributionKeeper(ctx.store).settle(
                ctx.staking, msg.delegator_address, msg.validator_address
            )
            try:
                ctx.staking.cancel_unbonding(
                    ctx.bank, msg.delegator_address, msg.validator_address,
                    msg.amount.amount, msg.creation_height, ctx.time_ns,
                )
            except StakingError as e:
                raise ValueError(str(e)) from e
            return 0, [(
                "cosmos.staking.v1beta1.EventCancelUnbondingDelegation",
                msg.validator_address, msg.amount.amount, msg.creation_height,
            )]
        if isinstance(msg, MsgUnjail):
            from celestia_app_tpu.modules.slashing import (
                SlashingError,
                SlashingKeeper,
            )

            try:
                SlashingKeeper(ctx.store).unjail(
                    ctx.staking, msg.validator_address, ctx.time_ns
                )
            except SlashingError as e:
                raise ValueError(str(e)) from e
            return 0, [("cosmos.slashing.v1beta1.EventUnjail", msg.validator_address)]
        if isinstance(
            msg,
            (
                MsgWithdrawDelegatorReward,
                MsgWithdrawValidatorCommission,
                MsgSetWithdrawAddress,
                MsgFundCommunityPool,
            ),
        ):
            from celestia_app_tpu.modules.distribution import (
                DistributionError,
                DistributionKeeper,
            )

            dist = DistributionKeeper(ctx.store)
            try:
                if isinstance(msg, MsgWithdrawDelegatorReward):
                    paid = dist.withdraw_rewards(
                        ctx.bank, ctx.staking,
                        msg.delegator_address, msg.validator_address,
                    )
                    return 0, [(
                        "cosmos.distribution.v1beta1.EventWithdrawRewards",
                        msg.validator_address, paid,
                    )]
                if isinstance(msg, MsgWithdrawValidatorCommission):
                    paid = dist.withdraw_commission(ctx.bank, msg.validator_address)
                    return 0, [(
                        "cosmos.distribution.v1beta1.EventWithdrawCommission", paid,
                    )]
                if isinstance(msg, MsgSetWithdrawAddress):
                    dist.set_withdraw_address(
                        msg.delegator_address, msg.withdraw_address
                    )
                    return 0, []
                total = sum(c.amount for c in msg.amount if c.denom == "utia")
                ctx.assert_spendable(msg.depositor, total)
                dist.fund_community_pool(ctx.bank, msg.depositor, total)
                return 0, [(
                    "cosmos.distribution.v1beta1.EventFundCommunityPool", total,
                )]
            except DistributionError as e:
                raise ValueError(str(e)) from e
        if isinstance(msg, (
            MsgSubmitProposal, MsgSubmitProposalV1,
            MsgVote, MsgVoteV1, MsgVoteWeighted, MsgVoteWeightedV1,
            MsgDeposit, MsgDepositV1,
        )):
            from celestia_app_tpu.modules.gov import GovKeeper, ParamChange

            gov = GovKeeper(ctx.store, ctx.staking, ctx.bank)
            if isinstance(msg, MsgSubmitProposalV1):
                # gov v1: the single MsgExecLegacyContent's Content maps
                # onto the same proposal shape the v1beta1 surface takes
                # (the gov router executes legacy Content only).
                from celestia_app_tpu.tx.messages import _parse_gov_content

                exec_msg = msg.legacy_content()
                (
                    _title, _desc, v1_changes, spend_recipient, spend_amount,
                ) = _parse_gov_content(exec_msg.content)
                msg = MsgSubmitProposal(
                    _title, _desc, v1_changes, msg.initial_deposit,
                    msg.proposer, spend_recipient, spend_amount,
                )
            if isinstance(msg, MsgSubmitProposal):
                deposit = sum(c.amount for c in msg.initial_deposit if c.denom == "utia")
                ctx.assert_spendable(msg.proposer, deposit)
                spend = None
                if msg.spend_recipient:
                    spend = (
                        msg.spend_recipient,
                        sum(c.amount for c in msg.spend_amount if c.denom == "utia"),
                    )
                pid = gov.submit(
                    msg.proposer,
                    [ParamChange(c.subspace, c.key, c.value) for c in msg.changes],
                    deposit,
                    ctx.time_ns,
                    spend=spend,
                )
                return 0, [("cosmos.gov.v1beta1.EventSubmitProposal", pid)]
            if isinstance(msg, (MsgVote, MsgVoteV1)):
                gov.vote(msg.proposal_id, msg.voter, msg.option, ctx.time_ns)
                return 0, [("cosmos.gov.v1beta1.EventVote", msg.proposal_id, msg.voter)]
            if isinstance(msg, (MsgVoteWeighted, MsgVoteWeightedV1)):
                from celestia_app_tpu.modules.gov import VoteOption
                from celestia_app_tpu.state.dec import Dec

                gov.vote_weighted(
                    msg.proposal_id, msg.voter,
                    [(VoteOption(o), Dec.from_str(w)) for o, w in msg.options],
                    ctx.time_ns,
                )
                return 0, [("cosmos.gov.v1beta1.EventVote", msg.proposal_id, msg.voter)]
            deposit = sum(c.amount for c in msg.amount if c.denom == "utia")
            ctx.assert_spendable(msg.depositor, deposit)
            gov.deposit(msg.proposal_id, msg.depositor, deposit, ctx.time_ns)
            return 0, [("cosmos.gov.v1beta1.EventDeposit", msg.proposal_id, deposit)]
        raise ValueError(f"no handler for {type(msg).__name__}")

    @staticmethod
    def _track_vesting_delegation(ctx: Ctx, delegator: str, amount: int) -> None:
        """Vesting bookkeeping BEFORE a staking escrow moves: delegations
        (incl. a create-validator self-bond) consume locked tokens first
        (sdk TrackDelegation), so a vesting account's later-received
        liquid funds stay spendable."""
        acc = ctx.auth.get_account(delegator)
        if acc is not None and acc.vesting_type:
            acc.track_delegation(amount, ctx.time_ns)
            ctx.auth.set_account(acc)

    def _handle_authz_exec(self, ctx: Ctx, msg, gas_remaining: int):
        """MsgExec (sdk authz DispatchActions): each inner msg's signer is
        the GRANTER; the grant (granter -> grantee=tx signer, msg type) is
        checked-and-consumed, then the msg runs through the normal
        handlers.  PFBs cannot ride in an exec (blobs only travel in
        BlobTxs), matching the reference's gatekeeping."""
        from celestia_app_tpu.modules.authz import AuthzError, AuthzKeeper

        authz = AuthzKeeper(ctx.store)
        gas_total, events = 0, []
        for inner in msg.inner_msgs():
            if isinstance(inner, (MsgPayForBlobs, MsgAuthzExec)):
                raise ValueError(
                    f"{type(inner).__name__} cannot be nested in MsgExec"
                )
            granter = getattr(inner, "signer", None) or getattr(
                inner, "from_address", None
            )
            if not granter:
                raise ValueError(
                    f"cannot determine granter for {type(inner).__name__}"
                )
            try:
                authz.accept(granter, msg.grantee, inner, ctx.time_ns)
            except AuthzError as e:
                raise ValueError(str(e)) from e
            used, evts = self._handle_msg(ctx, inner, gas_remaining - gas_total)
            gas_total += used
            events.extend(evts)
        return gas_total, events

    def _handle_ibc_msg(self, ctx: Ctx, msg):
        """Transfer sends + the three relay callbacks through the versioned
        middleware stack (tokenfilter > PFM [v2] > transfer,
        app/app.go:329-346)."""
        from celestia_app_tpu.modules.ibc import (
            ChannelKeeper,
            Height,
            TransferKeeper,
            build_transfer_stack,
        )

        from celestia_app_tpu.modules.ibc.transfer import ack_is_error

        channels = ChannelKeeper(ctx.store)
        if isinstance(msg, MsgTransfer):
            if msg.token.denom == "utia":
                # Escrow is an outflow: vesting tokens cannot leave via IBC.
                ctx.assert_spendable(msg.sender, msg.token.amount)
            keeper = TransferKeeper(channels, ctx.bank)
            packet = keeper.send_transfer(
                source_channel=msg.source_channel,
                sender=msg.sender,
                receiver=msg.receiver,
                denom=msg.token.denom,
                amount=msg.token.amount,
                timeout_height=Height(
                    msg.timeout_revision_number, msg.timeout_revision_height
                ),
                timeout_timestamp_ns=msg.timeout_timestamp_ns,
                memo=msg.memo,
                source_port=msg.source_port,
            )
            return 0, [("ibc.send_packet", packet.marshal().hex())]
        if isinstance(msg, MsgRecvPacket):
            packet = msg.packet()
            # Redundant relays are no-op successes in DeliverTx (ibc-go
            # ErrNoOpMsg), so a racing relayer's batched siblings survive.
            if channels.has_receipt(packet):
                return 0, [("ibc.noop", "recv", packet.sequence)]
            dest_chan = channels.channel(
                packet.destination_port, packet.destination_channel
            )
            if dest_chan.connection_id:
                # Connection-backed channel: the packet commitment must be
                # PROVEN in the sender's state through the light client.
                from celestia_app_tpu.modules.ibc.handshake import verify_recv_proof

                verify_recv_proof(
                    ctx.store, dest_chan, packet,
                    msg.state_proof(), msg.proof_height,
                )
            channels.recv_packet(packet, ctx.height, ctx.time_ns)
            # The app callback runs on a cache; its state lands only when
            # the ack is a success (ibc-go msg_server.go RecvPacket's
            # cacheCtx) — an error ack must not leave minted vouchers or
            # half-done forwards behind.  The destination port routes to
            # the app module (ibc-go's port router): transfer or icahost.
            recv_ctx = ctx.branch()
            from celestia_app_tpu.modules.ibc.ica import ICA_HOST_PORT

            if packet.destination_port == ICA_HOST_PORT:
                if self.app_version < 2:
                    raise ValueError(
                        "icahost is a v2 module (app/modules.go:185-187)"
                    )
                from celestia_app_tpu.modules.ibc.ica import (
                    ICAHostKeeper,
                    ICAHostModule,
                )

                ica = ICAHostModule(
                    ICAHostKeeper(recv_ctx.store), self._handle_msg
                )
                ack, recv_events = ica.on_recv_packet(recv_ctx, packet)
            else:
                recv_keeper = TransferKeeper(
                    ChannelKeeper(recv_ctx.store), recv_ctx.bank
                )
                stack = build_transfer_stack(
                    self.app_version, recv_keeper,
                    token_filter=self.ibc_token_filter,
                )
                ack = stack.on_recv_packet(recv_ctx, packet)
                # Middleware (PFM) may have sent onward packets during recv.
                recv_events = [
                    ("ibc.send_packet", p.marshal().hex()) for p in recv_keeper.sent
                ]
            events = [("ibc.write_acknowledgement", packet.marshal().hex(), ack.hex())]
            if not ack_is_error(ack):
                ctx.store.write_back(recv_ctx.store)
                events += recv_events
            channels.write_acknowledgement(packet, ack)
            return 0, events
        from celestia_app_tpu.modules.ibc.ica import (
            CONTROLLER_PORT_PREFIX,
            ICA_HOST_PORT,
        )

        def _ica_port(port: str) -> bool:
            # Port routing (ibc-go's router): the ONLY non-transfer app
            # here is ICA; every other port belongs to the transfer app
            # (send_transfer escrows for arbitrary ports, so the refund
            # callbacks must fire for them too).
            return port == ICA_HOST_PORT or port.startswith(CONTROLLER_PORT_PREFIX)

        keeper = TransferKeeper(channels, ctx.bank)
        stack = build_transfer_stack(
            self.app_version, keeper, token_filter=self.ibc_token_filter
        )
        if isinstance(msg, MsgAcknowledgement):
            packet = msg.packet()
            if channels.packet_commitment(
                packet.source_port, packet.source_channel, packet.sequence
            ) is None:
                return 0, [("ibc.noop", "ack", packet.sequence)]
            src_chan = channels.channel(packet.source_port, packet.source_channel)
            if src_chan.connection_id:
                from celestia_app_tpu.modules.ibc.handshake import verify_ack_proof

                verify_ack_proof(
                    ctx.store, src_chan, packet, msg.acknowledgement,
                    msg.state_proof(), msg.proof_height,
                )
            channels.acknowledge_packet(packet)
            # Only ICA acks bypass the transfer app's refund-on-error
            # callback; an ICA controller's ack just clears the commitment.
            if not _ica_port(packet.source_port):
                stack.on_acknowledgement_packet(ctx, packet, msg.acknowledgement)
            return 0, [("ibc.acknowledge_packet", packet.sequence)]
        packet = msg.packet()  # MsgTimeout
        if channels.packet_commitment(
            packet.source_port, packet.source_channel, packet.sequence
        ) is None:
            return 0, [("ibc.noop", "timeout", packet.sequence)]
        src_chan = channels.channel(packet.source_port, packet.source_channel)
        if src_chan.connection_id:
            # Proven non-receipt on the counterparty at the proof height;
            # the timestamp bound comes from the counterparty's ATTESTED
            # consensus time at that height, never the local clock (a
            # lagging local clock would otherwise let the sender refund
            # escrow while the receiver could still accept the packet).
            from celestia_app_tpu.modules.ibc.handshake import (
                counterparty_proof_time,
                verify_timeout_proof,
            )

            verify_timeout_proof(
                ctx.store, src_chan, packet, msg.state_proof(), msg.proof_height
            )
            proof_time_ns = counterparty_proof_time(
                ctx.store, src_chan, msg.proof_height
            )
        else:
            # Harness-direct channels (no connection/client): trusted mode.
            proof_time_ns = ctx.time_ns
        channels.timeout_packet(packet, msg.proof_height, proof_time_ns)
        if not _ica_port(packet.source_port):
            stack.on_timeout_packet(ctx, packet)
        return 0, [("ibc.timeout_packet", packet.sequence)]

    def _end_block(self, ctx: Ctx, height: int) -> None:
        """Gov clocks + blobstream (v1 only) + height/signal upgrades
        (app/app.go:458-477)."""
        from celestia_app_tpu.modules.gov import GovKeeper

        GovKeeper(ctx.store, ctx.staking, ctx.bank).end_blocker(ctx.time_ns)
        # Matured unbonding delegations release back to delegators
        # (x/staking EndBlocker's unbonding queue); returning tokens
        # re-encumber a vesting account's lock (sdk TrackUndelegation at
        # CompleteUnbonding).
        for delegator, amount in ctx.staking.complete_unbondings(
            ctx.bank, ctx.time_ns
        ):
            acc = ctx.auth.get_account(delegator)
            if acc is not None and acc.vesting_type:
                acc.track_undelegation(amount)
                ctx.auth.set_account(acc)
        if self.app_version == 1:
            from celestia_app_tpu.modules.blobstream.keeper import BlobstreamKeeper

            BlobstreamKeeper(ctx.store, ctx.staking).end_blocker(height, ctx.time_ns)
        if (
            self.app_version == 1
            and self.v2_upgrade_height is not None
            and height >= self.v2_upgrade_height
        ):
            from celestia_app_tpu.app.module_manager import ModuleManager

            ModuleManager().run_migrations(ctx, 1, 2)
            self.app_version = 2
            return
        if self.app_version >= 2:
            keeper = SignalKeeper(ctx.store, ctx.staking)
            up = keeper.should_upgrade(height)
            if up is not None:
                from celestia_app_tpu.app.module_manager import ModuleManager

                ModuleManager().run_migrations(ctx, self.app_version, up.app_version)
                self.app_version = up.app_version
                keeper.reset_tally()
