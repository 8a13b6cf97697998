"""The light cell's seam to the program: what `light-500.bisect` takes from
cometbft_tpu beside benchmarks/program.py (device probe, boot, counters,
tracer: those hold for every cell).

The program's types for a light block (types.block.Header, types.commit's
Commit with each signature under the address its spec gives it, types.light's
SignedHeader and LightBlock over program.build_validator_set), the entry the window drives (light.verifier.verify)
and the control. Nothing here needs a span, a counter or a function that
the commit before PR 35 lacks. As in program.py, cometbft_tpu is imported
inside functions only.
"""

from __future__ import annotations

from benchmarks import program
from benchmarks.reference import light_ref


def build_light_block(spec: light_ref.LightBlockSpec):
    """The program's LightBlock for a LightBlockSpec. A block no hop
    verifies is unsigned in the benchmark's data: its commit here holds
    no signatures, and only its header and its set are ever read."""
    from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from cometbft_tpu.types.block import Consensus, Header
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.types.light import LightBlock, SignedHeader
    from cometbft_tpu.utils import cmttime

    h = spec.header
    header = Header(
        version=Consensus(block=h.version[0], app=h.version[1]),
        chain_id=h.chain_id, height=h.height,
        time=cmttime.Timestamp(*h.time),
        last_block_id=BlockID(hash=h.last_block_hash,
                              part_set_header=PartSetHeader(
                                  total=h.last_parts_total,
                                  hash=h.last_parts_hash)),
        last_commit_hash=h.last_commit_hash, data_hash=h.data_hash,
        validators_hash=h.validators_hash,
        next_validators_hash=h.next_validators_hash,
        consensus_hash=h.consensus_hash, app_hash=h.app_hash,
        last_results_hash=h.last_results_hash,
        evidence_hash=h.evidence_hash, proposer_address=h.proposer_address)
    c = spec.commit
    block_id = BlockID(hash=c.block_hash, part_set_header=PartSetHeader(
        total=c.parts_total, hash=c.parts_hash))
    commit = Commit(height=c.height, round_=c.round, block_id=block_id,
                    signatures=[
        CommitSig.absent() if i in spec.absent else CommitSig(
            block_id_flag=BlockIDFlag.COMMIT, validator_address=addr,
            timestamp=cmttime.Timestamp(*stamp), signature=sig)
        for i, (addr, stamp, sig) in enumerate(
            zip(spec.addresses, c.stamps, c.sigs))])
    return LightBlock(signed_header=SignedHeader(header=header, commit=commit),
                      validator_set=program.build_validator_set(spec.vals))


def fresh_block(block, pristine_vals, corrupt_lane: int | None = None):
    """(SignedHeader, ValidatorSet) of a kept light block as a client gets
    them from a peer: a new Commit object over the same signatures
    (program.fresh, with one flipped if asked) and a new ValidatorSet
    object with new Validators: a copy of `pristine_vals`, a set object of
    the block's validators that is itself never handed to the program, so
    that nothing an earlier hop left on a kept object (sign-bytes, the
    set's hash stamp, columns, address map) rides the new one."""
    from cometbft_tpu.types.light import SignedHeader

    return (SignedHeader(header=block.signed_header.header,
                         commit=program.fresh(block.signed_header.commit,
                                              corrupt_lane)),
            pristine_vals.copy())


def verify_args(params: light_ref.Params) -> tuple:
    """(trusting period, now, clock drift, trust level) in the program's
    types, as light.verifier.verify takes them after the two blocks."""
    from cometbft_tpu.types.validation import Fraction
    from cometbft_tpu.utils import cmttime

    return (params.trusting_period_ns,
            cmttime.Timestamp(*divmod(params.now_ns, 10**9)),
            params.max_clock_drift_ns, Fraction(*params.trust_level))


def entries() -> dict:
    from cometbft_tpu.light import verifier

    return {"verify": verifier.verify}


def control_entries() -> dict:
    """The control: the hop with the trusting check left out. The new
    block is held to itself (its header, its commit and its set agree:
    LightBlock.validate_basic) and to 2/3 of its OWN set
    (verify_commit_light) and to nothing of the trusted one, which breaks
    the configuration's guarantee that more than 1/3 of the trusted set's
    power signed: it accepts the hops a client has to bisect."""
    from cometbft_tpu.types import validation
    from cometbft_tpu.types.light import LightBlock

    def verify(trusted_header, _trusted_vals, header, vals, *_rest):
        LightBlock(signed_header=header, validator_set=vals).validate_basic(
            trusted_header.chain_id)
        validation.verify_commit_light(
            trusted_header.chain_id, vals, header.commit.block_id,
            header.height, header.commit)

    return {"verify": verify}


def verdict_of(call) -> str:
    """Run one hop to its end and say what it answered, in light_ref's
    words. light.verifier wraps what the commit checks raise in
    ErrInvalidHeader (the trusting check's wrong signature it lets through
    as it is): the cause says which answer it was. Anything but an answer
    is "error:<type>"."""
    from cometbft_tpu.light import errors
    from cometbft_tpu.types import validation

    try:
        call()
    except errors.ErrNewValSetCantBeTrusted:
        return "reject:untrusted"
    except errors.ErrOldHeaderExpired:
        return "reject:expired"
    except (errors.ErrInvalidHeader,
            validation.ErrInvalidCommitSignature, ValueError) as exc:
        cause = getattr(exc, "cause", exc)
        if isinstance(cause, validation.ErrInvalidCommitSignature):
            text = str(cause)
            return "reject#" + text[text.index("(#") + 2:text.index(")")]
        if isinstance(cause, validation.ErrNotEnoughVotingPowerSigned):
            return "reject:power"
        if isinstance(exc, errors.ErrInvalidHeader):
            return "reject:header"
        if str(exc).startswith("double vote"):
            return "reject:double-vote"
        return f"error:{type(exc).__name__}"
    except Exception as exc:  # noqa: BLE001 - the answer is the failure
        return f"error:{type(exc).__name__}"
    return "accept"
