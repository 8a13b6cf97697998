"""Both drivers end to end on the CPU at a 4-validator committee: verdicts
equal the reference's; the control and every planted fault come out as
not correct. (The rung the batches rode is the CPU's here, so
`offchip_batches` is over its limit in every rehearsal and is left out of
what these tests read: they look at the verdict numbers and at
`host_rescued_lanes`.)"""

import pytest

from benchmarks import run

CELLS = ["hub-150.commit", "committee-10k-mixed.commit", "hub-150.catchup"]


def _numbers(result):
    return {k: v["value"] for k, v in result["compared"].items()}


def _rehearse(root, cell, seed, entries="entries", seconds=10.0):
    return run.run_cell(root, cell, seed, seconds, False, entries=entries,
                        on_chip=False)


@pytest.mark.parametrize("cell", CELLS)
def test_verdicts_equal_the_references(tiny_root, device_plane, cell):
    result = _rehearse(tiny_root, cell, seed=2**31 + 7)
    numbers = _numbers(result)
    assert numbers["verdict_mismatches"] == 0 and numbers["errors"] == 0
    assert numbers["host_rescued_lanes"] == 0
    # every corrupt operation of the window (one in five here) is compared
    assert numbers["corrupt_compared"] >= max(5, result["attempted"] // 5)
    assert result["failed"] == 0 and result["attempted"] >= 10
    assert set(result["metrics"]) == set(
        run.load_cell(tiny_root, cell).end_to_end)
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(tiny_root, device_plane, cell):
    """The program's own quorum-only path in the full one's place, found
    as control.py finds it."""
    result = _rehearse(tiny_root, cell, seed=11, entries="control_entries")
    assert not result["correct"]
    assert _numbers(result)["verdict_mismatches"] >= 1


@pytest.mark.parametrize("cell", ["hub-150.commit", "hub-150.catchup"])
def test_half_of_the_batch_left_out_is_not_correct(tiny_root, device_plane,
                                                   monkeypatch, cell):
    from cometbft_tpu.types import validation

    real = validation._raise_first_bad

    def first_half_only(commit, idxs, mask):
        half = len(mask) // 2
        return real(commit, idxs[:half], list(mask)[:half])

    monkeypatch.setattr(validation, "_raise_first_bad", first_half_only)
    result = _rehearse(tiny_root, cell, seed=12)
    assert not result["correct"]
    assert _numbers(result)["verdict_mismatches"] >= 1


@pytest.mark.parametrize("cell", ["hub-150.commit", "hub-150.catchup"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        tiny_root, device_plane, monkeypatch, cell):
    """The wrong signature is named one lane too far."""
    from cometbft_tpu.types import validation

    real = validation._raise_first_bad

    def one_too_far(commit, idxs, mask):
        return real(commit, [i + 1 for i in idxs], mask)

    monkeypatch.setattr(validation, "_raise_first_bad", one_too_far)
    result = _rehearse(tiny_root, cell, seed=13)
    assert not result["correct"]
    assert _numbers(result)["verdict_mismatches"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_a_valid_lane_condemned_by_the_device_is_not_correct(
        tiny_root, device_plane, monkeypatch, cell):
    """The kernel's mask says invalid for a valid signature and the
    program's host-oracle re-check overturns it: every verdict is right,
    and the run is not correct, because the device path did not give it."""
    from cometbft_tpu.ops import ed25519_kernel

    real = ed25519_kernel.apply_recheck

    def condemn_lane_0(mask, eligible, rows, info):
        if len(mask) and mask[0] and eligible[0]:
            mask[0] = False
        return real(mask, eligible, rows, info)

    monkeypatch.setattr(ed25519_kernel, "apply_recheck", condemn_lane_0)
    result = _rehearse(tiny_root, cell, seed=14)
    numbers = _numbers(result)
    assert numbers["verdict_mismatches"] == 0 and numbers["errors"] == 0
    assert numbers["host_rescued_lanes"] >= 1
    assert not result["correct"]
