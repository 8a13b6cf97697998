"""The seams (run.seam): a cell whose driver's module brings its own data,
program objects, entries, reference and signature count is run and judged
by them, with no file of the harness edited; a cell whose module brings
none runs exactly the functions every cell ran before there were seams."""

import json
import os
import re

import pytest

import benchmarks.drivers
from benchmarks import check, program, run
from benchmarks.tests.conftest import _read, _write

SEAMS = ("make_data", "build_program_objects", "entries", "control_entries",
         "reference_verdicts", "sigs_of")

# What a later PR's files hold, in one module for the test: a cell over TWO
# validator sets (even heights signed by one, odd by the other), driven
# through the program's quorum-only entry and judged by a quorum-only
# reference written here (VerifyCommitLight: the signatures in order until
# more than 2/3 of the power has signed, and no further).
PLANTED = '''
from benchmarks import program
from benchmarks.drivers import commit
from benchmarks.reference import commit_ref


def make_data(cell, seed):
    from benchmarks import datagen

    made = [datagen.make_validators(cell.config, seed + i) for i in (0, 1)]
    rings = [datagen.make_ring(cell.config, spec, signers, seed)
             for spec, signers in made]
    cell.specs = [spec for spec, _signers in made]
    cell.vals_spec = cell.specs[0]
    cell.ring = [rings[i % 2][i] for i in range(len(rings[0]))]
    cell.schedule = datagen.Schedule(cell.traffic, len(cell.ring),
                                     len(cell.vals_spec.pubs), seed)


def build_program_objects(cell):
    cell.sets = [program.build_validator_set(spec) for spec in cell.specs]
    cell.commits = [program.build_commit(cell.sets[i % 2], spec)
                    for i, spec in enumerate(cell.ring)]


def entries():
    from cometbft_tpu.types import validation

    return {"verify_commit": validation.verify_commit_light}


def quorum_lanes(vals):
    tally, needed = 0, sum(vals.powers) * 2 // 3
    for i, power in enumerate(vals.powers):
        tally += power
        if tally > needed:
            return i + 1
    return len(vals.powers)


def reference_verdicts(cell, sample):
    out, lanes = {}, 0
    for r in sample:
        vals, spec = cell.specs[r.ring_idx % 2], cell.ring[r.ring_idx]
        if r.corrupt_lane is not None:
            spec = spec.with_flipped(r.corrupt_lane)
        checked = commit_ref.commit_lanes(vals, spec)[:quorum_lanes(vals)]
        lanes += len(checked)
        bad = [i for i, lane in enumerate(checked)
               if not commit_ref.verify_lane(lane)]
        out[r.k] = f"reject#{bad[0]}" if bad else "accept"
    return out, lanes


def sigs_of(cell, record):
    return {"ed25519": quorum_lanes(cell.specs[record.ring_idx % 2])}


class Driver(commit.Driver):
    def _one(self, k):
        self.cell.vals = self.cell.sets[self.cell.schedule.op(k)[0] % 2]
        return super()._one(k)
'''


@pytest.fixture
def planted_cell(tiny_root, monkeypatch):
    """`two-sets.light` in the tiny root, by new files and new entries
    alone; the package benchmarks.drivers also looks in the tiny root's
    drivers/ for the length of the test."""
    drivers_dir = os.path.join(tiny_root, "benchmarks", "drivers")
    os.makedirs(drivers_dir, exist_ok=True)
    with open(os.path.join(drivers_dir, "planted_light.py"), "w") as fh:
        fh.write(PLANTED)
    monkeypatch.setattr(benchmarks.drivers, "__path__",
                        list(benchmarks.drivers.__path__) + [drivers_dir])
    conf = _read(os.path.join(tiny_root, "benchmarks/configs/hub-150.json"))
    # the rung that is due on the CPU, so that `correct` can read true
    # here: no batch served by the host oracle
    conf["guarantees"]["rung"] = {"plus": ["metrics.fallback_verifies"]}
    _write(os.path.join(tiny_root, "benchmarks/configs/two-sets.json"),
           dict(conf, name="two-sets"))
    mix = _read(os.path.join(tiny_root,
                             "benchmarks/traffic/commit-serial.json"))
    _write(os.path.join(tiny_root, "benchmarks/traffic/light-serial.json"),
           dict(mix, driver="planted_light"))
    bench_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bench_path) as fh:
        kept = fh.read()
    bench = json.loads(kept)
    bench["configs"].append({"name": "two-sets",
                             "file": "benchmarks/configs/two-sets.json"})
    bench["workloads"].append({"name": "two-sets.light", "config": "two-sets",
                               "traffic": "light-serial", "chips": 1})
    for metric in bench["end_to_end"]:
        if metric["name"] == "commit_verify_ms":
            metric["workloads"].append("two-sets.light")
    _write(bench_path, bench)
    yield "two-sets.light"
    with open(bench_path, "w") as fh:
        fh.write(kept)


def _numbers(result):
    return {k: v["value"] for k, v in result["compared"].items()}


def test_a_planted_cell_is_judged_by_its_own_reference(
        tiny_root, device_plane, planted_cell, monkeypatch, capfd):
    own = run.run_cell(tiny_root, planted_cell, 2**31 + 32, 6.0, False,
                       on_chip=False)
    out = capfd.readouterr().out
    numbers = _numbers(own)
    assert numbers.pop("corrupt_compared") >= own["attempted"] // 5 >= 5
    assert numbers == {"verdict_mismatches": 0, "errors": 0,
                       "offchip_batches": 0, "host_rescued_lanes": 0}
    assert own["correct"] and own["failed"] == 0
    assert set(own["metrics"]) == {"commit_verify_ms", "setup_s"}
    # its own count: 3 of the 4 equal powers pass 2/3, and no lane further
    signatures = int(re.search(r"\((\d+) signatures\)", out).group(1))
    assert signatures == 3 * own["attempted"]

    # the same cell under the default reference: VerifyCommit holds a
    # corrupt signature behind the quorum against the light entry
    module = run.load_cell(tiny_root, planted_cell).driver
    monkeypatch.delattr(module, "reference_verdicts")
    monkeypatch.delattr(module, "sigs_of")
    default = run.run_cell(tiny_root, planted_cell, 2**31 + 32, 6.0, False,
                           on_chip=False)
    out = capfd.readouterr().out
    assert not default["correct"]
    assert _numbers(default)["verdict_mismatches"] >= 1
    assert int(re.search(r"\((\d+) signatures\)", out).group(1)) == (
        4 * default["attempted"])


def test_a_cell_without_seams_runs_the_functions_it_always_ran(
        tiny_root, device_plane, monkeypatch):
    """hub-150.commit: its driver's module brings none of the six, each
    default runs, and the result is what the schedule and VerifyCommit
    give: every field but the timings."""
    cell = run.load_cell(tiny_root, "hub-150.commit")
    assert cell.driver.__name__ == "benchmarks.drivers.commit"
    assert not [name for name in SEAMS if hasattr(cell.driver, name)]
    ran = []

    def spy(module, name):
        real = getattr(module, name)

        def spied(*args, **kwargs):
            ran.append(name)
            return real(*args, **kwargs)

        monkeypatch.setattr(module, name, spied)

    for module, name in ((run, "make_data"), (run, "build_program_objects"),
                         (program, "entries"), (check, "reference_verdicts"),
                         (run, "sigs_of")):
        spy(module, name)
    seen = []
    real_compare = check.compare

    def compare(cell, records, *args):
        seen.append((cell, records))
        return real_compare(cell, records, *args)

    monkeypatch.setattr(check, "compare", compare)
    result = run.run_cell(tiny_root, "hub-150.commit", 2**31 + 33, 4.0,
                          False, on_chip=False)
    (cell, records), = seen
    assert ran[:3] == ["make_data", "build_program_objects", "entries"]
    assert ran.count("sigs_of") == len(records)
    assert ran[-1] == "reference_verdicts"
    assert "control_entries" not in ran
    # every answer is the schedule's: the corrupt lane named, else accepted
    assert [r.k for r in records] == list(range(len(records)))
    for r in records:
        ring_idx, lane = cell.schedule.op(r.k)
        assert (r.ring_idx, r.corrupt_lane) == (ring_idx, lane)
        assert r.verdict == ("accept" if lane is None else f"reject#{lane}")
    corrupt = sum(r.corrupt_lane is not None for r in records)
    assert result["attempted"] == len(records) and result["failed"] == 0
    numbers = _numbers(result)
    # on the CPU no batch rides the rung that is due: over its limit here
    assert numbers.pop("offchip_batches") >= 1 and not result["correct"]
    assert numbers == {"verdict_mismatches": 0, "errors": 0,
                       "host_rescued_lanes": 0, "corrupt_compared": corrupt}
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert list(result["metrics"]) == ["commit_verify_ms", "setup_s"]
    assert result["metrics"]["commit_verify_ms"]["value"] == pytest.approx(
        1e3 * (records[-1].t_end - records[0].t_start) / len(records),
        rel=0.01)


def test_the_control_takes_the_cells_own_control_entries(
        tiny_root, device_plane, planted_cell, monkeypatch):
    """entries="control_entries" (control.py) finds the module's own, and
    the program's where the module has none."""
    module = run.load_cell(tiny_root, planted_cell).driver
    asked = []

    def own_control():
        asked.append("own")
        return module.entries()

    def programs_control():
        asked.append("program")
        return module.entries()

    monkeypatch.setattr(program, "control_entries", programs_control)
    run.run_cell(tiny_root, planted_cell, 34, 2.0, False,
                 entries="control_entries", on_chip=False)
    monkeypatch.setattr(module, "control_entries", own_control,
                        raising=False)
    run.run_cell(tiny_root, planted_cell, 34, 2.0, False,
                 entries="control_entries", on_chip=False)
    assert asked == ["program", "own"]
