"""A whole run with the chip check skipped, at a tiny size on the CPU, with
the timed path broken underneath, once for each fault a served cell can
have: a served token altered where the decode program produces it, and a
decode step that returns its state (the KV cache) unchanged. The broken run
must read a wider gap than the sound run of the same seed and come out not
correct. The control (the program's own lower-precision path where the
configuration names one, else the reference in the next lower precision in
the program's place) must read wider than the sound program too, and come
out not correct at the cell's own limits.

The cells are ``BENCHMARK.json``'s workloads, so a cell's faults and
control are checked here once its entry is there.

At this size the approx model's own gaps are wide (four layers of width 128
quantized to uint8 per tensor), so only the exact cells' sound runs are held
to the chip-set limits here."""
import pytest

import faults
import harness
import run

CELLS = [w["name"] for w in harness.benchmark()["workloads"]]
SEED = 2**35 + 4


@pytest.fixture
def restore():
    undo = []
    yield undo
    for u in undo:
        u()


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(cell, fault, restore):
    sound = run.run(cell, SEED, 2.0, False, rehearse=True)
    broken = run.run(cell, SEED, 2.0, False, rehearse=True,
                     patch=lambda prog: restore.append(faults.FAULTS[fault](prog)))
    if harness.load_config(harness.load_cell(cell)["config"])["mode"] == "exact":
        assert sound["correct"], sound["checks"]
    assert not broken["correct"], broken["checks"]
    gap = "served_logit_gap"
    assert broken["checks"][gap]["value"] > sound["checks"][gap]["value"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_wider_than_the_program(cell):
    """The control, judged in the program's place against the cell's own
    limits, comes out not correct, and reads wider than the program."""
    seed = 2**35 + 5
    mode = harness.load_config(harness.load_cell(cell)["config"]).get("control_mode")
    if mode is None:
        low = run.run(cell, seed, 2.0, False, rehearse=True, control=True)
        program = low["program"]
    else:
        # the program's own lower-precision path, against the same reference
        sound = run.run(cell, seed, 2.0, False, rehearse=True)
        low = run.run(cell, seed, 2.0, False, rehearse=True, program_mode=mode)
        program = {k: c["value"] for k, c in sound["checks"].items()}
    assert not low["correct"], low["checks"]
    for name in run.GAPS:
        assert low["checks"][name]["value"] > program[name]
