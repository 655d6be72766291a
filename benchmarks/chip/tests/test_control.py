"""The control, the reference one precision step below the one the
configuration states put in the program's place, at a small size: it
reads at least three times what the sound program reads on one of the
compared numbers, and each planted fault ten times, on the same seed.
This is the separation ``set_limits.py`` relies on. The limits
themselves come from the chip at the cells' own sizes, where the DeepFM
control reads some twenty times higher than at this size on the CPU
(PERF.md), so this test does not apply them to the control."""
from __future__ import annotations

import json

import pytest

import calibrate
from small import cell_names, small_cell


@pytest.mark.parametrize("name", cell_names())
def test_control_and_faults_separate_from_the_program(name, tmp_path):
    """For the four-chip cell this is also its reference's check against
    the program (CD-Adam over four host devices)."""
    out = tmp_path / "cal.json"
    cell = small_cell(name)
    rc = calibrate.main(["--workload", name, "--seeds", "1",
                         "--control-seeds", "1", "--first-seed",
                         str(2 ** 35 + 3), "--out", str(out)],
                        require_tpu=False, cell=cell)
    assert rc == 0
    s = json.loads(out.read_text())["summary"]
    assert all(r["lower"] < 1e-2 for r in s.values()), s
    assert any(r["control"] >= 3 * r["lower"] for r in s.values()), s
    for fault in ("fault_half", "fault_no_gossip"):
        assert any(r[fault] >= 10 * r["lower"] for r in s.values()), s
