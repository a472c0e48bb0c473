"""The control, the reference in float8 put in the program's place, reads
worse than the program at a small size on the CPU (on the chip, at the
cells' sizes, ``bench/control.py`` sets the limits)."""
import pytest

from bench import control, smoke


@pytest.mark.parametrize("cell", [smoke.SERVE_CELL, smoke.RELAYOUT_CELL])
def test_control_reads_worse_than_the_program(cell):
    row = control.measure(cell, 2**31 + 5, 0.3, **smoke.kw(cell))
    assert row["failed"] == 0
    for name, program in row["program"].items():
        assert row["control"][name] > program


def test_relayout_control_fails_its_limit():
    from bench import harness
    row = control.measure(smoke.RELAYOUT_CELL, 7, 0.3, **smoke.kw(smoke.RELAYOUT_CELL))
    limit = harness.read_json(harness.BENCH / "limits" / f"{smoke.RELAYOUT_CELL}.json")
    assert row["control"]["relayout_err"] > limit["relayout_err"]
    assert row["program"]["relayout_err"] < limit["relayout_err"]
