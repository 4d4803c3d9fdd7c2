"""The control (``control.py``: the reference with lines cut to 1,024
bytes in the program's place) reads as not correct in every cell, at the
configuration's tiny size: its answers go through the cell's own check
and verdict.  At this size few lines pass 1,024 bytes, so the test cuts
at 256: the same guarantee, broken more often."""
import pytest

import control


@pytest.mark.parametrize("workload", [
    "etl.apache_combined.rows",
    "serve.nginx_combined.shippers",
])
def test_control_fails_the_check(workload):
    checks, correct = control.readings(workload, 2**37 + 3, 4,
                                       rehearse=True, cut=256)
    assert correct is False
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
