"""The control comes out not correct at a size a test run can hold: the
plain reference one precision step below the configurations' bf16
(every matrix product's operands in float8 e4m3) put in the program's
place, read by the cell's own comparison and limits, while the program
itself, read in the same run, stays inside them. On the chip the same
run is made at each cell's own size with ``chipbench/run.py --control
1``."""
import pytest

import sizes


@pytest.mark.parametrize("workload,small,seconds", [
    ("gpt2.train_lp", sizes.TRAIN, 5.0),
    ("qwen3.serve_chat", sizes.SERVE_DEEP, 3.0),
])
def test_control_fails_where_the_program_passes(workload, small, seconds):
    res, run = sizes.execute(workload, small, seconds=seconds, control=True)
    assert not res["correct"], res["checks"]
    assert all(v <= run.limits[k] for k, v in res["program"].items()), (
        res["program"], run.limits)
