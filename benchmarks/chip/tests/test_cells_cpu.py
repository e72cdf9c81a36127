"""The harness end to end on the CPU at tiny sizes: each cell's driver,
checks and result line."""
import pytest

import cells


@pytest.mark.parametrize("workload", cells.WORKLOADS)
def test_coverify_cell_is_correct(workload):
    result, ctx = cells.run_cell(cells.found(workload))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["metrics"]["sweep_s"]["value"] > 0
    assert list(result)[-1] == "checks"
    cells.dumps(result)
