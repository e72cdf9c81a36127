"""The harness end to end on the CPU at tiny sizes: each cell's driver,
checks and result line."""
import pytest

import cells
from bench import common


@pytest.mark.parametrize("workload", cells.WORKLOADS)
def test_coverify_cell_is_correct(workload):
    f = cells.found(workload)
    result, ctx = cells.run_cell(f)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in f["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    cells.dumps(result)


def test_every_cell_has_a_cpu_case():
    missing = [str(cells.case_path(w).relative_to(common.ROOT))
               for w in cells.WORKLOADS if not cells.case_path(w).exists()]
    assert not missing, f"add the CPU test case of each driver: {missing}"
