"""The control: the plain reference computed in bfloat16, the precision
below the configuration's float32, put in the program's place, at a small
size on the CPU.  Each cell's limits fail it, and pass the program."""
import jax
import pytest

from bench import calibrate, check
from bench.run import Harness
from bench.tests import tiny


@pytest.mark.parametrize("workload", ["ds7b-silo", "phi3-partition",
                                      "ds7b-mesh4-psum"])
def test_control_fails_and_program_passes(workload):
    jax.config.update("jax_enable_compilation_cache", False)
    cell = tiny.cell(workload)
    if cell.mix.get("mesh_agg"):
        # the control is the reference alone: no mesh needed for it
        cell.mix = {**cell.mix, "mesh_agg": None}
    (row,) = calibrate.readings(Harness(cell), [2**31 + 77], [2**31 + 77])
    ok, _ = check.judge(row["program"], cell.limits)
    assert ok, row
    ok, checks = check.judge(row["control"], cell.limits)
    assert not ok, checks
    for fault in ("half_batch",):
        ok, checks = check.judge(row[fault], cell.limits)
        assert not ok, (fault, checks)
