"""The output comparison, at a tiny size on the CPU: a sound run passes
the cell's limits, the control (the reference in bfloat16 in the
program's place) fails one, and a run with a fault planted under the
timed path comes out not correct."""
import pytest

from chipbench import cell as C
from chipbench import control
from chipbench import faults
from chipbench import reference as R
from chipbench import run as RUN

WORKLOADS = [w["name"] for w in C.load_benchmark()["workloads"]]
SEED = 3_000_000_019


def tiny(workload):
    _, cfg, _ = C.resolve(C.load_benchmark(), workload)
    return C.load_module(C.ROOT, "families", cfg["family"]).TINY


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_passes_and_control_fails(workload):
    gaps, ref, checked, cell = control.readings(workload, SEED,
                                                overrides=tiny(workload))
    assert checked.distilled
    assert R.judge(gaps, cell.cfg["limits"])[0], gaps
    ctl = control.control_gaps(cell, checked, ref)
    assert not R.judge(ctl, cell.cfg["limits"])[0], ctl


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_run_with_a_fault_is_not_correct(fault):
    workload = WORKLOADS[0]
    argv = ["--workload", workload, "--seed", str(SEED), "--seconds", "0.2",
            "--trace", "0"]
    with faults.planted(fault):
        result = RUN.run(argv, require_tpu=False, overrides=tiny(workload))
    assert result["correct"] is False
    assert list(result)[-1] == "checks"
