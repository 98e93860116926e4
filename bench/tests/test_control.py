"""The control: the reference computed in bfloat16, put in the program's
place, must come out not correct; the program itself must come out correct.
(At a small size on the CPU; PERF.md gives the readings on the chip.)"""
import pytest

from bench.tests._cpu_run import small_run


@pytest.mark.parametrize("control,expect", [(None, True), ("bfloat16", False)])
def test_control_is_refused(control, expect):
    result, extra = small_run("telemetry.dash", control=control)
    assert result["correct"] is expect, result["checks"]
    assert list(result)[-1] == "checks"
    if control:
        # more than one number separates the control from the program
        failed = [k for k, c in result["checks"].items()
                  if c["value"] > c["limit"]]
        assert {"est_gap", "h_gap", "exact_gap"} <= set(failed), failed
