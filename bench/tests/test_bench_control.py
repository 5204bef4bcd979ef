"""The control of each configuration -- its reference computed in the
nearest precision below the one it states, put in the program's place --
reads far worse than the program, at a size a test run holds. On the chip
the same readings, at each cell's own size, set its limits (PERF.md)."""

import dataclasses

import jax
import pytest

from bench import calibrate, harness, run as bench_run
from bench.tests import test_bench_faults as smoke


def _readings(info, seed):
    driver = harness.load_module(info["driver"])
    cell = driver.setup(info["config"], info["traffic"], seed)
    if hasattr(driver, "prepare"):
        driver.prepare(cell, 1.5)
    rec = driver.run(cell, 1.5)
    driver.release(cell)
    return (driver.readings(cell, rec),
            driver.readings(cell, rec, control=info["config"]["control"]),
            rec)


@pytest.mark.parametrize("which", ["ivim", "lm"])
def test_control_reads_far_worse_than_the_program(which):
    info = smoke._ivim_info() if which == "ivim" else smoke._lm_info()
    limits = harness.read_json(info["limits"])
    prog, ctrl, _ = _readings(info, 2 ** 31 + 21)
    # the harness's own comparison passes the program
    assert bench_run.compare(limits, dict(prog, window_compiles=0))[1], prog
    assert any(ctrl[k] >= 3 * max(prog[k], 1e-6) for k in limits), \
        (prog, ctrl)
    # the same comparison fails the control where the smoke size reads on
    # the cell's own scale: IVIM at its published widths. The smoke LM
    # (width 64, 2 layers) reads below the limits set at 1.5B on the chip.
    if which == "ivim":
        assert not bench_run.compare(limits,
                                     dict(ctrl, window_compiles=0))[1], ctrl


def test_attainment_judges_every_request_due():
    info = smoke._lm_info()
    driver = harness.load_module(info["driver"])
    cell = driver.setup(info["config"], info["traffic"], 4)
    rec = driver.run(cell, 1.5)
    out = calibrate.attainment(rec, {"ttft_ms": 1e6, "gap_ms": 1e6})
    assert out["due"] == rec["attempted"] and out["met"] == out["due"]
    strict = calibrate.attainment(rec, {"ttft_ms": 0.0, "gap_ms": 0.0})
    assert strict["met"] == 0
    assert dataclasses.is_dataclass(driver.model_config(info["config"]))
    assert jax.devices()[0].platform == "cpu"
