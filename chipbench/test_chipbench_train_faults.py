"""A training cell's whole run at the rehearsal size on the CPU: a sound run
is correct, and each fault planted in the timed path makes it incorrect,
by the rehearsal's limits and by the cell's limits on the chip.

The faults a one-chip training cell can have: a step that returns its
state unchanged, a step that takes the mean over half of the batch, and a
loss altered where it is produced. (The exchange between chips does not
exist on one chip.) A last test keeps the control: the reference computed
with float8 operands, put in the program's place, is not correct."""
from __future__ import annotations

import json

import pytest

from chipbench import run

CELL = "granite3_8b.train_failslow"


def _run(capsys, seed: int) -> dict:
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "3",
                   "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _fails_chip_limits(readings: dict) -> bool:
    """Whether some reading is over the cell's limit on the chip (or under
    it, for a number that has to reach its limit)."""
    from chipbench import harness

    chip = harness.resolve_cell(harness.load_manifest(), CELL).limits["limits"]
    return any(v > chip[k] for k, v in readings.items() if k in chip and k != "strategies_dispatched")


def _plant(monkeypatch, fault):
    import repro.train.train_step as ts

    real_make = ts.make_train_step

    def make(cfg, opt_cfg, **kw):
        return fault(real_make(cfg, opt_cfg, **kw))

    monkeypatch.setattr(ts, "make_train_step", make)


def test_sound_run_is_correct(capsys):
    out = _run(capsys, 2**33 + 5)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "checks"


FAULTS = {
    "state_unchanged": lambda real: lambda p, o, b: (p, o, real(p, o, b)[2]),
    "half_batch": lambda real: lambda p, o, b: real(
        p, o, {k: v[: v.shape[0] // 2] for k, v in b.items()}),
    "loss_altered": lambda real: lambda p, o, b: (
        lambda r: (r[0], r[1], {**r[2], "loss": r[2]["loss"] * 1.01}))(real(p, o, b)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(capsys, monkeypatch, fault):
    _plant(monkeypatch, FAULTS[fault])
    out = _run(capsys, 2**33 + 6)
    assert out["correct"] is False, (fault, out["checks"])
    assert _fails_chip_limits({k: c["value"] for k, c in out["checks"].items()}), out["checks"]


def test_control_is_not_correct():
    from chipbench import calibrate, harness

    cell = harness.resolve_cell(harness.load_manifest(), CELL)
    job = harness.Job(cell=cell, seed=2**33 + 7, seconds=0.0, tracer=None,
                      compiles=None, t_start=0.0, rehearsal=True)
    got = calibrate.train_readings(job)
    limits = job.limits()
    assert any(v > limits[k] for k, v in got["control"].items()), got
    assert any(v > limits[k] for k, v in got["half_batch"].items()), got
    assert _fails_chip_limits(got["control"]) and _fails_chip_limits(got["half_batch"]), got
