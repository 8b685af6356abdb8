"""The fleet cell's whole run at the rehearsal size on the CPU: a sound run
is correct, and each fault planted in the timed path makes it incorrect,
by the rehearsal's limits and by the cell's limits on the chip.

The faults the screen can have: a step that returns its state unchanged,
a step that leaves half of the streams out, and a flag altered where it
is produced (``calibrate.FLEET_FAULTS``, which the chip's readings use
too). A last test keeps the control: the reference's posterior in
bfloat16, put in the program's place, is not correct."""
from __future__ import annotations

import json

from chipbench import calibrate, harness, run

CELL = "fleet8k.screen_failslow"


def _run(capsys, seed: int) -> dict:
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.6",
                   "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _fails_chip_limits(readings: dict) -> bool:
    """Whether some reading is over the cell's limit on the chip."""
    chip = harness.resolve_cell(harness.load_manifest(), CELL).limits["limits"]
    return any(v > chip[k] for k, v in readings.items())


def test_sound_run_is_correct(capsys):
    out = _run(capsys, 2**33 + 5)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"


def _assert_planted_fault_fails(capsys, monkeypatch, fault: str) -> None:
    calibrate.plant_fleet_fault(fault, monkeypatch.setattr)
    out = _run(capsys, 2**33 + 6)
    assert out["correct"] is False, (fault, out["checks"])
    assert _fails_chip_limits({k: c["value"] for k, c in out["checks"].items()}), out["checks"]


def test_state_unchanged_is_not_correct(capsys, monkeypatch):
    _assert_planted_fault_fails(capsys, monkeypatch, "state_unchanged")


def test_half_streams_left_out_is_not_correct(capsys, monkeypatch):
    _assert_planted_fault_fails(capsys, monkeypatch, "half_streams")


def test_flag_altered_is_not_correct(capsys, monkeypatch):
    _assert_planted_fault_fails(capsys, monkeypatch, "flag_altered")


def test_control_is_not_correct():
    cell = harness.resolve_cell(harness.load_manifest(), CELL)
    job = harness.Job(cell=cell, seed=2**33 + 7, seconds=0.0, tracer=None,
                      compiles=None, t_start=0.0, rehearsal=True)
    got = calibrate.fleet_readings(job, ticks=300)
    limits = job.limits()
    assert any(v > limits[k] for k, v in got["control"].items()), got
    assert _fails_chip_limits(got["control"]), got
