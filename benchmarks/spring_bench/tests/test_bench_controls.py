"""The correctness controls at a size a test run can hold: on tiny
training cells on the CPU, sound runs of the program stay inside the
limits, and the control (the reference on an int8 grid) and each planted
fault read outside them on at least one number."""

from __future__ import annotations

import pytest

import controls
import harness


def outside(numbers: dict, limits: dict) -> bool:
    return any(numbers[k] > limits[k] for k in limits)


@pytest.mark.parametrize("workload", ["tiny.train.qs", "tiny.train.dense"])
def test_control_and_faults_fail_sound_runs_pass(tiny_bench, workload):
    manifest, bench = tiny_bench()
    cell = harness.load_cell(workload, manifest, bench)
    limits = cell.checks["limits"]
    got = dict(controls.train_readings(
        cell, 1, ["sound", "control", "half_batch", "unchanged"]))
    assert not outside(got["sound"], limits), got["sound"]
    for kind in ("control", "half_batch", "unchanged"):
        assert outside(got[kind], limits), (kind, got[kind])


@pytest.fixture(scope="module")
def witnesses(tmp_path_factory):
    from conftest import make_bench

    manifest, bench = make_bench(tmp_path_factory.mktemp("witness"))
    cell = harness.load_cell("tiny.train.qs", manifest, bench)
    got = dict(controls.train_readings(
        cell, 2, ["sound", "sr_key", "f32_path", "eps_program"]))
    return cell.checks["limits"], got


@pytest.mark.parametrize("kind", ["sr_key", "f32_path"])
def test_witness_paths_stay_inside_the_limits(witnesses, kind):
    limits, got = witnesses
    assert not outside(got[kind], limits), got[kind]


def test_another_sr_key_moves_the_losses(witnesses):
    _, got = witnesses
    assert got["sr_key"]["losses"] != got["sound"]["losses"]


def test_eps_witness_at_the_programs_eps_is_the_sound_run(witnesses):
    # the tiny configuration states the program's eps: the reference at
    # that eps is the reference itself
    _, got = witnesses
    assert controls.program_rms_eps() == 1e-6
    assert got["eps_reference"]["loss_gap"] == 0.0
    assert got["eps_program"]["change_gap"] == got["sound"]["change_gap"]
