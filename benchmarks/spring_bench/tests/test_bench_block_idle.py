"""The reader of masked_matmul's idle block share on synthetic step
reports: the counter's entries 8 and 7, and None where the program
counts no idle blocks (an 8-entry counter, as a program without the
idle-step table reads), no blocks, or nothing at all."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import harness

READER = "masked_matmul_block_idle.train"


def _run(tiles):
    report = None if tiles is False else {"module": "jit_plain_step", "scopes": {},
                                          "mm_tiles": tiles}
    return NS(cell=None, trace=None, counters={"steps": 2}, chips=1, step_report=report)


def _read(tiles):
    reader = harness.load_module(harness.BENCH_DIR / "metrics" / f"{READER}.py")
    return reader.read(_run(tiles))


@pytest.mark.parametrize("tiles,want", [
    ([4.0, 40.0, 2.0, 40.0, 4.0, 40.0, 1.0, 16.0, 12.0], 75.0),
    ([40.0, 40.0, 40.0, 40.0, 40.0, 40.0, 16.0, 16.0, 0.0], 0.0),
    ([0.0, 40.0, 0.0, 40.0, 0.0, 40.0, 0.0, 16.0, 16.0], 100.0),
])
def test_block_idle_share(tiles, want):
    assert _read(tiles) == pytest.approx(want)


@pytest.mark.parametrize("tiles", [
    [30.0, 40.0, 10.0, 40.0, 40.0, 40.0, 12.0, 16.0],   # the counter without idle entry
    [0.0] * 9,                                          # no block counted
    None,                                               # no counter
    False,                                              # no step report
])
def test_block_idle_reads_nothing_without_idle_entry(tiles):
    assert _read(tiles) is None
