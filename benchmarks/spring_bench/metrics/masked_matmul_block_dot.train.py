"""Percent of masked_matmul's block grid steps that take the one-dot
path (both operand blocks full: every 128-tile occupied), over every
forward, dx and dw call: 100 * one_dot / blocks, from entries 6 and 7 of
the program's in-step tile counter (``metrics["mm_tiles"]``) of one step
counted after the window (``step_probe.py``).  None where the counter
has no block entries."""

import step_probe


def read(run):
    report = step_probe.step_report(run)
    tiles = None if report is None else report["mm_tiles"]
    if not tiles or len(tiles) < 8 or tiles[7] <= 0:
        return None
    return 100.0 * tiles[6] / tiles[7]
