"""Percent of masked_matmul's block grid steps that are idle (issue no
dot, so the kernel loads no operand block for them), over every forward,
dx and dw call: 100 * idle / blocks, from entries 8 and 7 of the
program's in-step tile counter (``metrics["mm_tiles"]``) of one step
counted after the window (``step_probe.py``).  None where the counter
has no idle entry."""

import step_probe


def read(run):
    report = step_probe.step_report(run)
    tiles = None if report is None else report["mm_tiles"]
    if not tiles or len(tiles) < 9 or tiles[7] <= 0:
        return None
    return 100.0 * tiles[8] / tiles[7]
