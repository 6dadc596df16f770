"""Percent of masked_matmul's MXU grid steps that the train step skips:
100 * (1 - issued / total) over every forward, dx and dw call, padding
tiles included, from the program's in-step tile counter
(``metrics["mm_tiles"]``) of one step counted after the window
(``step_probe.py``)."""

import step_probe


def read(run):
    return step_probe.tile_skip_percent(run)
