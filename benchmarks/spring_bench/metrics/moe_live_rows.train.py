"""Percent of the held experts' dispatch-buffer rows that hold a token in
training: 100 * live / buffer from the program's in-step counter
``metrics["moe_rows"]`` (``[live, buffer, dropped]``, summed over MoE
layers) of one step counted after the window (``layer_probe.py``); None
where any pair was dropped or the program counts nothing."""

import layer_probe


def read(run):
    return layer_probe.live_rows_percent(run)
