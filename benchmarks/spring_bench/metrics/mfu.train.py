"""Model FLOP/s utilisation of training, in percent: the configuration's
model FLOPs per token (``work/<config>.py``, recomputation not counted)
times the tokens of the window, over the window and the chips' bf16 peak."""


def read(run):
    c = run.counters
    work = run.work(run.cell.config_name)
    flops = work.train_flops_per_token(run.cell.config, c["seq"]) * c["tokens"]
    return 100.0 * flops / c["window_s"] / (run.chips * run.peaks["bf16_flops_per_s"])
