"""Host milliseconds per training step outside the device step: the
harness's batch fetch and the step's dispatch, up to the loss read that
waits for the device."""


def read(run):
    return 1e3 * run.counters["host_s_per_step"]
