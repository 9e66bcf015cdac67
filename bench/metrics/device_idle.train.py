"""Share of the traced training window in which no operation ran on the
device."""


def read(run):
    if run.kind != "train" or run.red is None or run.red.window_s <= 0:
        return None
    return (1.0 - run.red.busy_s / run.red.window_s) * 100.0
