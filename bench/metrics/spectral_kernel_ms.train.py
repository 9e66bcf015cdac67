"""Device milliseconds per training step in the Pallas spectral kernels
(every family, forward and backward), from the trace."""


def read(run):
    if run.kind != "train" or run.red is None or not run.window["steps"]:
        return None
    from harness.layers import is_kernel

    t = run.red.op_seconds(is_kernel)
    return t / run.window["steps"] * 1e3 if t > 0 else None
