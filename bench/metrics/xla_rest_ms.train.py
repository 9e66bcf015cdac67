"""Device milliseconds per training step in every operation that is not
a Pallas kernel: lifting, projection, skips, GELU, the FFTs and the SHT's
Legendre einsums, layout copies, the loss and the optimizer (exclusive
times, from the trace)."""


def read(run):
    if run.kind != "train" or run.red is None or not run.window["steps"]:
        return None
    from harness.layers import is_kernel

    t = run.red.op_seconds(lambda n: not is_kernel(n))
    return t / run.window["steps"] * 1e3 if t > 0 else None
