"""Device busy milliseconds per engine batch (one padded forward of
``max_batch`` fields, with its transfers), from the trace."""


def read(run):
    if run.kind != "serve" or run.red is None:
        return None
    batches = run.window["engine"].get("batches")
    if not batches:
        return None
    return run.red.busy_s / batches * 1e3
