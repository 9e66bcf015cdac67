"""Share of their roofline the Pallas spectral kernels reach in a
training step: the least time the chip needs for the step's kernel calls
(each call the larger of its FLOPs over peak FLOP/s and its bytes over
peak bandwidth, from the call shapes by ``bench/kernels/*``) over the
kernels' device time per step."""


def read(run):
    if (run.kind != "train" or run.red is None or not run.kernel_calls
            or not run.window["steps"]):
        return None
    from harness.kernels import least_seconds
    from harness.layers import is_kernel

    t = run.red.op_seconds(is_kernel) / run.window["steps"]
    if t <= 0:
        return None
    return least_seconds(run.kernel_calls, run.peaks)["seconds"] / t * 100.0
