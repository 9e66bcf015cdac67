"""Model FLOP/s utilisation of the whole training step: the
configuration's model FLOPs per sample (forward and backward, counted
by ``bench/models/<family>.py``) times the samples per second of the
traced window, over the chip's peak bf16 FLOP/s."""


def read(run):
    if run.kind != "train" or not run.window["samples"]:
        return None
    rate = run.window["samples"] / run.window["window_s"]
    return run.flops_per_sample * rate / run.peaks["bf16_flops"] * 100.0
