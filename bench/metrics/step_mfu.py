"""The step's share of the chip's bf16 peak: model operations of the
useful tokens the window's steps served, over the step program's device
time."""


def read(run):
    total_ns, count = run.step_device()
    if count == 0 or total_ns == 0:
        return None
    flops = run.useful_flops()
    return 100.0 * flops / (total_ns * 1e-9) / run.peak("bf16_flops_per_s")
