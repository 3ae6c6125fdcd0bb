"""Device time of the donated step program per execution, over the
traced window."""


def read(run):
    total_ns, count = run.step_device()
    if count == 0:
        return None
    return total_ns / count * 1e-6
