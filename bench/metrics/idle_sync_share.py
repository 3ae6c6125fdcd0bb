"""Share of the traced window in which the device was idle while the
scheduler waited in ``sched.sync``: the launch of the step and the
token read-back (device trace, spans mapped onto it by the window's
edges)."""
from bench import spans


def read(run):
    return spans.idle_share(run, spans.SYNC)
