"""Share of the traced window in which the device was idle while the
scheduler was in host code: ``sched.admit``, ``sched.feed``,
``sched.dispatch`` or ``sched.post`` (device trace, spans mapped onto it
by the window's edges)."""
from bench import spans


def read(run):
    return spans.idle_share(run, spans.HOST)
