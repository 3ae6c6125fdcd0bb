"""Output tokens that reached the host inside the window, over the
window's length (host clock)."""


def read(run):
    return run.tokens_in_window() / run.window_s
