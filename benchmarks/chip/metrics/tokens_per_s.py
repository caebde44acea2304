"""Training tokens completed per second: every token of every step in
the window, over the time from the window's start to the last step's
completion (host clock)."""


def read(run):
    return run.tokens / run.window_s
