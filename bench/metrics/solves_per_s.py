"""Requests answered over the measured window."""


def value(run):
    return len(run.answers) / run.window_s if run.window_s > 0 else None
