"""Device: share of the traced window in which no operation ran on the
device."""

from bench.core import idle_share


def value(run):
    return idle_share(run)
