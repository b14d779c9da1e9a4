"""Whole solution: useful solve FLOPs over the window and the chip's bf16
peak."""

from bench.core import mfu


def value(run):
    return mfu(run, ("solve",), "solution_mfu.service")
