"""Whole solution: useful factor and solve FLOPs over the window and the
chip's bf16 peak."""

from bench.core import mfu


def value(run):
    return mfu(run, ("factor", "solve"), "solution_mfu.fresh")
