"""Share of its roofline of the dense solve layer (`solve_tiled` as dispatched):
least time of the solve calls made inside ``flush`` spans over the device busy
time inside those spans."""

from bench.core import roofline


def value(run):
    return roofline(run, "flush", "solve", "dense", "dense_solve_roofline")
