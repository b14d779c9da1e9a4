"""Share of its roofline of the dense factor layer (`lu_fused` as dispatched):
least time of the factor calls made inside ``factor`` spans over the device busy
time inside those spans."""

from bench.core import roofline


def value(run):
    return roofline(run, "factor", "factor", "dense", "dense_factor_roofline")
