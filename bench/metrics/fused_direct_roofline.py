"""Share of its roofline that the ``fused_direct`` (VPU) kernel reaches.

Least time of the window's calls (``bench/roofline.py``: the problem's
operations and one read and one write of the grid, at the published
peaks) over the kernel's device time in the trace, per chip.  Silent
where the cell's plan runs another backend.
"""
from bench.roofline import kernel_roofline


def read(run):
    return kernel_roofline(run, "fused_direct")
