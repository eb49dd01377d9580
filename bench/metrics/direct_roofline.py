"""Share of its roofline that the unfused ``direct`` (VPU) kernel
reaches: one step per call, the problem's operations and one read and
one write of the grid at the published peaks (``bench/roofline.py``)
over the kernel's device time in the trace, per chip.  Silent where the
cell's plan runs another backend."""
from bench.roofline import kernel_roofline


def read(run):
    return kernel_roofline(run, "direct")
