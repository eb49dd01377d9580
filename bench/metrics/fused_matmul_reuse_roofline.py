"""Share of its roofline that the ``fused_matmul_reuse`` (MXU) kernel
reaches: the same work as every other backend's share (the problem's,
not the MXU's redundant operations), over this kernel's device time.
Silent where the cell's plan runs another backend.
"""
from bench.roofline import kernel_roofline


def read(run):
    return kernel_roofline(run, "fused_matmul_reuse")
