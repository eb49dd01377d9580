"""The work a stencil call must do, and the least time the chip could
take for it.

The work comes from the problem alone, never from the backend that
implements it: a call that advances ``points`` grid points by ``t``
steps of a stencil with ``nnz`` taps needs ``2 * nnz * points * t``
operations and one read and one write of the grid.  Redundant MXU
operations, halo re-reads and deeper fusion are the implementation's
cost and are not counted, so a share of this roofline cannot pass 100%
under any backend.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind that
    is not in the table is an error: another chip's peaks would make
    every share a guess."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise ValueError(f"no peaks for device_kind {device_kind!r} in "
                         f"{path}; known: {sorted(table)}")
    return table[device_kind]


def stencil_work(nnz: int, points: int, t: int, itemsize: int):
    """``(flops, bytes)`` of one call: ``t`` steps over ``points``."""
    return 2 * nnz * points * t, 2 * points * itemsize


def least_time(flops: float, nbytes: float, peaks: dict):
    """``(seconds, bound)``: the larger of operations over peak FLOP/s
    and bytes over peak HBM bandwidth, and which of the two it is."""
    compute = flops / peaks["flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (memory, "memory") if memory >= compute else (compute, "compute")


def kernel_roofline(run, backend: str):
    """Percent of its roofline that ``backend``'s kernels reached in a
    traced time-stepping run: least time of the window's calls on one
    chip over the Pallas kernels' device time per chip.  None where the
    cell's plan runs another backend or the trace holds no kernel."""
    trace = run.device_trace
    if (trace is None or run.cell.traffic["driver"] != "step"
            or not run.plans or run.plans[0].backend != backend
            or trace.kernel_s <= 0):
        return None
    flops, nbytes = stencil_work(run.nnz, run.points // run.chips, run.t,
                                 run.itemsize)
    seconds, bound = least_time(flops, nbytes, run.peaks)
    run.notes[backend + "_roofline_bound"] = bound
    return 100.0 * run.calls * seconds / trace.kernel_s
