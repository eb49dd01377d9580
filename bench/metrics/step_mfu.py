"""The whole time step's share of the chip's peak FLOP/s: the problem's
operations completed in the window over window time, chips and the
published peak.  It bounds every kernel's share from outside, so a
kernel taken off the path cannot hide a slower step."""


def read(run):
    if run.cell.traffic["driver"] != "step" or run.window_s <= 0:
        return None
    flops = 2 * run.nnz * run.points * run.t * run.calls
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["flops_per_s"])
