"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, in time-stepping cells."""


def read(run):
    if run.device_trace is None or run.cell.traffic["driver"] != "step":
        return None
    return run.device_trace.idle_share()
