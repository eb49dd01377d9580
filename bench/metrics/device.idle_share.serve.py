"""Share of the traced window in which no operation ran on the device,
in serving cells."""


def read(run):
    if (run.device_trace is None
            or run.cell.traffic["driver"] != "open_loop"):
        return None
    return run.device_trace.idle_share()
