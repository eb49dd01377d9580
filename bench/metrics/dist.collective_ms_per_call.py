"""Device time of collective operations per plan call, on the chip that
spent the most on them, from the trace.  Silent on unsharded plans."""


def read(run):
    if (run.device_trace is None or not run.plans
            or run.plans[0].mesh is None or not run.calls):
        return None
    return run.device_trace.collective_s_max / run.calls * 1e3
