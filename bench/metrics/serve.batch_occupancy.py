"""Share of launched batch slots that carried a request (the rest is
the coalescer's padding), from ``ServeMetrics`` over the window."""


def read(run):
    if not run.serve or not run.serve["batch_slots"]:
        return None
    return 100.0 * run.serve["batch_occupancy"]
