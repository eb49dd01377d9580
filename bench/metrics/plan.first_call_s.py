"""What each plan's first call (compile, or load from the persistent
cache) costs beyond a steady call, summed over the cell's plans."""


def read(run):
    return float(sum(run.first_call_s)) if run.first_call_s else None
