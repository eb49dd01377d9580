"""Requests answered per batched launch, from ``ServeMetrics``."""


def read(run):
    if not run.serve or not run.serve["batches"]:
        return None
    return run.serve["responded"] / run.serve["batches"]
