"""Seconds the program counted making executables inside the cell's
plan calls (XLA compiles and persistent-cache loads,
``StencilPlan.compile_s``), summed over the cell's plans.  Silent where
the program keeps no such counter."""


def read(run):
    secs = [getattr(p, "compile_s", None) for p in run.plans]
    if not secs or None in secs:
        return None
    return float(sum(secs))
