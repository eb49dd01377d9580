"""Host seconds spent building the cell's plans (selection, sizing,
weight composition): ``StencilPlan.build_time_s``, summed."""


def read(run):
    return float(sum(run.build_s)) if run.build_s else None
