"""HBM read amplification of the step plan's substrate: grid bytes the
kernel fetches per step over the grid's own bytes, from the plan's
``SubstrateGeom``, checked against the auditor's walk of the launch's
BlockSpecs (the walk's number goes to the run's notes beside it)."""


def _local_plan(plan):
    """The per-shard plan a sharded ``fused`` plan runs: the program
    builds it for each shard's halo-extended block, rows padded to the
    sublane tile, with every geometry choice left to auto sizing."""
    from repro.kernels import stencil_plan
    from repro.kernels.common import sublane_tile

    halo = plan.t * plan.spec.radius
    shape = [n + 2 * halo for n in plan.halo_plan["local_shape"]]
    if len(shape) >= 2:
        shape[-2] += -shape[-2] % sublane_tile(plan.dtype.itemsize)
    return stencil_plan(plan.weights, tuple(shape), plan.dtype, plan.t,
                        backend=plan.backend, interpret=plan.interpret)


def read(run):
    if run.cell.traffic["driver"] != "step" or not run.plans:
        return None
    from repro import audit

    plan = run.plans[0]
    if plan.mesh is not None:
        plan = _local_plan(plan)
    ctx = plan.ctx
    geom = ctx.resolve_geom(ctx.t * ctx.radius)
    report = audit.audit_context(ctx, plan.backend, flops=False)
    walked = [c for c in report.checks if c.name == "blocks/read-amp-geom"]
    run.notes["read_amp_audited"] = [c.actual for c in walked]
    run.notes["read_amp_audit_ok"] = bool(walked) and all(
        c.passed for c in walked)
    return float(geom.read_amp)
