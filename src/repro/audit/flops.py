"""Jaxpr FLOP counter: prove the model's arithmetic terms on traced code.

``jax.make_jaxpr`` on a built plan runner yields the exact computation
the backend launches -- every ``pallas_call`` with its grid, every
``dot_general`` with its contraction dims, every vector ``add``/``mul``.
Counting FLOPs there (dot-general MACs as 2*B*M*N*K, elementwise float
ops at their output size, scaled by grid size and ring-gated fire
frequency) gives a ground truth that is independent of both the analytic
performance model AND the per-kernel mirror walks below, so each can be
checked against it:

  * ``flops/structural``       -- jaxpr-counted (vector, dot) FLOPs ==
    the plain-Python mirror of ``_stencil_steps`` / ``_banded_step``
    over the audited launch geometries, exact integer equality, and the
    traced runner launches exactly the declared number of pallas calls.
  * ``flops/alpha``            -- the fused kernel's audited tap-count
    ratio nnz(w_fused) / (t * nnz(w)) equals ``perfmodel.fusion_alpha``
    (the paper's alpha; only provable when the base weights realize the
    spec, else skipped).
  * ``flops/beta``             -- executed stencil points per output
    point across a t-step in-VMEM launch equal ``perfmodel.reuse_beta``
    (the paper's beta halo-recompute factor), rtol 1e-9 -- the audited
    shrinking-region sum telescopes to exactly (1/t) sum_j prod_m
    (1 + 2*r*j/size_m).
  * ``flops/matrix-reuse-model`` -- audited MXU FLOPs per output point
    of the reuse backends match ``(beta / S) * flops_vector`` with S the
    measured band sparsity (``flops_matrix_reuse``; the sparse-compacted
    launch is additionally scaled by its kept-row fraction); rtol 5e-2
    absorbs final-chunk remainders on widths not divisible by tile_n.
  * ``flops/sparse-compaction`` -- the compacted contraction (engine
    ``"sparse_matmul"``, DESIGN.md §14) does exactly the packed-row FLOP
    count -- S * dense on tile-aligned widths -- integer-exact against
    the traced jaxpr, and never exceeds the dense count.  The expectation
    is derived from ``bands_shape`` alone, so tampered gather metadata
    cannot hide a mis-compaction.

All model lookups go through the ``perfmodel`` module attribute at check
time so a monkeypatched (i.e. wrong) model is caught, not baked in.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np

from .report import AuditCheck


# --------------------------------------------------------------------------
# Jaxpr walk
# --------------------------------------------------------------------------
_ELEMENTWISE = {"add", "sub", "mul", "add_any"}


def _is_float(aval) -> bool:
    try:
        return np.issubdtype(aval.dtype, np.floating)
    except Exception:
        return False


def count_jaxpr_flops(jaxpr, launches):
    """Count (vector_flops, dot_flops, n_pallas_calls) in a closed jaxpr.

    ``launches`` is the audit spec's ordered :class:`LaunchAudit` tuple;
    pallas calls are matched to it in trace order so each body's
    ring-gated compute branch is weighted by its fire frequency
    (grid_steps / ring).  Only floating-dtype outputs count -- integer
    index arithmetic inside kernel bodies is free.
    """
    state = {"vector": 0, "dot": 0, "pallas": 0}
    _walk(getattr(jaxpr, "jaxpr", jaxpr), 1, 1, launches, state)
    return state["vector"], state["dot"], state["pallas"]


def _walk(jaxpr, mult, ring, launches, state):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "pallas_call":
            inner = eqn.params["jaxpr"]
            gm = eqn.params.get("grid_mapping")
            grid = gm.grid if gm is not None else eqn.params.get("grid", ())
            steps = math.prod(grid) if grid else 1
            k = state["pallas"]
            state["pallas"] += 1
            lg_ring = 1
            if k < len(launches):
                lg_ring = launches[k].launch_geometry().ring
            _walk(getattr(inner, "jaxpr", inner), mult * steps, lg_ring,
                  launches, state)
        elif prim == "cond":
            # pl.when lowers to cond; inside a ringed pallas body the
            # taken branch fires once per cell = steps / ring.  The
            # untaken branch is empty, so summing branches stays exact.
            for br in eqn.params["branches"]:
                _walk(getattr(br, "jaxpr", br), mult // ring, 1,
                      launches, state)
        elif prim == "dot_general":
            if _is_float(eqn.outvars[0].aval):
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                lshape = eqn.invars[0].aval.shape
                rshape = eqn.invars[1].aval.shape
                b = math.prod(lshape[i] for i in lb)
                k_dim = math.prod(lshape[i] for i in lc)
                m = math.prod(lshape) // max(b * k_dim, 1)
                n = math.prod(rshape) // max(
                    math.prod(rshape[i] for i in rb) * k_dim, 1)
                state["dot"] += mult * 2 * b * m * n * k_dim
        elif prim in _ELEMENTWISE:
            if _is_float(eqn.outvars[0].aval):
                state["vector"] += mult * math.prod(eqn.outvars[0].aval.shape)
        else:
            for v in eqn.params.values():
                for j in _jaxprs_in(v):
                    _walk(j, mult, ring, launches, state)


def _jaxprs_in(v):
    """Jaxpr-valued params (pjit bodies etc.), unwrapped."""
    from jax.extend import core as jcore
    vals = v if isinstance(v, (tuple, list)) else (v,)
    out = []
    for item in vals:
        item = getattr(item, "jaxpr", item)
        if isinstance(item, jcore.Jaxpr):
            out.append(item)
    return out


# --------------------------------------------------------------------------
# Plain-Python mirrors of the kernel compute walks
# --------------------------------------------------------------------------
def _region(lg):
    """Shape of the f32 region ``compute`` receives (DESIGN.md §9/§10):
    the scratch read window of a ring, or the flat block."""
    if lg.scratch_shape is not None:
        return tuple(hi - lo for lo, hi in lg.read_bounds)
    return lg.in_block


def mirror_launch_flops(launch, lg):
    """(vector_flops, dot_flops, executed_points) of one launch, walked
    exactly as ``_stencil_steps`` / ``_banded_step`` trace: shrinking
    regions per inner step, per-tap mul+add on VPU, per-chunk-per-offset
    dot + accumulate add on MXU.  Totals scaled by the launch's cells."""
    w = np.asarray(launch.weights)
    r = launch.radius
    wrap = lg.kind not in ("coltiled", "slab_coltiled")
    cur = list(_region(lg))
    vec = dot = points = 0
    for _ in range(launch.t_inner):
        n = cur[-1] if wrap else cur[-1] - 2 * r
        lead = [cur[i] - (w.shape[i] - 1) for i in range(w.ndim - 1)]
        m = math.prod(lead)
        points += m * n
        if launch.engine == "matmul":
            start = 0
            while start < n:
                wcur = min(launch.tile_n, n - start)
                dot += launch.n_offsets * 2 * m * wcur * (wcur + 2 * r)
                vec += launch.n_offsets * m * wcur    # acc = acc + dot
                start += wcur
        elif launch.engine == "sparse_matmul":
            # Compacted contraction (DESIGN.md §14): full-width chunks
            # contract only the packed rows -- summed over offsets that
            # is exactly 2*m*tile_n*bands_shape[0] (= S * dense) -- while
            # remainder chunks re-expand to the dense band prefix to stay
            # graph-identical to the dense path (bitwise equality).
            start = 0
            while start < n:
                wcur = min(launch.tile_n, n - start)
                if wcur == launch.tile_n:
                    dot += 2 * m * wcur * launch.bands_shape[0]
                else:
                    dot += launch.n_offsets * 2 * m * wcur * (wcur + 2 * r)
                vec += launch.n_offsets * m * wcur    # acc = acc + dot
                start += wcur
        else:
            nnz = int(np.count_nonzero(w))
            vec += 2 * nnz * m * n                    # per tap: mul + add
        cur = lead + [n]
    return vec * lg.cells, dot * lg.cells, points * lg.cells


def _sparse_dense_dots(launch, lg):
    """(compacted, dense) MXU FLOPs of one sparse launch.

    The compacted expectation is derived from ``bands_shape`` alone --
    independent of the ``band_lo``/``band_spans`` gather metadata -- so a
    mis-compacted packed operand surfaces as a FLOP mismatch against the
    traced jaxpr rather than silently passing.  The dense count is the
    same walk with the full ``wcur + 2r`` contraction depth; on widths
    divisible by tile_n their ratio is exactly the kept-row fraction S."""
    w = np.asarray(launch.weights)
    r = launch.radius
    wrap = lg.kind not in ("coltiled", "slab_coltiled")
    cur = list(_region(lg))
    s_dot = d_dot = 0
    for _ in range(launch.t_inner):
        n = cur[-1] if wrap else cur[-1] - 2 * r
        lead = [cur[i] - (w.shape[i] - 1) for i in range(w.ndim - 1)]
        m = math.prod(lead)
        start = 0
        while start < n:
            wcur = min(launch.tile_n, n - start)
            d = launch.n_offsets * 2 * m * wcur * (wcur + 2 * r)
            s_dot += 2 * m * wcur * launch.bands_shape[0] \
                if wcur == launch.tile_n else d
            d_dot += d
            start += wcur
        cur = lead + [n]
    return s_dot * lg.cells, d_dot * lg.cells


# --------------------------------------------------------------------------
# Checks
# --------------------------------------------------------------------------
def audit_flops(ctx, audit_spec, run) -> List[AuditCheck]:
    """FLOP checks for one backend's audited launches against its traced
    runner ``run`` (the registry-built callable, pre-jit)."""
    import jax
    import jax.numpy as jnp
    from repro.core import perfmodel as pm
    from repro.kernels.stencil_matmul import band_sparsity

    checks: List[AuditCheck] = []
    launches = audit_spec.launches
    x = jnp.zeros(ctx.grid_shape, ctx.dtype)
    try:
        jx = jax.make_jaxpr(run)(x)
    except Exception as e:  # pragma: no cover - tracing never executes
        checks.append(AuditCheck(
            "flops/structural", False, actual=repr(e),
            detail="plan runner failed to trace"))
        return checks

    traced_vec, traced_dot, n_pallas = count_jaxpr_flops(jx, launches)
    mirror_vec = mirror_dot = 0
    per_launch = []
    for launch in launches:
        lg = launch.launch_geometry()
        v, d, p = mirror_launch_flops(launch, lg)
        mirror_vec += v
        mirror_dot += d
        per_launch.append((launch, lg, p))
    structural_ok = (traced_vec == mirror_vec and traced_dot == mirror_dot
                     and n_pallas == len(launches))
    checks.append(AuditCheck(
        "flops/structural", structural_ok,
        expected={"vector": mirror_vec, "dot": mirror_dot,
                  "pallas_calls": len(launches)},
        actual={"vector": traced_vec, "dot": traced_dot,
                "pallas_calls": n_pallas},
        detail="jaxpr-counted FLOPs vs the kernel-walk mirror over the "
               "audited launch geometries"))

    # ---- sparse compaction: traced MXU FLOPs == packed-row expectation
    if launches and all(l.engine == "sparse_matmul" for l in launches):
        expected_dot = dense_dot = 0
        for launch, lg, _ in per_launch:
            s_d, d_d = _sparse_dense_dots(launch, lg)
            expected_dot += s_d
            dense_dot += d_d
        checks.append(AuditCheck(
            "flops/sparse-compaction",
            traced_dot == expected_dot and expected_dot <= dense_dot,
            expected={"dot": expected_dot, "dense_dot": dense_dot},
            actual={"dot": traced_dot,
                    "kept": traced_dot / dense_dot if dense_dot else None},
            detail="traced MXU FLOPs of the compacted contraction must "
                   "equal the packed-row expectation (S * dense on "
                   "tile-aligned widths), integer-exact, and never exceed "
                   "the dense count"))

    spec, t = ctx.spec, ctx.t
    base_nnz = int(np.count_nonzero(np.asarray(ctx.weights)))
    canonical = base_nnz == spec.num_points

    # ---- alpha: fused tap count vs the paper's fusion model -----------
    fused = [l for l in launches
             if l.t_inner == 1 and l.radius == t * spec.radius and t > 1]
    if fused and launches[0].engine == "matmul":
        if canonical:
            wf_nnz = int(np.count_nonzero(np.asarray(fused[0].weights)))
            audited_alpha = wf_nnz / (t * base_nnz)
            model_alpha = pm.fusion_alpha(spec, t)
            checks.append(AuditCheck(
                "flops/alpha",
                math.isclose(audited_alpha, model_alpha, rel_tol=1e-9),
                expected=model_alpha, actual=audited_alpha,
                detail="nnz(fused)/ (t * nnz(base)) vs fusion_alpha"))
        else:
            checks.append(AuditCheck(
                "flops/alpha", True, skipped=True,
                detail="base weights do not realize the spec tap set; "
                       "alpha is a spec-level model term"))

    # ---- beta: audited halo recompute of t-step in-VMEM launches ------
    for launch, lg, points in per_launch:
        if launch.t_inner <= 1:
            continue
        geom = launch.geom
        audited_beta = points / (launch.t_inner * lg.cells
                                 * math.prod(lg.out_block))
        model_beta = pm.reuse_beta(
            spec, launch.t_inner, strip_m=geom.strip_m,
            z_slab=geom.z_slab if geom.dim == 3 else None,
            w_tile=geom.w_tile or None)
        checks.append(AuditCheck(
            "flops/beta",
            math.isclose(audited_beta, model_beta, rel_tol=1e-9),
            expected=model_beta, actual=audited_beta,
            detail=f"executed points per output point, {launch.engine} "
                   f"t_inner={launch.t_inner} vs reuse_beta"))

        # ---- full matrix-reuse FLOP model on the MXU reuse backends ----
        if launch.engine in ("matmul", "sparse_matmul") and canonical:
            s_meas = band_sparsity(np.asarray(launch.weights),
                                   launch.tile_n)
            audited_per_point = mirror_dot / (lg.cells
                                              * math.prod(lg.out_block))
            model_per_point = (model_beta / s_meas) \
                * launch.t_inner * 2 * spec.num_points
            if launch.engine == "sparse_matmul":
                # The compacted launch executes the kept-row fraction of
                # the dense model (DESIGN.md §14).
                model_per_point *= launch.bands_shape[0] / (
                    launch.n_offsets * (launch.tile_n + 2 * launch.radius))
            checks.append(AuditCheck(
                "flops/matrix-reuse-model",
                math.isclose(audited_per_point, model_per_point,
                             rel_tol=5e-2),
                expected=model_per_point, actual=audited_per_point,
                detail="audited MXU FLOPs per output point vs "
                       "(beta/S) * flops_vector (* kept-row fraction for "
                       "the compacted launch), S measured from the built "
                       "bands"))
    return checks
