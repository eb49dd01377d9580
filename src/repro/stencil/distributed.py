"""Distributed stencil runtime: spatial decomposition + halo exchange.

The grid is sharded spatially across mesh axes; each step (or fused group of
``t`` steps) exchanges halos with neighbor shards via ``lax.ppermute`` rings
(periodic global boundary == ring wrap), then applies the stencil locally.

Three execution modes mirror the paper's fusion taxonomy at cluster scale:

  * ``stepwise``: halo depth ``r``, one exchange per time step -- the
    conventional scheme (communication-bound at scale).
  * ``fused``:    halo depth ``t*r``, ONE exchange per ``t`` steps; the halo
    overlap is recomputed locally.  This is temporal fusion's redundancy
    factor alpha materialized as *communication amortization*: per-step halo
    bytes drop by ~t at the cost of O((t*r)^2) redundant edge compute --
    exactly the compute/traffic trade the paper's model prices.
  * ``overlap``:  stepwise's exchange schedule, double-buffered
    (DESIGN.md §15): each step ISSUES the ppermute pair first, computes
    the interior rows -- which depend only on shard-local data -- while
    the halo slabs are in flight, then finishes the two ``r``-deep edge
    strips from the received slabs.  Bit-for-bit equal to ``stepwise``
    (identical per-cell tap order); the win is that interior compute is
    no longer serialized behind the exchange latency.  Requires exactly
    one sharded dim; :data:`overlap_stats` counts the trace-time
    interleave and :func:`overlap_independence_report` proves, on the
    jaxpr, that the interior never consumes a ppermute result.

Boundaries (DESIGN.md §15): ``boundary`` names the per-axis global edge
mode.  ``periodic`` is the historical ring wrap, bit for bit; non-periodic
axes synthesize their halos locally -- unsharded dims pad with the mode,
sharded dims exchange as usual and the FIRST/LAST shards overwrite their
out-of-domain halo slab with the mode's fill (``jax.lax.axis_index``
masks).  Because every mode re-applies per exchange, ``stepwise`` and
``overlap`` match the per-step re-padding oracle at any fusion depth;
``fused`` would bake step-1 boundary values into ``t`` steps, so it
rejects non-periodic specs.

``local_apply`` is pluggable so the local update can run on the Pallas VPU
or MXU kernels (see repro.kernels.ops) -- the selector chooses per the
paper's criteria.

Trace scopes (``repro.core.trace``): the halo exchange and its assembly
(:func:`_extend`) run under ``repro.dist.exchange`` and the Pallas local
apply (row pad, kernel call, interior slice) under ``repro.dist.local``;
both reach the ``op_name`` of every device op they cover.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import trace

from .boundary import PAD_MODE, is_periodic, resolve_boundary
from .reference import _offsets


def apply_stencil_valid(xp: jax.Array, weights: jax.Array,
                        support=None) -> jax.Array:
    """Stencil on a halo-extended block: output shape = input - 2r per dim.

    ``support``: optional host-side bool mask of the kernel's nonzero
    structure.  Tap VALUES stay dynamic (runtime weights, paper §5.1
    convention) but structurally-zero taps are skipped at trace time --
    a 3.8x compute cut for Star-2D3R vs iterating its enclosing box
    (EXPERIMENTS.md §Perf, stencil cell)."""
    import numpy as np
    dim = weights.ndim
    radius = (weights.shape[0] - 1) // 2
    w = jnp.asarray(weights, xp.dtype)
    out_shape = tuple(n - 2 * radius for n in xp.shape)
    y = jnp.zeros(out_shape, xp.dtype)
    for off in _offsets(radius, dim):
        widx = tuple(o + radius for o in off)
        if support is not None and not bool(np.asarray(support)[widx]):
            continue
        sl = tuple(slice(radius + o, radius + o + n) for o, n in zip(off, out_shape))
        y = y + w[widx] * xp[sl]
    return y


def _halo_exchange_dim(x: jax.Array, dim: int, radius: int, axis_name: str) -> jax.Array:
    """Extend ``x`` by ``radius`` on both sides of ``dim`` with neighbor data.

    Periodic ring: shard i receives its left halo from shard i-1's right edge
    and its right halo from shard i+1's left edge.
    """
    n = jax.lax.psum(1, axis_name)

    def edge(lo, hi):
        idx = [slice(None)] * x.ndim
        idx[dim] = slice(lo, hi)
        return x[tuple(idx)]

    right_edge = edge(x.shape[dim] - radius, x.shape[dim])  # goes to right neighbor's left halo
    left_edge = edge(0, radius)                             # goes to left neighbor's right halo

    fwd = [(i, (i + 1) % n) for i in range(n)]   # i -> i+1
    bwd = [(i, (i - 1) % n) for i in range(n)]   # i -> i-1
    left_halo = jax.lax.ppermute(right_edge, axis_name, fwd)
    right_halo = jax.lax.ppermute(left_edge, axis_name, bwd)
    return jnp.concatenate([left_halo, x, right_halo], axis=dim)


def _dim_fill(x: jax.Array, dim: int, h: int, mode: str, lo: bool) -> jax.Array:
    """The ``h``-deep boundary fill of one side of ``dim``, synthesized
    from the (unextended) shard-local rows of ``x`` -- what an edge shard
    writes where an interior shard keeps its received halo slab."""
    def sl(a, b):
        s = [slice(None)] * x.ndim
        s[dim] = slice(a, b)
        return tuple(s)

    m = x.shape[dim]
    if mode == "zero":
        return jnp.zeros_like(x[sl(0, h)])
    if mode == "replicate":
        reps = [1] * x.ndim
        reps[dim] = h
        return jnp.tile(x[sl(0, 1) if lo else sl(m - 1, m)], reps)
    if mode == "reflect":
        src = x[sl(1, h + 1)] if lo else x[sl(m - h - 1, m - 1)]
        return jnp.flip(src, axis=dim)
    raise ValueError(f"unknown boundary mode {mode!r}")


def _mask_edge_shards(xe: jax.Array, dim: int, radius: int, mode: str,
                      axis_name: str) -> jax.Array:
    """Overwrite the FIRST/LAST shards' out-of-domain halo slabs of the
    exchanged dim with the mode's fill; interior shards keep their true
    received slabs (``jnp.where`` on ``axis_index`` masks)."""
    def sl(a, b):
        s = [slice(None)] * xe.ndim
        s[dim] = slice(a, b)
        return tuple(s)

    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.psum(1, axis_name)
    m = xe.shape[dim]
    core = xe[sl(radius, m - radius)]
    lo = jnp.where(idx == 0, _dim_fill(core, dim, radius, mode, True),
                   xe[sl(0, radius)])
    hi = jnp.where(idx == n - 1, _dim_fill(core, dim, radius, mode, False),
                   xe[sl(m - radius, m)])
    return jnp.concatenate([lo, core, hi], axis=dim)


def _extend(x: jax.Array, radius: int, dim_axis_names: Sequence[Optional[str]],
            modes: Optional[Sequence[str]] = None) -> jax.Array:
    """Halo-extend every dim: ppermute when sharded, mode pad when local.

    ``modes`` (DESIGN.md §15) names each dim's global boundary; ``None``
    = all periodic, the historical graph bit for bit.  Non-periodic
    sharded dims still run the full ring exchange (every shard executes
    the same collective), then the edge shards mask their out-of-domain
    slab with the mode's locally-synthesized fill.
    """
    # Fault-injection hook (repro.testing.faults): models a failed
    # ppermute ring at trace time.  No-op unless armed.
    from repro.testing.faults import maybe_fail
    maybe_fail("halo")
    if modes is None:
        modes = ("periodic",) * len(dim_axis_names)
    with jax.named_scope(trace.DIST_EXCHANGE):
        for dim, axis_name in enumerate(dim_axis_names):
            if axis_name is None:
                pad = [(0, 0)] * x.ndim
                pad[dim] = (radius, radius)
                x = jnp.pad(x, pad, mode=PAD_MODE[modes[dim]])
            else:
                x = _halo_exchange_dim(x, dim, radius, axis_name)
                if modes[dim] != "periodic":
                    x = _mask_edge_shards(x, dim, radius, modes[dim],
                                          axis_name)
    return x


#: Trace-time interleave counters of the ``overlap`` stepper.  Python
#: increments these as the step TRACES, so they prove code structure:
#: ``interior_before_recv_consumed`` counts steps whose interior update
#: was fully constructed before any received halo slab was touched --
#: nonzero means the interior is not serialized behind the exchange.
#: Reset with :func:`reset_overlap_stats`; snapshot with
#: :func:`overlap_stats`.
_OVERLAP_STATS = {"overlap_steps": 0, "exchanges_issued": 0,
                  "interior_launches": 0, "edge_launches": 0,
                  "interior_before_recv_consumed": 0}


def overlap_stats() -> dict:
    """Snapshot of the overlap stepper's trace-time interleave counters."""
    return dict(_OVERLAP_STATS)


def reset_overlap_stats() -> None:
    for k in _OVERLAP_STATS:
        _OVERLAP_STATS[k] = 0


def _overlap_step(x: jax.Array, w, radius: int,
                  dim_axis_names: Sequence[Optional[str]],
                  modes: Sequence[str], sd: int, local_apply) -> jax.Array:
    """One double-buffered exchange/compute step on one shard (DESIGN.md
    §15).  Issue the sharded dim's ppermute pair FIRST, pad the unsharded
    dims, run the interior update (no recv dependence) while the slabs
    are in flight, then the two ``r``-deep edge strips from the received
    slabs, and reassemble.  Bit-for-bit equal to ``stepwise``: every
    output cell sees the identical tap values in the identical order --
    only the schedule changes.
    """
    from repro.testing.faults import maybe_fail
    maybe_fail("halo")
    axis_name = dim_axis_names[sd]

    def sl(a, b):
        s = [slice(None)] * x.ndim
        s[sd] = slice(a, b)
        return tuple(s)

    # 1. Issue the exchange: edge slabs leave now; the recv slabs are not
    #    consumed until step 3.  (Slab values are independent of the
    #    unsharded-dim pads, which commute across axes -- padding the
    #    received slab below reproduces stepwise's layout bitwise.)
    n = jax.lax.psum(1, axis_name)
    m = x.shape[sd]
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    recv_lo = jax.lax.ppermute(x[sl(m - radius, m)], axis_name, fwd)
    recv_hi = jax.lax.ppermute(x[sl(0, radius)], axis_name, bwd)
    _OVERLAP_STATS["exchanges_issued"] += 1

    def pad_unsharded(arr):
        for dim, ax in enumerate(dim_axis_names):
            if ax is not None:
                continue
            pad = [(0, 0)] * arr.ndim
            pad[dim] = (radius, radius)
            arr = jnp.pad(arr, pad, mode=PAD_MODE[modes[dim]])
        return arr

    # 2. Interior: shard-local data only.  ``local_apply`` trims radius
    #    from EVERY dim, which along the unextended sharded dim is
    #    exactly the rows whose support would need the halo.
    x1 = pad_unsharded(x)
    interior = local_apply(x1, w, 1)
    _OVERLAP_STATS["interior_launches"] += 1
    _OVERLAP_STATS["interior_before_recv_consumed"] += 1
    _OVERLAP_STATS["overlap_steps"] += 1

    # 3. Edge strips: first touch of the received slabs.  Edge shards of
    #    a non-periodic dim overwrite the out-of-domain slab with the
    #    mode's locally-synthesized fill.
    lo_halo, hi_halo = pad_unsharded(recv_lo), pad_unsharded(recv_hi)
    if modes[sd] != "periodic":
        idx = jax.lax.axis_index(axis_name)
        lo_halo = jnp.where(idx == 0,
                            _dim_fill(x1, sd, radius, modes[sd], True),
                            lo_halo)
        hi_halo = jnp.where(idx == n - 1,
                            _dim_fill(x1, sd, radius, modes[sd], False),
                            hi_halo)
    m1 = x1.shape[sd]
    lo_in = jnp.concatenate([lo_halo, x1[sl(0, 2 * radius)]], axis=sd)
    hi_in = jnp.concatenate([x1[sl(m1 - 2 * radius, m1)], hi_halo], axis=sd)
    lo_out = local_apply(lo_in, w, 1)
    hi_out = local_apply(hi_in, w, 1)
    _OVERLAP_STATS["edge_launches"] += 2
    return jnp.concatenate([lo_out, interior, hi_out], axis=sd)


def _walk_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every jaxpr nested in its eqn params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else (val,)
            for v in vals:
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _walk_jaxprs(inner)


def overlap_independence_report(mesh, dim_axis_names, weights, x,
                                boundary=None,
                                local_apply: Optional[Callable] = None) -> dict:
    """Prove, on the traced jaxpr, that the overlap stepper's interior
    update is independent of the in-flight exchange.

    Traces a single overlap step and taints every ``ppermute`` output
    plus its transitive consumers.  The step's output reassembly is a
    3-operand concatenate ``[lo_out, interior, hi_out]``; the proof is
    that its pattern is tainted/UNTAINTED/tainted -- the interior
    operand never touched a received slab, so XLA is free to schedule
    it against the collective's latency.  Counted in
    ``reassembly_concats``; ``interior_independent`` is the verdict.
    """
    step = make_distributed_stepper(
        mesh, dim_axis_names, weights, t=1, mode="overlap",
        local_apply=local_apply, boundary=boundary)
    closed = jax.make_jaxpr(step)(x)
    ppermutes = mixed = reassembly = 0
    for jpr in _walk_jaxprs(closed.jaxpr):
        if not any(e.primitive.name == "ppermute" for e in jpr.eqns):
            continue
        tainted = set()
        for eqn in jpr.eqns:
            if eqn.primitive.name == "ppermute":
                ppermutes += 1
                tainted.update(eqn.outvars)
                continue
            # Literals carry .val; true vars do not.
            flags = [v in tainted for v in eqn.invars
                     if not hasattr(v, "val")]
            if eqn.primitive.name == "concatenate" and flags:
                if any(flags) and not all(flags):
                    mixed += 1
                    if len(flags) == 3 and flags[0] and flags[2] \
                            and not flags[1]:
                        reassembly += 1
            if any(flags):
                tainted.update(eqn.outvars)
    return {
        "ppermute_eqns": ppermutes,
        "mixed_concats": mixed,
        "reassembly_concats": reassembly,
        "interior_independent": ppermutes >= 2 and reassembly >= 1,
    }


def make_distributed_stepper(
    mesh: Mesh,
    dim_axis_names: Sequence[Optional[str]],
    weights,
    t: int = 1,
    mode: str = "stepwise",
    local_apply: Optional[Callable] = None,
    boundary=None,
) -> Callable:
    """Build a jit-able ``t``-step distributed stencil update.

    Args:
      mesh: the device mesh.
      dim_axis_names: per grid-dim mesh axis name (None = unsharded dim).
      weights: dense ``(2r+1)^d`` base kernel.
      t: number of time steps per invocation.
      mode: "stepwise" (t exchanges, halo r), "fused" (1 exchange, halo
        t*r) or "overlap" (stepwise's schedule with the interior update
        overlapping the in-flight exchange; requires exactly one sharded
        dim).
      local_apply: optional ``f(x_extended, weights, t) -> block`` override
        running the local update (e.g. a Pallas kernel path).  It receives a
        block extended by ``t*r`` (fused) or ``r`` (stepwise/overlap,
        called t times with t=1) and must return the valid interior.
      boundary: per-axis global boundary modes (DESIGN.md §15); ``None``
        = all periodic, the historical graph bit for bit.  ``fused``
        rejects non-periodic specs: its pad-once halo would bake step-1
        boundary values into ``t`` steps, diverging from the per-step
        re-padding oracle.

    Returns a function ``step(x) -> x'`` operating on the globally-sharded
    array; wrap in ``jax.jit`` with matching shardings.
    """
    import numpy as _np
    radius = (jnp.asarray(weights).shape[0] - 1) // 2
    support = _np.asarray(weights) != 0          # static structure
    w = jnp.asarray(weights)
    spec = P(*dim_axis_names)
    modes = resolve_boundary(boundary, len(dim_axis_names))

    if local_apply is None:
        def local_apply(xp, w_, steps):
            for i in range(steps):
                xp = apply_stencil_valid(xp, w_, support=support)
            return xp

    if mode == "stepwise":
        def shard_fn(x):
            for _ in range(t):
                xe = _extend(x, radius, dim_axis_names, modes)
                x = local_apply(xe, w, 1)
            return x
    elif mode == "fused":
        if not is_periodic(modes):
            raise ValueError(
                "fused halo exchange cannot honor non-periodic boundaries "
                f"(boundary={modes!r}): one depth-t*r exchange supplies "
                "step-1 boundary values to all t steps, but every mode "
                "re-applies per step (DESIGN.md §15); use mode='stepwise' "
                "or 'overlap'")
        def shard_fn(x):
            xe = _extend(x, radius * t, dim_axis_names)
            return local_apply(xe, w, t)
    elif mode == "overlap":
        sharded = [d for d, ax in enumerate(dim_axis_names)
                   if ax is not None]
        if len(sharded) != 1:
            raise ValueError(
                "overlap mode interleaves ONE exchange with the interior "
                f"update and needs exactly one sharded dim, got "
                f"shard_spec {tuple(dim_axis_names)!r}; shard a single "
                "dim or use mode='stepwise'")
        sd = sharded[0]

        def shard_fn(x):
            for _ in range(t):
                x = _overlap_step(x, w, radius, dim_axis_names, modes,
                                  sd, local_apply)
                # Pin each step's compilation to the single-step form:
                # without the barrier XLA fuses the edge strips of step
                # k into the interior of step k+1 with different FMA
                # contraction, breaking the bitwise == stepwise contract
                # (and pessimizing the fused t-step graph).
                x = jax.lax.optimization_barrier(x)
            return x
    else:
        raise ValueError(f"unknown mode {mode!r}")

    return jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec,),
                         out_specs=spec, check_vma=False)


def pallas_local_apply(
    backend: str = "fused_matmul_reuse",
    interpret: Optional[bool] = None,
    tile_m: Optional[int] = None,
    tile_n: Optional[int] = None,
    h_block: Optional[int] = None,
    z_slab: Optional[int] = None,
    z_block: Optional[int] = None,
    w_tile: Optional[int] = None,
    w_block: Optional[int] = None,
    guard: bool = False,
) -> Callable:
    """Build a ``local_apply`` plug-in running the strip-mined Pallas kernels.

    The returned callable matches ``make_distributed_stepper``'s contract:
    it receives each shard's halo-extended block (depth ``steps * r``, any
    grid rank the kernels support -- 1D, 2D or 3D-sharded meshes) and
    returns the valid interior.  The kernel's own modulo-wrap periodicity
    is harmless because the halo ring it wraps into is discarded.

    ``backend`` is any registered backend name
    (``repro.kernels.registered_backends()``) -- notably
    ``"fused_matmul_reuse"``, which keeps all t intermediates in VMEM so the
    shard pays HBM traffic once per exchange, not per step.  Execution goes
    through the plan cache (``repro.kernels.plan``): the per-shard plan is
    built once per (block shape, depth) signature and reused across steps
    and traces.  By default (``tile_m=None``) the extended block's row
    count is zero-padded up to the sublane tile and the geometry is
    auto-sized like any plan's: an extended extent (local + 2*halo) is
    rarely tile-aligned, and the padding rows only feed the discarded
    halo ring.  Pass explicit tiles to pin the geometry instead (the
    block is then used as is).  ``h_block``/``z_block`` select the halo
    block heights of the substrate (``None`` = auto) -- the ring's modulo
    wrap is harmless here.  ``w_tile``/``w_block`` select the column-tiled
    W substrate (DESIGN.md §10) for W-sharded meshes whose local width
    still exceeds VMEM (``None`` = auto: full width whenever it fits the
    budget); the column walk's wrap is as harmless as the row wrap -- it
    only pollutes the discarded halo ring.

    ``guard=True`` builds the per-shard plan through the guarded
    execution layer (``repro.kernels.guard``, DESIGN.md §11): a kernel
    failure walks the degradation ladder instead of crashing the
    stepper.  The ladder is a pure function of the plan signature and
    process env -- every shard sees the same (block shape, depth, env)
    signature, so all shards land on the SAME fallback rung without
    communicating.
    """
    import numpy as _np

    def local_apply(xe, w, steps):
        with jax.named_scope(trace.DIST_LOCAL):
            return _apply(xe, w, steps)

    def _apply(xe, w, steps):
        from repro.kernels.common import sublane_tile
        from repro.kernels.plan import stencil_plan  # deferred: avoid cycle

        wn = _np.asarray(w)
        radius = (wn.shape[0] - 1) // 2
        h = steps * radius
        kw = dict(tile_n=tile_n)
        xp = xe
        if xe.ndim >= 2:
            kw.update(tile_m=tile_m, h_block=h_block, w_tile=w_tile,
                      w_block=w_block)
            pad = -xe.shape[-2] % sublane_tile(xe.dtype.itemsize)
            if tile_m is None and pad:
                rows = [(0, 0)] * xe.ndim
                rows[-2] = (0, pad)
                xp = jnp.pad(xe, rows)
        if xe.ndim == 3:
            kw.update(z_slab=z_slab, z_block=z_block)
        if guard:
            from repro.kernels.guard import guarded_stencil_plan
            plan = guarded_stencil_plan(
                wn, xp.shape, xp.dtype, steps, backend=backend,
                interpret=interpret, **kw)
        else:
            plan = stencil_plan(
                wn, xp.shape, xp.dtype, steps, backend=backend,
                interpret=interpret, **kw,
            )
        full = plan(xp)
        return full[tuple(slice(h, n - h) for n in xe.shape)]

    return local_apply


def halo_bytes_per_step(
    local_shape: Sequence[int],
    dim_axis_names: Sequence[Optional[str]],
    radius: int,
    t: int,
    mode: str,
    dtype_bytes: int,
) -> int:
    """Analytic per-t-steps halo traffic (both directions, all sharded dims).

    Used by benchmarks to show the fused mode's communication amortization.
    ``overlap`` moves the same depth-r slabs on the same t-exchange
    schedule as ``stepwise`` -- its win is latency hiding, not fewer
    bytes -- except the slabs are sliced from the UNEXTENDED shard, so
    their faces skip the earlier-dim halo growth stepwise pays.
    """
    h = radius if mode in ("stepwise", "overlap") else radius * t
    exchanges = t if mode in ("stepwise", "overlap") else 1
    total = 0
    shape = list(local_shape)
    for dim, ax in enumerate(dim_axis_names):
        if ax is None:
            continue
        face = 1
        for d2, n in enumerate(shape):
            if d2 != dim:
                # ``_extend`` processes dims in order, so by the time dim is
                # exchanged EVERY earlier dim is already halo-extended --
                # whether by ppermute (sharded) or periodic pad (local) --
                # and the exchanged face spans n + 2h along it.  ``overlap``
                # issues its slabs before any padding, so faces stay bare.
                face *= n + (2 * h if d2 < dim and mode != "overlap" else 0)
        total += 2 * h * face * dtype_bytes
    return total * exchanges
