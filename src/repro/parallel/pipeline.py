"""GPipe-style pipeline parallelism over a mesh axis (normally ``pod``).

The model's layer stack is split into S contiguous stages (S = size of the
pipeline axis).  Each microbatch flows stage->stage via ``ppermute``; the
schedule is the classic GPipe fill-drain loop expressed as one lax.scan of
(M + S - 1) ticks, running under shard_map so every stage executes the
same program on its own parameter shard (SPMD-friendly: no per-stage
programs to compile).

Cost model (surfaces in the §Roofline collective term): per tick one
boundary activation crosses the pod link; bubble fraction = (S-1)/(M+S-1).

This is the optional large-scale alternative to folding ``pod`` into data
parallelism.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipelined_forward(
    layer_fn: Callable,          # (layer_params, x) -> x  (one layer)
    stage_params,                # params with leading dim L/S (this stage's)
    x_microbatches,              # (M, mb, ...) microbatched inputs
    mesh: Mesh,
    axis: str = "pod",
):
    """Run the layer stack over all microbatches through the pipeline.

    Called INSIDE shard_map (axis present).  Returns (M, mb, ...) outputs
    (valid on the LAST stage; other stages hold garbage -- caller
    ppermutes/psums as needed).
    """
    S = jax.lax.psum(1, axis)
    stage = jax.lax.axis_index(axis)
    M = x_microbatches.shape[0]
    ticks = M + S - 1

    def stage_apply(carry_x):
        def body(x, lp):
            return layer_fn(lp, x), None
        y, _ = jax.lax.scan(body, carry_x, stage_params)
        return y

    buf = jnp.zeros_like(x_microbatches)         # output collector
    state = jnp.zeros_like(x_microbatches[0])    # in-flight activation

    def tick(carry, t):
        state, buf = carry
        # stage 0 ingests microbatch t (if valid)
        mb_idx = jnp.clip(t, 0, M - 1)
        injected = jnp.where(
            (stage == 0) & (t < M),
            x_microbatches[mb_idx],
            state,
        )
        out = stage_apply(injected)
        # last stage retires microbatch t - (S-1)
        ret_idx = jnp.clip(t - (S - 1), 0, M - 1)
        buf = jnp.where(
            (stage == S - 1) & (t >= S - 1),
            buf.at[ret_idx].set(out),
            buf,
        )
        # shift boundary activations to the next stage
        perm = [(i, (i + 1) % S) for i in range(S)]
        state = jax.lax.ppermute(out, axis, perm)
        return (state, buf), None

    (_, buf), _ = jax.lax.scan(tick, (state, buf), jnp.arange(ticks))
    return buf


def make_pipelined_step(layer_fn, n_layers: int, mesh: Mesh,
                        axis: str = "pod", microbatches: int = 4):
    """Build f(stacked_params, x) running layers split over ``axis``.

    stacked_params leaves have leading dim n_layers; x is (B, ...).  The
    batch is cut into ``microbatches`` along dim 0.
    """
    S = mesh.shape[axis]
    if n_layers % S:
        raise ValueError(f"{n_layers} layers not divisible into {S} stages")
    per_stage = n_layers // S

    def split_stage(params):
        # executed inside shard_map: leading L dim is sharded by in_specs
        return params

    def fn(params, x):
        B = x.shape[0]
        mb = B // microbatches
        xm = x.reshape(microbatches, mb, *x.shape[1:])
        out = pipelined_forward(layer_fn, params, xm, mesh, axis)
        out = out.reshape(B, *x.shape[1:])
        # broadcast the last stage's result to all stages (masked psum)
        stage = jax.lax.axis_index(axis)
        masked = jnp.where(stage == S - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(masked, axis) if S > 1 else out

    in_specs = (P(axis), P())        # params layer-sharded; x replicated
    out_specs = P()
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
