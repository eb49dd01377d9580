"""Distributed stencil with halo exchange on a 2D device mesh.

Shows the paper's temporal-fusion trade at cluster scale: fused execution
does ONE deep halo exchange per t steps (vs t shallow ones), paying with
redundant halo compute -- the distributed alpha.

The mesh spans every device there is: the chips of a TPU host (2x2 on
four chips), or 8 CPU devices faked by the CPU backend:

    PYTHONPATH=src python examples/distributed_stencil.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hlo_cost import analyze_hlo
from repro.kernels import stencil_plan
from repro.stencil import StencilSpec, make_weights
from repro.stencil.reference import apply_stencil_steps


def main():
    # Only the CPU backend reads this, and only before it first starts.
    jax.config.update("jax_num_cpu_devices", 8)
    devices = jax.devices()
    cols = 2 if len(devices) % 2 == 0 else 1
    mesh = Mesh(np.array(devices).reshape(-1, cols), ("x", "y"))
    spec = StencilSpec("box", 2, 1)
    w = make_weights(spec, seed=0)
    t = 4
    n = 512
    x = np.random.default_rng(0).normal(size=(n, n)).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("x", "y")))
    print(f"domain {n}x{n} over mesh {dict(mesh.shape)}; {spec.name}, t={t}")

    ref = apply_stencil_steps(jnp.asarray(x), jnp.asarray(w), t)
    for mode in ("stepwise", "fused"):
        # one plan object drives local AND distributed execution: mesh +
        # shard_spec route it through the halo-exchange stepper, with the
        # exchange schedule planned once at build time (plan.halo_plan)
        plan = stencil_plan(w, (n, n), np.float32, t, mesh=mesh,
                            shard_spec=("x", "y"), dist_mode=mode,
                            backend="reference")
        y = plan(xs)
        err = float(jnp.abs(y - ref).max())
        pc = analyze_hlo(plan.fn.lower(
            jax.ShapeDtypeStruct(x.shape, jnp.float32)).compile().as_text())
        rounds = pc.coll_counts.get("collective-permute", 0)
        hb = plan.halo_plan["halo_bytes_per_call"]
        print(f"  {mode:9s}: max|err|={err:.1e}  collective-permutes={rounds:.0f}"
              f"  halo-bytes/shard/{t}steps={hb}")
    print("fused mode: 1 exchange round instead of t -- latency amortized,")
    print("halo overlap recomputed locally (the paper's alpha, distributed).")


if __name__ == "__main__":
    main()
