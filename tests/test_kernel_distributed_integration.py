"""Integration: the Pallas kernels run as the distributed stepper's local
update (the full production path: halo exchange -> VPU/MXU kernel)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(n, code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


class TestKernelAsLocalApply:
    def test_fused_direct_kernel_inside_shard_map(self):
        out = run_with_devices(4, """
            import jax, numpy as np, jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.stencil import StencilSpec, make_weights, fuse_weights
            from repro.stencil.reference import apply_stencil_steps
            from repro.stencil.distributed import make_distributed_stepper
            from repro.kernels.stencil_direct import stencil_direct
            from repro.kernels.stencil_matmul import stencil_matmul

            mesh = Mesh(np.array(jax.devices()).reshape(2,2), ("x","y"))
            spec = StencilSpec("box", 2, 1)
            w = make_weights(spec, seed=3)
            t = 2
            n = 64
            x = np.random.default_rng(0).normal(size=(n,n)).astype(np.float32)
            xs = jax.device_put(x, NamedSharding(mesh, P("x","y")))
            ref = apply_stencil_steps(jnp.asarray(x), jnp.asarray(w), t)

            # VPU kernel path: fused t steps in one kernel on the extended
            # block; kernel's modulo-wrap periodicity is harmless on the
            # interior because the stepper discards the halo ring.
            def local_vpu(xe, w_, steps):
                r = (np.asarray(w_).shape[0]-1)//2 if hasattr(w_,'shape') else 1
                h = steps * 1
                full = stencil_direct(xe, w, t=steps, tile_m=xe.shape[0],
                                      tile_n=xe.shape[1], interpret=True)
                return full[h:-h, h:-h]

            step = make_distributed_stepper(mesh, ("x","y"), w, t=t,
                                            mode="fused", local_apply=local_vpu)
            with mesh:
                y = step(xs)
            err = float(jnp.abs(y - ref).max())
            assert err < 1e-4, err

            # MXU kernel path: composed weights, one banded contraction
            wf = fuse_weights(w, t)
            def local_mxu(xe, w_, steps):
                h = t * 1
                full = stencil_matmul(xe, wf, tile_m=xe.shape[0],
                                      tile_n=xe.shape[1], interpret=True)
                return full[h:-h, h:-h]

            step2 = make_distributed_stepper(mesh, ("x","y"), w, t=t,
                                             mode="fused", local_apply=local_mxu)
            with mesh:
                y2 = step2(xs)
            err2 = float(jnp.abs(y2 - ref).max())
            assert err2 < 1e-4, err2
            print("OK", err, err2)
        """)
        assert "OK" in out

    def test_pallas_local_apply_column_tiled(self):
        """A W-sharded mesh whose local update runs the COLUMN-TILED
        substrate (DESIGN.md §10): the column walk's wrap only pollutes
        the discarded halo ring, exactly like the row wrap, so the
        stepper still reproduces the global oracle."""
        out = run_with_devices(2, """
            import jax, numpy as np, jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.stencil import StencilSpec, make_weights
            from repro.stencil.reference import apply_stencil_steps
            from repro.stencil.distributed import (make_distributed_stepper,
                                                   pallas_local_apply)

            mesh = Mesh(np.array(jax.devices()), ("w",))
            w = make_weights(StencilSpec("box", 2, 1), seed=5)
            t = 2
            x = np.random.default_rng(1).normal(size=(32, 128)) \\
                  .astype(np.float32)
            xs = jax.device_put(x, NamedSharding(mesh, P(None, "w")))
            ref = apply_stencil_steps(jnp.asarray(x), jnp.asarray(w), t)

            # the halo-extended local block is (32+2t*r, 64+2t*r) = (36, 68):
            # tile_m divides the extended rows; 68 is not a multiple of
            # w_tile=32, so this also exercises the remainder path
            for backend in ("fused_direct", "fused_matmul_reuse"):
                la = pallas_local_apply(backend, interpret=True,
                                        tile_m=18, h_block=9,
                                        w_tile=32, w_block=4)
                step = make_distributed_stepper(mesh, (None, "w"), w, t=t,
                                                mode="fused",
                                                local_apply=la)
                with mesh:
                    y = step(xs)
                err = float(jnp.abs(y - ref).max())
                assert err < 1e-4, (backend, err)
            print("OK")
        """)
        assert "OK" in out

    def test_pallas_local_apply_plugin(self):
        """The packaged plug-in (stencil.distributed.pallas_local_apply)
        drives every fused kernel regime -- including the new
        intermediate-reuse MXU path -- inside shard_map."""
        out = run_with_devices(4, """
            import jax, numpy as np, jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.stencil import StencilSpec, make_weights
            from repro.stencil.reference import apply_stencil_steps
            from repro.stencil.distributed import (make_distributed_stepper,
                                                   pallas_local_apply)

            mesh = Mesh(np.array(jax.devices()).reshape(2,2), ("x","y"))
            w = make_weights(StencilSpec("box", 2, 1), seed=3)
            t, n = 2, 64
            x = np.random.default_rng(0).normal(size=(n,n)).astype(np.float32)
            xs = jax.device_put(x, NamedSharding(mesh, P("x","y")))
            ref = apply_stencil_steps(jnp.asarray(x), jnp.asarray(w), t)

            for backend in ("fused_direct", "fused_matmul",
                            "fused_matmul_reuse"):
                la = pallas_local_apply(backend, interpret=True)
                step = make_distributed_stepper(mesh, ("x","y"), w, t=t,
                                                mode="fused", local_apply=la)
                with mesh:
                    y = step(xs)
                err = float(jnp.abs(y - ref).max())
                assert err < 1e-4, (backend, err)
            print("OK")
        """)
        assert "OK" in out


class TestKernel3DLocalApply:
    def test_pallas_local_apply_on_3d_sharded_mesh(self):
        """The halo-plane substrate runs as the local update of a
        3D-sharded mesh: z and y sharded across the ring, x local, for
        both the VPU and the intermediate-reuse MXU regimes -- and the
        mesh-parameterized 3D plan drives the same stepper with a halo
        plan matching the analytic traffic model."""
        out = run_with_devices(4, """
            import jax, numpy as np, jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.stencil import StencilSpec, make_weights
            from repro.stencil.distributed import (halo_bytes_per_step,
                                                   make_distributed_stepper,
                                                   pallas_local_apply)
            from repro.stencil.reference import apply_stencil_steps
            from repro.kernels import stencil_plan

            mesh = Mesh(np.array(jax.devices()).reshape(2,2), ("x","y"))
            w = make_weights(StencilSpec("box", 3, 1), seed=3)
            t, shape = 2, (16, 32, 32)
            x = np.random.default_rng(0).normal(size=shape).astype(np.float32)
            xs = jax.device_put(x, NamedSharding(mesh, P("x","y",None)))
            ref = apply_stencil_steps(jnp.asarray(x), jnp.asarray(w), t)

            for backend in ("fused_direct", "fused_matmul_reuse"):
                la = pallas_local_apply(backend, interpret=True)
                step = make_distributed_stepper(mesh, ("x","y",None), w, t=t,
                                                mode="fused", local_apply=la)
                with mesh:
                    y = step(xs)
                err = float(jnp.abs(y - ref).max())
                assert err < 1e-4, (backend, err)

            for mode in ("stepwise", "fused"):
                plan = stencil_plan(w, shape, np.float32, t, mesh=mesh,
                                    shard_spec=("x","y",None), dist_mode=mode)
                err = float(jnp.abs(plan(xs) - ref).max())
                assert err < 1e-4, (mode, err)
                hp = plan.halo_plan
                assert hp["local_shape"] == (8, 16, 32)
                assert hp["halo_bytes_per_call"] == halo_bytes_per_step(
                    (8, 16, 32), ("x","y",None), 1, t, mode, 4)
            print("OK")
        """)
        assert "OK" in out


class TestDistributedPlan:
    def test_mesh_parameterized_plan(self):
        """A mesh-parameterized StencilPlan drives the halo-exchange stepper
        through the same object as local plans: plan(x) on the sharded grid,
        plan.halo_plan matching the analytic traffic model, and a cache key
        that separates sharded from local signatures."""
        out = run_with_devices(4, """
            import jax, numpy as np, jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.stencil import StencilSpec, make_weights
            from repro.stencil.distributed import halo_bytes_per_step
            from repro.stencil.reference import apply_stencil_steps
            from repro.kernels import stencil_plan, plan_cache_stats

            mesh = Mesh(np.array(jax.devices()).reshape(2,2), ("x","y"))
            w = make_weights(StencilSpec("box", 2, 1), seed=3)
            t, n = 2, 64
            x = np.random.default_rng(0).normal(size=(n,n)).astype(np.float32)
            xs = jax.device_put(x, NamedSharding(mesh, P("x","y")))
            ref = apply_stencil_steps(jnp.asarray(x), jnp.asarray(w), t)

            for mode in ("stepwise", "fused"):
                plan = stencil_plan(w, (n, n), np.float32, t, mesh=mesh,
                                    shard_spec=("x", "y"), dist_mode=mode)
                err = float(jnp.abs(plan(xs) - ref).max())
                assert err < 1e-4, (mode, err)
                hp = plan.halo_plan
                assert hp["local_shape"] == (n//2, n//2)
                assert hp["exchanges_per_call"] == (t if mode == "stepwise"
                                                    else 1)
                assert hp["halo_bytes_per_call"] == halo_bytes_per_step(
                    (n//2, n//2), ("x","y"), 1, t, mode, 4)
                assert "halo plan" in plan.explain()

            # same signature => cached; local signature => distinct plan
            before = plan_cache_stats()
            again = stencil_plan(w, (n, n), np.float32, t, mesh=mesh,
                                 shard_spec=("x", "y"), dist_mode="fused")
            assert plan_cache_stats()["hits"] == before["hits"] + 1
            local = stencil_plan(w, (n, n), np.float32, t)
            assert local is not again
            err = float(jnp.abs(again.run(xs, 2)
                                - apply_stencil_steps(jnp.asarray(x),
                                                      jnp.asarray(w),
                                                      2*t)).max())
            assert err < 1e-4, err
            print("OK")
        """)
        assert "OK" in out


class TestLocalApplyGeometry:
    def test_unaligned_extended_block_pads_to_a_legal_plan(self):
        """An extended block whose row count is off the 8-row sublane
        tile (local + 2*halo) is padded and auto-sized -- a geometry a
        compiled plan accepts -- and still returns the valid interior."""
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels import plan_cache_stats, stencil_plan
        from repro.stencil import StencilSpec, make_weights
        from repro.stencil.distributed import (apply_stencil_valid,
                                               pallas_local_apply)

        w = make_weights(StencilSpec("box", 2, 1), seed=3)
        xe = jnp.asarray(np.random.default_rng(0).normal(size=(34, 40))
                         .astype(np.float32))
        for backend in ("fused_direct", "fused_matmul_reuse"):
            la = pallas_local_apply(backend, interpret=True)
            y = la(xe, jnp.asarray(w), 1)
            ref = apply_stencil_valid(xe, jnp.asarray(w))
            assert y.shape == (32, 38)
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                       atol=1e-5)
            plan = stencil_plan(w, (40, 40), np.float32, 1, backend=backend,
                                interpret=True, tile_n=None)
            assert plan.grid_shape == (40, 40)   # the padded plan exists
        assert plan_cache_stats()["hits"] >= 2
