"""Serving drivers: LLM decode loop + the batched stencil engine.

Default (no subcommand): the batched greedy-decoding server driver --
prefill -> decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
        --batch 4 --prompt-len 16 --gen 32

Exercises the runnable serving path end-to-end on CPU with the reduced
configs: cache init, full-sequence prefill, then one-token steps with the
same stacked-scan decode the decode_32k/long_500k dry-run cells lower at
production shapes.  Reports tokens/s and verifies the KV-cached stream
matches the uncached forward pass (greedy consistency check).

``stencil`` subcommand: drive the batched plan-sharing stencil engine
(``repro.serve``, DESIGN.md §12) with a closed-loop client -- a fixed
window of outstanding requests over one plan signature -- and report
requests/s, batch occupancy, and P50/P99 latency.

    PYTHONPATH=src python -m repro.launch.serve stencil \\
        --requests 256 --window 16 --shape star --t 2 --grid 32,32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, SMOKE
from repro.models.api import get_model
from repro.models import layers as nn_layers
from repro.models import transformer, rwkv_model


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("--arch", default="llama3.2-1b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--check", action="store_true",
                    help="verify cached decode == uncached forward argmax")

    sub = ap.add_subparsers(dest="cmd")
    st = sub.add_parser(
        "stencil",
        help="batched plan-sharing stencil serving engine (repro.serve)")
    st.add_argument("--requests", type=int, default=256,
                    help="total requests the closed loop issues")
    st.add_argument("--window", type=int, default=16,
                    help="closed-loop concurrency (outstanding requests)")
    st.add_argument("--shape", choices=("box", "star"), default="star")
    st.add_argument("--radius", type=int, default=1)
    st.add_argument("--t", type=int, default=2, dest="depth",
                    help="fusion depth (time steps per request)")
    st.add_argument("--grid", default="32,32",
                    help="comma-separated grid shape, e.g. 32,32 or 8,16,16")
    st.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32")
    st.add_argument("--max-batch", type=int, default=None,
                    help="override REPRO_SERVE_MAX_BATCH")
    st.add_argument("--timeout-ms", type=int, default=None,
                    help="override REPRO_SERVE_QUEUE_TIMEOUT_MS")
    st.add_argument("--no-guard", action="store_true",
                    help="skip the guarded-execution ladder (DESIGN.md §11)")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    ap = build_parser()
    args = ap.parse_args(argv)
    if getattr(args, "cmd", None) == "stencil":
        # Same fail-fast convention as the LLM flags: degenerate loop
        # bounds die with a usage error, not a hang in the closed loop.
        for name in ("requests", "window", "radius", "depth"):
            value = getattr(args, name)
            if value < 1:
                flag = {"depth": "t"}.get(name, name.replace("_", "-"))
                ap.error(f"--{flag} must be >= 1, got {value}")
        for name in ("max_batch", "timeout_ms"):
            value = getattr(args, name)
            floor = 1 if name == "max_batch" else 0
            if value is not None and value < floor:
                ap.error(f"--{name.replace('_', '-')} must be >= {floor}, "
                         f"got {value}")
        try:
            grid = tuple(int(n) for n in args.grid.split(","))
        except ValueError:
            ap.error(f"--grid must be comma-separated integers, "
                     f"got {args.grid!r}")
        if not grid or any(n < 1 for n in grid) or len(grid) > 3:
            ap.error(f"--grid needs 1-3 positive dims, got {args.grid!r}")
        args.grid_shape = grid
        return args
    # Reject degenerate loop bounds up front: --prompt-len 0 would leave
    # the prefill loop body unexecuted and crash on the undefined next
    # token; --gen 0 similarly empties the decode loop.  ap.error exits
    # with a usage message and status 2, the argparse convention.
    for name in ("batch", "prompt_len", "gen"):
        value = getattr(args, name)
        if value < 1:
            ap.error(f"--{name.replace('_', '-')} must be >= 1, got {value}")
    return args


def serve_stencil(args) -> dict:
    """Closed-loop drive of the batched stencil engine; returns (and
    prints) the metrics snapshot."""
    from repro.serve import StencilServer
    from repro.stencil.spec import StencilSpec
    from repro.stencil.weights import jacobi_weights

    spec = StencilSpec(args.shape, len(args.grid_shape), args.radius)
    weights = jacobi_weights(spec)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else np.float32
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.normal(size=args.grid_shape), dtype=dtype)
          for _ in range(min(args.window, args.requests))]

    with StencilServer(max_batch=args.max_batch,
                       queue_timeout_ms=args.timeout_ms,
                       guard=not args.no_guard) as server:
        # closed loop: keep `window` requests outstanding, issue a new one
        # as each completes; reuse the window's input arrays round-robin
        outstanding = []
        issued = 0
        t0 = time.perf_counter()
        while issued < args.requests or outstanding:
            while issued < args.requests and len(outstanding) < len(xs):
                outstanding.append(server.submit(
                    weights, xs[issued % len(xs)], t=args.depth))
                issued += 1
            outstanding.pop(0).result()
        wall = time.perf_counter() - t0
        snap = server.stats()

    lat = snap["latency"]
    print(f"stencil serve: {spec.name} t={args.depth} "
          f"grid={args.grid_shape} dtype={args.dtype} "
          f"guard={not args.no_guard}")
    print(f"  requests   : {snap['responded']}/{snap['submitted']} "
          f"in {wall:.2f}s wall ({snap['responded']/wall:.0f} req/s)")
    print(f"  batches    : {snap['batches']} "
          f"(occupancy {snap['batch_occupancy']:.2f}, "
          f"degraded {snap['degraded_batches']})")
    print(f"  latency ms : p50={lat['p50_ms']:.2f} p99={lat['p99_ms']:.2f} "
          f"mean={lat['mean_ms']:.2f} max={lat['max_ms']:.2f}")
    pc = snap["plan_cache"]
    print(f"  plan cache : {pc['hits']} hits / {pc['misses']} misses "
          f"({snap['engine_plans']} engine plans)")
    return snap


def main(argv=None):
    from repro.core.envutil import init_compile_cache

    args = parse_args(argv)
    init_compile_cache()
    if getattr(args, "cmd", None) == "stencil":
        serve_stencil(args)
        return

    cfg = (SMOKE if args.smoke else ARCHS)[args.arch]
    if cfg.family in ("whisper", "vlm", "hybrid", "moe"):
        print(f"note: serve CLI drives dense/rwkv families; {cfg.family} "
              "decode is exercised by tests + the decode dry-run cells")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, P, G = args.batch, args.prompt_len, args.gen
    max_seq = P + G + 1
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(B, P)).astype(np.int32)

    serve = jax.jit(model.decode_step)

    # prefill by streaming the prompt through the decode path (simple and
    # family-agnostic; transformer families also have a batched prefill)
    caches = model.init_caches(B, max_seq)
    t0 = time.perf_counter()
    for i in range(P):
        nxt, caches = serve(params, caches, jnp.asarray(prompts[:, i:i+1]),
                            jnp.asarray(i, jnp.int32))
    jax.block_until_ready(nxt)
    t_prefill = time.perf_counter() - t0

    out = [np.asarray(nxt)]
    t0 = time.perf_counter()
    for i in range(P, P + G - 1):
        nxt, caches = serve(params, caches, jnp.asarray(out[-1]),
                            jnp.asarray(i, jnp.int32))
        out.append(np.asarray(nxt))
    jax.block_until_ready(nxt)
    t_gen = time.perf_counter() - t0
    gen = np.concatenate(out, axis=1)

    print(f"arch={cfg.name} B={B} prompt={P} gen={G}")
    print(f"prefill: {t_prefill*1e3:8.1f} ms  ({B*P/t_prefill:8.0f} tok/s)")
    print(f"decode : {t_gen*1e3:8.1f} ms  ({B*(G-1)/t_gen:8.0f} tok/s)")
    print(f"sample completions (first 8 ids): {gen[:2, :8].tolist()}")

    if args.check and cfg.family == "dense":
        full = np.concatenate([prompts, gen[:, :-1]], axis=1)
        h, _, _ = transformer.forward(params, jnp.asarray(full), cfg)
        logits = nn_layers.lm_logits(params, h, cfg)
        want = np.asarray(jnp.argmax(logits[:, P - 1:], -1))
        ok = np.array_equal(want, gen)
        print(f"greedy consistency vs uncached forward: "
              f"{'OK' if ok else 'MISMATCH'}")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
