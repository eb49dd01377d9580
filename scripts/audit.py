#!/usr/bin/env python
"""Static plan audit matrix: prove model==code across the registry.

Runs :func:`repro.audit.audit_context` for every registered backend over
the 1D / 2D / 3D grid matrix -- including the column-tiled remainder
widths (W not divisible by w_tile, DESIGN.md §10) and off-128 3D grids
-- writes the machine-readable ``AUDIT_report.json`` (uploaded as a CI
artifact), prints one summary line per audit, and exits nonzero if ANY
check is violated.  Everything is static: no kernel executes, so the
sweep runs in seconds on a CPU-only container.

    PYTHONPATH=src python scripts/audit.py [--out AUDIT_report.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np  # noqa: E402

from repro import audit  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.stencil.spec import StencilSpec  # noqa: E402
from repro.stencil.weights import jacobi_weights  # noqa: E402

# (grid, t, spec kwargs, pinned substrate kwargs): the paper's three ranks,
# full-width and column-tiled rings, divisible and remainder widths.  Pinned w_tile on
# the remainder cases forces the edge-tile path regardless of the VMEM
# budget's auto choice.
MATRIX = [
    ((1000,), 2, dict(dim=1, radius=1, shape="star"), {}),
    ((4096,), 3, dict(dim=1, radius=2, shape="star"), {}),
    ((256, 512), 2, dict(dim=2, radius=1, shape="box"), {}),
    ((256, 512), 3, dict(dim=2, radius=2, shape="star"), {}),
    ((128, 257), 2, dict(dim=2, radius=1, shape="box"),
     dict(w_tile=128, w_block=32)),
    ((128, 300), 2, dict(dim=2, radius=1, shape="star"),
     dict(w_tile=128, w_block=32)),
    ((32, 64, 128), 2, dict(dim=3, radius=1, shape="box"), {}),
    ((24, 48, 100), 2, dict(dim=3, radius=1, shape="star"), {}),
    # Boundary-mode rows (DESIGN.md §15): non-periodic index maps swap
    # mod-wrap for reflect-at-block; the mode-aware coverage check must
    # hold on every rank, including a remainder width and a mixed 3D
    # spec.  fused_matmul rejects t>1 non-periodic (ValueError ->
    # incompatible_configs), which is itself part of the contract.
    ((256, 512), 2, dict(dim=2, radius=1, shape="box"),
     dict(boundary="reflect")),
    ((256, 512), 2, dict(dim=2, radius=2, shape="star"),
     dict(boundary=("zero", "replicate"))),
    ((128, 300), 2, dict(dim=2, radius=1, shape="star"),
     dict(w_tile=128, w_block=32, boundary=("reflect", "periodic"))),
    ((1000,), 2, dict(dim=1, radius=1, shape="star"),
     dict(boundary="replicate")),
    ((32, 64, 128), 2, dict(dim=3, radius=1, shape="box"),
     dict(boundary=("reflect", "periodic", "zero"))),
]


def _context(grid, t, spec_kw, pinned):
    from repro.stencil.boundary import resolve_boundary
    spec = StencilSpec(**spec_kw)
    return registry.PlanContext(
        spec=spec, weights=jacobi_weights(spec), grid_shape=grid,
        dtype=np.dtype(np.float32), t=t, tile_m=None, tile_n=None,
        interpret=True,
        h_block=pinned.get("h_block"), z_slab=pinned.get("z_slab"),
        z_block=pinned.get("z_block"), w_tile=pinned.get("w_tile"),
        w_block=pinned.get("w_block"),
        boundary=resolve_boundary(pinned.get("boundary"), len(grid)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="AUDIT_report.json",
                    help="report path (default AUDIT_report.json)")
    args = ap.parse_args(argv)

    reports, skipped_cfg = [], []
    violations = 0
    for grid, t, spec_kw, pinned in MATRIX:
        for name in registry.registered_backends():
            ctx = _context(grid, t, spec_kw, pinned)
            try:
                rep = audit.audit_context(ctx, name)
            except ValueError as e:
                # The backend itself rejects this configuration (e.g.
                # fused_matmul refuses t>1 non-periodic boundaries) -- the
                # builder raises identically, so there is no plan to audit.
                skipped_cfg.append({"backend": name, "grid": list(grid),
                                    "t": t, "reason": str(e)})
                continue
            print(rep.summary())
            reports.append(rep)
            violations += len(rep.violations)

    audited = [r for r in reports if r.exempt is None]
    payload = {
        "ok": violations == 0,
        "n_audits": len(audited),
        "n_exempt": len(reports) - len(audited),
        "n_violations": violations,
        "n_checks": sum(len(r.checks) for r in reports),
        "incompatible_configs": skipped_cfg,
        "reports": [r.to_dict() for r in reports],
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"audit: {len(audited)} audits ({payload['n_checks']} checks), "
          f"{payload['n_exempt']} exempt, "
          f"{len(skipped_cfg)} incompatible configs, "
          f"{violations} violations -> {args.out}")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
