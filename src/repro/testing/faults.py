"""Deterministic fault injection for the guarded execution layer.

Every failure class the degradation ladder (DESIGN.md §11) must survive
has an injection point wired through this module:

- ``compile``: raise a Mosaic-shaped compile error inside the substrate
  launchers (``strip_substrate_call`` / ``slab_substrate_call``) at trace
  time -- each plan traces its jitted runner exactly once, so "the Nth
  compile" is well-defined.
- ``vmem``: raise a RESOURCE_EXHAUSTED-shaped VMEM overflow at the same
  point, as if the tile estimate lied.
- ``nan``: corrupt a guarded step's output with NaN (consumed by
  ``GuardedPlan`` via :func:`corrupt_output`) to exercise the watchdog.
- ``halo``: raise inside the distributed stepper's halo exchange
  (``stencil.distributed._extend``).
- ``geometry``: corrupt the next built :class:`LaunchGeometry` (consumed
  by the substrate's geometry builders via :func:`corrupt_geometry`) so
  its block walk repeats the previous ring step -- the launched/audited
  structure silently drifts from the analytic traffic model, which is
  exactly the violation class ``repro.audit`` must catch (negative
  tests in ISSUE 8).

Faults come from two sources, checked in order:

1. the :func:`inject` context manager (tests -- scoped, nestable), and
2. the ``REPRO_FAULTS`` env var (CI matrix legs and subprocess tests),
   a comma list of ``kind[:times[@skip]]`` terms: ``compile`` fires
   once; ``compile:3`` fires three times; ``vmem:1@2`` skips two hits
   then fires once; ``compile:inf`` fires forever.

Both are process-local and deterministic -- no randomness, so a fault
sweep is exactly reproducible.  When no fault is configured every hook
is a few-nanosecond no-op; the guard layer stays invisible in
production (the clean-run acceptance bar in ISSUE 6).
"""
from __future__ import annotations

import math
import os
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

from ..core.envutil import env_str

ENV_VAR = "REPRO_FAULTS"

KINDS = ("compile", "vmem", "nan", "halo", "geometry")

# Messages mimic the shape of real failures so ``classify_failure`` in
# repro.kernels.guard exercises the same patterns production errors hit.
# "(injected)" marks them unambiguously in logs and event dumps.
_MESSAGES = {
    "compile": ("INTERNAL: Mosaic failed to compile TPU kernel: "
                "unsupported lowering (injected)"),
    "vmem": ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem "
             "while allocating scratch (injected)"),
    "halo": "injected fault: halo exchange ppermute failed",
}


@dataclass
class FaultSpec:
    """One armed fault: fire ``times`` times after ``skip`` initial hits."""

    kind: str
    times: float = 1  # math.inf for "always"
    skip: int = 0
    fired: int = field(default=0, compare=False)
    hits: int = field(default=0, compare=False)

    def should_fire(self) -> bool:
        self.hits += 1
        if self.hits <= self.skip:
            return False
        if self.fired >= self.times:
            return False
        self.fired += 1
        return True


def parse_faults(raw: str) -> List[FaultSpec]:
    """Parse a ``REPRO_FAULTS`` value; raises ValueError on malformed
    terms so a typo'd CI matrix leg fails loudly, not silently clean."""
    specs: List[FaultSpec] = []
    for term in raw.split(","):
        term = term.strip()
        if not term:
            continue
        kind, times, skip = term, 1.0, 0
        if ":" in term:
            kind, _, rest = term.partition(":")
            times_s, _, skip_s = rest.partition("@")
            try:
                times = math.inf if times_s.strip() == "inf" \
                    else float(int(times_s))
                skip = int(skip_s) if skip_s else 0
            except ValueError:
                raise ValueError(
                    f"{ENV_VAR}: malformed term {term!r}; expected "
                    f"kind[:times[@skip]] with integer or 'inf' times"
                ) from None
        kind = kind.strip()
        if kind not in KINDS:
            raise ValueError(
                f"{ENV_VAR}: unknown fault kind {kind!r}; "
                f"expected one of {', '.join(KINDS)}")
        if times < 1 or skip < 0:
            raise ValueError(
                f"{ENV_VAR}: malformed term {term!r}; "
                f"times must be >= 1 and skip >= 0")
        specs.append(FaultSpec(kind, times, skip))
    return specs


# --------------------------------------------------------------------------
# Active-fault state: an explicit stack from inject() layered over the
# env-derived specs.  Env specs are re-parsed only when the raw string
# changes, so counters (fired/hits) persist across hook calls within one
# configuration -- that is what makes "fail the Nth compile" meaningful.
# --------------------------------------------------------------------------
_STACK: List[List[FaultSpec]] = []
_ENV_RAW: Optional[str] = None
_ENV_SPECS: List[FaultSpec] = []


def _env_specs() -> List[FaultSpec]:
    global _ENV_RAW, _ENV_SPECS
    raw = env_str(ENV_VAR)
    if raw != _ENV_RAW:
        _ENV_RAW = raw
        _ENV_SPECS = parse_faults(raw) if raw else []
    return _ENV_SPECS


def active_faults() -> List[FaultSpec]:
    """All armed specs, innermost inject() scope first, env last."""
    out: List[FaultSpec] = []
    for layer in reversed(_STACK):
        out.extend(layer)
    out.extend(_env_specs())
    return out


def reset_faults() -> None:
    """Drop all injected scopes and force env re-parse (test hygiene)."""
    global _ENV_RAW, _ENV_SPECS
    _STACK.clear()
    _ENV_RAW = None
    _ENV_SPECS = []


def fault_hits() -> Dict[str, int]:
    """How many times each kind actually fired (for assertions)."""
    counts: Dict[str, int] = {}
    for spec in active_faults():
        counts[spec.kind] = counts.get(spec.kind, 0) + spec.fired
    return counts


@contextmanager
def inject(kind: str, times: float = 1, skip: int = 0) -> Iterator[FaultSpec]:
    """Arm one fault for the dynamic extent of the block.

    Yields the spec so tests can assert ``spec.fired`` afterwards.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown fault kind {kind!r}; "
                         f"expected one of {', '.join(KINDS)}")
    spec = FaultSpec(kind, times, skip)
    layer = [spec]
    _STACK.append(layer)
    try:
        yield spec
    finally:
        _STACK.remove(layer)


# --------------------------------------------------------------------------
# Hooks called from production code.
# --------------------------------------------------------------------------
def maybe_fail(kind: str) -> None:
    """Raise the configured failure for ``kind`` if a matching fault is
    armed and due.  No-op (beyond one env read) when nothing is armed."""
    if not _STACK and ENV_VAR not in os.environ:
        return  # fast path: nothing armed anywhere
    for spec in active_faults():
        if spec.kind == kind and spec.should_fire():
            raise RuntimeError(_MESSAGES.get(kind,
                                             f"injected fault: {kind}"))


def corrupt_output(y):
    """If a ``nan`` fault is due, poison one element of ``y`` (the
    guarded step's output) with NaN; otherwise return ``y`` unchanged.
    Called only from the guard layer, never from kernels themselves."""
    if not _STACK and ENV_VAR not in os.environ:
        return y
    for spec in active_faults():
        if spec.kind == "nan" and spec.should_fire():
            import jax.numpy as jnp
            idx = (0,) * y.ndim
            return y.at[idx].set(jnp.nan)
    return y


def corrupt_geometry(lg):
    """If a ``geometry`` fault is due, return a copy of the launch
    geometry whose input block walk repeats the previous last-grid-axis
    step (step ``j`` fetches step ``j-1``'s block): a consecutive
    duplicate that shrinks the fetched block multiset AND stores the
    wrong global rows -- the model/code drift the static auditor exists
    to flag.  Identity (beyond one env read) when nothing is armed, so
    production launches never pay for the hook."""
    if not _STACK and ENV_VAR not in os.environ:
        return lg
    for spec in active_faults():
        if spec.kind == "geometry" and spec.should_fire():
            import dataclasses
            orig = lg.in_index_map

            def warped(*ix):
                j = ix[-1]
                return orig(*ix[:-1], j - 1 if isinstance(j, int) and j > 0
                            else j)

            return dataclasses.replace(lg, in_index_map=warped)
    return lg
