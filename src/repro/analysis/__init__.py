"""Static analysis & sanitizers for the jax_pallas reproduction.

Three layers, all wired into CI as a gating job (see README "Static
analysis & sanitizers"):

- ``pallas_check`` — declarative Pallas grid geometry checker: every
  kernel under ``src/repro/kernels/`` registers its ``pallas_call``
  signature (grid, BlockSpecs, index maps, masked dims, aliases) and the
  checker concretely enumerates the grid to prove output-block
  disjointness, in-bounds tiling and declared-only input/output aliasing.
- ``jaxlint`` — an AST pass over ``src/repro/`` flagging tracer leaks,
  silent int64/float64 promotion hazards in window/availability
  arithmetic, jitted ``lax.scan`` entry points without donated carries,
  and ``pallas_call`` sites not registered with the geometry checker.
- ``sanitize`` — ``jax.experimental.checkify`` runtime invariants on the
  fleet scan's §IV.A/§IV.B state machine (window monotonicity,
  availability conservation, link head and victim-cache order), switched
  on with ``REPRO_SANITIZE=1``.

Entry points: ``python -m repro.analysis`` or
``python -m benchmarks.run --only analysis``.
"""

from repro.analysis.pallas_check import (  # noqa: F401
    BlockDecl, KernelGeometry, Violation, check_all, load_registry, register,
)
from repro.analysis.sanitize import enabled as sanitize_enabled  # noqa: F401
