"""Static analysis + runtime sanitizers for the engine's hot-path
invariants.

Everything the repo's headline numbers rest on — no implicit
device->host syncs in the decode loop, step functions compiling exactly
once, plan pytrees registered, Pallas BlockSpecs on (8, 128) register
tiles — is enforced mechanically
here instead of by scattered one-off test assertions:

  lint.py       AST lint framework: ``Finding``, the string-keyed
                checker registry (mirroring the engine's substrate
                registry), inline suppressions, ``lint_paths``.
  callgraph.py  Project-wide call graph; computes the *hot set* (every
                function upstream or downstream of ``lm.decode_step``,
                ``ContinuousScheduler.run``, ``engine.matmul``).
  checkers.py   The repo-specific checkers (RPR1xx host-sync, RPR2xx
                recompile hazards, RPR301 pytree registration, RPR4xx
                Pallas tiles).
  sanitize.py   Runtime layer: ``Sanitizer`` (``transfer_guard`` around
                the scheduler's steady-state decode window, optional NaN
                debugging) and ``CompileCounter`` (a compile-count
                sentinel on ``jax.log_compiles``).
  cli.py        ``repro-lint`` / ``python -m repro.analysis`` entry
                point; chains ruff when it is installed.

This module (and the lint machinery) imports no jax, so the lint pass
runs on a bare Python install; import :mod:`repro.analysis.sanitize`
explicitly for the runtime layer.
"""
from repro.analysis.lint import (Checker, Finding, available_checkers,
                                 get_checker, lint_paths, lint_source,
                                 register_checker)

__all__ = [
    "Checker",
    "Finding",
    "available_checkers",
    "get_checker",
    "lint_paths",
    "lint_source",
    "register_checker",
]
