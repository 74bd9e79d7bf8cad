"""repro.lint — trace-safety & device-residency static analysis.

An AST-based linter purpose-built for this codebase's jax/Pallas
invariants (DESIGN.md §11). Three layers:

- `resolver`: walks the package, resolves which functions are
  (transitively) traced — ``@jax.jit`` / ``partial(jit, ...)``
  decorators, ``jax.jit(fn)`` / ``shard_map(fn)`` / ``pallas_call(fn)``
  / ``vmap(fn)`` call forms — and maintains a call graph so rules apply
  to everything reachable from a trace entry point.
- `rules`: a registry of small rule classes (id, severity, fixture
  tests) covering host-sync-in-jit, unhashable static args, the devtree
  scatter/sort-free contracts, obs-gated ``block_until_ready``, donation
  misuse, and Python-side nondeterminism in traced code.
- `cli`: ``python -m repro.lint [paths] [--baseline lint_baseline.json]
  [--format gh|json]`` with a suppression syntax
  (``# lint: disable=RULE — reason``) and a committed baseline confined
  to the legacy LM-skeleton modules, so the treecode packages are held
  to zero findings.

`runtime` closes the loop at runtime: ``no_implicit_transfers()`` wraps
``jax.transfer_guard("disallow")`` around device-resident step loops,
and ``REPRO_DEBUG_NANS=1`` threads ``jax_debug_nans`` through
`Simulation` / `ServeFrontend`.
"""
from repro.lint.findings import Finding, Severity
from repro.lint.resolver import TraceResolver, scan_paths
from repro.lint.rules import ALL_RULES, get_rule, run_rules
from repro.lint.baseline import (BASELINE_SCOPE, load_baseline,
                                 write_baseline, apply_baseline)
from repro.lint.cli import lint_paths, main

__all__ = [
    "Finding", "Severity", "TraceResolver", "scan_paths",
    "ALL_RULES", "get_rule", "run_rules",
    "BASELINE_SCOPE", "load_baseline", "write_baseline", "apply_baseline",
    "lint_paths", "main",
]
