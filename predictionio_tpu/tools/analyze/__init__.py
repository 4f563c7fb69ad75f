"""Repo-wide static analysis: the KNOWN_ISSUES invariants as lint passes.

The hardest-won knowledge in this codebase lives in KNOWN_ISSUES.md as
prose — timed regions must end in a real host transfer (#3/#7), padded
gathers must state their out-of-bounds policy (#5), jitted bodies must
be pure. Before this package, three ad-hoc AST lints enforced slices of
it from test files, each gated on a hand-maintained module list that new
files silently escaped. This package is the single home for all of it:

- :mod:`walker` discovers and parses every analyzed module ONCE
  (``predictionio_tpu/`` + ``chip_smoke.py`` + ``diagnostics/``) — coverage
  is automatic for every future module, opt-OUT instead of opt-in.
- :mod:`findings` defines the finding record (rule id, file:line, fix
  hint, stable baseline key) and the checked-in suppression baseline
  (``conf/lint_baseline.json``): accepted findings are pinned by key so
  NEW debt can't hide behind old, and entries that stop matching are
  themselves findings until removed.
- :mod:`passes` holds the pass registry; each pass walks the shared
  module set and yields findings.
- :mod:`runner` runs the whole thing (``pio lint``, text or ``--json``;
  exit 0 clean / 1 findings / 2 internal error) and is the single
  tier-1 pytest entry point (tests/test_lint.py).
- :mod:`runtime` is the dynamic half of the lock-order pass: a lock
  proxy the chaos tests install to record the REAL acquisition order.

Everything here is stdlib-only (ast + os + json): ``pio lint`` must run
in a checkout without initializing jax.
"""

from predictionio_tpu.tools.analyze.findings import Baseline, Finding
from predictionio_tpu.tools.analyze.runner import run_lint

__all__ = ["Baseline", "Finding", "run_lint"]
