"""Timing honesty (KNOWN_ISSUES #3/#7), repo-wide.

Re-homes the original tests/test_timing_lint.py checks on the shared
walker and drops their hand-maintained scope lists:

- ``timing-wall-clock``: no ``time.time()`` anywhere — durations come
  from ``time.perf_counter()`` (monotonic; a wall-clock delta can go
  NEGATIVE mid-measurement under NTP steps), wall-clock timestamps from
  timezone-aware ``datetime``. Was already package-wide; now also
  covers ``chip_smoke.py`` and ``diagnostics/``.
- ``timing-block-until-ready``: no ``block_until_ready`` anywhere — on
  the early rounds' remote backend it returned before results landed on
  host, silently under-reporting any clock stopped behind it; timed
  regions must end in a real host transfer (``jax.device_get``).
  Was opt-IN (a 18-module list new files silently escaped); now every
  module is covered and a kernel with a legitimate non-timing use
  opts OUT in its own source (``# pio-lint: allow=...`` with the
  justification in the comment).
"""

from __future__ import annotations

from typing import List, Sequence

import ast

from predictionio_tpu.tools.analyze.findings import Finding
from predictionio_tpu.tools.analyze.passes import Pass
from predictionio_tpu.tools.analyze.walker import (
    Module, from_import_aliases, import_aliases,
)

_WALL = "timing-wall-clock"
_BLOCK = "timing-block-until-ready"


def _wall_clock_findings(mod: Module) -> List[Finding]:
    assert mod.tree is not None
    module_aliases = import_aliases(mod.tree, "time")
    func_aliases = from_import_aliases(mod.tree, "time", "time")
    if not module_aliases and not func_aliases:
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        hit = ((isinstance(fn, ast.Attribute) and fn.attr == "time"
                and isinstance(fn.value, ast.Name)
                and fn.value.id in module_aliases)
               or (isinstance(fn, ast.Name) and fn.id in func_aliases))
        if hit and not mod.line_allows(node.lineno, _WALL):
            out.append(Finding(
                rule=_WALL, path=mod.rel, line=node.lineno,
                message="time.time() in timing-sensitive code",
                hint="use time.perf_counter() for durations (monotonic) "
                     "or timezone-aware datetime for wall-clock "
                     "timestamps"))
    return out


def _block_findings(mod: Module) -> List[Finding]:
    assert mod.tree is not None
    if mod.module_allows(_BLOCK):
        return []
    out = []
    for node in ast.walk(mod.tree):
        name = None
        if (isinstance(node, ast.Attribute)
                and node.attr == "block_until_ready"):
            name = node.attr
        elif (isinstance(node, ast.Name)
                and node.id == "block_until_ready"):
            name = node.id
        if name and not mod.line_allows(node.lineno, _BLOCK):
            out.append(Finding(
                rule=_BLOCK, path=mod.rel, line=node.lineno,
                message="block_until_ready can return before results "
                        "land on host (KNOWN_ISSUES #3) — any clock "
                        "stopped behind it under-reports where it "
                        "returns early",
                hint="end the timed region in a real host transfer "
                     "(jax.device_get of at least one element); for a "
                     "genuine non-timing dispatch barrier, suppress "
                     "with '# pio-lint: allow="
                     "timing-block-until-ready' and say why"))
    return out


def run(modules: Sequence[Module]) -> List[Finding]:
    out: List[Finding] = []
    for mod in modules:
        if mod.tree is None:
            continue
        if "time" in mod.source:
            out.extend(_wall_clock_findings(mod))
        if "block_until_ready" in mod.source:
            out.extend(_block_findings(mod))
    return out


PASS = Pass(
    name="timing",
    rules=(_WALL, _BLOCK),
    doc="time.time() banned; block_until_ready never ends a timed "
        "region (KNOWN_ISSUES #3/#7)",
    run=run)
