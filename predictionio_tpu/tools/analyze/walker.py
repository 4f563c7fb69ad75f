"""Shared module walker: every analyzed file, parsed once.

The pre-framework lints each re-walked and re-parsed the tree (and two
of them only looked at hand-maintained module lists). Here discovery is
centralized and coverage is the WHOLE repo-of-record — the
``predictionio_tpu`` package, ``chip_smoke.py`` and ``diagnostics/`` — so a
new module is analyzed the moment it exists. Passes receive the same
parsed :class:`Module` list; nothing re-reads the filesystem.

Opt-outs are per-line or per-module pragmas in the source itself
(:func:`line_allows` / :func:`module_allows`), so an exemption lives
next to the code it exempts and travels with it through refactors —
unlike the old central module lists, which drifted.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

#: top-level entries under the repo root that are analyzed, beyond the
#: package itself (tests/ is deliberately excluded: tests seed defects
#: on purpose and assert on lint internals)
_EXTRA_FILES = ("chip_smoke.py",)
_EXTRA_DIRS = ("diagnostics",)

_PRAGMA = "pio-lint:"


def repo_root() -> str:
    """The directory holding ``predictionio_tpu/`` (and ``chip_smoke.py``)."""
    pkg = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.dirname(pkg)


@dataclasses.dataclass
class Module:
    """One analyzed source file: path, text and its parsed AST."""
    path: str                 # absolute
    rel: str                  # repo-relative, "/"-separated
    source: str
    tree: Optional[ast.AST]   # None when the file does not parse
    parse_error: Optional[str] = None

    _lines: Optional[List[str]] = dataclasses.field(
        default=None, repr=False, compare=False)
    _pragmas: Optional[Dict[int, Set[str]]] = dataclasses.field(
        default=None, repr=False, compare=False)
    _module_pragmas: Optional[Set[str]] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def lines(self) -> List[str]:
        if self._lines is None:
            self._lines = self.source.splitlines()
        return self._lines

    # -------------------------------------------------------- pragmas
    def _scan_pragmas(self) -> None:
        per_line: Dict[int, Set[str]] = {}
        module_wide: Set[str] = set()
        for i, text in enumerate(self.lines, start=1):
            if _PRAGMA not in text:
                continue
            tail = text.split(_PRAGMA, 1)[1]
            for clause in tail.replace(";", " ").split():
                if clause.startswith("allow="):
                    per_line.setdefault(i, set()).update(
                        clause[len("allow="):].split(","))
                elif clause.startswith("module-allow="):
                    module_wide.update(
                        clause[len("module-allow="):].split(","))
        self._pragmas = per_line
        self._module_pragmas = module_wide

    def line_allows(self, line: int, rule: str) -> bool:
        """Is ``rule`` suppressed at ``line``? The pragma may sit on the
        flagged line itself or on the line directly above it (for lines
        too long to carry a trailing comment)."""
        if self._pragmas is None:
            self._scan_pragmas()
        assert self._pragmas is not None
        for at in (line, line - 1):
            if rule in self._pragmas.get(at, ()):
                return True
        return False

    def module_allows(self, rule: str) -> bool:
        if self._module_pragmas is None:
            self._scan_pragmas()
        assert self._module_pragmas is not None
        return rule in self._module_pragmas


def _iter_paths(root: str) -> Iterator[str]:
    pkg = os.path.join(root, "predictionio_tpu")
    for base, dirs, files in os.walk(pkg):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(base, f)
    for extra in _EXTRA_FILES:
        p = os.path.join(root, extra)
        if os.path.isfile(p):
            yield p
    for d in _EXTRA_DIRS:
        dp = os.path.join(root, d)
        if not os.path.isdir(dp):
            continue
        for base, dirs, files in os.walk(dp):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(base, f)


def discover(root: Optional[str] = None) -> List[Module]:
    """Every analyzed module, parsed. A file that fails to parse still
    appears (``tree=None`` + ``parse_error``) so the runner can turn it
    into a finding instead of silently shrinking coverage."""
    root = root or repo_root()
    out: List[Module] = []
    for path in _iter_paths(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            tree: Optional[ast.AST] = ast.parse(source, filename=path)
            err = None
        except SyntaxError as e:
            tree, err = None, f"{e.msg} (line {e.lineno})"
        out.append(Module(path=path, rel=rel, source=source, tree=tree,
                          parse_error=err))
    return out


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def import_aliases(tree: ast.AST, module: str) -> Set[str]:
    """Local names bound to ``module`` (``import time as t`` -> {"t"})."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == module:
                    out.add(a.asname or a.name)
    return out


def from_import_aliases(tree: ast.AST, module: str,
                        name: str) -> Set[str]:
    """Local names bound to ``module.name`` via ``from module import``."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == module:
            for a in node.names:
                if a.name == name:
                    out.add(a.asname or a.name)
    return out


def module_alias_map(tree: ast.AST) -> Dict[str, str]:
    """Local name -> dotted module it is bound to, for both spellings
    (``import a.b.c as x`` and ``from a.b import c [as x]``). Used to
    resolve cross-module references like ``als._train_hybrid_jit`` in a
    ``register_jit`` call back to the module that defines the function."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def str_arg(call: ast.Call, index: int = 0) -> Optional[str]:
    """The call's positional arg as a literal string, else None."""
    if len(call.args) > index:
        a = call.args[index]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a.value
    return None


def literal_prefix(node: ast.AST) -> Optional[str]:
    """Best-effort leading literal of a string expression: a constant,
    an f-string's leading text, or a ``"lit" + x`` concatenation —
    enough to match dynamically-built env names against declared
    prefixes."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        return literal_prefix(node.left)
    if (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "format"):
        return literal_prefix(node.func.value)
    return None


def jit_decorated_defs(tree: ast.AST) -> List[ast.FunctionDef]:
    """Function defs whose decorators resolve to ``jax.jit`` — bare,
    ``jax.jit(...)`` with arguments, or ``partial(jax.jit, ...)``."""
    out: List[ast.FunctionDef] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            target = dec
            if (isinstance(dec, ast.Call) and dec.args
                    and isinstance(dec.func, ast.Name)
                    and dec.func.id == "partial"):
                target = dec.args[0]
            if isinstance(target, ast.Call):
                target = target.func
            if dotted_name(target) == "jax.jit":
                out.append(node)  # type: ignore[arg-type]
                break
    return out


def registered_jit_defs(modules: Sequence["Module"]) -> List[
        Tuple["Module", ast.FunctionDef]]:
    """Every function def registered through ``serving/aot.register_jit``,
    resolved across modules: ``register_jit("n", f)`` binds a local def,
    ``register_jit("n", als._train_hybrid_jit)`` follows the ``als``
    import back to ops/als.py. These bodies are traced by jax.jit at
    serve/train time, so the purity and host-sync passes treat them
    exactly like ``@jax.jit`` defs."""
    by_modname: Dict[str, "Module"] = {}
    for m in modules:
        if not m.rel.endswith(".py"):
            continue
        modname = m.rel[:-3].replace("/", ".")
        if modname.endswith(".__init__"):
            modname = modname[: -len(".__init__")]
        by_modname[modname] = m
    out: List[Tuple["Module", ast.FunctionDef]] = []
    seen: Set[Tuple[str, int]] = set()

    def add(mod: "Module", fn: ast.FunctionDef) -> None:
        key = (mod.rel, fn.lineno)
        if key not in seen:
            seen.add(key)
            out.append((mod, fn))

    for m in modules:
        if m.tree is None:
            continue
        aliases = module_alias_map(m.tree)
        local_defs = {n.name: n for n in ast.walk(m.tree)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))}
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call) or len(node.args) < 2:
                continue
            dn = dotted_name(node.func)
            if not dn or not (dn == "register_jit"
                              or dn.endswith(".register_jit")):
                continue
            target = node.args[1]
            if isinstance(target, ast.Name) and target.id in local_defs:
                add(m, local_defs[target.id])
            elif (isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)):
                target_mod = by_modname.get(
                    aliases.get(target.value.id, ""))
                if target_mod is None or target_mod.tree is None:
                    continue
                for n in ast.walk(target_mod.tree):
                    if (isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))
                            and n.name == target.attr):
                        add(target_mod, n)
                        break
    return out


def jitted_bodies(tree: ast.AST) -> List[Tuple[str, ast.FunctionDef]]:
    """(name, def) for every function traced by jax.jit in this module:
    decorated defs plus local defs wrapped at module level
    (``g = jax.jit(f)`` / ``register_jit("n", f)``-style references are
    resolved by name)."""
    defs = {n.name: n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    out = {n.name: n for n in jit_decorated_defs(tree)}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and dotted_name(node.func) == "jax.jit" and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in defs):
            out.setdefault(node.args[0].id, defs[node.args[0].id])
    return sorted(out.items())
