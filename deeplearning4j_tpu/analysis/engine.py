"""graftlint engine: parsed files, suppressions, the rule registry, the runner.

The reference shipped its project invariants as prose (CONTRIBUTING.md,
review checklists); ours are sharper than prose can hold — "donation
flows through ops/dispatch.py", "every ``DL4J_TPU_*`` read goes through
ops/env.py", "chaos is config-driven, never ambient" — and they have all
been broken at least once before being written down. This package turns
each of those hard-won rules into an AST check (error-prone / pytype
style: stdlib ``ast`` + ``tokenize`` only, zero new dependencies) so the
NEXT violation fails a quick-tier test.

Mechanics
---------
* A :class:`Rule` has a kebab-case ``name``, a ``severity`` ("error" |
  "warning"), a one-line ``doc``, and ``check(parsed) -> [Finding]``.
  Rules with repo-global invariants (the knob table vs CLAUDE.md) also
  implement ``check_project(root) -> [Finding]``.
* Suppressions are explicit and must carry a justification::

      t0 = time.time()  # graftlint: disable=nondeterminism-in-jit -- host-side timer, not traced

  A standalone suppression comment applies to the NEXT code line; a
  trailing comment applies to its own line.  File-level::

      # graftlint: disable-file=host-sync-under-lock -- single-threaded tool

  A suppression with no ``-- justification`` text, or naming an unknown
  rule, is itself reported (rule ``bad-suppression``) — silencing the
  linter is allowed, silently is not.
* Exit contract (``__main__``): 0 = clean, 1 = findings, 2 = usage/crash.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

SEVERITIES = ("error", "warning")

#: the scanned surface, relative to the repo root — the library, every
#: entrypoint the driver runs, and the harness scripts; tests/ is excluded
#: (fixtures there must be able to SPELL violations) and so is this
#: package's own fixture dir
DEFAULT_TARGETS = (
    "deeplearning4j_tpu",
    "examples",
    "scripts",
    "benchmarks",
    "bench.py",
    "chip_smoke.py",
    "__graft_entry__.py",
)

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*(disable|disable-file)=([\w,-]+)"
    r"(?:\s*--\s*(\S.*))?")


@dataclass
class Finding:
    rule: str
    path: str          # repo-relative
    line: int
    message: str
    severity: str = "error"

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def to_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "severity": self.severity, "message": self.message}


@dataclass
class Suppression:
    line: int           # line the suppression APPLIES to (not the comment)
    rules: Tuple[str, ...]
    justification: str
    file_level: bool = False


@dataclass
class ParsedFile:
    """One source file: AST + the suppression map mined from its comments."""

    path: str                        # absolute
    rel: str                         # repo-relative (what findings report)
    source: str
    tree: ast.AST
    #: line -> rule names suppressed on that line
    line_disables: Dict[int, Set[str]] = field(default_factory=dict)
    #: rules suppressed for the whole file
    file_disables: Set[str] = field(default_factory=set)
    #: malformed suppressions (missing justification / unknown syntax)
    bad_suppressions: List[Finding] = field(default_factory=list)

    @property
    def lines(self) -> List[str]:
        return self.source.splitlines()

    def is_suppressed(self, rule: str, line: int) -> bool:
        if rule in self.file_disables:
            return True
        return rule in self.line_disables.get(line, ())


def _mine_comments(source: str) -> List[Tuple[int, str]]:
    """(lineno, comment_text) for every comment token; tolerant of files
    tokenize chokes on (returns what it got up to the error)."""
    out: List[Tuple[int, str]] = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out.append((tok.start[0], tok.string))
    except (tokenize.TokenError, IndentationError):
        pass
    return out


def parse_file(path: str, rel: str, known_rules: Set[str]) -> ParsedFile:
    with open(path, encoding="utf-8") as f:
        source = f.read()
    tree = ast.parse(source, filename=rel)
    pf = ParsedFile(path=path, rel=rel, source=source, tree=tree)

    lines = source.splitlines()
    for lineno, comment in _mine_comments(source):
        m = _SUPPRESS_RE.search(comment)
        if m is None:
            # only comments that ATTEMPT a suppression (tool name followed
            # by a colon) are malformed; prose mentions of the name are fine
            if re.search(r"graftlint\s*:", comment):
                pf.bad_suppressions.append(Finding(
                    "bad-suppression", rel, lineno,
                    "unparseable graftlint comment — expected "
                    "'# graftlint: disable[-file]=<rule> -- <justification>'"))
            continue
        kind, names_s, justification = m.group(1), m.group(2), m.group(3)
        names = tuple(n for n in names_s.split(",") if n)
        if not justification or not justification.strip():
            pf.bad_suppressions.append(Finding(
                "bad-suppression", rel, lineno,
                f"suppression of {names_s!r} has no justification — append "
                "' -- <why this site is exempt>'"))
            continue
        unknown = [n for n in names if n not in known_rules]
        if unknown:
            pf.bad_suppressions.append(Finding(
                "bad-suppression", rel, lineno,
                f"suppression names unknown rule(s) {', '.join(unknown)} — "
                "see --list-rules"))
            continue
        if kind == "disable-file":
            pf.file_disables.update(names)
            continue
        # trailing comment -> its own line; standalone comment line -> the
        # next non-comment, non-blank source line
        target = lineno
        stripped = (lines[lineno - 1].strip()
                    if lineno - 1 < len(lines) else "")
        if stripped.startswith("#"):
            j = lineno  # 0-based index of the next line
            while j < len(lines) and (
                    not lines[j].strip() or lines[j].strip().startswith("#")):
                j += 1
            target = j + 1
        pf.line_disables.setdefault(target, set()).update(names)
    return pf


class Rule:
    """Base class; subclasses set name/severity/doc and override check()."""

    name: str = ""
    severity: str = "error"
    doc: str = ""

    def check(self, parsed: ParsedFile) -> List[Finding]:
        return []

    def check_project(self, root: str,
                      parsed_files: Sequence[ParsedFile]) -> List[Finding]:
        """Repo-global invariants (cross-file / vs CLAUDE.md); most rules
        have none."""
        return []

    # -- helpers shared by the concrete rules ------------------------------
    def finding(self, parsed: ParsedFile, node_or_line,
                message: str) -> Finding:
        line = (node_or_line if isinstance(node_or_line, int)
                else getattr(node_or_line, "lineno", 1))
        return Finding(self.name, parsed.rel, line, message, self.severity)


def _registry() -> List[Rule]:
    from deeplearning4j_tpu.analysis import (
        rules_conventions,
        rules_env,
        rules_jit,
        rules_threads,
    )

    rules: List[Rule] = []
    for mod in (rules_jit, rules_env, rules_conventions, rules_threads):
        rules.extend(cls() for cls in mod.RULES)
    return rules


_RULES_CACHE: Optional[List[Rule]] = None


def all_rules() -> List[Rule]:
    global _RULES_CACHE
    if _RULES_CACHE is None:
        _RULES_CACHE = _registry()
    return _RULES_CACHE


def rule_names() -> Set[str]:
    return {r.name for r in all_rules()} | {"bad-suppression"}


def iter_python_files(root: str,
                      targets: Iterable[str] = DEFAULT_TARGETS
                      ) -> List[Tuple[str, str]]:
    """(abs_path, rel_path) for every .py under the targets; skips caches,
    hidden dirs, and this package's test fixtures."""
    out: List[Tuple[str, str]] = []
    for target in targets:
        top = os.path.join(root, target)
        if os.path.isfile(top):
            out.append((top, os.path.relpath(top, root)))
            continue
        if not os.path.isdir(top):
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(
                d for d in dirnames
                if not d.startswith(".") and d != "__pycache__"
                and d != "fixtures")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    p = os.path.join(dirpath, fn)
                    out.append((p, os.path.relpath(p, root)))
    return out


@dataclass
class Report:
    findings: List[Finding]
    files_scanned: int
    suppressions_used: int

    @property
    def clean(self) -> bool:
        return not self.findings

    def to_dict(self) -> Dict[str, object]:
        return {
            "clean": self.clean,
            "files_scanned": self.files_scanned,
            "suppressions_used": self.suppressions_used,
            "findings": [f.to_dict() for f in self.findings],
        }


def run_paths(paths: Optional[Sequence[str]] = None,
              root: Optional[str] = None,
              rules: Optional[Sequence[Rule]] = None,
              project_checks: bool = True) -> Report:
    """Run the suite. ``paths`` defaults to DEFAULT_TARGETS under ``root``
    (default: the repo root inferred from this package's location)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    rules = list(rules) if rules is not None else all_rules()
    known = {r.name for r in rules} | {"bad-suppression"}
    findings: List[Finding] = []
    parsed_files: List[ParsedFile] = []
    suppressed = 0
    files = iter_python_files(root, paths or DEFAULT_TARGETS)
    for path, rel in files:
        try:
            pf = parse_file(path, rel, known)
        except SyntaxError as e:
            findings.append(Finding("syntax-error", rel, e.lineno or 1,
                                    f"does not parse: {e.msg}"))
            continue
        parsed_files.append(pf)
        findings.extend(pf.bad_suppressions)
        for rule in rules:
            for f in rule.check(pf):
                if pf.is_suppressed(f.rule, f.line):
                    suppressed += 1
                else:
                    findings.append(f)
    if project_checks:
        for rule in rules:
            findings.extend(rule.check_project(root, parsed_files))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return Report(findings=findings, files_scanned=len(files),
                  suppressions_used=suppressed)
