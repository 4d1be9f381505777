"""Concurrency rules: host syncs under locks, unlocked cross-thread writes.

* host-sync-under-lock: the batcher/tracer/pipeline planes hold small
  locks on hot paths; a device sync (``np.asarray`` on a device array,
  ``jax.device_get``, ``block_until_ready``) inside such a critical
  section stalls every thread contending for the lock until the device
  answers — the listener bulk-readback rule (CLAUDE.md, obs
  span contract: spans are HOST-side events only).
* thread-shared-state: a class that launches ≥1 thread at ``self``-bound
  entry points and mutates the same attribute from several of them
  without a lock is a data race waiting for load. ``__init__`` writes
  are exempt (happens-before the thread start), and so are plain
  constant assignments (``self._draining = True``) — the GIL-atomic
  minimal-flag pattern is the repo's sanctioned signal mechanism.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set

from deeplearning4j_tpu.analysis.engine import Finding, ParsedFile, Rule
from deeplearning4j_tpu.analysis.rules_jit import call_name, dotted_name

#: modules where these rules apply — the threaded planes
_THREADED_SCOPES = (
    "deeplearning4j_tpu/serving/", "deeplearning4j_tpu/obs/",
    "deeplearning4j_tpu/etl/", "deeplearning4j_tpu/parallel/fleet.py",
    "deeplearning4j_tpu/resilience/",
)

_SYNC_CALLS = {"np.asarray", "numpy.asarray", "jax.device_get",
               "jnp.asarray"}


def _in_scope(rel: str) -> bool:
    rel = rel.replace(os.sep, "/")
    return any(rel.startswith(s) for s in _THREADED_SCOPES)


def _lockish(expr: ast.AST) -> bool:
    name = dotted_name(expr) or ""
    if isinstance(expr, ast.Call):
        name = call_name(expr) or ""
    return "lock" in name.lower()


class HostSyncUnderLock(Rule):
    name = "host-sync-under-lock"
    severity = "warning"
    doc = ("device readback (np.asarray/device_get/block_until_ready) "
           "inside a `with <lock>` critical section in a threaded plane — "
           "the wait for the device stalls every contending thread")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        if not _in_scope(parsed.rel):
            return []
        findings: List[Finding] = []
        rule = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.lock_depth = 0

            def visit_With(self, node: ast.With):
                locked = any(_lockish(i.context_expr) for i in node.items)
                if locked:
                    self.lock_depth += 1
                self.generic_visit(node)
                if locked:
                    self.lock_depth -= 1

            def visit_FunctionDef(self, node):
                # a nested def under a lock runs LATER, not under the lock
                saved, self.lock_depth = self.lock_depth, 0
                self.generic_visit(node)
                self.lock_depth = saved

            visit_AsyncFunctionDef = visit_FunctionDef
            visit_Lambda = visit_FunctionDef

            def visit_Call(self, node: ast.Call):
                if self.lock_depth > 0:
                    cname = call_name(node) or ""
                    if (cname in _SYNC_CALLS
                            or cname.split(".")[-1] == "block_until_ready"):
                        findings.append(rule.finding(
                            parsed, node,
                            f"{cname}() under a held lock — the readback "
                            "waits for the device while every other "
                            "thread blocks; move it outside the critical "
                            "section"))
                self.generic_visit(node)

        V().visit(parsed.tree)
        return findings


class ThreadSharedState(Rule):
    name = "thread-shared-state"
    severity = "warning"
    doc = ("the same self.<attr> mutated without a lock from several "
           "thread entry points of one class — a data race; guard with "
           "the class lock or reduce to a constant flag")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        if not _in_scope(parsed.rel):
            return []
        findings: List[Finding] = []
        for node in ast.walk(parsed.tree):
            if isinstance(node, ast.ClassDef):
                findings.extend(self._check_class(parsed, node))
        return findings

    def _check_class(self, parsed: ParsedFile,
                     cls: ast.ClassDef) -> List[Finding]:
        # thread entry points: methods referenced as Thread(target=self.X)
        entries: Set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Call):
                cname = (call_name(node) or "").split(".")[-1]
                if cname != "Thread":
                    continue
                for kw in node.keywords:
                    if (kw.arg == "target"
                            and isinstance(kw.value, ast.Attribute)
                            and isinstance(kw.value.value, ast.Name)
                            and kw.value.value.id == "self"):
                        entries.add(kw.value.attr)
        if len(entries) == 0:
            return []
        # per-entry-method unlocked non-constant self.<attr> writes
        unlocked: Dict[str, List] = {}
        for node in cls.body:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name == "__init__":
                continue  # happens-before any thread start
            if node.name not in entries:
                continue
            for attr, assign in self._unlocked_writes(node):
                unlocked.setdefault(attr, []).append((node.name, assign))
        findings = []
        for attr, sites in unlocked.items():
            methods = {m for m, _ in sites}
            if len(methods) >= 2:
                m, assign = sites[0]
                findings.append(self.finding(
                    parsed, assign,
                    f"self.{attr} written without a lock from "
                    f"{len(methods)} thread entry points "
                    f"({', '.join(sorted(methods))}) — racing writes; "
                    "guard with the class lock"))
        return findings

    def _unlocked_writes(self, fn):
        out = []

        class V(ast.NodeVisitor):
            def __init__(self):
                self.lock_depth = 0

            def visit_With(self, node):
                locked = any(_lockish(i.context_expr) for i in node.items)
                if locked:
                    self.lock_depth += 1
                self.generic_visit(node)
                if locked:
                    self.lock_depth -= 1

            def visit_Assign(self, node):
                if self.lock_depth == 0:
                    # constant flags (True/False/None/numbers) are the
                    # sanctioned GIL-atomic signal pattern
                    if not isinstance(node.value, ast.Constant):
                        for t in node.targets:
                            if (isinstance(t, ast.Attribute)
                                    and isinstance(t.value, ast.Name)
                                    and t.value.id == "self"
                                    and not t.attr.endswith("_lock")):
                                out.append((t.attr, node))
                self.generic_visit(node)

            def visit_AugAssign(self, node):
                if self.lock_depth == 0:
                    t = node.target
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        out.append((t.attr, node))
                self.generic_visit(node)

        V().visit(fn)
        return out


RULES = (HostSyncUnderLock, ThreadSharedState)
