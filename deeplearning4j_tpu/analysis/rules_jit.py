"""jit-discipline rules.

* buffer donation invalidates the caller's arrays, so ``donate_argnums``
  is only allowed inside ops/dispatch.py, which owns the no-re-read
  contract (and its tests);
* a traced function reading the wall clock or an unseeded RNG bakes one
  sample into the compiled program — nondeterminism the retrace cache
  then hides.
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Set

from deeplearning4j_tpu.analysis.engine import Finding, ParsedFile, Rule


def dotted_name(node: ast.AST) -> Optional[str]:
    """'jax.config.update' for an Attribute/Name chain; None otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Optional[str]:
    return dotted_name(call.func)


class DonationThroughDispatch(Rule):
    name = "donation-through-dispatch"
    severity = "error"
    doc = ("jax.jit(donate_argnums=...) outside ops/dispatch.py — all "
           "buffer donation flows through the dispatch helpers, which own "
           "the no-re-read contract and its tests")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        if parsed.rel.replace(os.sep, "/").endswith("ops/dispatch.py"):
            return []
        findings: List[Finding] = []
        for node in ast.walk(parsed.tree):
            if isinstance(node, ast.Call):
                name = (call_name(node) or "").split(".")[-1]
                # direct jax.jit(...) AND the decorator idiom
                # functools.partial(jax.jit, donate_argnums=...)
                if name == "partial":
                    if not any(
                            (dotted_name(a) or "").split(".")[-1] == "jit"
                            for a in node.args):
                        continue
                elif name != "jit":
                    continue
                for kw in node.keywords:
                    if kw.arg in ("donate_argnums", "donate_argnames"):
                        findings.append(self.finding(
                            parsed, node,
                            "direct donation outside ops/dispatch.py — a "
                            "caller that re-reads a donated arg gets "
                            "deleted-buffer errors only on the backends "
                            "that implement donation; route through "
                            "dispatch.train_step_jit/instrumented_jit"))
        return findings


#: nondeterministic calls that must not appear inside traced functions
NONDET_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic", "time.time_ns",
    "os.urandom", "random.random", "random.randint", "random.choice",
    "random.shuffle", "random.uniform", "np.random.rand",
    "np.random.randn", "np.random.randint", "np.random.normal",
    "np.random.uniform", "np.random.permutation", "numpy.random.rand",
    "numpy.random.randn",
}


class NondeterminismInJit(Rule):
    name = "nondeterminism-in-jit"
    severity = "error"
    doc = ("wall clock / unseeded RNG inside a jitted function — the value "
           "is sampled ONCE at trace time and baked into the compiled "
           "program; thread jax.random keys or pass host values as args")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        # traced defs: decorated with *jit*, or passed by name to a call
        # whose callee mentions jit (instrumented_jit(step), jax.jit(fn))
        traced: List[ast.AST] = []
        jit_arg_names: Set[str] = set()
        for node in ast.walk(parsed.tree):
            if isinstance(node, ast.Call):
                cname = (call_name(node) or "")
                if "jit" in cname.split(".")[-1]:
                    for arg in node.args:
                        if isinstance(arg, ast.Name):
                            jit_arg_names.add(arg.id)
        for node in ast.walk(parsed.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                deco = [dotted_name(d.func) if isinstance(d, ast.Call)
                        else dotted_name(d) for d in node.decorator_list]
                if any(d and "jit" in d.split(".")[-1] for d in deco):
                    traced.append(node)
                elif node.name in jit_arg_names:
                    traced.append(node)
        findings: List[Finding] = []
        for fn in traced:
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    name = call_name(node)
                    if name in NONDET_CALLS:
                        findings.append(self.finding(
                            parsed, node,
                            f"{name}() inside traced function "
                            f"{getattr(fn, 'name', '<fn>')!r} is evaluated "
                            "once at trace time, then frozen into the "
                            "compiled program"))
        return findings


RULES = (DonationThroughDispatch, NondeterminismInJit)
