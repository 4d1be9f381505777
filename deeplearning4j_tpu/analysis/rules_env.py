"""Env-knob registry enforcement (ops/env.py is THE table) and the
chaos-never-ambient contract.

The knob table (deeplearning4j_tpu/ops/env.py) exists so a typo'd
``DL4J_TPU_*`` name fails loudly instead of silently meaning "default".
That only holds if every read actually goes through the table — this
rule closes the loop:

* no ``os.environ`` READ of a ``DL4J_TPU_*`` name outside ops/env.py
  (writes — ``os.environ[k] = v`` / ``setdefault`` — stay legal: tests
  and bench legs pin knobs for subprocesses);
* every ``DL4J_TPU_*`` string literal anywhere (code OR docstring) names
  a registered knob — typos fail the gate;
* project-level: the table and CLAUDE.md agree both ways (every knob
  documented, every documented name registered).

Chaos (resilience/chaos.py) is config-driven and never ambient: a chaos
object reaches a component only as an explicit constructor argument. An
env-read inside the chaos module, or a ``*ChaosConfig(...)`` constructed
at import time / as a parameter default, would arm fault injection
behind the caller's back — exactly what the contract forbids.
"""

from __future__ import annotations

import ast
import os
import re
from typing import List, Optional, Set

from deeplearning4j_tpu.analysis.engine import Finding, ParsedFile, Rule
from deeplearning4j_tpu.analysis.rules_jit import call_name, dotted_name
from deeplearning4j_tpu.ops.env import KNOBS

KNOB_NAME_RE = re.compile(r"DL4J_TPU_[A-Z0-9][A-Z0-9_]*")

#: name-shaped fragments that are prefixes/patterns in prose (e.g.
#: "DL4J_TPU_SERVE_*"), not knobs themselves
_PROSE_OK = {"DL4J_TPU_SERVE", "DL4J_TPU_FLEET", "DL4J_TPU_CKPT",
             "DL4J_TPU_OBS"}


def _is_env_table(rel: str) -> bool:
    return rel.replace(os.sep, "/").endswith("deeplearning4j_tpu/ops/env.py")


def _extract_names(text: str) -> Set[str]:
    out = set()
    for m in KNOB_NAME_RE.finditer(text):
        name = m.group(0).rstrip("_")
        out.add(name)
    return out


class EnvKnobRegistry(Rule):
    name = "env-knob-registry"
    severity = "error"
    doc = ("DL4J_TPU_* env read outside ops/env.py, or a DL4J_TPU_* "
           "literal that is not a registered knob (typo)")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        if _is_env_table(parsed.rel):
            return []
        findings: List[Finding] = []
        for node in ast.walk(parsed.tree):
            # -- direct reads: os.environ.get / os.getenv -----------------
            if isinstance(node, ast.Call):
                cname = call_name(node) or ""
                if cname in ("os.environ.get", "os.getenv",
                             "environ.get") and node.args:
                    first = node.args[0]
                    if (isinstance(first, ast.Constant)
                            and isinstance(first.value, str)
                            and first.value.startswith("DL4J_TPU_")):
                        findings.append(self.finding(
                            parsed, node,
                            f"direct os.environ read of {first.value} — "
                            "go through deeplearning4j_tpu.ops.env "
                            "(raw/get_int/get_float/get_bool/nonempty) so "
                            "typos fail and the table stays the one source "
                            "of defaults"))
            # -- subscript READ: os.environ["DL4J_TPU_X"] in Load ctx -----
            if (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)
                    and (dotted_name(node.value) or "").endswith("environ")):
                sl = node.slice
                if (isinstance(sl, ast.Constant) and isinstance(sl.value, str)
                        and sl.value.startswith("DL4J_TPU_")):
                    findings.append(self.finding(
                        parsed, node,
                        f"direct os.environ[{sl.value!r}] read — go "
                        "through deeplearning4j_tpu.ops.env"))
            # -- literal typo check (code and docstrings alike) -----------
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)):
                for name in _extract_names(node.value):
                    if name not in KNOBS and name not in _PROSE_OK:
                        findings.append(self.finding(
                            parsed, node,
                            f"{name} is not a registered knob — add it to "
                            "ops/env.py (and CLAUDE.md) or fix the typo"))
        return findings

    def check_project(self, root, parsed_files) -> List[Finding]:
        claude = os.path.join(root, "CLAUDE.md")
        try:
            with open(claude, encoding="utf-8") as f:
                text = f.read()
        except OSError:
            return []
        documented = _extract_names(text)
        findings: List[Finding] = []
        for name in sorted(set(KNOBS) - documented):
            findings.append(Finding(
                self.name, "CLAUDE.md", 1,
                f"registered knob {name} is undocumented in CLAUDE.md — "
                "add it next to its plane's section", self.severity))
        for name in sorted(documented - set(KNOBS) - _PROSE_OK):
            findings.append(Finding(
                self.name, "CLAUDE.md", 1,
                f"CLAUDE.md documents {name} but it is not a registered "
                "knob — register it in ops/env.py or fix the doc",
                self.severity))
        return findings


class ChaosAmbient(Rule):
    name = "chaos-ambient"
    severity = "error"
    doc = ("chaos config constructed at import time / as a parameter "
           "default, or an env read inside the chaos module — fault "
           "injection must arrive as an explicit constructor argument")

    def check(self, parsed: ParsedFile) -> List[Finding]:
        findings: List[Finding] = []
        rel = parsed.rel.replace(os.sep, "/")
        in_chaos_module = rel.endswith("resilience/chaos.py")
        func_depth = 0

        rule = self

        class V(ast.NodeVisitor):
            def _enter(self, node):
                nonlocal func_depth
                for d in (list(node.args.defaults)
                          + list(node.args.kw_defaults)):
                    if d is not None:
                        self._check_default(d)
                func_depth += 1
                for stmt in node.body:
                    self.visit(stmt)
                func_depth -= 1

            visit_FunctionDef = _enter
            visit_AsyncFunctionDef = _enter

            def _check_default(self, d):
                for sub in ast.walk(d):
                    if isinstance(sub, ast.Call):
                        cname = (call_name(sub) or "").split(".")[-1]
                        if cname.endswith("ChaosConfig"):
                            findings.append(rule.finding(
                                parsed, sub,
                                f"{cname}(...) as a parameter default is "
                                "ambient chaos — default to None and "
                                "require the caller to pass a config"))

            def visit_Call(self, node):
                cname = (call_name(node) or "")
                leaf = cname.split(".")[-1]
                if leaf.endswith("ChaosConfig") and func_depth == 0:
                    findings.append(rule.finding(
                        parsed, node,
                        f"{leaf}(...) at import time is ambient chaos — "
                        "construct configs inside the test/bench that "
                        "owns them"))
                if in_chaos_module and cname in (
                        "os.environ.get", "os.getenv", "environ.get"):
                    findings.append(rule.finding(
                        parsed, node,
                        "env read inside the chaos module — chaos is "
                        "config-driven, never ambient; plumb the value "
                        "through the config object"))
                self.generic_visit(node)

        V().visit(parsed.tree)
        return findings


RULES = (EnvKnobRegistry, ChaosAmbient)
