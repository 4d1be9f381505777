"""graftlint — the project-invariant static-analysis plane.

AST-based (stdlib ``ast`` + ``tokenize``, zero dependencies, jax-free)
rule engine that mechanically enforces the contracts CLAUDE.md records as
prose: donation discipline, env-knob registry coverage, chaos-never-ambient,
ledger registration, signal-handler minimalism, jit determinism, lock
hygiene, docstring provenance.

Usage::

    python -m deeplearning4j_tpu.analysis            # lint the repo
    python -m deeplearning4j_tpu.analysis --json     # machine-readable
    python -m deeplearning4j_tpu.analysis --list-rules
    python -m deeplearning4j_tpu.analysis path/to/file.py dir/

Suppression (justification REQUIRED)::

    t0 = time.time()  # graftlint: disable=nondeterminism-in-jit -- host-side timer, not traced
    # graftlint: disable-file=host-sync-under-lock -- single-threaded tool

Gate: tests/test_analysis.py (quick tier) runs the full suite over the
committed tree and fails on any finding; ``repo_clean()`` is that sweep
as a boolean.
"""

from deeplearning4j_tpu.analysis.engine import (
    DEFAULT_TARGETS,
    Finding,
    ParsedFile,
    Report,
    Rule,
    all_rules,
    parse_file,
    rule_names,
    run_paths,
)

__all__ = [
    "DEFAULT_TARGETS", "Finding", "ParsedFile", "Report", "Rule",
    "all_rules", "parse_file", "rule_names", "run_paths", "repo_clean",
]


def repo_clean() -> bool:
    """True when the default-target sweep has zero findings."""
    return run_paths().clean
