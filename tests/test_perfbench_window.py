"""The serving window's reader and the A/A tool's arithmetic, in the gate
(ISSUE 37; left over from PR 36, which could touch only the benchmark's own
files): the cases of perfbench/tests/test_window.py (13: which request and
which token belong to a window, every candidate for a tail, the worst value
of a failed request, the silences, the attended pairs) and of
perfbench/tests/test_aa.py (15: the driver's spread and the contract's, a
bound's rounding, a check's verdicts, the tables), brought in as they
stand. Plain Python over hand-built requests and result files: no JAX, no
program, nothing measured. `perfbench/tests` itself stays outside `tests/`
and outside the gate; what this file collects is those two files' tests.

Reference anchor: none in the reference (it has no serving benchmark); the
window's definition is PERF.md section 2.
"""
from perfbench.tests.test_aa import *        # noqa: F401,F403
from perfbench.tests.test_window import *    # noqa: F401,F403
