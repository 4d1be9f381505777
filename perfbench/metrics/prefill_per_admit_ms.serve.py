"""Tick delay per admission, NOT the prefill's device time: how much one
admission lengthens the tick that follows it, as the host sees it. Over the
window's ticks (`serve.batch`) with `admits` >= 1, the median of (the tick
less the median tick with `admits` 0) over `admits`. The part of a prefill
that runs on the device while the host still books the admission is not in
it, so a faster host admission raises this number and a faster prefill need
not lower it; the device's own time per `jit_admit` is on the trace's
"XLA Modules" line, which no reader keeps yet (ROADMAP.md S16)."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.prefill_per_admit_ms(ctx)
