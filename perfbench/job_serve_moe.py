"""The `serve_moe` job: the `serve` job (perfbench/job_serve.py) for a model
whose every layer routes its feed-forward over experts and whose attention
layers come in two kinds, window beside global. The configuration file's
`model_type` names the builder: which serve-only model of the program is
built, on weights made by which plain reference, and which reference judges
the tokens served.

Everything around the model is the `serve` job's own `run`: the same engine,
HTTP `POST /generate` streamed, the paged decoder, the closed loop of
clients, the warm-up of every prefill shape, the warm-in, the window, the
drain, so `serve_tokens_per_s`, the tails and `setup_s` mean here what they
mean there, and the readers get the same `ctx` keys.

The reference reads each compared request at one of a few padded lengths
(WIDTHS), not one width for all: the mix's median request is a fifth of its
longest, and the reference runs beside 11 GB of weights after the engine is
freed.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from perfbench import job_serve

# padded lengths a compared sequence is read at: the smallest that holds it
WIDTHS = (1024, 2048, 4096, 8192)


def _smallthinker(cell: Dict[str, Any]):
    """`smallthinker`: the program's HybridLM with rotary window layers
    beside global ones and dropless routed experts, served in the
    configuration's dtype at the cell's served context."""
    from deeplearning4j_tpu.models import hybrid
    from perfbench import reference_smallthinker

    conf = cell["conf"]
    cfg = hybrid.HybridConfig.from_published(
        conf, max_len=int(cell["model"]["max_len"]),
        dtype_policy=cell["model"].get("dtype_policy", "performance"))
    if np.dtype(cfg.compute_dtype).name != conf["weights_dtype"]:
        raise ValueError(f"the program serves {cfg.compute_dtype}, the "
                         f"configuration states {conf['weights_dtype']}")
    return (lambda params: hybrid.HybridLM.from_state(cfg, params),
            reference_smallthinker)


BUILDERS = {"smallthinker": _smallthinker}


def build(cell: Dict[str, Any]):
    """(params -> model, the reference module) for the cell's configuration.
    Imports the program's model first of all, so that a program without it
    fails here, at once."""
    kind = cell["conf"].get("model_type")
    if kind not in BUILDERS:
        raise ValueError(f"job serve_moe: no builder for model_type "
                         f"{kind!r} (have {sorted(BUILDERS)})")
    return BUILDERS[kind](cell)


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, chips: int) -> Dict[str, Any]:
    model_of, reference = build(cell)
    return job_serve.run(
        cell, seed, seconds, trace, t_start, chips,
        job_serve.Served(reference.init_params, model_of,
                         functools.partial(reference_gaps,
                                           reference=reference)))


def width_for(n_prompt: int, rows: int, widths=WIDTHS) -> int:
    """The padded length for a request: its prompt less one and the longest
    answer have to fit (the answer's rows are cut from `n_prompt - 1` on)."""
    need = n_prompt - 1 + rows
    fit = [w for w in widths if w >= need]
    if not fit:
        raise ValueError(f"a prompt of {n_prompt} tokens and {rows} answer "
                         f"rows fit none of the widths {widths}")
    return fit[0]


def reference_gaps(cell: Dict[str, Any], params, sample, *, reference,
                   lowp=None) -> List[float]:
    """The reference over each (prompt, served tokens) of `sample`, each at
    the smallest padded length that holds it, one count of answer rows for
    the whole cell."""
    rows = cell["mix"]["output_tokens"]["max"]
    widths = tuple(cell.get("reference_widths", WIDTHS))
    gaps: List[float] = []
    for prompt, served in sample:
        gaps += reference.serve_gaps(
            cell["conf"], params, prompt, served,
            width_for(len(prompt), rows, widths), lowp=lowp,
            rows=rows).tolist()
    return gaps
