"""The `serve_sambay` job: the `serve` job (perfbench/job_serve.py) for a
decoder-hybrid-decoder (SambaY): Mamba-1 layers and window differential
attention in a self-decoder, one full attention layer whose keys and values
the cross-decoder's layers read, beside gated memory units. The
configuration file's `model_type` says which serve-only model of the program
is built, on weights made by which plain reference, and which reference
judges the tokens served.

Everything around the model is the `serve` job's own `run`: the same engine,
HTTP `POST /generate` streamed, the paged decoder, the closed loop of
clients, the warm-up of every prefill shape, the warm-in, the window, the
drain, so `serve_tokens_per_s`, the tails and `setup_s` mean here what they
mean there, and the readers get the same `ctx` keys.

The reference reads each compared request at one of a few padded lengths
(the cell's `reference_widths`, perfbench/job_serve_moe.py's `width_for`),
every layer over every position: the admission's early stop is checked, not
assumed. Besides the logit numbers `correct` holds `state_bits_lost` over
the Mamba-1 state pool as the last tick left it (perfbench/job_serve_hybrid.py
reads the Mamba-2 pool the same way): served tokens cannot tell a state kept
in fewer bits than the configuration states.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

from perfbench import job_serve
from perfbench.job_serve_hybrid import mantissa_bits_lost
from perfbench.job_serve_moe import reference_gaps


def _phi4flash(cell: Dict[str, Any]):
    """`phi4flash`: the program's HybridLM with Mamba-1, differential
    attention, cross-attention and GMU layers, served in the configuration's
    dtype at the cell's served context."""
    from deeplearning4j_tpu.models import hybrid
    from perfbench import reference_phi4flash

    conf = cell["conf"]
    cfg = hybrid.HybridConfig.from_published(
        conf, max_len=int(cell["model"]["max_len"]),
        dtype_policy=cell["model"].get("dtype_policy", "performance"))
    if np.dtype(cfg.compute_dtype).name != conf["weights_dtype"]:
        raise ValueError(f"the program serves {cfg.compute_dtype}, the "
                         f"configuration states {conf['weights_dtype']}")
    return (lambda params: hybrid.HybridLM.from_state(cfg, params),
            reference_phi4flash, "selective")


MODELS = {"phi4flash": _phi4flash}


def build(cell: Dict[str, Any]):
    """(params -> model, the reference module, the state pool's leaf that
    the configuration holds to float32) for the cell's configuration.
    Builds the program's model configuration first of all, so that a
    program without it fails here, at once."""
    kind = cell["conf"].get("model_type")
    if kind not in MODELS:
        raise ValueError(f"job serve_sambay: no model for model_type "
                         f"{kind!r} (have {sorted(MODELS)})")
    return MODELS[kind](cell)


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, chips: int) -> Dict[str, Any]:
    model_of, reference, state_leaf = build(cell)

    def state_bits_lost(engine) -> Dict[str, float]:
        # the state pool as the last tick left it
        return {"state_bits_lost": mantissa_bits_lost(
            buf for pool in engine.state_pools().values()
            for buf in pool.get(state_leaf, ()))}

    return job_serve.run(
        cell, seed, seconds, trace, t_start, chips,
        job_serve.Served(reference.init_params, model_of,
                         functools.partial(reference_gaps,
                                           reference=reference),
                         state_bits_lost))
