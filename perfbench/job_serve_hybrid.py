"""The `serve_hybrid` job: the `serve` job (perfbench/job_serve.py) for a
model that is not the GPT-2-shaped TransformerLM. The configuration file's
`model_type` names the builder: which serve-only model of the program is
built, on weights made by which plain reference, and which reference judges
the tokens served.

Everything around the model is the `serve` job's own `run`: the same engine,
HTTP `POST /generate` streamed, the paged decoder, the closed loop of
clients, the warm-up of every prefill shape, the warm-in, the window, the
drain, so `serve_tokens_per_s`, the tails and `setup_s` mean here what they
mean there, and the readers get the same `ctx` keys (`traced["ticks"]`, the
decoder's ticks of the traced tail, which the state-update readers lay beside
the device's events, among them). This file adds to `correct` one number that
reads the recurrent state itself (`state_bits_lost`): served tokens cannot
tell a state kept in fewer bits than the configuration states (PERF.md, "How
correct is decided").
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from perfbench import job_serve
from perfbench.job_serve import reference_width


def _granite(cell: Dict[str, Any]):
    """`granitemoehybrid` without experts: the program's HybridLM, served
    in the configuration's dtype at the cell's served context."""
    from deeplearning4j_tpu.models import hybrid
    from perfbench import reference_granite

    conf = cell["conf"]
    cfg = hybrid.HybridConfig.from_published(
        conf, max_len=int(cell["model"]["max_len"]))
    if np.dtype(cfg.compute_dtype).name != conf["weights_dtype"]:
        raise ValueError(f"the program serves {cfg.compute_dtype}, the "
                         f"configuration states {conf['weights_dtype']}")
    return (lambda params: hybrid.HybridLM.from_state(cfg, params),
            reference_granite, "ssm")


BUILDERS = {"granitemoehybrid": _granite}


def build(cell: Dict[str, Any]):
    """(params -> model, the reference module, the state pool's leaf that
    the configuration holds to float32) for the cell's configuration.
    Imports the program's model first of all, so that a program without it
    fails here, at once."""
    kind = cell["conf"].get("model_type")
    if kind not in BUILDERS:
        raise ValueError(f"job serve_hybrid: no builder for model_type "
                         f"{kind!r} (have {sorted(BUILDERS)})")
    return BUILDERS[kind](cell)


def mantissa_bits_lost(arrays) -> float:
    """How many of float32's 23 mantissa bits the worst of `arrays` leaves
    unused: for an array, the largest k such that 99% of its elements that
    are not 0 have their k lowest bits at 0. Arithmetic in float32 leaves
    none (the lowest bit is set in half of the elements: 0); values that
    were rounded to bfloat16 on the way, or are held in it, read 16, to
    float16 13. Nothing to read is not a number."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def counts(x):
        bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        live = (bits << 1) != 0
        # the lowest bit set, the 24th at the latest: trailing zeros 0..23
        low = bits | jnp.uint32(1 << 23)
        zeros = lax.population_count((low & (~low + jnp.uint32(1)))
                                     - jnp.uint32(1))
        return jnp.sum(live), jnp.stack(
            [jnp.sum(live & (zeros >= k)) for k in range(1, 24)])

    lost = float("nan")
    for x in arrays:
        n, at_least = (np.asarray(v) for v in counts(x))
        if n:
            held = [k for k in range(1, 24) if at_least[k - 1] >= 0.99 * n]
            lost = np.nanmax([lost, float(max(held, default=0))])
    return float(lost)


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


def reference_gaps(cell: Dict[str, Any], params, sample, *, reference,
                   lowp=None) -> List[float]:
    """The reference over each (prompt, served tokens) of `sample`, one
    padded length and one count of answer rows for the whole cell."""
    mix = cell["mix"]
    width = reference_width(mix)
    gaps: List[float] = []
    for prompt, served in sample:
        gaps += reference.serve_gaps(
            cell["conf"], params, prompt, served, width, lowp=lowp,
            rows=mix["output_tokens"]["max"]).tolist()
    return gaps
