"""The one general traffic generator. A mix is a data file of parameters
under perfbench/traffic/; its `generator` key names one of the two kinds
here. Every seed gets the same amount of work. A chat mix's lengths are drawn
once from a fixed stream, and client c sends the same lengths in the same
order whatever the seed: the order decides which prompts meet in the opening
burst and so the tail of the time to first token, which read 513 to 563 ms
over six seeds while two runs of one seed agreed within 1% (PERF.md,
Findings). What the seed changes is what no time depends on: the prompts'
tokens, the system prompts' tokens and the sampling seeds.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

SIZES_STREAM = 20250925   # the fixed stream the sizes of a mix come from


def train_batch(mix: Dict[str, Any], vocab: int, seed: int, step: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Tokens and next-token targets of optimizer step `step`: uniform ids,
    every row different, the same for the same (seed, step)."""
    rng = np.random.default_rng([int(seed), int(step)])
    toks = rng.integers(0, vocab, (mix["batch"], mix["seq"] + 1),
                        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def _lengths(spec: Dict[str, Any], n: int, rng) -> np.ndarray:
    if spec["distribution"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['distribution']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def chat_requests(mix: Dict[str, Any], vocab: int, seed: int,
                  per_client: int) -> List[List[Dict[str, Any]]]:
    """For each client its list of requests, in the order it will send
    them. A request: `tokens` (a system prompt and a user part), `n_new`,
    `temperature`, `seed`."""
    n_clients = mix["clients"]
    total = n_clients * per_client
    sizes = np.random.default_rng(SIZES_STREAM)
    user = _lengths(mix["user_tokens"], total, sizes)
    out = _lengths(mix["output_tokens"], total, sizes)
    which = sizes.integers(0, mix["system_prompts"], total)
    rng = np.random.default_rng(int(seed))
    systems = rng.integers(0, vocab, (mix["system_prompts"],
                                      mix["system_tokens"]), dtype=np.int32)
    n_greedy = int(round(n_clients * mix["greedy_share"]))
    clients: List[List[Dict[str, Any]]] = []
    for c in range(n_clients):
        temp = 0.0 if c < n_greedy else float(mix["temperature"])
        reqs = []
        for j in range(per_client):
            i = c * per_client + j
            body = rng.integers(0, vocab, int(user[i]), dtype=np.int32)
            reqs.append({
                "tokens": np.concatenate([systems[which[i]], body]),
                "n_new": int(out[i]),
                "temperature": temp,
                "seed": int(rng.integers(0, 2**31 - 1)),
            })
        clients.append(reqs)
    return clients


def warm_lengths(mix: Dict[str, Any]) -> List[int]:
    """Prompt lengths that reach every prefill width the mix can: from its
    shortest prompt to its longest, the next never more than a quarter
    longer than the last (the step at length n is the largest power of two
    that is at most n / 4). A program pads a prompt up to the next rung of
    a ladder of widths; where no rung is less than a quarter above the one
    below (the program's are powers of two and one and a half times them,
    so a third at the least) some length here falls on every rung a prompt
    of the mix can, and few fall on the same: one request a length is what
    the warm-up costs in every run."""
    lo = mix["system_tokens"] + mix["user_tokens"]["min"]
    hi = mix["system_tokens"] + mix["user_tokens"]["max"]
    out, n = [], lo
    while n < hi:
        out.append(n)
        n += 1 << max(0, (n // 4).bit_length() - 1)
    return out + [hi]
