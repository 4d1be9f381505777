"""Streaming /generate against the paged-KV serving plane.

A client's view of the block-pool decode path (deeplearning4j_tpu/
serving/paged.py — the subsystem the reference's one-record Camel route,
dl4j-streaming/.../routes/DL4jServeRouteBuilder.java, never grew):

  1. a ServingEngine serves a small TransformerLM with the paged KV
     arena (DL4J_TPU_SERVE_KV_BLOCK) and two SLO classes;
  2. several requests SHARE a long system prompt — the prefix cache
     hashes the shared blocks once and later admissions reference them
     instead of recomputing/storing their KV (watch prefix_hits and
     kv capacity at /models);
  3. one request streams: POST /generate with ``"stream": true`` chunks
     NDJSON ``{"token": t}`` events per decode tick and a final
     ``{"done": true, "tokens": [...]}`` record.

Run from the repo root:  python examples/serving_generate.py
"""

import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
)
from deeplearning4j_tpu.ops import env as envknob  # noqa: E402
from deeplearning4j_tpu.serving import ServingEngine  # noqa: E402

# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")

D_MODEL = 32 if SMOKE else 128
N_LAYERS = 2 if SMOKE else 4
MAX_LEN = 64 if SMOKE else 256
N_NEW = 8 if SMOKE else 32
N_CLIENTS = 3 if SMOKE else 6
VOCAB = 64


def post(url, path, payload, timeout=300):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def get(url, path, timeout=60):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return json.loads(r.read())


def main() -> None:
    lm = TransformerLM(TransformerConfig(
        vocab_size=VOCAB, d_model=D_MODEL, n_layers=N_LAYERS,
        n_heads=4, d_ff=2 * D_MODEL, max_len=MAX_LEN, use_flash=False))
    eng = ServingEngine(model=lm, kv_block=8,
                        slo_classes="interactive:60,batch:300").start()
    try:
        kv = get(eng.url, "/models")["kv"]["default@v1"]
        print(f"=== paged KV arena: {kv['blocks_total']} blocks x "
              f"{kv['block_tokens']} tokens = {kv['capacity_tokens']} "
              f"tokens across {kv['lanes']} lanes ===")

        # a shared system prompt long enough to span whole KV blocks —
        # the prefix cache dedupes it across the client requests below
        rng = np.random.default_rng(0)
        system = rng.integers(1, VOCAB, MAX_LEN // 2).tolist()

        print(f"--- {N_CLIENTS} clients, one shared system prompt ---")
        for i in range(N_CLIENTS):
            out = post(eng.url, "/generate",
                       {"tokens": system + [i + 1], "n_new": N_NEW,
                        "temperature": 0.0, "slo": "interactive"})
            print(f"client {i}: {out['tokens'][0][:8]}...")

        served = get(eng.url, "/metrics")["serving"]
        print(f"prefix cache: {served['prefix_hits']}/"
              f"{served['prefix_lookups']} block lookups hit "
              f"(shared system prompt stored once)")

        print("--- streaming client (NDJSON chunks per decode tick) ---")
        req = urllib.request.Request(
            eng.url + "/generate",
            data=json.dumps({"tokens": system + [42], "n_new": N_NEW,
                             "temperature": 0.0, "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            for raw in resp:
                event = json.loads(raw)
                if "token" in event:
                    print(f"  token: {event['token']}")
                elif event.get("done"):
                    print(f"  done: {event['tokens']}")

        kv = get(eng.url, "/models")["kv"]["default@v1"]
        print(f"=== arena after traffic: {kv['blocks_in_use']} blocks "
              f"held ({kv['prefix_blocks_cached']} by the prefix cache), "
              f"{kv['blocks_total'] - kv['blocks_in_use']} free ===")
    finally:
        eng.stop()


if __name__ == "__main__":
    main()
