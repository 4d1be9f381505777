"""ServingEngine: the HTTP front door over batcher + decoder + registry.

Replaces the request-at-a-time core of the reference's serving route
(DL4jServeRouteBuilder.java — restore one checkpoint, run output() per
record) with the dynamically-batched engine while keeping the route's
wire surface (streaming/serving.ModelServer subclasses this unchanged):

  POST /predict   {"record": [...]}           -> {"output": [...]}
                  {"record_base64": "..."}     -> {"output": [...]}
                  {"batch": [[...], ...]}      -> {"outputs": [[...], ...]}
                  optional: "model", "version", "timeout_s"
                  429 when the batcher queue is full (backpressure),
                  504 when the request's deadline expires in queue.
  POST /generate  {"tokens": [[ids]], "n_new": K, "temperature"?,
                  "top_k"?, "top_p"?, "seed"?, "slo"?} -> {"tokens":
                  [[ids]]} (paged block-pool decode by default —
                  serving/paged.py; the fixed slot pool when
                  DL4J_TPU_SERVE_KV_BLOCK=0; lm.generate for static
                  filters / mesh / MoE models). With "stream": true the
                  response is chunked application/x-ndjson: one
                  {"token": t} line per generated token as it is
                  sampled, then {"done": true, "tokens": [...]} (or
                  {"error": ...} if generation failed mid-stream).
  GET  /health    {"ok": true, "model": "<type>", "models": [...]}
  GET  /metrics   {"serving": <ServingStats>, "models": [<per-model
                  state incl. dispatch_stats>]}
  GET  /models    registry listing; POST /models {"action": load|warmup|
                  serve|unload, ...} drives the lifecycle.

Env knobs (read at engine construction):
  DL4J_TPU_SERVE_BATCH       "0" disables dynamic batching (naive locked
                             per-request path — the bench's comparison leg)
  DL4J_TPU_SERVE_MAX_BATCH   batcher flush size (default 64)
  DL4J_TPU_SERVE_MAX_WAIT_MS batcher deadline flush (default 10)
  DL4J_TPU_SERVE_QUEUE_CAP   queued rows before 429 (default 512)
  DL4J_TPU_SERVE_TIMEOUT_S   default per-request deadline (default 60)
  DL4J_TPU_SERVE_SLOTS       continuous-decode slot pool size (default 4;
                             the paged pool reuses it as its lane FLOOR)
  DL4J_TPU_SERVE_CONTINUOUS  "0" routes /generate to lm.generate always
  DL4J_TPU_SERVE_KV_BLOCK    paged-KV block size in tokens (default 16;
                             "0" falls back to the fixed slot pool)
  DL4J_TPU_SERVE_KV_BLOCKS   paged-KV arena size in blocks (default 0 =
                             auto-size from DL4J_TPU_HBM_GB via
                             ops/memory.kv_arena_blocks)
  DL4J_TPU_SERVE_SLO_CLASSES scheduling classes "name:deadline_s,..."
                             highest priority first ("" = one default
                             class at the request timeout — pre-SLO FIFO)

Resilience plane (ISSUE 8 — serving/resilience.py):
  DL4J_TPU_SERVE_BREAKER_FAILS consecutive inference failures that open a
                             model's circuit breaker (default 5; 0
                             disables). Open breaker -> requests fast-fail
                             HTTP 503 + Retry-After instead of piling
                             onto a doomed queue; after the cooldown one
                             half-open probe closes it on success.
  DL4J_TPU_SERVE_WATCHDOG_S  in-flight dispatch wall deadline (default
                             30; 0 disables): a hung device call fails
                             its futures with a diagnosis, trips the breaker, journals
                             serve.wedged and replaces the worker thread.
  DL4J_TPU_SERVE_DRAIN_S     graceful-drain deadline (default 20):
                             stop(drain=True) / SIGTERM stops admission
                             (503), drains admitted work to completion,
                             then flushes the obs journal — the serving
                             twin of ResilientTrainer's
                             checkpoint-before-death.
"""

from __future__ import annotations

import base64
import itertools
import json
import math
import os
import queue as stdqueue
import signal
import threading
import time
import weakref
from concurrent.futures import TimeoutError as FutureTimeoutError
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from deeplearning4j_tpu.obs import journal as obs_journal
from deeplearning4j_tpu.obs import registry as obs_registry
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.obs.exporter import PROMETHEUS_CONTENT_TYPE
from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.serving.batcher import (
    DynamicBatcher,
    QueueFullError,
    RequestTimeoutError,
)
from deeplearning4j_tpu.retrieval.stats import RetrievalStats
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.serving.resilience import (
    BreakerOpenError,
    CircuitBreaker,
    ClientRequestError,
    DrainingError,
    ModelWedgedError,
    WorkerDeadError,
    _env_float,
    breaker_fails_default,
    drain_s_default,
    watchdog_s_default,
)
from deeplearning4j_tpu.serving.slo import parse_slo_classes
from deeplearning4j_tpu.serving.telemetry import ServingStats


class ServingEngine:
    def __init__(self, model=None, model_path: Optional[str] = None,
                 port: int = 0, input_shape=None, *, normalizer=None,
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 queue_capacity: Optional[int] = None,
                 request_timeout_s: Optional[float] = None,
                 slots: Optional[int] = None,
                 kv_block: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 mesh_devices: Optional[int] = None,
                 role: Optional[str] = None,
                 slo_classes: Optional[str] = None,
                 breaker_fails: Optional[int] = None,
                 breaker_cooldown_s: float = 2.0,
                 watchdog_s: Optional[float] = None,
                 drain_s: Optional[float] = None,
                 chaos=None,
                 handle_signals: bool = False) -> None:
        from deeplearning4j_tpu.ops import dispatch

        dispatch.enable_compile_cache()
        self.max_batch = int(max_batch if max_batch is not None
                             else _env_float("DL4J_TPU_SERVE_MAX_BATCH", 64))
        self.max_wait_ms = (max_wait_ms if max_wait_ms is not None
                            else _env_float("DL4J_TPU_SERVE_MAX_WAIT_MS", 10))
        self.queue_capacity = int(
            queue_capacity if queue_capacity is not None
            else _env_float("DL4J_TPU_SERVE_QUEUE_CAP", 512))
        self.request_timeout_s = (
            request_timeout_s if request_timeout_s is not None
            else _env_float("DL4J_TPU_SERVE_TIMEOUT_S", 60))
        self.slots = int(slots if slots is not None
                         else _env_float("DL4J_TPU_SERVE_SLOTS", 4))
        # paged-KV plane (serving/paged.py): block size 0 = fixed pool
        self.kv_block = int(kv_block if kv_block is not None
                            else _env_float("DL4J_TPU_SERVE_KV_BLOCK", 16))
        self.kv_blocks = int(kv_blocks if kv_blocks is not None
                             else _env_float("DL4J_TPU_SERVE_KV_BLOCKS", 0))
        # mesh serving (ISSUE 18, serving/mesh.py): >= 2 shards the
        # paged decode tick over that many devices; the decoder build
        # GATES incompatible knobs loudly (never a silent dense
        # fallback). The import is lazy so engines that never decode
        # don't pull the mesh plane in.
        self.mesh_devices = int(
            mesh_devices if mesh_devices is not None
            else _env_float("DL4J_TPU_SERVE_MESH", 0))
        # prefill/decode disaggregation role: routing metadata published
        # with the replica addr (serving/fleet.py); a prefill-role
        # engine still answers everything — the ROUTER enforces the
        # split, the role just declares intent
        self.role = (role if role is not None
                     else envknob.raw("DL4J_TPU_SERVE_ROLE", "")
                     ).strip().lower()
        if self.role not in ("", "prefill", "decode"):
            raise ValueError(
                f"DL4J_TPU_SERVE_ROLE {self.role!r} must be '', "
                "'prefill' or 'decode'")
        # a typo'd operator spec must fail HERE, not collapse to FIFO
        self.slo_classes = parse_slo_classes(
            slo_classes if slo_classes is not None
            else envknob.raw("DL4J_TPU_SERVE_SLO_CLASSES", ""))
        self.batching_enabled = (
            envknob.raw("DL4J_TPU_SERVE_BATCH", "").strip().lower()
            not in ("0", "off", "false", "no"))
        self.continuous_enabled = (
            envknob.raw("DL4J_TPU_SERVE_CONTINUOUS", "").strip().lower()
            not in ("0", "off", "false", "no"))
        self.stats = ServingStats()
        # the serving ledger joins the central MetricsRegistry (ISSUE 7):
        # one Prometheus scrape covers serving counters AND every
        # registered net ledger (dispatch/memory/pipeline/resilience);
        # completed-request latencies feed a real bucket histogram there
        _metrics = obs_registry.default_registry()
        _metrics.register_ledger(self, "serving_stats", self.stats)
        self.stats.on_latency = lambda s: _metrics.histogram(
            "dl4j_serving_latency_seconds", s)
        self._rid = itertools.count(1)  # observability request ids
        # -- resilience plane (serving/resilience.py) ---------------------
        self.breaker_fails = int(breaker_fails if breaker_fails is not None
                                 else breaker_fails_default())
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.watchdog_s = float(watchdog_s if watchdog_s is not None
                                else watchdog_s_default())
        self.drain_s = float(drain_s if drain_s is not None
                             else drain_s_default())
        self.chaos = chaos  # resilience/chaos.ServingChaos, never ambient
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._draining = False   # admission gate (checked per request)
        self._drained = False    # a full drain() pass already ran
        self._old_handlers: Dict[int, Any] = {}
        self.registry = ModelRegistry(chaos=chaos, stats=self.stats)
        self._batchers: Dict[str, DynamicBatcher] = {}
        # /embed rides its OWN per-record batchers (ISSUE 17): embedding
        # rows and /predict rows share a model but not an output shape,
        # and the DynamicBatcher contract is one infer fn per queue
        self._embed_batchers: Dict[str, DynamicBatcher] = {}
        # named retrieval/store.VectorStore instances behind /search;
        # engine-level embed/search counters ride the same ledger class
        # the stores register per-index
        self._indexes: Dict[str, Any] = {}
        self.retrieval_stats = RetrievalStats()
        _metrics.register_ledger(self, "retrieval_stats",
                                 self.retrieval_stats)
        self._decoders: Dict[str, Any] = {}
        # LM records found ineligible for a KV pool -> the reason; their
        # /generate goes through lm.generate and /models says why
        self._no_decoder: Dict[str, str] = {}
        self._lock = threading.Lock()       # naive path + generate serialization
        self._engine_lock = threading.Lock()  # batcher/decoder creation
        # shadow mirror (ISSUE 14 — online/promote.ShadowMirror): when
        # attached, a fraction of answered /predict traffic is offered to
        # the candidate model OFF-thread; offer() never raises, never
        # blocks, never votes a breaker — the client path is unchanged
        self._shadow = None
        if model is not None or model_path is not None:
            # normalizer: explicit wins; a checkpoint zip's own section
            # otherwise (registry.load reads it) — /predict then applies
            # the exact statistics the model was trained under
            rec = self.registry.load("default", model=model,
                                     model_path=model_path,
                                     input_shape=input_shape,
                                     normalizer=normalizer)
            self.registry.serve(rec.name, rec.version)
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port),
                                          self._make_handler())
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None
        if handle_signals:
            self.install_signal_handlers()

    # -- compatibility surface (streaming/serving.ModelServer) ------------
    @property
    def model(self):
        rec = self.registry.default()
        return rec.model if rec is not None else None

    @property
    def input_shape(self):
        rec = self.registry.default()
        return rec.input_shape if rec is not None else None

    def predict(self, x: np.ndarray,
                timeout_s: Optional[float] = None) -> np.ndarray:
        """Batch-of-rows inference through the engine (dynamic batcher when
        enabled, the locked direct path otherwise)."""
        return self.predict_for(None, None, x, timeout_s=timeout_s)

    def _admit(self, rec) -> CircuitBreaker:
        """Per-request admission gate: draining engine and broken/open
        models fast-fail with a 503-class error BEFORE the request costs
        a queue slot — the whole point of the breaker is that a doomed
        queue never forms. Returns the model's breaker (check() already
        ran; a half-open probe rides through like any admitted request —
        its outcome closes or re-opens the breaker)."""
        if self._draining:
            self.stats.record_fast_fail()
            raise DrainingError("engine is draining; admission closed")
        if rec.state == "broken":
            # load/warmup-broken: no probe can rehabilitate a record that
            # never compiled — the operator reloads/re-warms (registry)
            self.stats.record_fast_fail()
            raise BreakerOpenError(
                f"model {rec.key} is broken ({rec.error}); reload or "
                "re-warm it", retry_after_s=5.0)
        breaker = self._breaker_for(rec)
        breaker.check()
        return breaker

    def predict_for(self, name, version, x,
                    timeout_s: Optional[float] = None) -> np.ndarray:
        rec = self.registry.get(name, version)
        # admission BEFORE the unloaded check: a broken record (failed
        # rollout, model None) must answer 503-with-Retry-After, not a
        # 400 that reads like a client mistake
        breaker = self._admit(rec)
        if rec.model is None:
            raise KeyError(f"{rec.key} is unloaded")
        x = np.asarray(x)
        rid = next(self._rid)
        with obs_trace.span("serve.request", rid=rid, model=rec.key,
                            rows=int(x.shape[0])):
            if not self.batching_enabled:
                # naive path: outcome accounting at the call boundary
                # (the batcher path records per DISPATCH via on_outcome)
                try:
                    out = self._direct_output(rec, x)
                except ClientRequestError:
                    raise  # payload error: no vote either way
                except Exception as e:  # noqa: BLE001 — serving boundary
                    breaker.record_failure(f"{type(e).__name__}: {e}")
                    raise
                breaker.record_success()
                self._offer_shadow(x, out)
                return out
            batcher = self._batcher_for(rec)
            # rid threads THROUGH the batcher: the serve.batch span on
            # the worker thread lists it, joining this request's span to
            # the coalesced dispatch it rode in
            out = batcher.predict(x, timeout_s=timeout_s, rid=rid)
            self._offer_shadow(x, out)
            return out

    def attach_shadow(self, mirror) -> None:
        """Install a shadow mirror on the /predict answer path. One at a
        time — promotion is a serialized operator action."""
        self._shadow = mirror

    def detach_shadow(self, mirror=None) -> None:
        """Remove the mirror (idempotent; a specific ``mirror`` detaches
        only itself, so a stale promoter can't evict its successor)."""
        if mirror is None or self._shadow is mirror:
            self._shadow = None

    def _offer_shadow(self, x, out) -> None:
        shadow = self._shadow
        if shadow is not None:
            shadow.offer(x, out)

    # -- embedding & retrieval plane (ISSUE 17, retrieval/) ----------------

    def embed_for(self, name, version, x,
                  timeout_s: Optional[float] = None,
                  layer=None, pool: Optional[str] = None) -> np.ndarray:
        """Encode rows to embeddings [N, dim] through the registered
        model's adapter (registry.ModelRecord.embed_adapter) — the same
        admission gate, dynamic batcher, and bucket ladder as /predict,
        so batcher==direct byte-equivalence holds by the same argument
        (per-request slices of a row-independent coalesced dispatch)."""
        rec = self.registry.get(name, version)
        breaker = self._admit(rec)
        if rec.model is None:
            raise KeyError(f"{rec.key} is unloaded")
        x = np.asarray(x)
        rid = next(self._rid)
        with obs_trace.span("serve.request", rid=rid, model=rec.key,
                            rows=int(x.shape[0]), kind="embed"):
            if not self.batching_enabled:
                try:
                    out = self._direct_embed(rec, x, layer, pool)
                except ClientRequestError:
                    raise  # payload error: no breaker vote either way
                except Exception as e:  # noqa: BLE001 — serving boundary
                    breaker.record_failure(f"{type(e).__name__}: {e}")
                    raise
                breaker.record_success()
            else:
                batcher = self._embed_batcher_for(rec, layer, pool)
                out = batcher.predict(x, timeout_s=timeout_s, rid=rid)
        self.retrieval_stats.bump("embed_requests")
        self.retrieval_stats.bump("embed_rows", int(x.shape[0]))
        return out

    def embed(self, x, timeout_s: Optional[float] = None) -> np.ndarray:
        """Default-model form of :meth:`embed_for`."""
        return self.embed_for(None, None, x, timeout_s=timeout_s)

    def _embed_rows(self, rec, x: np.ndarray, layer, pool) -> np.ndarray:
        """The one embed compute path both the direct call and the
        batcher's coalesced dispatch run: shape/normalize like /predict,
        pad up the bucket ladder (pad rows are zero and SLICED off — the
        encoders are row-independent, so they are inert by construction),
        encode, un-pad."""
        from deeplearning4j_tpu.ops import dispatch

        adapter = rec.embed_adapter(layer=layer, pool=pool)
        batch = self._shape_rows(rec, x)
        n = int(batch.shape[0])
        bucket = dispatch.bucket_size(n)
        if bucket > n:
            pad = np.zeros((bucket - n,) + batch.shape[1:], batch.dtype)
            batch = np.concatenate([batch, pad])
        out = np.asarray(adapter(batch))
        return out[:n]

    def _direct_embed(self, rec, x: np.ndarray, layer, pool) -> np.ndarray:
        with self._lock:
            return self._embed_rows(rec, x, layer, pool)

    def _embed_batcher_for(self, rec, layer=None,
                           pool: Optional[str] = None) -> DynamicBatcher:
        with self._engine_lock:
            batcher = self._embed_batchers.get(rec.key)
            if batcher is None:
                chaos = self.chaos

                def infer(batch, _rec=rec, _layer=layer, _pool=pool):
                    if chaos is not None:
                        chaos.on_infer()
                    return self._embed_rows(_rec, np.asarray(batch),
                                            _layer, _pool)

                batcher = DynamicBatcher(
                    infer, max_batch=self.max_batch,
                    max_wait_ms=self.max_wait_ms,
                    queue_capacity=self.queue_capacity,
                    default_timeout_s=self.request_timeout_s,
                    stats=self.stats,
                    watchdog_s=self.watchdog_s,
                    on_outcome=self._outcome_hook(rec),
                    on_wedged=self._wedged_hook(rec))
                self._embed_batchers[rec.key] = batcher
            return batcher

    def register_index(self, name: str, store) -> None:
        """Attach a retrieval/store.VectorStore behind /search."""
        with self._engine_lock:
            self._indexes[str(name)] = store

    def unregister_index(self, name: str):
        with self._engine_lock:
            return self._indexes.pop(str(name), None)

    def index(self, name: str):
        store = self._indexes.get(str(name))
        if store is None:
            raise ClientRequestError(f"no index named {name!r}")
        return store

    def search(self, index_name, queries, k: int = 10,
               nprobe: Optional[int] = None):
        """Top-k over a registered index's CURRENT published generation
        (ids, scores). Lock-free against publishes — a concurrent
        generation swap can never fail an admitted search (the store's
        snapshot discipline)."""
        if self._draining:
            self.stats.record_fast_fail()
            raise DrainingError("engine is draining; admission closed")
        store = self.index(index_name)
        rid = next(self._rid)
        q = np.asarray(queries, np.float32)
        with obs_trace.span("serve.request", rid=rid, index=str(index_name),
                            rows=int(q.shape[0]) if q.ndim > 1 else 1,
                            kind="search"):
            return store.search(q, k=k, nprobe=nprobe)

    def embed_report(self) -> Dict[str, Any]:
        """Per-model embedding dim + adapter kind for /models — AOT
        (config/param shapes/eval_shape), never a model dispatch."""
        out: Dict[str, Any] = {}
        for d in self.registry.describe():
            if d["state"] in ("broken", "unloaded"):
                continue
            rec = self.registry.get(d["name"], d["version"])
            if rec is None or rec.model is None:
                continue
            try:
                adapter = rec.embed_adapter()
            except TypeError:
                continue  # no embedding surface on this model family
            out[rec.key] = {"kind": adapter.kind, "dim": adapter.dim}
        return out

    def index_report(self) -> Dict[str, Any]:
        """Per-index capacity/row-count/generation for /models (the
        stores' own AOT accounting)."""
        with self._engine_lock:
            stores = dict(self._indexes)
        return {name: store.report() for name, store in stores.items()}

    def generate(self, tokens: np.ndarray, n_new: int, *,
                 temperature: float = 1.0, seed: int = 0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 slo: Optional[str] = None,
                 name=None, version=None) -> np.ndarray:
        """LM sampling: the paged block pool (or the fixed slot pool
        when DL4J_TPU_SERVE_KV_BLOCK=0) for plain temperature sampling
        on eligible models; lm.generate for static top_k/top_p filters,
        mesh-sharded or MoE models (the filters are compiled per-(n_new,
        k) there — models/transformer._filter_logits). ``slo`` names a
        scheduling class (serving/slo.py) — honored by the paged pool,
        ignored by the fallback paths (which have no scheduler)."""
        rec = self.registry.get(name, version)
        breaker = self._admit(rec)
        model = rec.model
        if model is None or not hasattr(model, "generate"):
            # addressing a non-LM model is the CLIENT's mistake — it
            # must not vote on (or ghost-probe) the model's health
            raise ClientRequestError(f"model {rec.key} has no generate()")
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        rid = next(self._rid)
        with obs_trace.span("serve.request", rid=rid, model=rec.key,
                            rows=int(tokens.shape[0]), kind="generate"):
            try:
                out = self._generate_inner(rec, model, tokens, n_new,
                                           temperature, seed, top_k,
                                           top_p, slo)
            except (RequestTimeoutError, FutureTimeoutError,
                    ClientRequestError):
                raise  # deadlines/payloads are not model-health evidence
            except Exception as e:  # noqa: BLE001 — serving boundary
                breaker.record_failure(f"{type(e).__name__}: {e}")
                raise
        breaker.record_success()
        return out

    def _generate_inner(self, rec, model, tokens, n_new, temperature,
                        seed, top_k, top_p, slo=None) -> np.ndarray:
        decoder = (self._decoder_for(rec)
                   if top_k is None and top_p is None else None)
        if decoder is not None:
            kwargs = {}
            if slo is not None and getattr(decoder, "supports_streaming",
                                           False):
                kwargs["slo"] = slo
            out = decoder.generate(tokens, int(n_new),
                                   temperature=float(temperature),
                                   seed=int(seed), **kwargs)
            return np.asarray(out)
        import jax.numpy as jnp

        with self._lock:
            # graftlint: disable=host-sync-under-lock -- host->device staging of the request tokens, not a readback; the lock deliberately serializes whole generate() calls (single-model contract)
            out = model.generate(jnp.asarray(tokens, jnp.int32), int(n_new),
                                 temperature=float(temperature),
                                 seed=int(seed), top_k=top_k, top_p=top_p)
        self.stats.record_tokens(int(np.asarray(out).size))
        return np.asarray(out)

    def generate_stream(self, tokens, n_new: int, *,
                        temperature: float = 1.0, seed: int = 0,
                        slo: Optional[str] = None,
                        name=None, version=None):
        """Streaming /generate for ONE prompt: an iterator of sampled
        token ids, each yielded as the decode tick produces it (paged
        pool). The fixed pool / lm.generate fallbacks yield the same
        wire sequence after generating fully — identical contract,
        later first token. Admission errors (429/503/400) raise HERE,
        before the caller commits response headers; mid-generation
        failures raise from the iterator."""
        rec = self.registry.get(name, version)
        prompt = np.asarray(tokens, np.int32).reshape(-1)
        decoder = (self._decoder_for(rec)
                   if getattr(rec.model, "generate", None) is not None
                   else None)
        if decoder is None or not getattr(decoder, "supports_streaming",
                                          False):
            # generate() runs the admission gate itself — admitting here
            # too would consume a half-open breaker probe twice
            out = self.generate(prompt, n_new, temperature=temperature,
                                seed=seed, slo=slo, name=name,
                                version=version)
            return iter(np.asarray(out).reshape(-1).tolist())
        breaker = self._admit(rec)
        rid = next(self._rid)
        q: stdqueue.Queue = stdqueue.Queue()
        # the request's span lives from here to the end of the stream,
        # which another thread may reach: the decoder hangs the queue,
        # admission and tick spans under it, all carrying rid
        sp = obs_trace.open_span("serve.request", rid=rid, model=rec.key,
                                 rows=1, kind="generate_stream")
        on_token = q.put
        if sp is not obs_trace.NULL_SPAN:
            # the first token's time and the newest's: what the span
            # lasts past the last one is the stream's own tail
            def on_token(t):
                now = time.perf_counter() - sp.start
                if "ttft_s" not in sp.attrs:
                    sp.set_attr("ttft_s", now)
                sp.set_attr("last_token_s", now)
                q.put(t)
        try:
            fut = decoder.submit(prompt, int(n_new),
                                 temperature=float(temperature),
                                 seed=int(seed), slo=slo, on_token=on_token,
                                 parent=sp)
        except BaseException as e:
            sp.set_attr("error", type(e).__name__)
            obs_trace.close_span(sp)
            raise

        def stream():
            sent = 0
            try:
                while True:
                    try:
                        t = q.get(timeout=0.2)
                    except stdqueue.Empty:
                        if fut.done():
                            break
                        continue
                    sent += 1
                    yield int(t)
                # on_token callbacks run BEFORE the future resolves
                # (serving/paged.py), so a done future means every token
                # is already queued — drain, then finish
                try:
                    fut.result(timeout=0)
                except (RequestTimeoutError, FutureTimeoutError,
                        ClientRequestError):
                    raise
                except Exception as e:  # noqa: BLE001 — serving boundary
                    breaker.record_failure(f"{type(e).__name__}: {e}")
                    raise
                breaker.record_success()
                while True:
                    try:
                        t = q.get_nowait()
                    except stdqueue.Empty:
                        return
                    sent += 1
                    yield int(t)
            except BaseException as e:
                sp.set_attr("error", type(e).__name__)
                raise
            finally:
                sp.set_attr("tokens", sent)
                obs_trace.close_span(sp)

        out = stream()
        if sp is not obs_trace.NULL_SPAN:
            # a generator nobody starts never reaches its ``finally``:
            # the span then closes when the generator is let go, so the
            # spans under it are not left naming a parent outside the ring
            weakref.finalize(out, obs_trace.close_span, sp)
        return out

    def prefill_for(self, name, version, tokens, n_new: int):
        """Prefill half of the disaggregated handoff (serving/mesh role
        split): run the paged pool's bucketed prompt prefill as its own
        dispatch and return ``(digests, k_blocks, v_blocks,
        block_tokens)`` — the full prompt blocks strictly below the
        write block, content-addressed by the PrefixCache digest chain.
        A decode replica adopts them via :meth:`prime_for`; the handoff
        is best-effort by construction (a dropped transfer just means
        the decode side recomputes the same bytes)."""
        rec = self.registry.get(name, version)
        breaker = self._admit(rec)
        decoder = self._decoder_for(rec)
        if decoder is None or not hasattr(decoder, "export_prefix"):
            raise ClientRequestError(
                f"model {rec.key} has no paged decoder to prefill")
        prompt = np.asarray(tokens, np.int32).reshape(-1)
        rid = next(self._rid)
        with obs_trace.span("serve.request", rid=rid, model=rec.key,
                            rows=1, kind="prefill"):
            try:
                digests, kb, vb = decoder.export_prefix(prompt,
                                                        int(n_new))
            except ClientRequestError:
                raise  # payload mistakes are not model-health evidence
            except Exception as e:  # noqa: BLE001 — serving boundary
                breaker.record_failure(f"{type(e).__name__}: {e}")
                raise
        breaker.record_success()
        return digests, kb, vb, int(decoder.block_tokens)

    def prime_for(self, name, version, digests, k_blocks,
                  v_blocks) -> int:
        """Decode half of the handoff: adopt prefill-exported KV blocks
        into the paged arena + prefix cache. Returns blocks adopted (a
        partial adoption — already-cached digests, exhausted free list —
        is fine: the next admission recomputes what was dropped)."""
        rec = self.registry.get(name, version)
        breaker = self._admit(rec)
        decoder = self._decoder_for(rec)
        if decoder is None or not hasattr(decoder, "import_prefix"):
            raise ClientRequestError(
                f"model {rec.key} has no paged decoder to prime")
        try:
            adopted = decoder.import_prefix(digests, k_blocks, v_blocks)
        except ClientRequestError:
            raise
        except Exception as e:  # noqa: BLE001 — serving boundary
            breaker.record_failure(f"{type(e).__name__}: {e}")
            raise
        breaker.record_success()
        return int(adopted)

    # -- internals --------------------------------------------------------
    @staticmethod
    def _normalize_rows(rec, x: np.ndarray) -> np.ndarray:
        """Apply the record's fitted normalizer (etl/normalize.py) to the
        request rows — the PURE array form (a batcher-coalesced batch
        shares buffers across requests; in-place would corrupt peers).
        Row-wise normalization commutes with batching, so the batched and
        naive paths stay byte-equivalent. Runs AFTER the input_shape
        reshape: statistics are per-final-axis (etl/normalize
        ``_column_stats_axes``), so they were fitted at the shape the
        trainer fed the net — per-channel for an image net, per-feature
        for a flat one. Normalizing the flat wire rows would broadcast
        (B, H*W*C) against per-channel stats and fail (or silently
        mis-scale) for any shaped-input model."""
        if rec.normalizer is None:
            return x
        return rec.normalizer.transform_array(x)

    @staticmethod
    def _shape_rows(rec, x: np.ndarray) -> np.ndarray:
        """Pre-dispatch input shaping (reshape + fitted normalizer). A
        failure HERE is the client's payload, not the model's health —
        wrapped as ClientRequestError so the breaker vote skips it (the
        HTTP layer still answers 400 like any payload error)."""
        try:
            if rec.input_shape is not None:
                x = x.reshape((x.shape[0],) + rec.input_shape)
            return ServingEngine._normalize_rows(rec, x)
        except Exception as e:  # noqa: BLE001 — input boundary
            raise ClientRequestError(
                f"bad request rows for {rec.key}: "
                f"{type(e).__name__}: {e}") from e

    def _direct_output(self, rec, x: np.ndarray) -> np.ndarray:
        """The naive per-request path the batcher replaces (kept for the
        DL4J_TPU_SERVE_BATCH=0 comparison and the bench's baseline): one
        locked output() dispatch per call."""
        x = self._shape_rows(rec, x)
        with self._lock:
            out = rec.model.output(x)
        out0 = out[0] if isinstance(out, (list, tuple)) else out
        return np.asarray(out0)

    def _breaker_for(self, rec) -> CircuitBreaker:
        with self._engine_lock:
            breaker = self._breakers.get(rec.key)
            if breaker is None:

                def on_transition(old, new, reason, _key=rec.key):
                    # the health timeline rides the flight recorder: a
                    # post-mortem of a degraded endpoint starts from
                    # WHEN each model broke/recovered and why
                    obs_journal.event("serve.health", model=_key,
                                      old=old, new=new, reason=reason)

                breaker = CircuitBreaker(
                    fails=self.breaker_fails,
                    cooldown_s=self.breaker_cooldown_s,
                    key=rec.key, stats=self.stats,
                    on_transition=on_transition)
                self._breakers[rec.key] = breaker
            return breaker

    def _batcher_for(self, rec) -> DynamicBatcher:
        with self._engine_lock:
            batcher = self._batchers.get(rec.key)
            if batcher is None:
                model = rec.model
                chaos = self.chaos

                def infer(batch, _rec=rec, _model=model):
                    if chaos is not None:
                        # per-DISPATCH injection point (deterministic
                        # under coalescing); a configured hang blocks
                        # right here — exactly where a hung device would
                        chaos.on_infer()
                    batch = self._shape_rows(_rec, np.asarray(batch))
                    out = _model.output(batch)
                    out0 = out[0] if isinstance(out, (list, tuple)) else out
                    return np.asarray(out0)

                batcher = DynamicBatcher(
                    infer, max_batch=self.max_batch,
                    max_wait_ms=self.max_wait_ms,
                    queue_capacity=self.queue_capacity,
                    default_timeout_s=self.request_timeout_s,
                    stats=self.stats,
                    watchdog_s=self.watchdog_s,
                    on_outcome=self._outcome_hook(rec),
                    on_wedged=self._wedged_hook(rec))
                self._batchers[rec.key] = batcher
            return batcher

    def _outcome_hook(self, rec):
        """Per-dispatch breaker feed for rec's batcher."""
        def on_outcome(ok: bool, exc, _key_rec=rec):
            breaker = self._breaker_for(_key_rec)
            if ok:
                breaker.record_success()
            elif isinstance(exc, ClientRequestError):
                # a malformed payload is 400-class CLIENT evidence: it
                # failed before the model dispatch and must not walk a
                # healthy model toward BROKEN (nor count as a success)
                pass
            elif isinstance(exc, WorkerDeadError):
                # a dead worker is categorical, not a vote: nothing will
                # dispatch for this model until an operator intervenes,
                # and /health must say so now
                breaker.trip(f"{exc}")
            else:
                breaker.record_failure(f"{type(exc).__name__}: {exc}")
        return on_outcome

    def _wedged_hook(self, rec):
        """Watchdog verdict for rec's batcher: categorical evidence — trip
        the breaker (no vote counting) and journal the wedge so a hung
        device leaves a readable timeline even if the process dies next."""
        def on_wedged(info, _key_rec=rec):
            self._breaker_for(_key_rec).trip(
                f"watchdog: {info['error']}")
            obs_journal.event(
                "serve.wedged", model=_key_rec.key,
                rows=int(info["rows"]),
                failed_requests=int(info["failed_requests"]),
                watchdog_s=float(info["watchdog_s"]))
            obs_journal.flush(fsync=True)
        return on_wedged

    def _decoder_for(self, rec):
        if not self.continuous_enabled:
            return None
        with self._engine_lock:
            if rec.key in self._no_decoder:
                return None
            decoder = self._decoders.get(rec.key)
            if decoder is None:
                # eligibility is the KV-pool contract: a single-device
                # dense TransformerLM (serving/decode.py gate)
                if getattr(rec.model, "_run_cfg", None) is None:
                    return None
                paged_kw = dict(
                    block_tokens=self.kv_block,
                    n_blocks=self.kv_blocks or None,
                    min_lanes=self.slots, stats=self.stats,
                    default_timeout_s=max(self.request_timeout_s,
                                          300.0),
                    chaos=self.chaos,
                    slo_classes=self.slo_classes or None,
                    queue_cap=self.queue_capacity)
                if self.mesh_devices >= 2:
                    # DL4J_TPU_SERVE_MESH: an incompatibility here (bf16
                    # KV dtype, spec mode, indivisible heads, no paged
                    # pool) raises OUT of this method — a user who asked
                    # for the sharded plane must never be silently
                    # served by the dense single-device path
                    if self.kv_block <= 0:
                        raise ValueError(
                            "DL4J_TPU_SERVE_MESH requires the paged KV "
                            "pool (DL4J_TPU_SERVE_KV_BLOCK > 0); the "
                            "fixed-slot pool has no sharded arena")
                    from deeplearning4j_tpu.serving.mesh import (
                        MeshPagedDecoder,
                    )

                    decoder = MeshPagedDecoder(
                        rec.model, devices=self.mesh_devices, **paged_kw)
                    self._decoders[rec.key] = decoder
                    return decoder
                try:
                    if self.kv_block > 0:
                        from deeplearning4j_tpu.ops import lowprec
                        from deeplearning4j_tpu.serving.paged import (
                            PagedDecoder,
                        )

                        spec = lowprec.spec_mode()
                        if spec:
                            # DL4J_TPU_SERVE_SPEC: the paged pool gains
                            # a draft-verify round (serving/speculate);
                            # a ValueError (mesh, vocab, MoE, draft
                            # derivation) lands in _no_decoder like any
                            # eligibility failure
                            from deeplearning4j_tpu.serving.speculate \
                                import SpeculativeDecoder

                            decoder = SpeculativeDecoder(
                                rec.model, draft=rec.draft_net(spec),
                                **paged_kw)
                        else:
                            decoder = PagedDecoder(rec.model, **paged_kw)
                    else:
                        from deeplearning4j_tpu.serving.decode import (
                            ContinuousDecoder,
                        )

                        decoder = ContinuousDecoder(
                            rec.model, slots=self.slots, stats=self.stats,
                            default_timeout_s=max(self.request_timeout_s,
                                                  300.0),
                            chaos=self.chaos)
                except ValueError as e:
                    self._no_decoder[rec.key] = str(e)
                    return None
                self._decoders[rec.key] = decoder
            return decoder

    # -- HTTP -------------------------------------------------------------
    def _make_handler(self):
        engine = self

        class Handler(BaseHTTPRequestHandler):
            # chunked transfer (the streaming /generate contract) is an
            # HTTP/1.1 construct; every non-streamed response carries an
            # explicit Content-Length, so keep-alive framing stays sound
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _send(self, code: int, obj, headers=None):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _read_json(self):
                n = int(self.headers.get("Content-Length", 0))
                return json.loads(self.rfile.read(n))

            def do_GET(self):
                if self.path == "/health":
                    # real health, not a constant: per-model states, and
                    # HTTP 503 when nothing can serve (all broken, or
                    # draining) so a load balancer actually routes away
                    code, body = engine.health()
                    self._send(code, body)
                elif self.path.split("?")[0] == "/health":
                    # liveness/readiness split (ISSUE 12 satellite): the
                    # plain path above keeps its 503-when-draining
                    # contract BYTE-unchanged; ?ready=1 is the router's
                    # probe — an answered 503 with live=true means
                    # alive-but-not-ready (drain), which must stop
                    # ADMISSION without voting on the replica breaker
                    # (only a connection-level failure means death)
                    query = self.path.partition("?")[2]
                    if "ready=1" in query.split("&"):
                        code, body = engine.readiness()
                        self._send(code, body)
                    else:
                        code, body = engine.health()
                        self._send(code, body)
                elif self.path.split("?")[0] == "/metrics":
                    # content negotiation: a Prometheus scraper (Accept:
                    # text/plain / openmetrics, or an explicit
                    # ?format=prometheus) gets text exposition of the
                    # CENTRAL registry — serving counters plus every
                    # registered net ledger in one scrape; everything
                    # else keeps the original JSON contract
                    accept = self.headers.get("Accept", "")
                    if ("format=prometheus" in self.path
                            or "text/plain" in accept
                            or "openmetrics" in accept):
                        body = (obs_registry.default_registry()
                                .render_prometheus().encode())
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         PROMETHEUS_CONTENT_TYPE)
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    else:
                        self._send(200, engine.metrics())
                elif self.path == "/models":
                    self._send(200, {
                        "models": engine.registry.describe(),
                        "default": (engine.registry.default().key
                                    if engine.registry.default() else None),
                        # KV capacity in TOKENS per live decoder (ISSUE
                        # 11 satellite): what the /generate plane can
                        # actually hold, not what it pre-allocated
                        "kv": engine.kv_report(),
                        # serve()-swap history (ISSUE 14 satellite): the
                        # audited rollback trail — who replaced whom, when
                        "lineage": engine.registry.lineage(),
                        # retrieval plane (ISSUE 17 satellite): per-model
                        # embedding dims + per-index capacity/rows, both
                        # computed from shapes, never a dispatch
                        "embed": engine.embed_report(),
                        "indexes": engine.index_report(),
                    })
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):
                try:
                    if self.path == "/predict":
                        self._do_predict()
                    elif self.path == "/embed":
                        self._do_embed()
                    elif self.path == "/search":
                        self._do_search()
                    elif self.path == "/generate":
                        self._do_generate()
                    elif self.path == "/prefill":
                        self._do_prefill()
                    elif self.path == "/prime":
                        self._do_prime()
                    elif self.path == "/models":
                        self._do_models()
                    else:
                        self._send(404, {"error": "not found"})
                except QueueFullError as e:
                    # rejected counter already bumped at submit()
                    self._send(429, {"error": f"QueueFull: {e}"})
                except (BreakerOpenError, DrainingError) as e:
                    # fast-fail counter already bumped at the admission
                    # gate; Retry-After is the shed contract — a client
                    # library backs off instead of hammering a breaker.
                    # RFC 9110 delta-seconds is an INTEGER: a fractional
                    # value is silently dropped by standard retry
                    # parsers, so round sub-second cooldowns UP to 1
                    self._send(503, {"error": f"Unavailable: {e}"},
                               headers={"Retry-After": str(max(
                                   1, math.ceil(e.retry_after_s)))})
                except ModelWedgedError as e:
                    # the watchdog's diagnosis — NOT a 504-by-rot: the
                    # client learns the dispatch hung, not that it merely
                    # queued too long
                    self._send(503, {"error": f"Wedged: {e}"},
                               headers={"Retry-After": "1"})
                except WorkerDeadError as e:
                    self._send(503, {"error": f"WorkerDead: {e}"},
                               headers={"Retry-After": "1"})
                except RequestTimeoutError as e:
                    # timeout counter already bumped where it expired
                    # (batcher worker / batcher.predict / decoder loop)
                    self._send(504, {"error": f"Timeout: {e}"})
                except FutureTimeoutError as e:
                    engine.stats.record_timeout()  # raw future wait only
                    self._send(504, {"error": f"Timeout: {e}"})
                except Exception as e:  # noqa: BLE001 — serving boundary
                    engine.stats.record_error()
                    self._send(400, {"error": f"{type(e).__name__}: {e}"})

            def _do_predict(self):
                from deeplearning4j_tpu.streaming.conversion import (
                    decode_record_base64,
                )

                payload = self._read_json()
                if "record_base64" in payload:
                    x = decode_record_base64(payload["record_base64"])[None]
                elif "record" in payload:
                    x = np.asarray(payload["record"], np.float32)[None]
                elif "batch" in payload:
                    x = np.asarray(payload["batch"], np.float32)
                else:
                    self._send(400,
                               {"error": "need record|record_base64|batch"})
                    return
                timeout = payload.get("timeout_s")
                out = engine.predict_for(
                    payload.get("model"), payload.get("version"), x,
                    # `is not None`: an explicit 0 means no-wait, not
                    # "use the 60s default"
                    timeout_s=(float(timeout) if timeout is not None
                               else None))
                key = "outputs" if "batch" in payload else "output"
                val = out.tolist() if "batch" in payload else out[0].tolist()
                self._send(200, {key: val})

            def _do_embed(self):
                payload = self._read_json()
                if "record" in payload:
                    x = np.asarray(payload["record"], np.float32)[None]
                elif "batch" in payload:
                    x = np.asarray(payload["batch"], np.float32)
                elif "tokens" in payload:
                    # token-id rows (BERT / word2vec lookup): keep them
                    # integral through the float envelope
                    x = np.asarray(payload["tokens"])
                    if x.ndim == 1:
                        x = x[None]
                else:
                    self._send(400, {"error": "need record|batch|tokens"})
                    return
                timeout = payload.get("timeout_s")
                layer = payload.get("layer")
                out = engine.embed_for(
                    payload.get("model"), payload.get("version"), x,
                    timeout_s=(float(timeout) if timeout is not None
                               else None),
                    layer=layer, pool=payload.get("pool"))
                key = "embeddings" if ("batch" in payload
                                       or "tokens" in payload) else "embedding"
                val = (out.tolist() if key == "embeddings"
                       else out[0].tolist())
                self._send(200, {key: val, "dim": int(out.shape[-1])})

            def _do_search(self):
                payload = self._read_json()
                if "queries" in payload:
                    q = np.asarray(payload["queries"], np.float32)
                elif "query" in payload:
                    q = np.asarray(payload["query"], np.float32)[None]
                else:
                    self._send(400, {"error": "need query|queries"})
                    return
                nprobe = payload.get("nprobe")
                ids, scores = engine.search(
                    payload.get("index", "default"), q,
                    k=int(payload.get("k", 10)),
                    nprobe=int(nprobe) if nprobe is not None else None)
                self._send(200, {"ids": ids.tolist(),
                                 "scores": scores.tolist()})

            def _do_generate(self):
                payload = self._read_json()
                toks = np.asarray(payload["tokens"], np.int32)
                # coerce filter args: JSON numbers often arrive as floats,
                # and a float top_k would both fail lax.top_k and pollute
                # the compile cache key
                tk = payload.get("top_k")
                tp = payload.get("top_p")
                if payload.get("stream"):
                    if tk is not None or tp is not None:
                        self._send(400, {"error": "stream does not "
                                         "support top_k/top_p"})
                        return
                    if toks.ndim > 1 and toks.shape[0] != 1:
                        self._send(400, {"error": "stream takes ONE "
                                         "prompt per request"})
                        return
                    gen = engine.generate_stream(
                        toks.reshape(-1), int(payload.get("n_new", 16)),
                        temperature=float(payload.get("temperature", 1.0)),
                        seed=int(payload.get("seed", 0)),
                        slo=payload.get("slo"),
                        name=payload.get("model"),
                        version=payload.get("version"))
                    self._stream_tokens(gen)
                    return
                out = engine.generate(
                    toks, int(payload.get("n_new", 16)),
                    temperature=float(payload.get("temperature", 1.0)),
                    seed=int(payload.get("seed", 0)),
                    top_k=int(tk) if tk is not None else None,
                    top_p=float(tp) if tp is not None else None,
                    slo=payload.get("slo"),
                    name=payload.get("model"),
                    version=payload.get("version"))
                self._send(200, {"tokens": out.tolist()})

            def _do_prefill(self):
                # prefill role surface (disaggregation): run the prompt
                # prefill here, hand the caller the content-addressed
                # block payload it forwards to a decode replica's /prime
                payload = self._read_json()
                toks = np.asarray(payload["tokens"], np.int32).reshape(-1)
                digests, kb, vb, bt = engine.prefill_for(
                    payload.get("model"), payload.get("version"),
                    toks, int(payload.get("n_new", 16)))
                self._send(200, {
                    "digests": [d.hex() for d in digests],
                    "k": base64.b64encode(
                        np.ascontiguousarray(kb).tobytes()).decode(),
                    "v": base64.b64encode(
                        np.ascontiguousarray(vb).tobytes()).decode(),
                    "shape": list(kb.shape),
                    "dtype": str(kb.dtype),
                    "block_tokens": int(bt),
                })

            def _do_prime(self):
                payload = self._read_json()
                shape = tuple(int(s) for s in payload["shape"])
                dtype = np.dtype(str(payload["dtype"]))
                kb = np.frombuffer(base64.b64decode(payload["k"]),
                                   dtype).reshape(shape)
                vb = np.frombuffer(base64.b64decode(payload["v"]),
                                   dtype).reshape(shape)
                digests = [bytes.fromhex(d) for d in payload["digests"]]
                adopted = engine.prime_for(
                    payload.get("model"), payload.get("version"),
                    digests, kb, vb)
                self._send(200, {"adopted": int(adopted)})

            def _stream_tokens(self, gen):
                # manual chunked framing: one NDJSON object per token,
                # flushed as sampled — a client reads tokens as the
                # decode ticks produce them. Submission errors raised
                # BEFORE this point (generate_stream submits eagerly)
                # still map to proper status codes in do_POST;
                # mid-generation failures can only ride the stream, the
                # headers are gone.
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def chunk(obj):
                    data = (json.dumps(obj) + "\n").encode()
                    self.wfile.write(b"%x\r\n" % len(data) + data
                                     + b"\r\n")
                    self.wfile.flush()

                out = []
                try:
                    for t in gen:
                        out.append(int(t))
                        chunk({"token": int(t)})
                    chunk({"done": True, "tokens": out})
                except (RequestTimeoutError, FutureTimeoutError) as e:
                    # timeout counters already bumped where they expired
                    chunk({"error": f"Timeout: {e}"})
                except Exception as e:  # noqa: BLE001 — serving boundary
                    engine.stats.record_error()
                    chunk({"error": f"{type(e).__name__}: {e}"})
                self.wfile.write(b"0\r\n\r\n")
                self.wfile.flush()

            def _do_models(self):
                payload = self._read_json()
                action = payload.get("action")
                name = payload.get("name")
                version = payload.get("version")
                if action == "load":
                    rec = engine.registry.load(
                        name, model_path=payload.get("path"),
                        input_shape=payload.get("input_shape"))
                    self._send(200, rec.describe())
                elif action == "warmup":
                    self._send(200, engine.registry.warmup(
                        name, version,
                        max_batch=int(payload.get("max_batch",
                                                  engine.max_batch)),
                        gen_tokens=int(payload.get("gen_tokens", 0))))
                elif action == "serve":
                    rec = engine.registry.serve(name, version)
                    self._send(200, rec.describe())
                elif action == "unload":
                    engine.retire(name, version)
                    self._send(200, engine.registry.get(name,
                                                        version).describe())
                else:
                    self._send(400, {"error": "action must be "
                                     "load|warmup|serve|unload"})

        return Handler

    def kv_report(self) -> Dict[str, Any]:
        """Per-model KV capacity in tokens (paged: arena blocks *
        block_tokens + occupancy + cached prefix blocks; fixed pool: the
        slots * max_len pre-allocation). Eligible decoders are built on
        first ask — capacity is a property of the configuration, so
        /models must report it before first /generate traffic; for
        non-LM models _decoder_for's cheap _run_cfg probe says no
        without pulling the transformer stack in. An LM the pool
        refused (mesh-built, MoE, an arena too small for one sequence)
        reports ``scheme: none`` with the refusal's reason."""
        out: Dict[str, Any] = {}
        for d in self.registry.describe():
            if d["state"] in ("broken", "unloaded"):
                continue
            rec = self.registry.get(d["name"], d["version"])
            if rec is None or rec.model is None:
                continue
            try:
                decoder = self._decoder_for(rec)
            except ValueError as e:
                # a LOUD mesh-gate refusal (bf16 KV, spec mode,
                # indivisible heads) must not 500 the whole /models GET
                # — report it per record instead
                out[rec.key] = {"error": str(e)}
                continue
            if decoder is not None and hasattr(decoder, "kv_capacity"):
                out[rec.key] = decoder.kv_capacity()
            elif rec.key in self._no_decoder:
                out[rec.key] = {"scheme": "none",
                                "reason": self._no_decoder[rec.key]}
        return out

    def state_pools(self) -> Dict[str, Any]:
        """Per live paged decoder of a model with recurrent layers, its
        per-lane state pool as the last tick left it
        (``PagedDecoder.state_pool``): the device buffers, for a reader
        that looks while no request is in flight."""
        with self._engine_lock:
            decoders = dict(self._decoders)
        pools = {key: d.state_pool() for key, d in decoders.items()
                 if hasattr(d, "state_pool")}
        return {key: pool for key, pool in pools.items() if pool}

    def hbm_report(self) -> Dict[str, Any]:
        """Per-replica HBM utilization (ISSUE 20 satellite): the
        AOT-priced resident bytes — every non-broken record's buffer
        pytrees (ops/memory.model_resident_bytes), every LIVE decoder's
        KV arena (blocks x kv_block_bytes, incl. the trash block, plus
        the per-lane state pool of a model with recurrent layers), and
        every registered ANN store's arena — summed against the
        HBM budget (ops/memory.hbm_budget_gb). Pure shape arithmetic,
        never a device read; it is also
        the bin-packing input the autoscaler's placement plane prices
        replicas with (serving/placement.py)."""
        from deeplearning4j_tpu.ops import memory as opsmem

        budget_bytes = int(opsmem.hbm_budget_gb() * 2.0**30)
        models: Dict[str, Any] = {}
        used = 0
        with self._engine_lock:
            decoders = dict(self._decoders)
            stores = dict(self._indexes)
        for d in self.registry.describe():
            if d["state"] in ("broken", "unloaded"):
                continue
            rec = self.registry.get(d["name"], d["version"])
            if rec is None or rec.model is None:
                continue
            entry = {"param_bytes": opsmem.model_resident_bytes(rec.model),
                     "kv_bytes": 0}
            decoder = decoders.get(rec.key)
            cfg = getattr(decoder, "cfg", None)
            if cfg is not None:
                if hasattr(decoder, "n_blocks"):
                    # paged arena: +1 is the trash block (serving/paged)
                    # (a pool a KV group: a window group's is smaller)
                    entry["kv_bytes"] = (
                        sum((n + 1) * opsmem.kv_block_bytes(
                            cfg, decoder.block_tokens,
                            getattr(decoder, "kv_dtype", None),
                            devices=int(getattr(decoder,
                                                "mesh_devices", 1)),
                            group=gi)
                            for gi, n in enumerate(decoder.group_blocks))
                        # a model with recurrent layers: its per-lane
                        # state pool rides in the arena (0 without)
                        + decoder.lanes
                        * opsmem.cache_needs(cfg).state_lane_bytes)
                elif hasattr(decoder, "slots"):
                    # fixed pool: one slot == one max_len-token block
                    entry["kv_bytes"] = decoder.slots \
                        * opsmem.kv_block_bytes(cfg, cfg.max_len)
            used += entry["param_bytes"] + entry["kv_bytes"]
            # aggregate by NAME, not name@version — the placement /
            # affinity plane works in model names, and every resident
            # version of a name occupies HBM toward that name's bill
            agg = models.setdefault(rec.name,
                                    {"param_bytes": 0, "kv_bytes": 0})
            agg["param_bytes"] += entry["param_bytes"]
            agg["kv_bytes"] += entry["kv_bytes"]
        indexes = {name: int(store.report()["arena_bytes"])
                   for name, store in stores.items()}
        used += sum(indexes.values())
        return {
            "budget_bytes": budget_bytes,
            "used_bytes": used,
            # exact ratio, never rounded: a tiny model on a big budget
            # must not report utilization 0.0 to the bin-packer
            "utilization": (used / budget_bytes if budget_bytes else None),
            "models": models,
            "indexes": indexes,
        }

    def metrics(self) -> Dict[str, Any]:
        return {"serving": self.stats.snapshot(),
                "models": self.registry.describe(),
                "health": self.model_health(),
                "draining": self._draining,
                "hbm": self.hbm_report()}

    def model_health(self) -> Dict[str, str]:
        """Per-model health: the breaker's verdict when the model has
        taken traffic, the registry lifecycle state otherwise (a
        load/warmup-broken record reads ``broken`` either way)."""
        out: Dict[str, str] = {}
        with self._engine_lock:
            breakers = dict(self._breakers)
        for d in self.registry.describe():
            key = f"{d['name']}@v{d['version']}"
            if d["state"] in ("broken", "unloaded"):
                out[key] = d["state"]
                continue
            breaker = breakers.get(key)
            out[key] = breaker.state if breaker is not None else d["state"]
        return out

    def health(self):
        """(http_code, body) for /health: 503 when the engine cannot take
        traffic — draining, or every loaded model broken — so a load
        balancer's probe actually routes away; 200 otherwise (including
        the no-models bootstrap state, which is healthy-but-empty)."""
        health = self.model_health()
        live = [k for k, v in health.items()
                if v not in ("broken", "unloaded")]
        loaded = [k for k, v in health.items() if v != "unloaded"]
        ok = not self._draining and (bool(live) or not loaded)
        rec = self.registry.default()
        body = {
            "ok": ok,
            "draining": self._draining,
            "model": (type(rec.model).__name__
                      if rec is not None and rec.model is not None
                      else None),
            "models": [r["name"] + "@v" + str(r["version"])
                       for r in self.registry.describe()],
            "health": health,
        }
        if self.role:
            # disaggregation role (serving/mesh): only a role-TAGGED
            # replica adds the key — the PR 12 plain-/health body stays
            # byte-unchanged for unified engines
            body["role"] = self.role
        return (200 if ok else 503), body

    def readiness(self):
        """(http_code, body) for /health?ready=1 — the liveness vs
        readiness split (ISSUE 12 satellite). Liveness is answering at
        all: ``live`` is constant True in every response this process
        manages to send (a dead replica answers with a connection error,
        not a body). Readiness is plain /health's ok bit: draining or
        all-broken => 503 + ready=false. A router reads the difference
        as admission-vs-ejection — an answered not-ready response stops
        NEW traffic without counting as a breaker failure, so a graceful
        drain is never misread as replica death."""
        code, body = self.health()
        body = dict(body)
        body["live"] = True
        body["ready"] = body["ok"]
        return code, body

    def retire(self, name, version=None) -> None:
        """Unload a record AND tear down its batcher/decoder."""
        rec = self.registry.get(name, version)
        with self._engine_lock:
            batcher = self._batchers.pop(rec.key, None)
            embed_batcher = self._embed_batchers.pop(rec.key, None)
            decoder = self._decoders.pop(rec.key, None)
            self._no_decoder.pop(rec.key, None)
            self._breakers.pop(rec.key, None)
        if batcher is not None:
            batcher.stop()
        if embed_batcher is not None:
            embed_batcher.stop()
        if decoder is not None:
            decoder.stop()
        self.registry.unload(rec.name, rec.version)

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ServingEngine":
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful drain: close admission (new requests 503 at the
        _admit gate), then wait — bounded by DL4J_TPU_SERVE_DRAIN_S — for
        every ADMITTED request to complete (batcher queues + in-flight,
        decoder pending + slots), and flush the obs journal so the
        timeline survives whatever comes next. The serving twin of
        ResilientTrainer's checkpoint-before-death. True when everything
        admitted was answered within the deadline."""
        budget = float(timeout_s if timeout_s is not None else self.drain_s)
        self._draining = True
        # seal BEFORE waiting on queues (ISSUE 12 satellite): a rollout
        # racing this drain (an HTTP /models thread mid load -> warmup ->
        # serve) must not promote a half-warmed record as the serving
        # default on an engine that is going down — the drain answers
        # admitted work against the STABLE default, and the SIGTERM path
        # (_preempt_stop -> stop -> drain) inherits the same ordering
        self.registry.seal()
        obs_journal.event("serve.drain", drain_s=budget)
        deadline = time.monotonic() + budget
        with self._engine_lock:
            batchers = (list(self._batchers.values())
                        + list(self._embed_batchers.values()))
            decoders = list(self._decoders.values())
        ok = True
        for b in batchers:
            ok = b.drain(max(0.0, deadline - time.monotonic())) and ok
        for d in decoders:
            ok = d.drain(max(0.0, deadline - time.monotonic())) and ok
        self.stats.record_drain(ok)
        obs_journal.event("serve.drain_complete", completed=ok)
        obs_journal.flush(fsync=True)
        self._drained = True
        return ok

    def stop(self, drain: bool = True,
             drain_timeout_s: Optional[float] = None) -> None:
        """Shutdown. ``drain=True`` (the default) answers everything
        already admitted before tearing down; ``drain=False`` is the
        old immediate stop (still fails — never abandons — queued and
        in-flight futures via the batcher/decoder stop contracts).
        Gated on ``_drained``, not the admission flag: the SIGTERM
        handler closes admission BEFORE the drain runs, and that must
        not suppress the drain itself."""
        if drain and not self._drained:
            self.drain(drain_timeout_s)
        self._draining = True
        self.restore_signal_handlers()
        if self._thread is not None:
            # shutdown() handshakes with a RUNNING serve_forever loop —
            # on a never-started engine it would block forever
            self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
        with self._engine_lock:
            batchers = (list(self._batchers.values())
                        + list(self._embed_batchers.values()))
            decoders = list(self._decoders.values())
            self._batchers.clear()
            self._embed_batchers.clear()
            self._decoders.clear()
        for b in batchers:
            b.stop()
        for d in decoders:
            d.stop()

    # -- preemption (the ResilientTrainer SIGTERM discipline) -------------
    def install_signal_handlers(self, signals=(signal.SIGTERM,)) -> None:
        """Wire graceful drain to preemption signals. Main thread only
        (the signal module's rule — same constraint ResilientTrainer
        documents); raises ValueError elsewhere."""
        for sig in signals:
            self._old_handlers[sig] = signal.signal(sig, self._on_signal)

    def restore_signal_handlers(self) -> None:
        for sig in list(self._old_handlers):
            try:
                signal.signal(sig, self._old_handlers[sig])
            except ValueError:
                # not the main thread (a drain thread's stop()): KEEP the
                # saved handler so a later main-thread stop can restore
                continue
            del self._old_handlers[sig]

    def _on_signal(self, signum, frame) -> None:
        # admission closes IN the handler (one flag write — safe in
        # signal context); EVERYTHING else — journaling included — runs
        # on the worker thread. The journal's append lock is a plain
        # non-reentrant Lock: the handler runs on the main thread
        # between bytecodes, and if that thread was mid-append when the
        # signal landed, taking the lock here would deadlock the whole
        # process at the exact moment it is being preempted.
        self._draining = True
        threading.Thread(target=self._preempt_stop, args=(int(signum),),
                         daemon=True, name="serve-drain").start()

    def _preempt_stop(self, signum: int) -> None:
        obs_journal.event("serve.preempt", signum=signum)
        self.stop(drain=True)

    @property
    def draining(self) -> bool:
        """Admission closed (stop()/drain()/SIGTERM). A replica process
        (serving/fleet.run_replica) polls this to know the signal landed
        without touching signal state itself."""
        return self._draining

    @property
    def drained(self) -> bool:
        """A full drain() pass completed — every admitted request was
        answered (or the drain deadline expired honestly)."""
        return self._drained

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"
