"""Model registry: named/versioned load → warmup → serve → unload.

The reference's serving route binds ONE model at route-build time
(DL4jServeRouteBuilder.java: the Camel route restores a single
ModelSerializer checkpoint and serves it until the route dies); rolling a
new model means rolling the route. A production endpoint needs the
lifecycle to be data, not deployment:

  load     restore a checkpoint (utils/serialization.ModelSerializer —
           the reference's three-part zip, ModelSerializer.java:70-110) or
           adopt a live model object, under a (name, version) key;
  warmup   pre-compile the inference bucket ladder (ops/dispatch
           bucket_size) BEFORE the model takes traffic, so the first real
           request never pays an XLA trace — the serving twin of the
           persistent-compile-cache rationale (a compile paid at warmup is
           free at p99);
  serve    atomically switch the default traffic target to (name,
           version) — the previous version keeps serving in-flight
           requests it already received;
  unload   drop the registry's references and DELETE the device buffers
           (jax array .delete()), so a retired version's params/optimizer
           HBM is reclaimed immediately instead of at GC's leisure.

Failure isolation (ISSUE 8 — the rollback primitive ROADMAP item 5's
shadow-eval promotion stands on): a load/warmup exception no longer
propagates with no per-model record — the record lands in state
``broken`` (with the error preserved for /models), the exception is
re-raised to the caller, and crucially the PRIOR serving version is
untouched: the default traffic target never moves on a failed rollout,
and ``serve()`` refuses to promote a broken record. Deterministic fault
injection: resilience/chaos.ServingChaosConfig (load_fail_name /
warmup_fail_name), consulted only when a chaos object is configured.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.ops import dispatch

# model attributes that hold device-buffer pytrees — walked by unload()
_BUFFER_ATTRS = ("params", "states", "updater_state", "opt")


def bucket_ladder(max_batch: int) -> List[int]:
    """The distinct bucket sizes a batcher can dispatch for batches of
    1..max_batch rows — the set warmup must pre-compile."""
    return sorted({dispatch.bucket_size(n) for n in range(1, max_batch + 1)})


class ModelRecord:
    """One (name, version) entry. ``state`` walks loaded → warm → serving
    → unloaded; the registry is the only writer."""

    def __init__(self, name: str, version: int, model, *,
                 input_shape: Optional[Tuple[int, ...]] = None,
                 path: Optional[str] = None, normalizer=None) -> None:
        self.name = name
        self.version = int(version)
        self.model = model
        self.input_shape = tuple(input_shape) if input_shape else None
        self.path = path
        # fitted DataNormalization (etl/normalize.py) applied to every
        # /predict request for this record — the training-time statistics
        # travel WITH the model (checkpoint zip normalizer.json section)
        self.normalizer = normalizer
        # active serving precision ('f32'/'bf16'/'int8') + the int8
        # accuracy-gate evidence measured at load (ISSUE 15) — the audit
        # trail a fleet rollout of a quantized model reads at /models
        self.precision = "f32"
        self.quant: Optional[Dict[str, Any]] = None
        self.state = "loaded"
        self.error: Optional[str] = None  # set when state == "broken"
        self.loaded_ts = time.strftime("%Y-%m-%dT%H:%M:%S")
        self.warmed_buckets: List[int] = []
        # the default this record REPLACED when serve() promoted it
        # ("name@vN" or None) — the auditable rollback target (ISSUE 14)
        self.prior_default: Optional[str] = None
        # self-drafts for speculative decoding (ISSUE 16), cached per
        # mode: ONE quantization per record however many decoders the
        # engine (re)builds around it
        self._drafts: Dict[str, Any] = {}
        # embedding adapters (ISSUE 17), cached per (layer, pool): the
        # /embed encoder reuses one adapter (and its compiled program
        # chain through the bucket ladder) across every request
        self._embedders: Dict[Tuple[Any, Any], Any] = {}

    @property
    def key(self) -> str:
        return f"{self.name}@v{self.version}"

    def draft_net(self, mode: str = "int8"):
        """The self-draft a SpeculativeDecoder proposes with
        (serving/speculate.py). An already-int8 record (the PR 15
        QuantizedNet wrapper) IS its own int8 form — one quantization,
        one gate verdict; otherwise the draft is derived from this
        record's weights via ops/lowprec.draft_lm and cached so repeat
        decoder builds never re-quantize."""
        mode = (mode or "int8").strip().lower()
        if self.model is None:
            raise ValueError(
                f"record {self.key} has no model (state={self.state})")
        if mode == "int8" and \
                getattr(self.model, "precision", None) == "int8":
            return self.model
        draft = self._drafts.get(mode)
        if draft is None:
            from deeplearning4j_tpu.ops import lowprec

            draft = lowprec.draft_lm(self.model, mode)
            self._drafts[mode] = draft
        return draft

    def embed_adapter(self, layer=None, pool: Optional[str] = None):
        """The embedding encoder over this record's model
        (retrieval/embed.resolve_adapter — MLN/CG hidden layer, BERT
        pooled embed_tokens, or word2vec lookup), cached per
        (layer, pool) like draft_net so repeat /embed batcher builds
        reuse one adapter and its compiled programs. Resolution never
        RUNS the model (dims come from config/param shapes/eval_shape —
        the /models AOT contract)."""
        if self.model is None:
            raise ValueError(
                f"record {self.key} has no model (state={self.state})")
        key = (layer, pool)
        adapter = self._embedders.get(key)
        if adapter is None:
            from deeplearning4j_tpu.retrieval.embed import resolve_adapter

            adapter = resolve_adapter(self.model, layer=layer, pool=pool,
                                      input_shape=self.input_shape)
            self._embedders[key] = adapter
        return adapter

    def describe(self) -> Dict[str, Any]:
        out = {
            "name": self.name,
            "version": self.version,
            "state": self.state,
            "model_type": type(self.model).__name__ if self.model is not None
            else None,
            "loaded_ts": self.loaded_ts,
            "warmed_buckets": list(self.warmed_buckets),
            "precision": self.precision,
        }
        if self.quant is not None:
            out["quant"] = dict(self.quant)
        if self.error is not None:
            out["error"] = self.error
        if self.input_shape:
            out["input_shape"] = list(self.input_shape)
        if self.normalizer is not None:
            out["normalizer"] = type(self.normalizer).__name__
        if self.prior_default is not None:
            out["prior_default"] = self.prior_default
        stats = getattr(self.model, "dispatch_stats", None)
        if stats is not None:
            out["dispatch_stats"] = stats.snapshot()
        return out


class ModelRegistry:
    def __init__(self, chaos=None, stats=None) -> None:
        self._lock = threading.RLock()
        self._records: Dict[str, Dict[int, ModelRecord]] = {}
        self._default: Optional[Tuple[str, int]] = None
        # serving resilience wiring (both optional): the chaos monkey
        # injects load/warmup faults deterministically; the stats ledger
        # (serving/telemetry.ServingStats) counts the isolations
        self.chaos = chaos
        self.stats = stats
        self._sealed = False
        # version lineage (ISSUE 14 satellite): every serve() swap is
        # recorded {"ts", "from", "to"} so a post-promotion rollback
        # target is auditable at /models, not just implicit
        self._lineage: List[Dict[str, Any]] = []

    def seal(self) -> None:
        """Freeze the lifecycle for shutdown (ISSUE 12 satellite): the
        engine seals the registry the moment its drain begins, so a
        rollout racing the drain (an HTTP /models thread mid load ->
        warmup -> serve) can never promote a half-warmed record as the
        serving default while the engine is going down — load/warmup
        isolation holds ACROSS drain, not just across failures. Sealed
        load/warmup/serve raise DrainingError (HTTP 503 + Retry-After,
        like any admission during drain); unload stays legal — teardown
        must still free device buffers."""
        with self._lock:
            self._sealed = True

    def _check_sealed(self) -> None:
        if self._sealed:
            from deeplearning4j_tpu.serving.resilience import DrainingError

            raise DrainingError(
                "registry is sealed (engine draining); lifecycle "
                "mutations refused")

    # -- lifecycle --------------------------------------------------------
    def load(self, name: str, model=None, model_path: Optional[str] = None,
             input_shape=None, normalizer=None, quant=None) -> ModelRecord:
        """Register a live model or restore a ModelSerializer zip; the
        version is auto-assigned (monotonic per name, starting at 1).
        A checkpoint zip's optional normalizer section is picked up
        automatically (an explicit ``normalizer`` wins) so /predict
        applies the exact statistics the model trained under. The
        optional quant.json section engages the calibrated int8 path the
        same way (ISSUE 15): under DL4J_TPU_QUANT the model is wrapped in
        an ops/lowprec.QuantizedNet and the accuracy delta vs the f32
        record is MEASURED on the spec's gate sample — a delta past
        DL4J_TPU_QUANT_MAX_DELTA raises inside this try block, so the
        record lands BROKEN through the same isolation as any failed
        restore and the serving default never moves.

        A restore that RAISES is isolated, not propagated bare: the
        version lands as a BROKEN record (error preserved, model None)
        and the exception re-raises — the default traffic target never
        moves, so the previously serving version keeps taking requests
        (the rollback primitive)."""
        if model is None and model_path is None:
            raise ValueError("need model or model_path")
        self._check_sealed()
        quant_info = None
        try:
            if self.chaos is not None:
                self.chaos.on_load(name)
            if model is None:
                from deeplearning4j_tpu.utils.serialization import (
                    ModelSerializer,
                )

                model = ModelSerializer.restore(model_path)
            if normalizer is None and model_path is not None:
                from deeplearning4j_tpu.utils.serialization import (
                    read_normalizer,
                )

                normalizer = read_normalizer(model_path)
            if quant is None and model_path is not None:
                from deeplearning4j_tpu.utils.serialization import read_quant

                quant = read_quant(model_path)
            model, quant_info = _maybe_quantize(model, quant)
        except Exception as e:
            self._record_broken(name, e, input_shape=input_shape,
                                path=model_path)
            if self.stats is not None:
                self.stats.record_load_failure()
            raise
        with self._lock:
            versions = self._records.setdefault(name, {})
            version = max(versions) + 1 if versions else 1
            rec = ModelRecord(name, version, model,
                              input_shape=input_shape, path=model_path,
                              normalizer=normalizer)
            from deeplearning4j_tpu.ops import lowprec

            rec.precision = lowprec.precision_of(model)
            rec.quant = quant_info
            versions[version] = rec
            # NOT auto-promoted to the traffic default: only serve()
            # switches traffic (the documented load -> warmup -> serve
            # lifecycle — a cold record must never take requests because
            # it happened to be loaded first)
            return rec

    def _record_broken(self, name: str, exc: Exception, *,
                       input_shape=None, path=None) -> ModelRecord:
        """Install a BROKEN record for a failed load so the rollout
        attempt is auditable at /models instead of vanishing into the
        caller's traceback. Never touches the serving default."""
        with self._lock:
            versions = self._records.setdefault(name, {})
            version = max(versions) + 1 if versions else 1
            rec = ModelRecord(name, version, None,
                              input_shape=input_shape, path=path)
            rec.state = "broken"
            rec.error = f"{type(exc).__name__}: {exc}"
            versions[version] = rec
            return rec

    def warmup(self, name: Optional[str] = None,
               version: Optional[int] = None, *, max_batch: int = 64,
               sample_row: Optional[np.ndarray] = None,
               gen_tokens: int = 0) -> Dict[str, Any]:
        """Compile the model's inference programs for every bucket size a
        batcher can dispatch, before the record takes traffic.

        The sample row defaults to zeros of ``input_shape`` (token models
        — no input_shape but a generate() — warm with a [b, 2] id batch).
        ``gen_tokens > 0`` additionally warms the LM sampler for that
        n_new (one compile per n_new — models/transformer._sample_kv_fn)."""
        self._check_sealed()
        rec = self.get(name, version)
        model = rec.model
        if model is None:
            raise ValueError(f"{rec.key} is unloaded")
        if sample_row is not None:
            row = np.asarray(sample_row)
        elif rec.input_shape is not None:
            row = np.zeros(rec.input_shape, np.float32)
        elif hasattr(model, "generate"):  # token-id model (the LM)
            row = np.zeros((2,), np.int32)
        else:
            raise ValueError(
                f"{rec.key}: warmup needs input_shape or sample_row")
        t0 = time.perf_counter()
        ladder = bucket_ladder(max_batch)
        try:
            if self.chaos is not None:
                self.chaos.on_warmup(rec.name)
            for b in ladder:
                batch = np.broadcast_to(row, (b,) + row.shape)
                out = model.output(batch)
                np.asarray(out[0] if isinstance(out, (list, tuple)) else out)
            if gen_tokens and hasattr(model, "generate"):
                np.asarray(model.generate(
                    np.zeros((1, 2), np.int32), int(gen_tokens)))
        except Exception as e:
            # a model that cannot compile/run its bucket ladder must not
            # take traffic: BROKEN, error preserved, prior serving
            # version untouched (warmup never promotes)
            with self._lock:
                rec.state = "broken"
                rec.error = f"{type(e).__name__}: {e}"
            if self.stats is not None:
                self.stats.record_warmup_failure()
            raise
        dt = time.perf_counter() - t0
        with self._lock:
            rec.warmed_buckets = ladder
            if rec.state in ("loaded", "broken"):
                # a broken-at-warmup record that now warms clean is
                # rehabilitated — the operator's re-warm IS the probe
                rec.state = "warm"
                rec.error = None
        return {"model": rec.key, "buckets": ladder,
                "gen_tokens": int(gen_tokens), "seconds": round(dt, 3)}

    def serve(self, name: Optional[str] = None,
              version: Optional[int] = None) -> ModelRecord:
        """Make (name, version) the default traffic target. Refuses a
        broken record (promoting a failed rollout would move traffic ONTO
        the failure the isolation just contained) and a sealed registry
        (a drain-racing rollout must not move traffic on a dying engine)."""
        self._check_sealed()
        rec = self.get(name, version)
        if rec.state == "broken":
            raise ValueError(
                f"{rec.key} is broken ({rec.error}); refusing to serve")
        if rec.model is None:
            raise ValueError(f"{rec.key} is unloaded")
        with self._lock:
            prev = self._default
            self._default = (rec.name, rec.version)
            rec.state = "serving"
            if prev is not None and prev != self._default:
                old = self._records.get(prev[0], {}).get(prev[1])
                if old is not None and old.state == "serving":
                    old.state = "warm"
            if prev != self._default:
                prev_key = f"{prev[0]}@v{prev[1]}" if prev else None
                rec.prior_default = prev_key
                self._lineage.append({
                    "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
                    "from": prev_key, "to": rec.key})
        return rec

    def mark_broken(self, name: str, version: Optional[int] = None, *,
                    error: str = "promotion gate failed") -> ModelRecord:
        """Land a record BROKEN post-hoc (the shadow promoter's refusal
        path: a candidate that warmed clean but failed its promotion
        gates must not stay promotable). Refuses to break the serving
        default — traffic never moves onto or off of a record through
        this door; error preserved for /models like any isolation."""
        rec = self.get(name, version)
        with self._lock:
            if self._default == (rec.name, rec.version):
                raise ValueError(
                    f"{rec.key} is the serving default; mark_broken would "
                    "break live traffic — demote it first")
            rec.state = "broken"
            rec.error = str(error)
        return rec

    # -- lineage ----------------------------------------------------------
    def lineage(self) -> List[Dict[str, Any]]:
        """The serve()-swap history, oldest first."""
        with self._lock:
            return [dict(e) for e in self._lineage]

    def rollback_target(self) -> Optional[Tuple[str, int]]:
        """(name, version) the CURRENT default replaced, if that record
        is still promotable (loaded, not broken/unloaded) — the audited
        answer to "what do we roll back to"."""
        with self._lock:
            if self._default is None:
                return None
            rec = self._records[self._default[0]][self._default[1]]
            prior = rec.prior_default
            if prior is None:
                return None
            pname, _, pver = prior.rpartition("@v")
            old = self._records.get(pname, {}).get(int(pver))
            if old is None or old.model is None or old.state == "broken":
                return None
            return pname, int(pver)

    def unload(self, name: str, version: Optional[int] = None) -> ModelRecord:
        """Drop the record's model and free its device buffers NOW."""
        rec = self.get(name, version)
        with self._lock:
            if self._default == (rec.name, rec.version):
                self._default = None
            model, rec.model, rec.state = rec.model, None, "unloaded"
        if model is not None:
            _delete_device_buffers(model)
        return rec

    # -- lookup -----------------------------------------------------------
    def get(self, name: Optional[str] = None,
            version: Optional[int] = None) -> ModelRecord:
        with self._lock:
            if name is None:
                if self._default is None:
                    raise KeyError("no model is serving")
                name, default_version = self._default
                if version is None:
                    version = default_version
            versions = self._records.get(name)
            if not versions:
                raise KeyError(f"unknown model {name!r}")
            if version is None:
                # newest loaded version of the name (serving wins if set)
                if self._default and self._default[0] == name:
                    version = self._default[1]
                else:
                    version = max(versions)
            rec = versions.get(int(version))
            if rec is None:
                raise KeyError(f"unknown version {name}@v{version}")
            return rec

    def default(self) -> Optional[ModelRecord]:
        with self._lock:
            if self._default is None:
                return None
            return self._records[self._default[0]][self._default[1]]

    def describe(self) -> List[Dict[str, Any]]:
        with self._lock:
            recs = [r for vs in self._records.values() for r in vs.values()]
        return [r.describe() for r in
                sorted(recs, key=lambda r: (r.name, r.version))]


def _maybe_quantize(model, spec):
    """Apply the calibrated int8 serving path (ops/lowprec.QuantizedNet)
    under the DL4J_TPU_QUANT policy and render the accuracy gate:

    * mode 'off', no spec, or a model without a layer stack → f32 as-is;
    * 'auto' (default): quantize only when the spec carries a gate sample
      AND the measured int8-vs-f32 max-abs output delta stays within
      DL4J_TPU_QUANT_MAX_DELTA — past the bar raises QuantGateError (the
      caller's try lands the record BROKEN; fail-safe by construction). A
      sample-less spec serves f32 with verdict 'ungated' rather than
      serving unproven int8 or breaking a perfectly good f32 record;
    * 'force': quantize even past the bar — delta still measured and
      reported, so the override is auditable, never silent.

    Returns (model_or_qnet, quant_info_dict_or_None)."""
    from deeplearning4j_tpu.ops import lowprec

    mode = lowprec.quant_mode()
    if spec is None or mode == "off" or not hasattr(model, "layers"):
        return model, None
    qnet = lowprec.QuantizedNet(model, spec)
    layers = qnet.quantized_layers()
    if not layers:
        return model, None
    info: Dict[str, Any] = {
        "mode": mode,
        "layers": layers,
        "max_delta": lowprec.quant_max_delta(),
    }
    sample = getattr(spec, "sample", None)
    if sample is None or getattr(sample, "size", 0) == 0:
        if mode != "force":
            info["verdict"] = "ungated"
            info["delta"] = None
            return model, info
        info["verdict"] = "forced-ungated"
        info["delta"] = None
        return qnet, info
    f32_out = np.asarray(model.output(sample))
    int8_out = np.asarray(qnet.output(sample))
    delta = float(np.max(np.abs(f32_out - int8_out)))
    info["delta"] = delta
    if delta <= info["max_delta"]:
        info["verdict"] = "ok"
        return qnet, info
    if mode == "force":
        info["verdict"] = "forced"
        return qnet, info
    raise lowprec.QuantGateError(
        f"int8 accuracy gate failed: measured delta {delta:.6g} > "
        f"DL4J_TPU_QUANT_MAX_DELTA {info['max_delta']:.6g} on the "
        f"{sample.shape[0]}-row calibration gate sample")


def _delete_device_buffers(model) -> None:
    """Best-effort immediate free of a model's device arrays (HBM is the
    scarce resource a retired version must hand back)."""
    import jax

    for attr in _BUFFER_ATTRS:
        tree = getattr(model, attr, None)
        if tree is None:
            continue
        for leaf in jax.tree_util.tree_leaves(tree):
            delete = getattr(leaf, "delete", None)
            if delete is not None:
                try:
                    delete()
                except Exception:  # noqa: BLE001 — already-deleted/shared leaves
                    pass
        try:
            setattr(model, attr, None)
        except Exception:  # noqa: BLE001 — read-only attrs stay
            pass
