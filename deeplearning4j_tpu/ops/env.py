"""Central ``DL4J_TPU_*`` env-knob registry — the one table every knob read
goes through.

The reference concentrated its runtime configuration in one typed surface
(``NeuralNetConfiguration`` + the ``Builder`` DSL,
deeplearning4j-nn/.../conf/NeuralNetConfiguration.java) precisely so a typo'd
setting failed loudly instead of silently meaning "default". Our env knobs
grew the opposite way: ~40 ``os.environ.get("DL4J_TPU_...")`` reads scattered
over serving/etl/resilience/obs/ops, each with its own duplicated
``_env_int``/``_env_float`` helper and nothing catching a misspelled name.
This module is the typed surface for them: every knob is registered here with
its name, raw default, parser kind and one-line doc, and the graftlint
``env-knob-registry`` rule (analysis/rules_env.py) mechanically enforces that

  * no module outside this one reads a ``DL4J_TPU_*`` var from ``os.environ``
    directly,
  * every ``DL4J_TPU_*`` string literal anywhere in the tree names a
    registered knob (typos fail the gate), and
  * every registered knob is documented in CLAUDE.md.

Import-weight contract: this module must stay importable WITHOUT jax — the
obs plane is deliberately jax-free (obs/journal.py) and reads its knobs here;
``ops/__init__`` is lazy (PEP 562) for the same reason.

Semantics contract: reads are DYNAMIC (``os.environ`` at call time, never
cached) because tests and bench legs flip knobs mid-process, and parse
failures fall back to the default rather than raising — a garbled knob must
not take down a training run, matching the pre-table ``_env_*`` helpers.
Tri-state policy knobs (donate/fuse/bucket) keep their site-local parsing
over :func:`raw`; the table owns the NAME and the documented default, not
every consumer's enum logic.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

__all__ = [
    "Knob", "KNOBS", "KnobError", "knob", "knob_names", "is_registered",
    "raw", "get_str", "get_int", "get_float", "get_bool", "nonempty",
]


class KnobError(KeyError):
    """Read of an unregistered DL4J_TPU_* name — almost always a typo."""


@dataclass(frozen=True)
class Knob:
    name: str
    default: str          # raw default, as the env string; "" = unset
    kind: str             # int | float | bool | flag | str | path | enum
    doc: str
    choices: Tuple[str, ...] = ()


KNOBS: Dict[str, Knob] = {}


def _register(name: str, default: str, kind: str, doc: str,
              choices: Tuple[str, ...] = ()) -> None:
    KNOBS[name] = Knob(name, default, kind, doc, choices)


# ---------------------------------------------------------------------------
# the table — grouped by plane; keep each doc line greppable next to the
# CLAUDE.md entry the consistency gate checks for
# ---------------------------------------------------------------------------

# dispatch efficiency (ops/dispatch.py)
_register("DL4J_TPU_DONATE", "", "enum",
          "buffer donation for train-step jits: '' auto (on for "
          "accelerators, off on CPU), 0 never, force always",
          choices=("", "0", "1", "force"))
_register("DL4J_TPU_BUCKET_BATCHES", "", "enum",
          "shape bucketing for ragged batches: '' auto (fit_iterator/"
          "output only), 1 every fit, 0 off",
          choices=("", "0", "1", "auto"))
_register("DL4J_TPU_FUSE", "", "enum",
          "fit_batches scan fusion: '' auto (per-step fallback for "
          "scanned-conv on XLA:CPU), force always, 0 never",
          choices=("", "0", "1", "force"))

# HBM-lean training (ops/remat.py + ops/memory.py)
_register("DL4J_TPU_REMAT", "", "enum",
          "activation-remat policy ladder for block scans and per-layer "
          "remat: none (default) / dots / block",
          choices=("", "none", "dots", "block"))
_register("DL4J_TPU_HBM_GB", "", "float",
          "per-chip HBM budget (GiB) the preflight/auto-fit/arena sizers "
          "fit against; '' = the device's bytes_limit on a TPU backend, "
          "the planning chip's published 16 elsewhere")
_register("DL4J_TPU_MEM_MEASURE_ELEMS", "2000000", "int",
          "batch*seq*d_model element ceiling under which measure_memory "
          "AOT-compiles on the CPU substrate for measured bytes")

# precision + pallas kernel gate (ops/)
_register("DL4J_TPU_STRICT_CONV", "", "enum",
          "3pass forces the three-pass bf16-split strict conv everywhere "
          "(equivalence harness)", choices=("", "3pass"))
_register("DL4J_TPU_PALLAS", "", "enum",
          "pallas LSTM kernel gate: '' auto (TPU only, measured-win "
          "table), 0 off, force on even off-TPU (tests that substitute "
          "the interpreted kernel themselves)",
          choices=("", "0", "false", "False", "force"))
_register("DL4J_TPU_PALLAS_FORCE", "", "flag",
          "1 bypasses the PALLAS_BENCH.json measured-win gate (bench legs "
          "measuring the kernel itself)")
_register("DL4J_TPU_PALLAS_PAGED", "", "enum",
          "paged-decode attention kernel gate (ops/pallas_paged.py): '' "
          "auto (TPU + fit + measured-win 'paged' group), 0 off, force on "
          "wherever the VMEM budget fits (compiled; tests substitute the "
          "interpreted kernel themselves)",
          choices=("", "0", "false", "False", "force"))
_register("DL4J_TPU_PALLAS_SGNS", "", "enum",
          "fused SGNS gather-dot-scatter kernel gate (ops/pallas_sgns.py): "
          "'' auto (TPU + fit + measured-win 'sgns' group), 0 off, force "
          "on wherever the scratch fits (compiled)",
          choices=("", "0", "false", "False", "force"))

# low-precision plane (ops/lowprec.py + etl/calibrate.py)
_register("DL4J_TPU_QUANT", "", "enum",
          "calibrated int8 serving: '' auto (quantize when the model zip "
          "carries quant.json AND the accuracy gate passes), 0 off, force "
          "(quantize even when the gate delta exceeds the bar — delta "
          "still measured and reported)",
          choices=("", "0", "off", "force"))
_register("DL4J_TPU_QUANT_MAX_DELTA", "0.05", "float",
          "int8 accuracy gate: max abs output delta vs the f32 record "
          "measured at registry load on the calibration gate sample; past "
          "it the record lands BROKEN (PR 8 isolation) and the serving "
          "default never moves")
_register("DL4J_TPU_BF16", "0", "bool",
          "bf16 master-weight training mode for the containers and "
          "TransformerLM/BertMLM: f32 master params + updater state, bf16 "
          "cast at the train-step boundary, dynamic loss scaling "
          "(halve-and-skip on non-finite grads)")
_register("DL4J_TPU_LOSS_SCALE", "", "str",
          "dynamic loss-scale policy 'init' or 'init:growth_interval' "
          "('' = 32768:2000: start at 2^15, double after 2000 clean "
          "steps, halve-and-skip on non-finite grads, floor 1)")
_register("DL4J_TPU_SERVE_KV_DTYPE", "", "enum",
          "paged-KV arena dtype: '' = the model's compute dtype, bf16 "
          "halves KV bytes (same DL4J_TPU_HBM_GB admits ~2x tokens), f32 "
          "forces full precision",
          choices=("", "bf16", "f32"))

# observability (obs/)
_register("DL4J_TPU_OBS", "0", "bool",
          "span tracer master switch (default OFF; obs off => training "
          "bit-exact)")
_register("DL4J_TPU_OBS_SPANS", "65536", "int",
          "span ring capacity per tracer (what the full ring pushes out is "
          "counted: Tracer.dropped, dl4j_spans_dropped_total)")
_register("DL4J_TPU_OBS_JOURNAL", "", "path",
          "flight-recorder JSONL path; '' = .obs_journal[.pN].jsonl under "
          "cwd (N = fleet/multihost process id)")
_register("DL4J_TPU_OBS_JOURNAL_N", "4096", "int",
          "flight-recorder event-ring cap")
_register("DL4J_TPU_OBS_FLUSH_S", "5", "float",
          "flight-recorder periodic flush interval (seconds)")
_register("DL4J_TPU_OBS_PORT", "0", "int",
          "standalone MetricsExporter HTTP port (0 = ephemeral)")

# serving engine (serving/)
_register("DL4J_TPU_SERVE_MAX_BATCH", "64", "int",
          "dynamic-batcher max rows per dispatched batch")
_register("DL4J_TPU_SERVE_MAX_WAIT_MS", "10", "float",
          "dynamic-batcher admission window (ms)")
_register("DL4J_TPU_SERVE_QUEUE_CAP", "512", "int",
          "request queue cap; past it /predict answers 429")
_register("DL4J_TPU_SERVE_TIMEOUT_S", "60", "float",
          "per-request deadline; past it /predict answers 504")
_register("DL4J_TPU_SERVE_SLOTS", "4", "int",
          "continuous-batching KV slot-pool size for /generate")
_register("DL4J_TPU_SERVE_BATCH", "", "bool",
          "0 = naive per-request baseline instead of dynamic batching")
_register("DL4J_TPU_SERVE_CONTINUOUS", "", "bool",
          "0 = disable continuous-batching decode for /generate")
_register("DL4J_TPU_SERVE_BREAKER_FAILS", "5", "int",
          "consecutive inference failures that open a model's circuit "
          "breaker (0 disables)")
_register("DL4J_TPU_SERVE_WATCHDOG_S", "30", "float",
          "hung-inference watchdog wall deadline per dispatch (0 "
          "disables)")
_register("DL4J_TPU_SERVE_DRAIN_S", "20", "float",
          "graceful-drain deadline on stop()/SIGTERM")
_register("DL4J_TPU_SERVE_KV_BLOCK", "16", "int",
          "paged-KV block size in tokens for /generate (0 = fall back "
          "to the fixed slot pool)")
_register("DL4J_TPU_SERVE_KV_BLOCKS", "0", "int",
          "paged-KV arena size in blocks (0 = auto-size from "
          "DL4J_TPU_HBM_GB via ops/memory.kv_arena_blocks)")
_register("DL4J_TPU_SERVE_SLO_CLASSES", "", "str",
          "SLO scheduling classes 'name:deadline_s,...' highest "
          "priority first ('' = one default class at the request "
          "timeout)")
_register("DL4J_TPU_SERVE_TICK_K", "1", "int",
          "decode tokens per jitted tick (lax.scan inside one dispatch) "
          "for the fixed-slot and paged /generate pools; the worker "
          "adaptively drops to 1 whenever admissions are pending or any "
          "lane is within k tokens of its budget, so scheduling "
          "semantics are per-token while steady-state decode pays the "
          "dispatch overhead once per k tokens")
_register("DL4J_TPU_SERVE_SPEC", "", "str",
          "self-speculative decoding draft for greedy /generate on the "
          "paged pool: '' off, int8 = weight-quantized self-draft, "
          "layers[:m] = truncated-layer self-draft (m = draft depth, "
          "default half the target's layers)")
_register("DL4J_TPU_SERVE_SPEC_K", "4", "int",
          "draft tokens proposed per speculative round (the target "
          "verifies k+1 positions in one dispatch)")
_register("DL4J_TPU_SERVE_MESH", "0", "int",
          "serving-mesh device count for the paged /generate plane: "
          ">= 2 runs the decode tick TP-style under shard_map over that "
          "many devices (attention heads + KV arena head-sharded, "
          "serving/mesh.MeshPagedDecoder — byte-identical to the "
          "single-device tick); 0/'' = single-device decoders")
_register("DL4J_TPU_SERVE_ROLE", "", "enum",
          "serving replica role for prefill/decode disaggregation: "
          "prefill = own long-prompt prefill and export primed KV "
          "blocks (/prefill), decode = own the latency-critical decode "
          "tick, '' = both; published in the replica-<id>.addr JSON so "
          "the FleetRouter routes /generate by role",
          choices=("", "prefill", "decode"))
_register("DL4J_TPU_SERVE_FLEET_REPLICAS", "2", "int",
          "serving-fleet replica count (ServingFleet default)")
_register("DL4J_TPU_SERVE_ROUTER_PORT", "0", "int",
          "FleetRouter HTTP port (0 = ephemeral)")
_register("DL4J_TPU_SERVE_REPLICA_FAILS", "3", "int",
          "consecutive connect/5xx failures that eject a replica from "
          "the router (0 disables replica breakers)")
_register("DL4J_TPU_SERVE_SCALE_MIN", "1", "int",
          "autoscaler floor: never scale the fleet below this many "
          "replicas")
_register("DL4J_TPU_SERVE_SCALE_MAX", "4", "int",
          "autoscaler ceiling: never scale the fleet above this many "
          "replicas")
_register("DL4J_TPU_SERVE_SCALE_UP_QUEUE", "8", "float",
          "scale-up pressure: mean queued requests per ready replica "
          "at or above this votes up for the tick")
_register("DL4J_TPU_SERVE_SCALE_UP_P99_FRAC", "0.8", "float",
          "scale-up pressure: a class p99 at or above this fraction of "
          "its SLO deadline votes up for the tick")
_register("DL4J_TPU_SERVE_SCALE_UP_SHED", "1", "int",
          "scale-up pressure: at least this many new router sheds "
          "since the previous tick votes up (0 disables the shed vote)")
_register("DL4J_TPU_SERVE_SCALE_WINDOW", "3", "int",
          "consecutive ticks of one-sided pressure before the "
          "autoscaler acts (the sustained-evidence window)")
_register("DL4J_TPU_SERVE_SCALE_DOWN_QUEUE", "0", "float",
          "scale-down pressure: mean queued requests per ready replica "
          "at or below this (with zero sheds) votes down for the tick")
_register("DL4J_TPU_SERVE_SCALE_COOLDOWN", "5", "int",
          "ticks after any scale action before the next one (counted "
          "in TICKS, not wall-clock, so decisions replay bit-exact)")
_register("DL4J_TPU_SERVE_TENANT_QUOTAS", "", "str",
          "per-tenant token-bucket quotas 'name:rate_per_s[:burst],...'"
          " ('' = no tenant metering; unlisted tenants are unmetered)")

# resilience / checkpointing (resilience/)
_register("DL4J_TPU_CKPT_EVERY", "0", "int",
          "checkpoint every N steps (0 = off)")
_register("DL4J_TPU_CKPT_KEEP", "3", "int",
          "keep-last-k checkpoints")
_register("DL4J_TPU_CKPT_ASYNC", "1", "bool",
          "0 = synchronous checkpoint writes")

# ETL / input pipeline (etl/, datasets/)
_register("DL4J_TPU_PIPELINE_WORKERS", "0", "int",
          "InputPipeline worker threads (0 = off; >0 also opts "
          "fit_iterator into auto-wrapping plain iterators)")
_register("DL4J_TPU_PREFETCH", "2", "int",
          "staged-batch queue depth (shared with AsyncDataSetIterator)")
_register("DL4J_TPU_DATA_DIR", "", "path",
          "dataset cache dir; '' = ~/.deeplearning4j_tpu")
_register("DL4J_TPU_OFFLINE", "", "flag",
          "any non-empty value skips dataset downloads (synthetic "
          "fallbacks engage immediately)")

# multihost / fleet (parallel/)
_register("DL4J_TPU_COORDINATOR", "", "str",
          "jax.distributed coordinator address (host:port); unset = "
          "single-process")
_register("DL4J_TPU_NUM_PROCESSES", "", "int",
          "jax.distributed process count")
_register("DL4J_TPU_PROCESS_ID", "", "int",
          "this process's jax.distributed / fleet rank; also suffixes the "
          "default obs journal path")
_register("DL4J_TPU_FLEET_HEARTBEAT_S", "5.0", "float",
          "elastic-fleet failure-detection heartbeat timeout (seconds)")
_register("DL4J_TPU_FLEET_MIN_WORKERS", "1", "int",
          "elastic-fleet round blocks below this live-membership size")
_register("DL4J_TPU_FLEET_DIR", "", "path",
          "default fleet spool/file-membership transport dir")

# online learning (online/)
_register("DL4J_TPU_ONLINE_WATERMARK", "64", "int",
          "StreamSource backpressure high watermark: push() blocks while "
          "this many batches sit undelivered")
_register("DL4J_TPU_ONLINE_IDLE_S", "0.2", "float",
          "idle window (seconds with no arrival) that ends a StreamSource "
          "poll pass / ContinuousTrainer fit round (0 = block until close)")
_register("DL4J_TPU_ONLINE_SNAPSHOT_ROUNDS", "1", "int",
          "candidate-snapshot cadence in fit rounds for "
          "ContinuousTrainer.export_candidate paths (0 = off)")
_register("DL4J_TPU_ONLINE_DRIFT_Z", "3.0", "float",
          "DriftMonitor alarm threshold: max per-column "
          "|live_mean - base_mean| / base_std")
_register("DL4J_TPU_ONLINE_DRIFT_MIN", "64", "int",
          "minimum live rows before DriftMonitor.check() renders a "
          "verdict")
_register("DL4J_TPU_ONLINE_SHADOW_FRACTION", "1.0", "float",
          "fraction of answered /predict traffic mirrored to the shadow "
          "candidate (deterministic stride, not RNG)")
_register("DL4J_TPU_ONLINE_SHADOW_MIN", "32", "int",
          "minimum mirrored requests before ShadowPromoter.evaluate() "
          "will pass a candidate")
_register("DL4J_TPU_ONLINE_GATE_AGREE", "0.0", "float",
          "promotion gate: minimum shadow-vs-primary argmax agreement "
          "fraction (0 disables the agreement gate)")

# embedding & retrieval serving (retrieval/)
_register("DL4J_TPU_EMBED_LAYER", "", "int",
          "feed-forward embedding layer: int index into the MLN "
          "activations list ('' = -2, the last hidden layer); CG vertex "
          "selection is per-adapter, not env-driven")
_register("DL4J_TPU_EMBED_POOL", "mean", "str",
          "sequence pooling for BertMLM /embed contextual embeddings",
          choices=("mean", "cls", "max"))
_register("DL4J_TPU_ANN_ROWS", "0", "int",
          "vector-index arena capacity in rows (0 = auto-size from "
          "DL4J_TPU_HBM_GB via ops/memory.ann_arena_rows)")
_register("DL4J_TPU_ANN_CLUSTERS", "0", "int",
          "IVF coarse-quantizer cluster count (0 = auto ~= sqrt(rows))")
_register("DL4J_TPU_ANN_NPROBE", "8", "int",
          "IVF clusters probed per /search query (recall/qps dial; "
          "measured recall@k vs the exact oracle rides "
          "retrieval_stats.last_recall)")

# bench / examples harness (bench.py, examples/)
_register("DL4J_TPU_EXAMPLE_SMOKE", "", "flag",
          "any non-empty value shrinks every examples/*.py to smoke-tier "
          "shapes (the -m examples tier sets it)")
_register("DL4J_TPU_W2V_CORPUS", "", "path",
          "real-text corpus for the word2vec bench leg ('' = synthetic, "
          "provenance-labelled)")
_register("DL4J_TPU_XPLANE_TRACE", "", "path",
          "per-leg xplane trace output dir (bench.py --trace)")


# ---------------------------------------------------------------------------
# readers — dynamic, registered-name-checked, default-on-garbage
# ---------------------------------------------------------------------------


def knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KnobError(
            f"{name} is not a registered DL4J_TPU knob — add it to "
            "deeplearning4j_tpu/ops/env.py (and CLAUDE.md) or fix the "
            "typo") from None


def knob_names() -> Tuple[str, ...]:
    return tuple(sorted(KNOBS))


def is_registered(name: str) -> bool:
    return name in KNOBS


def raw(name: str, default: Optional[str] = None) -> str:
    """The raw env string, '' when unset and no default is given.

    ``default`` (when provided) overrides the table default — call sites
    with context-dependent fallbacks (e.g. CheckpointManager's explicit
    constructor args) pass their own."""
    k = knob(name)
    v = os.environ.get(name)
    if v is None or v == "":
        return default if default is not None else k.default
    return v


def get_str(name: str, default: Optional[str] = None) -> Optional[str]:
    v = raw(name, "" if default is None else default)
    return v if v != "" else default


def get_int(name: str, default: Optional[int] = None) -> Optional[int]:
    v = raw(name, "").strip()
    if v == "" and default is None:
        v = knob(name).default
    try:
        return int(v) if v != "" else default
    except ValueError:
        return default


def get_float(name: str, default: Optional[float] = None) -> Optional[float]:
    v = raw(name, "").strip()
    if v == "" and default is None:
        v = knob(name).default
    try:
        return float(v) if v != "" else default
    except ValueError:
        return default


_FALSY = ("0", "off", "false", "no")


def get_bool(name: str, default: Optional[bool] = None) -> bool:
    """The repo's bool convention: '0'/'off'/'false'/'no' => False, any
    other non-empty value => True, unset/empty => the table default (or
    the ``default`` override)."""
    v = raw(name, "").strip().lower()
    if v == "":
        if default is not None:
            return default
        v = knob(name).default.strip().lower()
        if v == "":
            return False
    return v not in _FALSY


def nonempty(name: str) -> bool:
    """``bool(os.environ.get(name))`` parity for flag knobs (OFFLINE,
    EXAMPLE_SMOKE) — any non-empty value, '0' included, is
    truthy; kept for behavior-identical migration of those sites."""
    knob(name)
    return bool(os.environ.get(name))
