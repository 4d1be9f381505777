"""AOT memory-accounting plane: the HBM ledger beside ops/dispatch's
dispatch ledger.

XLA's ahead-of-time path reports, per compiled program, exactly how many
bytes of arguments, outputs and temporaries (activations + workspace)
the executable will touch — ``jit(f).lower(args).compile()
.memory_analysis()`` — WITHOUT executing anything and on whatever
backend compiled it. That makes the memory cost of a training step
computable without a chip: the CPU build of the d512 L8 step shows the
remat ladder's temp-bytes reduction, and the same call on a TPU backend
reports real HBM.

Three surfaces:

  1. ``MemoryStats`` + ``analyze_jit`` — per-program byte accounting,
     exposed as ``net.memory_stats`` beside ``net.dispatch_stats`` on
     both containers and the flagship models (populated on demand via
     ``measure_memory``: AOT lowering is a compile, not a step, so it is
     never paid implicitly on the hot path).
  2. ``transformer_preflight`` — the OOM guard for the MFU-chase bench
     leg (bench.transformer_hbm_preflight delegates here): exact
     params/optimizer/grads via ``jax.eval_shape`` on the real inits,
     remat- and accum-aware analytic activation model for the
     bf16+flash regime, plus MEASURED AOT numbers merged in whenever the
     config is small enough to compile cheaply on the CPU substrate.
  3. ``auto_fit_transformer`` — given ``DL4J_TPU_HBM_GB``, pick the
     largest (batch, accum_steps, remat policy) triple that fits:
     largest batch first, then the cheapest way to afford it (no accum
     before accum, weakest remat rung before strongest — every rung down
     the ladder costs backward recompute).

The reference has no analog: its memory ceiling was JVM heap and its
failure mode an ``OutOfMemoryError`` mid-fit (SURVEY §3.1); here an OOM
wastes a whole chip run, so the guard must be computable without one.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.ops import env as envknob

ENV_HBM = "DL4J_TPU_HBM_GB"
# configs whose batch*seq*d_model element count is at or under this are
# cheap enough to AOT-compile on the CPU substrate for measured numbers
# (the d512 L8 b8 s256 evidence config compiles in ~2s on this host)
ENV_MEASURE_ELEMS = "DL4J_TPU_MEM_MEASURE_ELEMS"
_MEASURE_ELEMS_DEFAULT = 2_000_000


def hbm_budget_gb() -> float:
    """The per-chip HBM budget (GiB) the sizers fit against:
    ``DL4J_TPU_HBM_GB`` when set (planning without a chip —
    serving/placement.py, tests); on a TPU backend the device's own
    ``memory_stats()["bytes_limit"]``; otherwise the published HBM of
    the chip the sizers plan for (ops/device.PLANNING_KIND)."""
    from deeplearning4j_tpu.ops import device

    v = envknob.raw(ENV_HBM, "").strip()
    if v:
        try:
            return float(v)
        except ValueError:
            pass  # a garbled knob reads as unset (ops/env.py contract)
    limit = device.hbm_bytes_limit()
    if limit is not None:
        return limit / 2.0**30
    return float(device.peaks(device.PLANNING_KIND)["hbm_gb"])


class MemoryStats:
    """Per-program AOT memory accounting (bytes), keyed by the same
    program names DispatchStats uses (``train_step``, ``fit_batches``,
    ``output``) so the two ledgers line up row for row."""

    def __init__(self) -> None:
        self.programs: Dict[str, Dict[str, Any]] = {}

    def record(self, name: str, analysis: Dict[str, Any]) -> None:
        self.programs[name] = dict(analysis)

    def snapshot(self) -> Dict[str, Any]:
        return {k: dict(v) for k, v in self.programs.items()}


def analyze_compiled(compiled) -> Optional[Dict[str, Any]]:
    """Byte accounting of one compiled XLA executable, or None when the
    backend doesn't expose memory stats (the accounting is evidence,
    never a crash — same posture as dispatch.enable_compile_cache)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return None
    if ma is None:
        return None
    out = {
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    # live-at-once upper bound: args + temps + non-aliased outputs (a
    # donated step aliases outputs onto inputs, so alias_bytes nets out)
    out["peak_bytes"] = (out["argument_bytes"] + out["temp_bytes"]
                         + max(0, out["output_bytes"] - out["alias_bytes"]))
    return out


def analyze_lowered(lowered) -> Optional[Dict[str, Any]]:
    try:
        return analyze_compiled(lowered.compile())
    except Exception:  # noqa: BLE001
        return None


def analyze_jit(fn, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """AOT memory accounting for a jitted callable (accepts plain
    ``jax.jit`` results and dispatch.instrumented_jit wrappers — both
    expose ``.lower``; instrumented wrappers suppress the phantom-retrace
    count themselves). Args may be real arrays or ShapeDtypeStructs —
    lowering never executes."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        return analyze_lowered(lower(*args, **kwargs))
    except Exception:  # noqa: BLE001
        return None


def measure(stats: Optional[MemoryStats], name: str, fn, *args,
            **kwargs) -> Optional[Dict[str, Any]]:
    """analyze_jit + record into a MemoryStats (when given)."""
    analysis = analyze_jit(fn, *args, **kwargs)
    if stats is not None and analysis is not None:
        stats.record(name, analysis)
    return analysis


# ---------------------------------------------------------------------------
# transformer training-step sizing (the flagship's OOM guard + auto-fit)
# ---------------------------------------------------------------------------


def _tree_bytes(tree) -> int:
    import jax

    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def _cpu_substrate() -> bool:
    """True when the backend is the CPU — the only platform the measured
    path may compile on implicitly."""
    from deeplearning4j_tpu.ops import device

    return device.platform() == "cpu"


def transformer_preflight(cfg, batch: int, *, accum_steps: int = 1,
                          remat: Optional[str] = None,
                          hbm_gb: Optional[float] = None,
                          measure_aot: Optional[bool] = None,
                          ) -> Tuple[bool, Dict[str, Any]]:
    """HBM estimate for one TransformerLM training step under a remat
    policy and gradient-accumulation factor. Returns (fits, report).

    Params, optimizer state and gradients are EXACT
    (``jax.eval_shape`` on the real init_params/init_opt_state — zero
    allocation, works without the chip). Activations are an analytic
    per-layer residual count for the bf16+flash regime, scaled by the
    remat rung:

      none   every layer's residuals stay live for the backward
             (q/k/v/attn-out/mlp-in/x ~6 [B,S,D] buffers + 2 [B,S,F]
             gelu buffers + flash o/lse, per layer)
      dots   per layer only the dot OUTPUTS stay (5 [B,S,D] + 1 [B,S,F]),
             plus one layer's full residual set as the recompute peak
      block  per layer only the [B,S,D] residual carry stays, plus one
             layer's full residual set as the recompute peak (Chen et
             al. sublinear memory)

    accum_steps > 1 sizes activations/logits per MICROBATCH (batch/A)
    and doubles the gradient tree (accumulator + current microbatch
    grads — models/transformer._build_step's scan). Logits count
    [mb, S, V] f32 x2 (fwd + softmax residual); 1.25x slack for XLA
    temps.

    When the config is small enough to compile cheaply and jax is pinned
    to the CPU substrate (or ``measure_aot=True``), the ACTUAL step is
    AOT-lowered and ``memory_analysis`` numbers are merged into the
    report (``measured`` sub-dict) — measured-where-possible, analytic
    everywhere else; the fits verdict stays with the analytic total,
    whose activation model is the flash/TPU program (the CPU build
    materializes dense [B,H,T,T] scores the chip never allocates)."""
    import jax

    from deeplearning4j_tpu.models.transformer import (
        init_opt_state,
        init_params,
    )
    from deeplearning4j_tpu.ops.remat import remat_policy

    policy = remat_policy(remat if remat is not None else cfg.remat)
    if batch % accum_steps:
        raise ValueError(f"batch {batch} not divisible by accum_steps "
                         f"{accum_steps}")
    from deeplearning4j_tpu.ops import lowprec

    budget_gb = hbm_budget_gb() if hbm_gb is None else float(hbm_gb)
    seq = cfg.max_len
    # bf16 activations under the performance dtype policy OR bf16
    # master-weight training (DL4J_TPU_BF16 casts at the step boundary,
    # so the residuals the backward keeps are bf16 either way)
    bf16_acts = cfg.dtype_policy == "performance" or lowprec.train_policy()
    ib = 2 if bf16_acts else 4
    L = cfg.n_layers

    p_shapes = jax.eval_shape(lambda: init_params(cfg))
    param_b = _tree_bytes(p_shapes)
    opt_b = _tree_bytes(jax.eval_shape(init_opt_state, p_shapes))
    # accum materializes the zero accumulator tree ALONGSIDE the current
    # microbatch's grads; the plain step holds one grad tree
    grad_b = param_b * (2 if accum_steps > 1 else 1)

    mb = batch // accum_steps
    bsd = mb * seq * cfg.d_model
    ff = mb * seq * cfg.d_ff
    layer_full = 6 * bsd + 2 * ff + bsd + 2 * mb * seq
    if policy == "none":
        act_b = L * layer_full * ib
    elif policy == "dots":
        act_b = (L * (5 * bsd + ff) + layer_full) * ib
    else:  # block
        act_b = (L * bsd + layer_full) * ib
    logit_b = 2 * mb * seq * cfg.vocab_size * 4
    total = (param_b + opt_b + grad_b + act_b + logit_b) * 1.25

    report = {
        "params_gb": round(param_b / 2**30, 2),
        "opt_gb": round(opt_b / 2**30, 2),
        "grads_gb": round(grad_b / 2**30, 2),
        "activations_gb_est": round(act_b / 2**30, 2),
        "logits_gb": round(logit_b / 2**30, 2),
        "total_gb_est": round(total / 2**30, 2),
        "hbm_gb": budget_gb,
        "batch": batch,
        "accum_steps": accum_steps,
        "remat": policy,
        "train_dtype": "bf16" if bf16_acts else "f32",
        "estimate": "analytic",
    }

    limit = int(envknob.raw(ENV_MEASURE_ELEMS, "")
                or _MEASURE_ELEMS_DEFAULT)
    do_measure = (measure_aot if measure_aot is not None
                  else (_cpu_substrate() and batch * seq * cfg.d_model
                        <= limit))
    if do_measure:
        measured = _measure_train_step(cfg, batch, accum_steps, policy,
                                       p_shapes)
        if measured is not None:
            report["measured"] = measured
            report["estimate"] = "analytic+measured"

    return total <= budget_gb * 2**30, report


def _measure_train_step(cfg, batch, accum_steps, policy, p_shapes):
    """AOT-compile the REAL train step (no execution, no allocation
    beyond the compile) and return its memory_analysis bytes."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer as tfm

    cfg2 = dataclasses.replace(cfg, remat=policy, accum_steps=accum_steps)
    opt_shapes = jax.eval_shape(tfm.init_opt_state, p_shapes)
    toks = jax.ShapeDtypeStruct((batch, cfg.max_len), jnp.int32)
    analysis = analyze_jit(tfm.make_train_step(cfg2), p_shapes, opt_shapes,
                           toks, toks)
    if analysis is None:
        return None
    return {
        "temp_gb": round(analysis["temp_bytes"] / 2**30, 3),
        "argument_gb": round(analysis["argument_bytes"] / 2**30, 3),
        "output_gb": round(analysis["output_bytes"] / 2**30, 3),
        "peak_gb": round(analysis["peak_bytes"] / 2**30, 3),
        "note": ("AOT memory_analysis of the step as compiled on THIS "
                 "substrate (a CPU build materializes dense attention "
                 "scores the flash/TPU program streams through VMEM)"),
    }


def auto_fit_transformer(cfg, *, batches=(32, 16, 8, 4),
                         accum_steps=(1, 2, 4),
                         policies=None,
                         hbm_gb: Optional[float] = None,
                         ) -> Optional[Dict[str, Any]]:
    """Pick the largest (batch, accum_steps, remat) triple whose
    preflight fits the HBM budget (``DL4J_TPU_HBM_GB`` unless given).

    Preference order: largest global batch first; within a batch the
    CHEAPEST way to afford it — accum_steps ascending (each extra
    microbatch is another sequential pass), remat rungs weakest-first
    (each rung down the ladder buys HBM with backward recompute). The
    bench MFU-chase leg (bench.bench_transformer_big) calls this with
    accum pinned to 1; training scripts can let all three axes float.

    Returns {"batch", "accum_steps", "remat", "report"} or None when
    nothing fits."""
    from deeplearning4j_tpu.ops.remat import POLICIES

    if policies is None:
        policies = POLICIES
    for b in sorted(set(batches), reverse=True):
        for a in sorted(set(accum_steps)):
            if b % a:
                continue
            for p in policies:
                fits, rep = transformer_preflight(
                    cfg, b, accum_steps=a, remat=p, hbm_gb=hbm_gb)
                if fits:
                    return {"batch": b, "accum_steps": a, "remat": p,
                            "report": rep}
    return None


# ---------------------------------------------------------------------------
# paged-KV arena sizing (the serving-side twin of auto_fit_transformer)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StateLeaf:
    """One kind of per-lane recurrent state a model keeps beside its KV
    blocks: ``layers`` buffers of ``[lanes, *shape]`` in ``dtype`` (one
    buffer a layer, so that a tick updates each in place)."""
    name: str
    layers: int
    shape: Tuple[int, ...]
    dtype: str

    @property
    def lane_bytes(self) -> int:
        return self.layers * int(np.prod(self.shape)) \
            * np.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class KVGroup:
    """Layers that page their keys and values alike: ``layer_ids`` among
    the model's KV layers (the order of its arena's buffers), and
    ``window``, how many of the newest positions a layer of the group sees,
    the token's own among them (0: every earlier position). A group has a
    block pool and a per-lane table of its own in the paged decoder; a lane
    of a window group holds only the blocks its window reaches.
    ``readers`` is how many layers read each of the group's buffers: the
    layer that writes it, and layers that only read another layer's keys
    and values (cross-attention: one buffer, written once, read by all)."""
    layer_ids: Tuple[int, ...]
    window: int = 0
    readers: int = 1

    @property
    def layers(self) -> int:
        return len(self.layer_ids)


@dataclasses.dataclass(frozen=True)
class CacheNeeds:
    """What a served model holds per request on the device, as the paged
    decoder asks it: keys and values for ``kv_layers`` layers of
    ``kv_heads`` heads of ``head_dim`` (paged, priced a block), and
    ``state`` leaves indexed by lane (priced a lane). The GPT-2-shaped
    TransformerLM is the instance with every layer a KV layer, as many
    KV heads as query heads and no state. A block's row is always one
    token's heads side by side, ``kv_heads * head_dim`` wide (with the
    heads as an axis of their own the chip stores a buffer in another
    order than the scatter and the gathers want, and re-lays it).
    ``kv_per_layer`` asks for K and V as one buffer a layer,
    ``[blocks+1, block_tokens, kv_heads * head_dim]`` (a tick whose
    layers differ and are unrolled scatters into and reads each in
    place), instead of ONE ``[kv_layers, blocks+1, block_tokens,
    kv_heads * head_dim]`` (a tick that scans over layers that are
    alike carries it through the scan and reaches layer ``l`` at rows
    ``l * (blocks+1) + block``).

    ``groups`` says which KV layers page alike (:class:`KVGroup`): one
    group of every KV layer, no window, unless the model states its own
    (models/hybrid.py with window layers beside global ones: a group of
    each, which needs ``kv_per_layer``, since two groups' buffers differ
    in blocks). Every group's block is ``kv_heads * head_dim`` wide."""
    kv_layers: int
    kv_heads: int
    head_dim: int
    state: Tuple[StateLeaf, ...] = ()
    kv_per_layer: bool = False
    groups: Tuple[KVGroup, ...] = ()

    def __post_init__(self):
        if not self.groups:
            object.__setattr__(self, "groups", (
                KVGroup(tuple(range(self.kv_layers))),))
        ids = sorted(i for g in self.groups for i in g.layer_ids)
        if ids != list(range(self.kv_layers)):
            raise ValueError(f"the KV groups name layers {ids}, the model "
                             f"has {self.kv_layers} KV layers")
        if len(self.groups) > 1 and not self.kv_per_layer:
            raise ValueError("KV groups of their own pools need one buffer "
                             "a layer (kv_per_layer)")

    @property
    def state_lane_bytes(self) -> int:
        return sum(leaf.lane_bytes for leaf in self.state)

    @property
    def windowed(self) -> bool:
        return any(g.window for g in self.groups)


def cache_needs(cfg) -> CacheNeeds:
    """The model's own statement (``cfg.cache_needs()``) where its
    config makes one; else the dense transformer's: K and V in every
    layer for every head."""
    own = getattr(cfg, "cache_needs", None)
    if own is not None:
        return own()
    return CacheNeeds(cfg.n_layers, cfg.n_heads, cfg.d_model // cfg.n_heads)


def kv_block_bytes(cfg, block_tokens: int, dtype=None,
                   devices: int = 1, group: Optional[int] = None) -> int:
    """PER-DEVICE bytes of ONE paged KV block across the layers that
    hold keys and values (:func:`cache_needs`; the layers of KV group
    ``group`` alone where one is named): K and V,
    [layers, block_tokens, (kv_heads/devices) * head_dim] each, in
    the arena dtype (serving/paged.py's layout, ``[L, n_blocks+1, bt,
    H*hd]``: a token's heads side by side; bytes by shape, whatever the
    order). ``dtype=None`` resolves
    through ops/lowprec.kv_dtype — the model's compute dtype unless
    ``DL4J_TPU_SERVE_KV_DTYPE`` overrides it (bf16 halves KV bytes, so
    the same HBM budget admits ~2x tokens). ``devices`` is the serving
    mesh width (serving/mesh.py shards the arena's last axis by heads,
    so each device holds only its heads/devices slice of every block);
    closed-form
    arithmetic over shapes, no device touch."""
    from deeplearning4j_tpu.ops import lowprec

    if dtype is None:
        dtype = lowprec.kv_dtype(cfg)
    devices = max(1, int(devices))
    needs = cache_needs(cfg)
    layers = needs.kv_layers if group is None \
        else needs.groups[group].layers
    heads_local = -(-needs.kv_heads // devices)  # ceil: honest off-grid
    itemsize = np.dtype(dtype).itemsize
    return 2 * layers * int(block_tokens) * heads_local \
        * needs.head_dim * itemsize


def kv_group_blocks(needs: CacheNeeds, n_blocks: int, block_tokens: int,
                    lanes: int) -> Tuple[int, ...]:
    """Blocks of each KV group's pool for an arena stated as ``n_blocks``:
    a group without a window has them all; a window group's pool is
    derived, what its lanes can hold at the most (``window /
    block_tokens + 2`` blocks each: the window's reach, the block being
    written and the one a tick grows into before the oldest is let go),
    and never more than ``n_blocks``."""
    return tuple(
        int(n_blocks) if not g.window else
        min(int(n_blocks), int(lanes) * (g.window // int(block_tokens) + 2))
        for g in needs.groups)


def kv_arena_blocks(cfg, block_tokens: int, *, params=None,
                    hbm_gb: Optional[float] = None,
                    kv_fraction: float = 0.5,
                    max_blocks: int = 4096, dtype=None,
                    devices: int = 1, lanes: int = 64) -> int:
    """How many KV blocks the arena can afford under ``DL4J_TPU_HBM_GB``
    (interpreted PER DEVICE when ``devices`` > 1).

    Budget = HBM minus twice the parameter bytes (weights resident plus
    one transient copy for dispatch headroom; the serving mesh
    REPLICATES params — projections are column-sliced at trace time —
    so param bytes are NOT divided by ``devices``), minus the per-lane
    state pool of a model that keeps one (``lanes`` x
    ``cache_needs(cfg).state_lane_bytes``; nothing for a model of KV
    layers only), times
    ``kv_fraction`` (the rest stays free for prefill temporaries and
    the serving batcher's bucket programs), divided by
    :func:`kv_block_bytes` at that device count — head-sharding drops
    per-device block bytes to 1/devices, so capacity scales ~linearly
    with the mesh. Clamped to [one max_len sequence + 1, max_blocks] so
    a tiny budget still yields a decoder that can serve a single
    request and a huge one doesn't balloon the tick's gather. This
    replaces the fixed pool's ``slots * max_len`` over-allocation with
    sizing from the accounting plane (ISSUE 11 satellite; ``devices``
    is the ISSUE 18 mesh-serving satellite)."""
    budget = (hbm_gb if hbm_gb is not None else hbm_budget_gb()) * 2.0**30
    if params is not None:
        budget -= 2.0 * _tree_bytes(params)
    budget -= int(lanes) * cache_needs(cfg).state_lane_bytes
    # every KV layer priced as if its pool had all the blocks: a window
    # group's is smaller (kv_group_blocks), so this errs to the safe side
    per_block = kv_block_bytes(cfg, block_tokens, dtype, devices)
    blocks = int(max(0.0, budget) * float(kv_fraction) / per_block)
    floor = cfg.max_len // int(block_tokens) + 1
    return max(floor, min(int(max_blocks), blocks))


# ---------------------------------------------------------------------------
# ANN vector-arena sizing (the retrieval-side twin of kv_arena_blocks)
# ---------------------------------------------------------------------------


def ann_row_bytes(dim: int, dtype=np.float32) -> int:
    """Device bytes of ONE index row: a [dim] vector in the arena dtype."""
    return int(dim) * np.dtype(dtype).itemsize


def ann_arena_rows(dim: int, *, params=None,
                   hbm_gb: Optional[float] = None,
                   ann_fraction: float = 0.25,
                   max_rows: int = 1 << 20,
                   min_rows: int = 1024, dtype=np.float32) -> int:
    """How many vector rows the retrieval arena can afford under
    ``DL4J_TPU_HBM_GB`` — the AOT sizing behind ``DL4J_TPU_ANN_ROWS=0``
    (retrieval/store.VectorStore), pure closed-form arithmetic over
    shapes (the kv_arena_blocks discipline).

    Budget = HBM minus twice the encoder parameter bytes (weights
    resident plus a transient dispatch copy), times ``ann_fraction``
    (the serving KV arena and batcher programs own the rest), divided by
    three row copies (published snapshot + staging arena + one transient
    publish clone — the generation-swap publish keeps two arenas live
    plus the copy in flight), clamped to [min_rows, max_rows]."""
    budget = (hbm_gb if hbm_gb is not None else hbm_budget_gb()) * 2.0**30
    if params is not None:
        budget -= 2.0 * _tree_bytes(params)
    per_row = 3 * ann_row_bytes(dim, dtype)
    rows = int(max(0.0, budget) * float(ann_fraction) / per_row)
    return max(int(min_rows), min(int(max_rows), rows))


# ---------------------------------------------------------------------------
# model resident-bytes pricing (the placement plane's bin-packing input)
# ---------------------------------------------------------------------------

# the same buffer attrs the serving registry walks when it deletes a
# retired model's device buffers (serving/registry._delete_device_buffers)
# — what unload frees is exactly what residency must price
MODEL_BUFFER_ATTRS = ("params", "states", "updater_state", "opt")


def model_resident_bytes(model) -> int:
    """Device bytes a loaded model keeps RESIDENT: its params /
    batch-norm states / updater / optimizer pytrees, priced as pure
    shape x itemsize arithmetic over the tree leaves — never a device
    read (the kv_arena_blocks discipline).
    This is the per-model input to HBM bin-packing
    (serving/placement.py) and the /replicas utilization report."""
    total = 0
    for attr in MODEL_BUFFER_ATTRS:
        tree = getattr(model, attr, None)
        if tree is not None:
            total += _tree_bytes(tree)
    return total
