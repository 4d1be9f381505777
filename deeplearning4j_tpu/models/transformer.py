"""Transformer language model — the multi-axis-parallel flagship.

The reference's sequence models top out at GravesLSTM/GRU char-RNNs
(reference nn/layers/recurrent/, models era 2016); this framework adds a
decoder-only transformer LM as the flagship for the parallelism stack,
because it is the model family whose scale actually NEEDS the mesh:

  data axis   ('data')  : batch sharded — GSPMD inserts the gradient
                          all-reduce (the ParallelWrapper/param-averaging
                          successor, SURVEY.md section 2.7).
  model axis  ('model') : Megatron column/row sharding of every attention
                          and MLP matrix (parallel/tensor_parallel.py has
                          the explicit shard_map formulation; HERE the same
                          layout is expressed as GSPMD sharding annotations
                          and XLA derives the identical psum schedule —
                          the scaling-book recipe: pick a mesh, annotate,
                          let the compiler insert collectives).
  expert axis ('expert'): optional MoE FFN blocks, experts sharded
                          (parallel/expert_parallel.py math, GSPMD layout).
  seq axis    ('seq')   : ring attention for sequences beyond one chip's
                          HBM (parallel/sequence_parallel.py), used by
                          `ring_forward`.

Everything under `train_step` is ONE jitted XLA program: forward, backward,
Adam update, with bf16 MXU matmuls when dtype_policy="performance".
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPELINE_AXIS,
    SEQUENCE_AXIS,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_layers: int = 4
    n_heads: int = 4
    d_ff: int = 512
    max_len: int = 256
    moe_experts: int = 0          # 0 = dense FFN; >0 = MoE every block
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 1e-2
    dtype_policy: str = "strict"  # "strict" f32 | "performance" bf16 compute
    learning_rate: float = 3e-4
    # LR schedule (reference LearningRatePolicy role): linear warmup over
    # warmup_steps, then optional "cosine" decay to 0 at total_steps
    warmup_steps: int = 0
    lr_schedule: str = "none"     # "none" | "cosine"
    total_steps: int = 0
    # gradient accumulation: microbatches per optimizer step at 1/A the
    # activation memory. Dense: exact full-batch equivalence
    # (mean-of-means). MoE: the GROUPED objective (group = microbatch,
    # GShard/Switch semantics) — identical to PP with n_micro=A.
    accum_steps: int = 1
    seed: int = 0
    # flash-attention pallas kernel (ops/pallas_attention.py) on the
    # single-device path; the GSPMD-sharded path always uses dense XLA
    # attention (pallas custom calls don't auto-partition under GSPMD —
    # multi-chip attention goes through ring_forward instead)
    use_flash: bool = True
    # GPipe microbatch count used when TransformerLM is built on a mesh
    # with a 'pipe' axis (pipeline mode); must divide the fit() batch size
    pipeline_microbatches: int = 4
    # decoupled weight decay (AdamW, Loshchilov & Hutter): applied to
    # matrix params only (LN scales/biases and the position table exempt,
    # the standard LM recipe); 0 = plain Adam
    weight_decay: float = 0.0
    # global-norm gradient clipping before the optimizer update; 0 = off
    # (the reference's GradientNormalization ClipL2PerParamType role —
    # nn/conf/GradientNormalization.java — for the flagship)
    clip_grad_norm: float = 0.0
    # activation rematerialization for the block scan (ops/remat.py —
    # the Chen et al. sublinear-memory ladder): "auto" defers to the
    # DL4J_TPU_REMAT env knob (default none); "none" stores every
    # activation; "dots" keeps matmul outputs and recomputes elementwise
    # ops; "block" stores only the residual carry and recomputes the
    # whole block in the backward pass. Resolved at step-factory TRACE
    # time (the donation-policy discipline); composes with accum_steps
    # (remat shrinks per-microbatch activations, accum shrinks the
    # microbatch). Values are policy-invariant (remat==none is bit-exact
    # on the forward; grads agree to recompute-reassociation tolerance —
    # tests/test_remat.py).
    remat: str = "auto"

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype_policy == "performance" else jnp.float32


# ---------------------------------------------------------------------------
# Init + sharding layout
# ---------------------------------------------------------------------------


def init_params(cfg: TransformerConfig) -> Params:
    """Global-shaped params; block leaves stacked on a leading layer dim [L,...]
    so the forward is a lax.scan over layers (compile time O(1) in depth)."""
    key = jax.random.PRNGKey(cfg.seed)
    ks = jax.random.split(key, 10)
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers

    def norm(k, shape, scale):
        # float(scale): numpy f64 scalars are strongly typed and would
        # promote the whole tree to f64 under jax_enable_x64
        return jax.random.normal(k, shape, jnp.float32) * float(scale)

    def xavier(k, shape):
        return norm(k, shape, np.sqrt(2.0 / (shape[-2] + shape[-1])))

    def ones(shape):
        return jnp.ones(shape, jnp.float32)

    def zeros(shape):
        return jnp.zeros(shape, jnp.float32)

    blocks = {
        "ln1_g": ones((L, d)), "ln1_b": zeros((L, d)),
        "Wq": xavier(ks[0], (L, d, d)), "Wk": xavier(ks[1], (L, d, d)),
        "Wv": xavier(ks[2], (L, d, d)),
        # residual-branch output projections scaled down by depth (GPT-2 style)
        "Wo": norm(ks[3], (L, d, d), 0.02 / np.sqrt(2 * L)),
        "ln2_g": ones((L, d)), "ln2_b": zeros((L, d)),
    }
    if cfg.moe_experts:
        E = cfg.moe_experts
        blocks.update({
            "Wg": xavier(ks[4], (L, d, E)),
            "W1": xavier(ks[5], (L, E, d, f)), "b1": zeros((L, E, f)),
            "W2": norm(ks[6], (L, E, f, d), 0.02 / np.sqrt(2 * L)),
            "b2": zeros((L, E, d)),
        })
    else:
        blocks.update({
            "W1": xavier(ks[5], (L, d, f)), "b1": zeros((L, f)),
            "W2": norm(ks[6], (L, f, d), 0.02 / np.sqrt(2 * L)),
            "b2": zeros((L, d)),
        })
    return {
        "embed": norm(ks[7], (cfg.vocab_size, d), 0.02),
        "pos": norm(ks[8], (cfg.max_len, d), 0.01),
        "lnf_g": ones((d,)), "lnf_b": zeros((d,)),
        "blocks": blocks,
        # lm head tied to embed (reference EmbeddingLayer has no tying, but
        # tying is the modern default and halves the biggest matrix)
    }


def param_specs(cfg: TransformerConfig) -> Params:
    """Megatron PartitionSpecs (leading layer dim unsharded). Column-parallel
    weights shard the output dim over 'model'; row-parallel the input dim;
    MoE expert leaves additionally shard the expert dim over 'expert'."""
    col, row = P(None, None, MODEL_AXIS), P(None, MODEL_AXIS, None)
    blocks = {
        "ln1_g": P(), "ln1_b": P(),
        "Wq": col, "Wk": col, "Wv": col, "Wo": row,
        "ln2_g": P(), "ln2_b": P(),
    }
    if cfg.moe_experts:
        blocks.update({
            "Wg": P(),
            "W1": P(None, EXPERT_AXIS, None, MODEL_AXIS),
            "b1": P(None, EXPERT_AXIS, MODEL_AXIS),
            "W2": P(None, EXPERT_AXIS, MODEL_AXIS, None),
            "b2": P(None, EXPERT_AXIS, None),
        })
    else:
        blocks.update({"W1": col, "b1": P(None, MODEL_AXIS),
                       "W2": row, "b2": P()})
    return {
        "embed": P(None, MODEL_AXIS),
        "pos": P(),
        "lnf_g": P(), "lnf_b": P(),
        "blocks": blocks,
    }


def shard_params(params: Params, cfg: TransformerConfig, mesh: Mesh) -> Params:
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, jnp.ndarray),
    )


def megatron_param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Params:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(cfg),
        is_leaf=lambda x: isinstance(x, P))


def param_shardings_for_mesh(cfg: TransformerConfig, mesh: Mesh) -> Params:
    """THE single place that decides a mesh's param layout: depth-sharded
    (pipeline mode) when the mesh has a 'pipe' axis; Megatron/MoE GSPMD
    specs when it has a 'model'/'expert' axis; fully replicated otherwise
    (sequence-parallel and pure-DP meshes — activations shard, params
    don't). Training init, checkpoint restore and device_put all route
    through here so they can never diverge."""
    if PIPELINE_AXIS in mesh.shape:
        return pipeline_param_shardings(cfg, mesh)
    if MODEL_AXIS in mesh.shape or EXPERT_AXIS in mesh.shape:
        return megatron_param_shardings(cfg, mesh)
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(partial(init_params, cfg))
    return jax.tree_util.tree_map(lambda _: rep, shapes)


def shard_params_for_mesh(params: Params, cfg: TransformerConfig,
                          mesh: Mesh) -> Params:
    return jax.tree_util.tree_map(
        jax.device_put, params, param_shardings_for_mesh(cfg, mesh))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _attention(q, k, v, n_heads, use_flash=False):
    n, t, d = q.shape
    hd = d // n_heads
    q = q.reshape(n, t, n_heads, hd)
    k = k.reshape(n, t, n_heads, hd)
    v = v.reshape(n, t, n_heads, hd)
    if use_flash:
        # single dispatch policy lives in attention_auto (flash when the
        # pallas gate + VMEM fit allow, dense XLA otherwise)
        from deeplearning4j_tpu.ops.pallas_attention import attention_auto

        return attention_auto(q, k, v, causal=True).reshape(n, t, d)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(
        jnp.asarray(hd, q.dtype))
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, jnp.asarray(-1e9, s.dtype))
    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    p = jax.nn.softmax(s.astype(softmax_dtype(s.dtype)),
                       axis=-1).astype(q.dtype)
    return jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(n, t, d)


def _dense_block_f32(bp, h, n_heads: int, attend=None, ffn=None,
                     cdt=jnp.float32):
    """One transformer block (no flash) — the block body shared by the
    sequence-parallel (ring_forward) and pipeline-parallel
    (pipeline_forward) paths; forward() keeps its own cast-aware variant
    for the mixed-precision/flash path. cdt: compute dtype — f32 by
    default (the name records the original scope); bf16 under
    dtype_policy='performance' (params cast per use like forward(), the
    residual stream h carried in cdt — which also halves the ring/pipe
    ppermute traffic). `attend` overrides the attention op
    ((q, k, v) [N,T,F] -> [N,T,F]) so the ring/Ulysses strategies plug
    in; `ffn` overrides the feed-forward (x_normed -> residual delta) so
    the MoE branch shares the attention-residual half too."""
    c = lambda a: a.astype(cdt)
    if attend is None:
        attend = lambda q, k, v: _attention(q, k, v, n_heads)
    with jax.named_scope("block.attn"):
        x = _ln(h, c(bp["ln1_g"]), c(bp["ln1_b"]))
        q, k, v = x @ c(bp["Wq"]), x @ c(bp["Wk"]), x @ c(bp["Wv"])
        h = h + attend(q, k, v) @ c(bp["Wo"])
    with jax.named_scope("block.mlp"):
        x = _ln(h, c(bp["ln2_g"]), c(bp["ln2_b"]))
        if ffn is not None:
            return h + ffn(x)
        return (h + jax.nn.gelu(x @ c(bp["W1"]) + c(bp["b1"]))
                @ c(bp["W2"]) + c(bp["b2"]))


def _moe_ffn(bp, h, cfg: TransformerConfig, capacity: int = 0):
    """MoE FFN: routing + expert math shared with parallel/expert_parallel
    (called inline, not through its shard_map, so GSPMD shards the expert
    dim via the param shardings; returns (out, aux_loss)). capacity=0 ->
    the standard formula; decode_step passes the NO-DROP capacity n*t so
    one routing/expert body serves both batch and streamed paths."""
    from deeplearning4j_tpu.parallel.expert_parallel import (
        _routing,
        aux_loss_from_gates,
        expert_mlp,
    )

    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    n, t, d = h.shape
    xt = h.reshape(n * t, d)
    scores = xt @ bp["Wg"]
    gates = jax.nn.softmax(scores.astype(softmax_dtype(scores.dtype)),
                           axis=-1)
    if not capacity:
        capacity = max(1, int(cfg.moe_capacity_factor * n * t * cfg.moe_top_k
                              / cfg.moe_experts))
    dispatch, combine = _routing(gates, cfg.moe_top_k, capacity)
    y = expert_mlp(bp["W1"], bp["b1"], bp["W2"], bp["b2"],
                   dispatch.astype(h.dtype), combine.astype(h.dtype), xt)
    return y.reshape(n, t, d), aux_loss_from_gates(gates)


def _moe_block(bp, h, cfg: TransformerConfig, *, attend=None, cdt,
               capacity: int = 0):
    """One transformer block with the MoE FFN: _dense_block_f32 with its
    ffn override wired to _moe_ffn, returning (h, aux). The SINGLE
    definition shared by the sequence-parallel (ring_forward), pipelined
    (stage_fn), and KV-cache prefill paths — one place to change MoE cast
    discipline or aux accounting."""
    bp16 = {kk: vv.astype(cdt) for kk, vv in bp.items()}
    cap = {}

    def ffn(x):
        y, cap["aux"] = _moe_ffn(bp16, x, cfg, capacity=capacity)
        return y

    h = _dense_block_f32(bp, h, cfg.n_heads, attend=attend, ffn=ffn,
                         cdt=cdt)
    return h, cap["aux"]


def forward(params: Params, tokens: jax.Array, cfg: TransformerConfig,
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens [N, T] int32 -> (logits [N, T, V] f32, aux_loss scalar)."""
    # the scopes (embed, block.attn, block.mlp, head_loss; grad_accum and
    # adam in the step) name the step's parts in the compiled program's
    # metadata and so in a device trace; they change no arithmetic
    cdt = cfg.compute_dtype
    n, t = tokens.shape
    with jax.named_scope("embed"):
        h = params["embed"][tokens] + params["pos"][:t][None]
        h = h.astype(cdt)

    def block(carry, bp):
        h, aux = carry
        with jax.named_scope("block.attn"):
            x = _ln(h, bp["ln1_g"].astype(cdt), bp["ln1_b"].astype(cdt))
            q, k, v = x @ bp["Wq"].astype(cdt), x @ bp["Wk"].astype(cdt), \
                x @ bp["Wv"].astype(cdt)
            h = h + _attention(q, k, v, cfg.n_heads,
                               use_flash=cfg.use_flash) \
                @ bp["Wo"].astype(cdt)
        with jax.named_scope("block.mlp"):
            x = _ln(h, bp["ln2_g"].astype(cdt), bp["ln2_b"].astype(cdt))
            if cfg.moe_experts:
                bp16 = {kk: vv.astype(cdt) for kk, vv in bp.items()}
                y, a = _moe_ffn(bp16, x, cfg)
                h = h + y
                aux = aux + a
            else:
                inner = jax.nn.gelu(x @ bp["W1"].astype(cdt)
                                    + bp["b1"].astype(cdt))
                h = h + inner @ bp["W2"].astype(cdt) + bp["b2"].astype(cdt)
        return (h, aux), None

    from deeplearning4j_tpu.ops.remat import remat_wrap

    # remat policy ladder applied to the scan BODY (cfg.remat, resolved
    # at trace time): under autodiff the scan stores only what the
    # checkpoint policy saves per layer instead of every residual.
    # prevent_cse=False: the scan's loop boundary already blocks the CSE
    # the checkpoint barriers guard against (nn/common.remat_apply).
    block = remat_wrap(block, cfg.remat, prevent_cse=False)
    (h, aux), _ = lax.scan(block, (h, jnp.zeros((), jnp.float32)),
                           params["blocks"])
    with jax.named_scope("head_loss"):
        h = _ln(h.astype(jnp.float32), params["lnf_g"], params["lnf_b"])
        logits = h @ params["embed"].T  # tied head
        return logits.astype(jnp.float32), aux / cfg.n_layers


def nll_loss(logits: jax.Array, targets: jax.Array, mask=None) -> jax.Array:
    """Mean next-token NLL — THE cross-entropy shared by the training
    losses (dense/pipeline/ring) and evaluate(), so objective and metric
    can never drift. mask ([N, T] 0/1): masked positions excluded from
    numerator AND denominator."""
    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    # at-least-f32 (bf16 logits upcast; f64 stays f64 for the gradchecks)
    dt = softmax_dtype(logits.dtype)
    logp = jax.nn.log_softmax(logits.astype(dt), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return nll.mean()
    m = mask.astype(dt)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def loss_fn(params: Params, tokens: jax.Array, targets: jax.Array,
            cfg: TransformerConfig) -> jax.Array:
    logits, aux = forward(params, tokens, cfg)
    with jax.named_scope("head_loss"):
        return nll_loss(logits, targets) + cfg.moe_aux_coef * aux


# ---------------------------------------------------------------------------
# Training (one jitted step; Adam)
# ---------------------------------------------------------------------------


def init_opt_state(params: Params) -> Params:
    from deeplearning4j_tpu.ops import lowprec

    z = lambda a: jnp.zeros_like(a)
    opt = {
        "m": jax.tree_util.tree_map(z, params),
        "v": jax.tree_util.tree_map(z, params),
        "t": jnp.zeros((), jnp.int32),
    }
    # bf16 loss-scaled training (DL4J_TPU_BF16): the dynamic loss-scale
    # state rides INSIDE the opt tree — step arity, the opt-only donation
    # contract and the save/load npz round-trip all stay unchanged
    if lowprec.train_policy():
        opt.update(lowprec.opt_scale_entries())
    return opt


def _clip_by_global_norm(grads, max_norm):
    """Global-norm clip (the standard LM recipe): ONE implementation — the
    framework's shared gradient-normalization path
    (optimize/updaters.normalize_gradients, reference
    GradientNormalization ClipL2 role) applied to the WHOLE param tree."""
    from deeplearning4j_tpu.optimize.updaters import (
        _global_norm,
        normalize_gradients,
    )

    return (normalize_gradients(grads, "clip_l2_per_layer", max_norm),
            _global_norm(grads))


def _decay_mask(params):
    """AdamW applies decay to weight MATRICES only (keys 'W*' and the tied
    embedding); LN scales/biases, biases and the position table are
    exempt. The decision is BY NAME — block leaves carry a leading [L]
    layer dim, so ndim alone cannot tell a stacked bias (L, f) from a
    matrix."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, _ in flat:
        last = path[-1]
        name = str(getattr(last, "key", last))
        out.append(name.startswith("W") or name == "embed")
    return jax.tree_util.tree_unflatten(treedef, out)


@partial(jax.named_call, name="adam")
def _adam_update(params, grads, opt, lr, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.0, clip_grad_norm=0.0):
    if clip_grad_norm:
        grads, _ = _clip_by_global_norm(grads, clip_grad_norm)
    t = opt["t"] + 1
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               opt["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               opt["v"], grads)
    tf = t.astype(jnp.float32)
    corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    if weight_decay:
        mask = _decay_mask(params)
        new = jax.tree_util.tree_map(
            lambda p, m, v, d: p - lr * (corr * m / (jnp.sqrt(v) + eps)
                                         + (weight_decay * p if d else 0.0)),
            params, m, v, mask)
    else:
        new = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * corr * m / (jnp.sqrt(v) + eps),
            params, m, v)
    return new, {"m": m, "v": v, "t": t}


def _donation_kwargs():
    """Donate the OPT buffers (Adam m/v — 2/3 of the training-state HBM)
    to the step: the moment updates become in-place on device. Params are
    deliberately NOT donated — the repo's serial-vs-distributed equivalence
    pattern passes one initial params tree to several step functions
    (tests, dryrun legs), which donation would poison on real chips.
    Optimizer state is always built fresh per run (init_opt_state), so its
    donation is safe by construction.

    The on/off decision is the shared policy in ops/dispatch
    (donation_enabled: a CPU backend skips donation, the DL4J_TPU_DONATE
    env knob overrides both ways)."""
    if not dispatch.donation_enabled():
        return {}
    return {"donate_argnums": (1,)}


def _reject_lowprec(path: str) -> None:
    """The ring/pipeline step factories drop unknown opt keys (they
    rebuild {'m','v','t'} from _adam_update), so bf16 loss scaling would
    silently degrade to ls-less f32 there — reject loudly instead (the
    accum_steps-under-PP pattern)."""
    from deeplearning4j_tpu.ops import lowprec

    if lowprec.train_policy():
        raise ValueError(
            f"DL4J_TPU_BF16 is not supported on the {path} training path "
            "yet — unset it (the dense and accum paths support it)")


def _validate_schedule(cfg: TransformerConfig) -> None:
    """Shared by the dense AND pipelined step factories — a cfg the dense
    path rejects loudly must never train silently through the pipeline."""
    if cfg.lr_schedule not in ("none", "cosine"):
        raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r} "
                         "(known: none, cosine)")
    if cfg.lr_schedule == "cosine" and cfg.total_steps <= 0:
        raise ValueError("lr_schedule='cosine' needs total_steps > 0 "
                         "(otherwise the decay is silently dropped)")


def _scheduled_lr(cfg: TransformerConfig, t):
    """LR at integer step t (1-based): optional linear warmup then optional
    cosine decay to zero over cfg.total_steps (standard LM schedule; the
    reference's LR-policy role — optimize/updaters.py — for the flagship)."""
    tf = t.astype(jnp.float32)
    lr = jnp.asarray(cfg.learning_rate, jnp.float32)
    if cfg.warmup_steps > 0:
        lr = lr * jnp.minimum(1.0, tf / cfg.warmup_steps)
    if cfg.lr_schedule == "cosine" and cfg.total_steps > 0:
        frac = jnp.clip((tf - cfg.warmup_steps)
                        / max(1, cfg.total_steps - cfg.warmup_steps), 0.0, 1.0)
        lr = lr * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return lr


def _build_step(cfg: TransformerConfig):
    """The pure (unjitted) optimizer step shared by make_train_step and
    the fused multi-step path; validates cfg combinations loudly."""
    accum_steps = cfg.accum_steps
    # accum_steps > 1 with MoE = the GROUPED objective (group = one
    # microbatch): per-group expert capacity + aux statistics, the same
    # GShard/Switch semantics as the pipelined path — accum A=k and
    # PP n_micro=k optimize the IDENTICAL loss on identical groups
    # (test_accum_moe_equals_pipelined_groups). Dense configs remain
    # exactly full-batch equivalent (mean-of-means).
    _validate_schedule(cfg)
    from deeplearning4j_tpu.ops import lowprec

    lp = lowprec.train_policy()

    def step(params, opt, tokens, targets):
        if lp:
            # bf16 master-weight mode (ops/lowprec.py): the scale rides
            # the opt tree; the backward pass runs on the SCALED loss of
            # the bf16-cast params, grads come back f32 via the cast's
            # transpose and are unscaled before Adam
            ls = lowprec.opt_scale_state(opt)
            base = {"m": opt["m"], "v": opt["v"], "t": opt["t"]}
            scale = ls["scale"]

            def grad_loss(p, x, y):
                return loss_fn(
                    lowprec.cast_tree(p), x, y, cfg
                ).astype(jnp.float32) * scale
        else:
            ls = None
            base = opt

            def grad_loss(p, x, y):
                return loss_fn(p, x, y, cfg)

        if accum_steps == 1:
            loss, grads = jax.value_and_grad(grad_loss)(
                params, tokens, targets)
        else:
            b = tokens.shape[0]
            if b % accum_steps != 0:
                raise ValueError(
                    f"batch {b} not divisible by accum_steps {accum_steps}")
            mb = b // accum_steps
            xs = tokens.reshape(accum_steps, mb, *tokens.shape[1:])
            ys = targets.reshape(accum_steps, mb, *targets.shape[1:])

            def micro(carry, xy):
                loss_a, grads_a = carry
                loss_i, grads_i = jax.value_and_grad(grad_loss)(
                    params, xy[0], xy[1])
                with jax.named_scope("grad_accum"):
                    grads_a = jax.tree_util.tree_map(
                        lambda a, g: a + g / accum_steps, grads_a, grads_i)
                return (loss_a + loss_i / accum_steps, grads_a), None

            zero = jax.tree_util.tree_map(jnp.zeros_like, params)
            (loss, grads), _ = lax.scan(
                micro, (jnp.zeros((), jnp.float32), zero), (xs, ys))

        if lp:
            loss = loss / scale  # report the unscaled loss
            grads = lowprec.unscale(grads, scale)
            finite = lowprec.finite_tree(grads)
            lr = _scheduled_lr(cfg, base["t"] + 1)
            new_params, new_base = _adam_update(
                params, grads, base, lr,
                weight_decay=cfg.weight_decay,
                clip_grad_norm=cfg.clip_grad_norm)
            params = lowprec.select_trees(finite, new_params, params)
            # 't' is selected too: a skipped step must not advance the
            # LR schedule or the bias correction
            base = lowprec.select_trees(finite, new_base, base)
            ls = lowprec.advance_scale(ls, finite)
            return params, lowprec.opt_with_scale(base, ls), loss

        lr = _scheduled_lr(cfg, opt["t"] + 1)
        params, opt = _adam_update(params, grads, opt, lr,
                                   weight_decay=cfg.weight_decay,
                                   clip_grad_norm=cfg.clip_grad_norm)
        return params, opt, loss

    return step


def _mesh_shardings(cfg: TransformerConfig, mesh: Mesh):
    # param_shardings_for_mesh handles every mesh kind (Megatron when a
    # 'model'/'expert' axis exists, replicated for pure-DP meshes) — a
    # ('data',)-only mesh must not crash on a 'model' PartitionSpec
    from deeplearning4j_tpu.ops import lowprec

    pshard = param_shardings_for_mesh(cfg, mesh)
    oshard = {"m": pshard, "v": pshard, "t": NamedSharding(mesh, P())}
    if lowprec.train_policy():
        # the loss-scale scalars ride the opt tree replicated
        oshard.update({k: NamedSharding(mesh, P())
                       for k in lowprec.OPT_SCALE_KEYS})
    dshard = NamedSharding(
        mesh, P(DATA_AXIS) if DATA_AXIS in mesh.shape else P())
    return pshard, oshard, dshard


def make_train_step(cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Returns step(params, opt, tokens, targets) -> (params, opt, loss),
    jitted. With a mesh: params carry Megatron/MoE shardings, the batch is
    sharded over 'data', and GSPMD derives the full DP x TP x EP collective
    schedule (gradient all-reduce over 'data'; the two per-block psums over
    'model'; expert all-to-alls over 'expert').

    cfg.accum_steps > 1 = gradient accumulation: the batch is split into A
    microbatches whose gradients are averaged in a lax.scan before ONE
    optimizer update — for dense configs numerically the full-batch step
    (the loss is a batch mean, so mean-of-microbatch-grads == full-batch
    grad) at 1/A the activation memory. MoE configs train the GROUPED
    objective (expert capacity + aux statistics per microbatch group —
    GShard/Switch semantics, identical to the pipelined path at
    n_micro=A; test_accum_moe_equals_pipelined_groups)."""
    step = _build_step(cfg)
    if mesh is None:
        return jax.jit(step, **_donation_kwargs())
    pshard, oshard, dshard = _mesh_shardings(cfg, mesh)
    return jax.jit(
        step,
        in_shardings=(pshard, oshard, dshard, dshard),
        out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
        **_donation_kwargs(),
    )


def make_train_multi_step(cfg: TransformerConfig,
                          mesh: Optional[Mesh] = None):
    """K optimizer steps fused into ONE XLA program (the flagship's
    fit_batches — same role as MultiLayerNetwork.fit_batches): a lax.scan
    over stacked batches [K, N, T], removing the per-step dispatch
    round-trip. Serially equivalent to K fit() calls."""
    step = _build_step(cfg)
    multi = _multi_from_step(step)
    if mesh is None:
        return jax.jit(multi, **_donation_kwargs())
    pshard, oshard, dshard = _mesh_shardings(cfg, mesh)
    kshard = NamedSharding(mesh, P(None, DATA_AXIS))  # [K, N, T]
    return jax.jit(
        multi,
        in_shardings=(pshard, oshard, kshard, kshard),
        out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
        **_donation_kwargs(),
    )


# ---------------------------------------------------------------------------
# Ring-attention (sequence-parallel) forward for long context
# ---------------------------------------------------------------------------


def ring_forward(params: Params, tokens: jax.Array, cfg: TransformerConfig,
                 mesh: Mesh, strategy: str = "ring",
                 return_aux: bool = False):
    """Forward with attention computed sequence-parallel over the 'seq'
    mesh axis (parallel/sequence_parallel.py): exact full attention for
    sequences sharded over devices. strategy='ring' rotates K/V shards via
    ppermute (memory-optimal for very long T); strategy='ulysses' uses two
    head<->sequence all_to_alls (fewer collectives; needs heads divisible
    by the axis size). Long-context inference/eval, and (via
    return_aux=True) the sequence-parallel TRAIN step: the MoE
    load-balance aux loss is accumulated per block so SP training
    optimizes the SAME objective as the serial step."""
    from deeplearning4j_tpu.parallel.sequence_parallel import (
        ring_attention_sharded,
        ulysses_attention_sharded,
    )

    if strategy not in ("ring", "ulysses"):
        raise ValueError(f"unknown sequence-parallel strategy {strategy!r}")
    sharded_att = (ring_attention_sharded if strategy == "ring"
                   else ulysses_attention_sharded)
    n, t = tokens.shape
    hd = cfg.d_model // cfg.n_heads
    # DP x SP composition: shard the batch over 'data' inside the attention
    # shard_map too — otherwise every data slice would all-gather the batch
    # and compute the full attention redundantly
    batch_ax = DATA_AXIS if DATA_AXIS in mesh.shape else None

    def attend(q, k, v):
        split = lambda a: a.reshape(n, t, cfg.n_heads, hd)
        out = sharded_att(split(q), split(k), split(v), mesh, causal=True,
                          batch_axis=batch_ax)
        return out.reshape(n, t, cfg.d_model)

    cdt = cfg.compute_dtype
    h = (params["embed"][tokens] + params["pos"][:t][None]).astype(cdt)
    L = params["blocks"]["Wq"].shape[0]
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(L):
        bp = jax.tree_util.tree_map(lambda a, i=i: a[i], params["blocks"])
        if cfg.moe_experts:
            h, a = _moe_block(bp, h, cfg, attend=attend, cdt=cdt)
            aux_total = aux_total + a
        else:
            h = _dense_block_f32(bp, h, cfg.n_heads, attend=attend,
                                 cdt=cdt)
    h = _ln(h.astype(jnp.float32), params["lnf_g"], params["lnf_b"])
    logits = (h @ params["embed"].T).astype(jnp.float32)
    if return_aux:
        return logits, aux_total / cfg.n_layers
    return logits


# ---------------------------------------------------------------------------
# KV-cache decoding (autoregressive inference without the O(T^2)-per-token
# full-forward recompute; the reference's rnnTimeStep streaming idea —
# MultiLayerNetwork.rnnTimeStep :2152 carries h/c state — applied to
# attention: the carried state is each layer's K/V history)
# ---------------------------------------------------------------------------


def prefill_cache(params: Params, tokens: jax.Array, cfg: TransformerConfig,
                  ) -> Tuple[Params, jax.Array]:
    """Run the prompt through the model once, returning the per-layer K/V
    cache (leaves [L, N, max_len, H, hd]; positions beyond the prompt are
    garbage that decode's position mask never reads) plus the final hidden
    states [N, T, d] (f32, post-final-LN). Mirrors forward()'s block scan
    (same cast discipline), including the MoE FFN branch — the prompt
    routes with the standard capacity formula, so in the drop-free regime
    prefill+decode is exactly the full forward."""
    cdt = cfg.compute_dtype
    n, t = tokens.shape
    hd = cfg.d_model // cfg.n_heads
    h = (params["embed"][tokens] + params["pos"][:t][None]).astype(cdt)

    def block(h, bp):
        # the SHARED block body (_dense_block_f32); the attend override
        # both computes attention and CAPTURES this layer's K/V for the
        # cache (capture works because scan traces the body once and the
        # captured values are tracers feeding the scan outputs)
        captured = {}

        def attend(q, k, v):
            captured["k"], captured["v"] = k, v
            return _attention(q, k, v, cfg.n_heads, use_flash=cfg.use_flash)

        if cfg.moe_experts:
            h, _unused_aux = _moe_block(bp, h, cfg, attend=attend, cdt=cdt)
        else:
            h = _dense_block_f32(bp, h, cfg.n_heads, attend=attend,
                                 cdt=cdt)
        pad = ((0, 0), (0, cfg.max_len - t), (0, 0), (0, 0))
        kc = jnp.pad(captured["k"].reshape(n, t, cfg.n_heads, hd), pad)
        vc = jnp.pad(captured["v"].reshape(n, t, cfg.n_heads, hd), pad)
        return h, (kc, vc)

    h, (ks, vs) = lax.scan(block, h, params["blocks"])
    h = _ln(h.astype(jnp.float32), params["lnf_g"], params["lnf_b"])
    return {"k": ks, "v": vs}, h


def _moe_ffn_decode(bp, h, cfg: TransformerConfig) -> jax.Array:
    """MoE FFN for one decode step (h: [N, 1, d]): _moe_ffn with NO-DROP
    capacity — a streamed token only competes with the other N tokens of
    its own step (each token holds at most one slot per expert), so
    capacity = N makes decode drop-free. Matches the batch forward
    exactly whenever the batch run is itself drop-free (capacity-bound
    drops are inherently batch-vs-stream dependent — same boundary as any
    capacity-routed MoE)."""
    n, t, _ = h.shape
    return _moe_ffn(bp, h, cfg, capacity=n * t)[0]


def decode_step(params: Params, cache: Params, tok: jax.Array, pos,
                cfg: TransformerConfig) -> Tuple[Params, jax.Array]:
    """One autoregressive step: consume the token at position `pos`
    (writing its K/V into the cache) and return (updated cache, logits for
    position pos+1). tok: [N] int32; pos: traced scalar. Attention reads
    the full max_len cache under an `arange <= pos` mask — O(max_len) per
    token instead of the full forward's O(max_len^2). MoE blocks route
    through _moe_ffn_decode (no-drop capacity)."""
    cdt = cfg.compute_dtype
    n = tok.shape[0]
    hd = cfg.d_model // cfg.n_heads
    h = (params["embed"][tok] + params["pos"][pos])[:, None, :].astype(cdt)
    scale = 1.0 / float(np.sqrt(hd))
    visible = (jnp.arange(cfg.max_len) <= pos)[None, None, :]  # [1,1,T]

    def block(h, xs):
        bp, ck, cv = xs  # ck/cv: [N, T_max, H, hd]
        c = lambda a: a.astype(cdt)
        x = _ln(h, c(bp["ln1_g"]), c(bp["ln1_b"]))
        q = (x @ c(bp["Wq"])).reshape(n, cfg.n_heads, hd)
        k1 = (x @ c(bp["Wk"])).reshape(n, 1, cfg.n_heads, hd)
        v1 = (x @ c(bp["Wv"])).reshape(n, 1, cfg.n_heads, hd)
        ck = lax.dynamic_update_slice_in_dim(ck, k1.astype(ck.dtype), pos, 1)
        cv = lax.dynamic_update_slice_in_dim(cv, v1.astype(cv.dtype), pos, 1)
        s = jnp.einsum("nhd,nthd->nht", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * scale
        s = jnp.where(visible, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("nht,nthd->nhd", p,
                         cv.astype(jnp.float32)).reshape(n, 1, cfg.d_model)
        h = h + att.astype(cdt) @ c(bp["Wo"])
        x = _ln(h, c(bp["ln2_g"]), c(bp["ln2_b"]))
        if cfg.moe_experts:
            bp16 = {kk: c(vv) for kk, vv in bp.items()}
            h = h + _moe_ffn_decode(bp16, x, cfg)
        else:
            h = h + jax.nn.gelu(x @ c(bp["W1"]) + c(bp["b1"])) @ c(bp["W2"]) \
                + c(bp["b2"])
        return h, (ck, cv)

    h, (ks, vs) = lax.scan(block, h, (params["blocks"], cache["k"],
                                      cache["v"]))
    h = _ln(h[:, 0].astype(jnp.float32), params["lnf_g"], params["lnf_b"])
    return {"k": ks, "v": vs}, h @ params["embed"].T


# ---------------------------------------------------------------------------
# Sequence-parallel TRAINING (ring/Ulysses attention + loss + Adam in one
# jitted step over a ('seq',) or ('data', 'seq') mesh)
# ---------------------------------------------------------------------------


def make_ring_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                         strategy: str = "ring"):
    """Long-context TRAINING step: the forward's attention runs
    sequence-parallel over the mesh's 'seq' axis (ring ppermute schedule or
    Ulysses all-to-alls — parallel/sequence_parallel.py), everything else
    (LN/FFN/embedding, elementwise over T) is sharded by GSPMD from the
    token sharding, and autodiff transposes the ring into the backward
    collective schedule. Params stay replicated; tokens/targets are
    sharded [batch -> 'data' when present, T -> 'seq'].

    This closes the axis that previously stopped at forward/eval
    (ring_forward's docstring said inference/eval): sequences longer than
    one chip's activation memory now take REAL optimizer steps.
    SP-train == serial-train is locked by tests/test_ring_training.py."""
    (ins, outs) = _ring_step_shardings(cfg, mesh)
    return jax.jit(_build_ring_step(cfg, mesh, strategy),
                   in_shardings=ins, out_shardings=outs,
                   **_donation_kwargs())


def _build_ring_step(cfg, mesh, strategy):
    # validated HERE so every sequence-parallel factory (single- and
    # multi-step) rejects the unsupported configs
    if cfg.accum_steps != 1:
        raise ValueError("cfg.accum_steps must be 1 under sequence-parallel "
                         "training (shard 'data' for more batch instead)")
    _reject_lowprec("sequence-parallel")
    _validate_schedule(cfg)

    def sp_loss(params, tokens, targets):
        # same objective as the serial loss_fn: NLL + the MoE aux term
        # (aux == 0 for dense configs) — SP-train == serial-train
        logits, aux = ring_forward(params, tokens, cfg, mesh,
                                   strategy=strategy, return_aux=True)
        return nll_loss(logits, targets) + cfg.moe_aux_coef * aux

    def step(params, opt, tokens, targets):
        loss, grads = jax.value_and_grad(sp_loss)(params, tokens, targets)
        lr = _scheduled_lr(cfg, opt["t"] + 1)
        params, opt = _adam_update(params, grads, opt, lr,
                                   weight_decay=cfg.weight_decay,
                                   clip_grad_norm=cfg.clip_grad_norm)
        return params, opt, loss

    return step


def _ring_step_shardings(cfg, mesh):
    rep = NamedSharding(mesh, P())
    # the SAME layout decision as __init__/restore (param_shardings_for_mesh:
    # replicated on pure seq/data meshes, Megatron if the mesh also has a
    # 'model'/'expert' axis) — step and placement can never disagree
    pshard = param_shardings_for_mesh(cfg, mesh)
    oshard = {"m": pshard, "v": pshard, "t": rep}
    data_ax = DATA_AXIS if DATA_AXIS in mesh.shape else None
    dshard = NamedSharding(mesh, P(data_ax, SEQUENCE_AXIS))
    return ((pshard, oshard, dshard, dshard), (pshard, oshard, rep))


def make_ring_train_multi_step(cfg: TransformerConfig, mesh: Mesh, *,
                               strategy: str = "ring"):
    """K sequence-parallel optimizer steps fused into one XLA program
    (stacked batches [K, N, T] — fit_batches dispatch amortization for the
    long-context mode)."""
    step = _build_ring_step(cfg, mesh, strategy)
    (pshard, oshard, dshard, _), (_, _, rep) = _ring_step_shardings(cfg,
                                                                    mesh)
    kshard = NamedSharding(mesh, P(None, *dshard.spec))
    return jax.jit(
        _multi_from_step(step),
        in_shardings=(pshard, oshard, kshard, kshard),
        out_shardings=(pshard, oshard, rep),
        **_donation_kwargs(),
    )


# ---------------------------------------------------------------------------
# Pipeline-parallel forward (depth sharded over the 'pipe' axis)
# ---------------------------------------------------------------------------


def pipeline_forward(params: Params, tokens: jax.Array,
                     cfg: TransformerConfig, mesh: Mesh, *,
                     n_micro: int, axis: str = PIPELINE_AXIS,
                     data_axis: Optional[str] = None,
                     return_aux: bool = False):
    """Forward with the LAYER STACK sharded over the mesh's 'pipe' axis
    (parallel/pipeline_parallel.py GPipe schedule): stage s holds layers
    [s*L/S, (s+1)*L/S); microbatches flow through the ring via ppermute.
    Embedding and the tied head run replicated outside the pipeline (they
    are a small fraction of the params). Differentiable — jax.grad gives
    the backward pipeline via the scan/ppermute transposes. data_axis:
    optional PP x DP composition — each microbatch additionally sharded
    over that mesh axis. MoE blocks route per group (see the stage_fn
    note below); return_aux=True also returns the grouped load-balance
    aux loss for the pipelined TRAIN objective."""
    from deeplearning4j_tpu.parallel.pipeline_parallel import pipeline_apply

    n_stages = mesh.shape[axis]
    L = cfg.n_layers
    if L % n_stages != 0:
        raise ValueError(f"n_layers {L} not divisible by {n_stages} stages")
    per = L // n_stages
    # restack block leaves [L, ...] -> [S, per, ...] (stage-major)
    stage_params = jax.tree_util.tree_map(
        lambda a: a.reshape((n_stages, per) + a.shape[1:]), params["blocks"])

    cdt = cfg.compute_dtype
    moe = bool(cfg.moe_experts)

    if moe:
        # MoE under GPipe routes PER GROUP (group = one microbatch, or one
        # microbatch x data-slice under PP x DP) — the GShard/Switch group
        # semantics: capacity and load-balance statistics are computed over
        # the tokens that are physically together. With n_micro=1 this is
        # exactly the serial batch objective; with n_micro>1 it is the
        # grouped objective deployed MoE systems train (drop-free logits
        # still match serial bit-for-bit).
        def stage_fn(sp, h):
            def block(carry, bp):
                h, aux = carry
                h, a = _moe_block(bp, h, cfg, cdt=cdt)
                return (h, aux + a), None

            (h, aux), _ = lax.scan(
                block, (h, jnp.zeros((), jnp.float32)), sp)
            return h, aux
    else:
        def stage_fn(sp, h):
            def block(h, bp):
                return _dense_block_f32(bp, h, cfg.n_heads, cdt=cdt), None

            h, _ = lax.scan(block, h, sp)
            return h

    n, t = tokens.shape
    # bf16 policy: the residual stream (the thing the ring ppermutes each
    # tick) is carried in the compute dtype — half the ICI traffic
    h = (params["embed"][tokens] + params["pos"][:t][None]).astype(cdt)
    out = pipeline_apply(stage_params, h, mesh, stage_fn=stage_fn,
                         n_micro=n_micro, axis=axis, data_axis=data_axis,
                         with_aux=moe)
    if moe:
        h, aux = out
        # mean aux per layer per group (serial forward's /L, M=1 => equal)
        aux = aux / (cfg.n_layers * n_micro)
    else:
        h, aux = out, jnp.zeros((), jnp.float32)
    h = _ln(h.astype(jnp.float32), params["lnf_g"], params["lnf_b"])
    logits = (h @ params["embed"].T).astype(jnp.float32)
    if return_aux:
        return logits, aux
    return logits


# ---------------------------------------------------------------------------
# Pipeline-parallel TRAINING (GPipe fwd + autodiff bwd pipeline + Adam,
# one jitted step over a ('pipe',) or ('pipe', 'data') mesh)
# ---------------------------------------------------------------------------


def pipeline_param_shardings(cfg: TransformerConfig, mesh: Mesh,
                             axis: str = PIPELINE_AXIS) -> Params:
    """NamedShardings for pipeline mode: every block leaf [L, ...] sharded
    over 'pipe' on the LAYER dim (layer-major == stage-major because
    pipeline_forward's [L]->[S, L/S] restack is contiguous), so each device
    holds exactly its own stage's layers — the model can be S x larger than
    one chip's HBM. Embedding/pos/final-LN are replicated (small)."""
    shapes = jax.eval_shape(partial(init_params, cfg))
    rep = NamedSharding(mesh, P())

    def of(a, pipe: bool):
        if pipe:
            return NamedSharding(mesh, P(axis, *(None,) * (a.ndim - 1)))
        return rep

    return {
        k: (jax.tree_util.tree_map(lambda a: of(a, True), v)
            if k == "blocks"
            else jax.tree_util.tree_map(lambda a: of(a, False), v))
        for k, v in shapes.items()
    }


def shard_params_pipeline(params: Params, cfg: TransformerConfig, mesh: Mesh,
                          axis: str = PIPELINE_AXIS) -> Params:
    return jax.tree_util.tree_map(
        jax.device_put, params, pipeline_param_shardings(cfg, mesh, axis))


def make_pipeline_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                             n_micro: int, axis: str = PIPELINE_AXIS,
                             data_axis: Optional[str] = None):
    """Full pipelined TRAIN step: GPipe microbatch forward, backward
    pipeline from autodiff (scan/ppermute transposes — microbatch gradient
    accumulation falls out of the scan transpose), Adam update, all in ONE
    jitted XLA program. Returns step(params, opt, tokens, targets) ->
    (params, opt, loss), numerically the same optimizer step as the serial
    make_train_step on the same batch (PP-train == serial-train;
    tests/test_pipeline_training.py locks the loss curves together).

    The reference has no pipeline axis at all (SURVEY.md section 2.7); this
    is the beyond-reference leg that lets the flagship's depth exceed one
    chip's HBM while still taking real optimizer steps."""
    ins, outs = _pipeline_step_shardings(cfg, mesh, axis, data_axis)
    return jax.jit(_build_pipeline_step(cfg, mesh, n_micro, axis, data_axis),
                   in_shardings=ins, out_shardings=outs,
                   **_donation_kwargs())


def _build_pipeline_step(cfg, mesh, n_micro, axis, data_axis):
    # validated HERE so every pipelined factory (single- and multi-step)
    # rejects the unsupported configs, not just make_pipeline_train_step
    _reject_lowprec("pipelined")
    _validate_schedule(cfg)
    if cfg.accum_steps != 1:
        raise ValueError(
            "cfg.accum_steps must be 1 under pipelined training — n_micro "
            "IS the microbatch/accumulation count (the GPipe schedule)")

    def pp_loss(params, tokens, targets):
        # same shape as the serial loss_fn (NLL + aux; aux == 0 dense).
        # MoE aux is the GROUPED objective (group = microbatch): exactly
        # the serial objective at n_micro=1, the GShard/Switch grouped
        # objective at n_micro > 1.
        logits, aux = pipeline_forward(params, tokens, cfg, mesh,
                                       n_micro=n_micro, axis=axis,
                                       data_axis=data_axis, return_aux=True)
        return nll_loss(logits, targets) + cfg.moe_aux_coef * aux

    def step(params, opt, tokens, targets):
        loss, grads = jax.value_and_grad(pp_loss)(params, tokens, targets)
        lr = _scheduled_lr(cfg, opt["t"] + 1)
        params, opt = _adam_update(params, grads, opt, lr,
                                   weight_decay=cfg.weight_decay,
                                   clip_grad_norm=cfg.clip_grad_norm)
        return params, opt, loss

    return step


def _pipeline_step_shardings(cfg, mesh, axis, data_axis):
    pshard = pipeline_param_shardings(cfg, mesh, axis)
    oshard = {"m": pshard, "v": pshard, "t": NamedSharding(mesh, P())}
    dshard = NamedSharding(mesh,
                           P(data_axis) if data_axis is not None else P())
    return ((pshard, oshard, dshard, dshard),
            (pshard, oshard, NamedSharding(mesh, P())))


def make_pipeline_train_multi_step(cfg: TransformerConfig, mesh: Mesh, *,
                                   n_micro: int, axis: str = PIPELINE_AXIS,
                                   data_axis: Optional[str] = None):
    """K pipelined optimizer steps fused into one XLA program (lax.scan
    over stacked batches [K, N, T] — the fit_batches dispatch-amortization
    applied to the pipeline schedule)."""
    step = _build_pipeline_step(cfg, mesh, n_micro, axis, data_axis)
    (pshard, oshard, dshard, _), (_, _, lshard) = _pipeline_step_shardings(
        cfg, mesh, axis, data_axis)
    kshard = NamedSharding(
        mesh, P(None, *dshard.spec))
    return jax.jit(
        _multi_from_step(step),
        in_shardings=(pshard, oshard, kshard, kshard),
        out_shardings=(pshard, oshard, lshard),
        **_donation_kwargs(),
    )


def _multi_from_step(step):
    """Wrap a pure train step into a K-step lax.scan over stacked batches
    (shared by the dense, pipelined, and BERT-MLM multi-step factories —
    variadic so steps with any number of data stacks fit: (tokens,
    targets) here, (inputs, targets, weights) for the MLM)."""
    def multi(params, opt, *stacks):
        def body(carry, xs):
            params, opt = carry
            params, opt, loss = step(params, opt, *xs)
            return (params, opt), loss

        (params, opt), losses = lax.scan(body, (params, opt), stacks)
        return params, opt, losses

    return multi


# ---------------------------------------------------------------------------
# Convenience wrapper
# ---------------------------------------------------------------------------


class TransformerLM:
    """Flagship LM with the framework's fit/generate surface."""

    def __init__(self, cfg: TransformerConfig, mesh: Optional[Mesh] = None):
        dispatch.enable_compile_cache()
        self.cfg = cfg  # the user's config — persisted verbatim by save()
        # runtime config: flash is disabled under a mesh (pallas custom
        # calls don't auto-partition under GSPMD; multi-chip attention is
        # ring_forward's job) WITHOUT mutating cfg, so a mesh-trained
        # checkpoint reloaded on one device gets its flash path back
        self._run_cfg = (dataclasses.replace(cfg, use_flash=False)
                         if mesh is not None else cfg)
        self.mesh = mesh
        self.params = init_params(cfg)
        if mesh is not None:
            # pipeline mode (depth-sharded over 'pipe') or Megatron GSPMD,
            # decided by param_shardings_for_mesh
            self.params = shard_params_for_mesh(self.params, cfg, mesh)
        self.opt = init_opt_state(self.params)
        self._step = self._make_step()
        self._gen_cache: Dict[tuple, Any] = {}
        self.iteration = 0
        from deeplearning4j_tpu.ops.memory import MemoryStats

        # AOT memory ledger beside the containers' dispatch_stats
        # (ops/memory.py); populated on demand by measure_memory()
        self.memory_stats = MemoryStats()
        from deeplearning4j_tpu.obs.registry import register_net

        # ledger-registration convention (PR 7): every *_stats ledger
        # joins the central MetricsRegistry at its attach point — weakly
        # held, so short-lived models don't leak
        register_net(self)

    def _pipeline_mode(self) -> bool:
        return self.mesh is not None and PIPELINE_AXIS in self.mesh.shape

    def _pipeline_kwargs(self) -> Dict[str, Any]:
        return {
            "n_micro": self.cfg.pipeline_microbatches,
            "data_axis": (DATA_AXIS if DATA_AXIS in self.mesh.shape
                          else None),
        }

    def _sequence_mode(self) -> bool:
        return self.mesh is not None and SEQUENCE_AXIS in self.mesh.shape

    def _make_step(self):
        if self._pipeline_mode():
            return make_pipeline_train_step(self._run_cfg, self.mesh,
                                            **self._pipeline_kwargs())
        if self._sequence_mode():
            return make_ring_train_step(self._run_cfg, self.mesh)
        return make_train_step(self._run_cfg, self.mesh)

    @classmethod
    def from_state(cls, cfg: TransformerConfig, params: Params,
                   opt: Optional[Params] = None,
                   mesh: Optional[Mesh] = None) -> "TransformerLM":
        """Build an LM around EXISTING state without running (or paying
        for) a random init — the restore path for checkpoints whose params
        are already materialized/sharded (utils/sharded_checkpoint.py)."""
        dispatch.enable_compile_cache()
        lm = cls.__new__(cls)
        lm.cfg = cfg
        lm._run_cfg = (dataclasses.replace(cfg, use_flash=False)
                       if mesh is not None else cfg)
        lm.mesh = mesh
        lm.params = params
        lm.opt = opt if opt is not None else init_opt_state(params)
        lm._step = lm._make_step()
        lm._gen_cache = {}
        # the optimizer step count IS the training iteration — restoring it
        # keeps the listener iteration contract across checkpoint resumes
        lm.iteration = int(lm.opt["t"])
        from deeplearning4j_tpu.ops.memory import MemoryStats

        lm.memory_stats = MemoryStats()
        return lm

    def measure_memory(self, tokens: jax.Array,
                       targets: jax.Array) -> Optional[Dict[str, Any]]:
        """AOT memory accounting for the current train step on this batch
        shape (ops/memory.analyze_jit: lower + compile + memory_analysis,
        no execution) — recorded under 'train_step' in self.memory_stats.
        On the CPU substrate this measures the CPU build; against the
        chip it reports real HBM. Returns the byte dict, or None when the
        backend exposes no memory stats."""
        from deeplearning4j_tpu.ops import memory as memory_mod

        return memory_mod.measure(
            self.memory_stats, "train_step", self._step,
            self.params, self.opt, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(targets, jnp.int32))

    def fit(self, tokens: jax.Array, targets: jax.Array) -> jax.Array:
        self.params, self.opt, loss = self._step(
            self.params, self.opt, tokens, targets)
        self.iteration += 1
        return loss

    def fit_batches(self, tokens_k: jax.Array,
                    targets_k: jax.Array) -> jax.Array:
        """K fused optimizer steps in one XLA program: tokens/targets
        stacked [K, N, T]. Returns the K per-step losses. Serially
        equivalent to K fit() calls (make_train_multi_step)."""
        if getattr(self, "_multi_step", None) is None:
            if self._pipeline_mode():
                self._multi_step = make_pipeline_train_multi_step(
                    self._run_cfg, self.mesh, **self._pipeline_kwargs())
            elif self._sequence_mode():
                self._multi_step = make_ring_train_multi_step(
                    self._run_cfg, self.mesh)
            else:
                self._multi_step = make_train_multi_step(self._run_cfg,
                                                         self.mesh)
        self.params, self.opt, losses = self._multi_step(
            self.params, self.opt, tokens_k, targets_k)
        self.iteration += int(tokens_k.shape[0])
        return losses

    def fit_iterator(self, iterator, num_epochs: int = 1,
                     listeners=()) -> "TransformerLM":
        """fit(DataSetIterator) parity for the flagship (reference
        MultiLayerNetwork.fit :1017 semantics): DataSets carry token ids as
        features [N, T] and next-token ids as labels [N, T]. Works with
        any framework iterator incl. AsyncDataSetIterator prefetch; the
        IterationListener chain (optimize/listeners.py) is invoked with a
        host readback only when listeners are present. The iteration
        counter persists across calls (self.iteration — same contract as
        MultiLayerNetwork :1017), so resumed training never re-emits
        earlier iteration numbers to the listeners."""
        for _ in range(num_epochs):
            for ds in iterator:
                loss = self.fit(jnp.asarray(ds.features, jnp.int32),
                                jnp.asarray(ds.labels, jnp.int32))
                if listeners:
                    score = float(loss)
                    for lst in listeners:
                        lst.iteration_done(self, self.iteration, score)
            if hasattr(iterator, "reset"):
                iterator.reset()
        return self

    def evaluate(self, iterator) -> Dict[str, float]:
        """Held-out evaluation: mean next-token cross-entropy and
        perplexity over an iterator of DataSets carrying token ids
        ([N, T] features, next-ids labels — the fit_iterator layout).
        The per-batch loss is jitted once and losses stay device-side
        until ONE bulk readback (the evaluate(DataSetIterator) role —
        reference MultiLayerNetwork.evaluate :2316 — for the flagship)."""
        if getattr(self, "_eval_loss", None) is None:
            cfg = self._run_cfg

            @jax.jit
            def eval_loss(params, tokens, targets, mask):
                logits, _ = forward(params, tokens, cfg)
                return nll_loss(logits, targets, mask)

            self._eval_loss = eval_loss
        losses, counts = [], []
        for ds in iterator:
            x = jnp.asarray(ds.features, jnp.int32)
            y = jnp.asarray(ds.labels, jnp.int32)
            # labels_mask (variable-length sequences): masked positions
            # count in neither the loss nor the token total
            m = ds.labels_mask if ds.labels_mask is not None \
                else ds.features_mask
            if m is None:
                m_arr = jnp.ones(x.shape, jnp.float32)
                counts.append(x.shape[0] * x.shape[1])
            else:
                m_arr = jnp.asarray(m, jnp.float32)
                counts.append(float(np.asarray(m).sum()))
            losses.append(self._eval_loss(self.params, x, y, m_arr))
        if hasattr(iterator, "reset"):
            iterator.reset()
        if not losses:
            return {"loss": float("nan"), "perplexity": float("nan"),
                    "tokens": 0}
        w = np.asarray(counts, np.float64)
        ls = np.asarray(jnp.stack(losses), np.float64)  # ONE bulk readback
        mean = float((ls * w).sum() / w.sum())
        return {"loss": mean, "perplexity": float(np.exp(mean)),
                "tokens": int(w.sum())}

    def logits(self, tokens: jax.Array) -> jax.Array:
        return forward(self.params, tokens, self._run_cfg)[0]

    def output(self, tokens) -> jax.Array:
        """Container-compatible inference surface (MultiLayerNetwork.output
        / streaming ModelServer.predict): token ids in, logits out."""
        return self.logits(jnp.asarray(tokens).astype(jnp.int32))

    def save(self, path: str) -> None:
        """Checkpoint in the framework's ModelSerializer zip layout
        (shared writer — utils/serialization.write_flagship_zip;
        reference ModelSerializer.java:70-110 three-part semantic:
        configuration + coefficients + updater)."""
        from deeplearning4j_tpu.utils.serialization import (
            write_flagship_zip,
        )

        write_flagship_zip(path, "TransformerLM", self.cfg, self.params,
                           self.opt)

    @classmethod
    def load(cls, path: str, mesh: Optional[Mesh] = None,
             load_updater: bool = True) -> "TransformerLM":
        from deeplearning4j_tpu.utils.serialization import (
            _npz_bytes_into_tree,
            read_flagship_zip,
        )

        cfg_dict, coeff, upd, _ = read_flagship_zip(path, "TransformerLM")
        cfg = TransformerConfig(**cfg_dict)
        lm = cls(cfg, mesh=mesh)
        lm.params = _npz_bytes_into_tree(coeff, lm.params)
        if load_updater and upd is not None:
            lm.opt = _npz_bytes_into_tree(upd, lm.opt)
            # optimizer step count IS the training iteration (same
            # contract as from_state): resumed runs must not re-emit
            # earlier iteration numbers to listeners
            lm.iteration = int(lm.opt["t"])
        if mesh is not None:
            lm.params = shard_params_for_mesh(lm.params, cfg, mesh)
        return lm

    def _sample_fn(self, n_new: int, top_k=None, has_top_p=False):
        """Jitted sampler, cached per n_new (a fresh @jax.jit closure per
        generate() call would recompile every time); temperature and key are
        traced args so they never force recompiles. The token buffer keeps
        the prompt at positions 0..t-1 (RIGHT-padded with zeros that causal
        masking makes invisible), so position embeddings match training —
        left-padding would condition sampling on a fake zero-token prefix."""
        cached = self._gen_cache.get((n_new, top_k, has_top_p))
        if cached is not None:
            return cached
        cfg = self._run_cfg
        filt = self._filter_logits

        @jax.jit
        def sample(params, buf, pos0, key, temperature, top_p):
            def one(carry, i):
                buf, key = carry
                logits, _ = forward(params, buf, cfg)
                pos = pos0 + i  # next write index; condition on pos-1
                last = jnp.take_along_axis(
                    logits, (pos - 1)[None, None, None].repeat(
                        buf.shape[0], 0), axis=1)[:, 0]
                key, sub = jax.random.split(key)
                tempered = last / jnp.maximum(temperature, 1e-6)
                nxt = jax.random.categorical(
                    sub, filt(tempered, top_k,
                              top_p if has_top_p else None))
                buf = lax.dynamic_update_slice_in_dim(
                    buf, nxt[:, None].astype(buf.dtype), pos, axis=1)
                return (buf, key), nxt

            (_, _), out = lax.scan(one, (buf, key), jnp.arange(n_new))
            return out.T  # [N, n_new]

        self._gen_cache[(n_new, top_k, has_top_p)] = sample
        return sample

    @staticmethod
    def _filter_logits(logits, top_k: Optional[int], top_p):
        """Top-k / nucleus (top-p) filtering of TEMPERED logits (callers
        scale by temperature first — the standard order, so the nucleus is
        computed on the distribution actually sampled). top_k is static
        (lax.top_k needs a static k; one compile per k); top_p is a TRACED
        scalar (or None to skip) — sweeping it never recompiles. Filters
        compose: k first, then the smallest set of remaining tokens whose
        cumulative probability reaches top_p (the top token always
        survives: its preceding cumulative mass is 0)."""
        if top_k is not None:
            kth = lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p is not None:
            sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep_sorted = (cum - probs) < top_p  # cumprob BEFORE the token
            thresh = jnp.min(
                jnp.where(keep_sorted, sorted_desc, jnp.inf), axis=-1,
                keepdims=True)
            logits = jnp.where(logits < thresh, -jnp.inf, logits)
        return logits

    def _sample_kv_fn(self, n_new: int, top_k=None, has_top_p=False):
        """KV-cache sampler (prefill once, then one decode_step per token
        — O(max_len) each instead of a full O(max_len^2) forward). Cached
        per n_new; the prefill width max_len - n_new is static, so prompt
        length never forces a recompile (window right-padded; pad K/V
        entries are either overwritten before first read or masked)."""
        key_c = ("kv", n_new, top_k, has_top_p)
        cached = self._gen_cache.get(key_c)
        if cached is not None:
            return cached
        cfg = self._run_cfg
        filt = self._filter_logits

        @jax.jit
        def sample(params, buf, pos0, key, temperature, top_p):
            cache, _ = prefill_cache(params, buf, cfg)
            n = buf.shape[0]
            tok = jnp.take_along_axis(
                buf, (pos0 - 1)[None, None].repeat(n, 0), axis=1)[:, 0]

            def one(carry, i):
                cache, tok, key = carry
                cache, logits = decode_step(params, cache, tok,
                                            pos0 - 1 + i, cfg)
                key, sub = jax.random.split(key)
                tempered = logits / jnp.maximum(temperature, 1e-6)
                nxt = jax.random.categorical(
                    sub, filt(tempered, top_k,
                              top_p if has_top_p else None))
                return (cache, nxt.astype(buf.dtype), key), nxt

            _, out = lax.scan(one, (cache, tok, key), jnp.arange(n_new))
            return out.T  # [N, n_new]

        self._gen_cache[key_c] = sample
        return sample

    def generate(self, prompt: jax.Array, n_new: int, temperature: float = 1.0,
                 seed: int = 0, use_cache: Optional[bool] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None) -> jax.Array:
        """Sample n_new tokens after the prompt (static shapes throughout:
        one compile per n_new). prompt len + n_new must fit max_len; longer
        prompts keep their last (max_len - n_new) tokens. use_cache:
        KV-cache decoding (O(max_len) per token) — default on for DENSE
        single-device models; the full-forward sampler remains the
        default for mesh-sharded models and for MoE (where capacity-bound
        routing is batch-vs-stream dependent: KV decode routes each step
        as its own no-drop group, which matches the batch forward only in
        the drop-free regime — pass use_cache=True to opt in). Tensor-
        parallel ('model') meshes support use_cache=True: GSPMD shards
        prefill+decode on the head dim (equivalence-locked by
        test_tp_mesh_kv_decode_equals_serial)."""
        cfg = self._run_cfg
        if n_new >= cfg.max_len:
            raise ValueError(f"n_new {n_new} must be < max_len {cfg.max_len}")
        if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
            raise ValueError(f"top_k {top_k} must be in [1, vocab_size]")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p {top_p} must be in (0, 1]")
        if use_cache is None:
            # MoE stays opt-in: flipping it on by default would silently
            # change sampled tokens for capacity-bound configs (the
            # default moe_capacity_factor=1.25 regime)
            use_cache = self.mesh is None and not cfg.moe_experts
        t = prompt.shape[1]
        keep = min(t, cfg.max_len - n_new)
        window = prompt[:, t - keep:]
        width = (cfg.max_len - n_new) if use_cache else cfg.max_len
        buf = jnp.pad(window, ((0, 0), (0, width - keep)))
        has_tp = top_p is not None
        fn = (self._sample_kv_fn(n_new, top_k, has_tp) if use_cache
              else self._sample_fn(n_new, top_k, has_tp))
        return fn(
            self.params, buf, jnp.asarray(keep, jnp.int32),
            jax.random.PRNGKey(seed), jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p if has_tp else 1.0, jnp.float32))
