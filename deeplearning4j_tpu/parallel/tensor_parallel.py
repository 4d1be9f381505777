"""Tensor (model) parallelism: Megatron-style sharded dense/attention.

The reference has NO tensor parallelism (SURVEY.md section 2.7: "Absent in
reference ... tensor parallelism, pipeline parallelism"; model scale in 2016
did not require it). This framework treats model parallelism as first-class:
weight matrices too large for one chip's HBM are sharded over the mesh's
'model' axis and the forward/backward run as SPMD programs with exactly one
collective per block boundary.

The layout is the classic column-then-row pairing:

  column-parallel dense:  W [F, H] sharded on H  -> each device computes its
                          slice of the output; NO collective (output stays
                          feature-sharded).
  row-parallel dense:     W [H, F] sharded on H with the input feature-
                          sharded -> partial products are summed with ONE
                          psum over ICI; output is replicated again.

A transformer block needs exactly two psums (one after attention's output
projection, one after the MLP's second matmul) — the same schedule XLA's
GSPMD derives for Megatron shardings, written here explicitly with
`shard_map` so tests can assert the collective structure and the dryrun can
validate it on a virtual mesh.

Gradients: `shard_map` is differentiable; the transpose of psum is identity
(cotangent already replicated) and the transpose of the implicit slice is a
psum, so `jax.grad` through these functions yields mathematically-correct
full gradients with the matching reverse collectives.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS

Params = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Param init + sharding placement
# ---------------------------------------------------------------------------


def init_tp_block_params(key, d_model: int, d_ff: int, num_heads: int,
                         dtype=jnp.float32) -> Params:
    """Standard transformer block params, laid out for column/row sharding.

    Shapes are GLOBAL; `shard_tp_params` places them on the mesh. Weight
    init delegates to the framework's WeightInit.XAVIER
    (nn/weights.init_weights — reference WeightInitUtil.java:93-123)."""
    from deeplearning4j_tpu.nn.weights import init_weights

    ks = jax.random.split(key, 6)

    def xavier(k, shape):
        return init_weights(k, shape, "xavier", shape[0], shape[-1],
                            None).astype(dtype)

    return {
        "ln1_g": jnp.ones((d_model,), dtype),
        "ln1_b": jnp.zeros((d_model,), dtype),
        "Wq": xavier(ks[0], (d_model, d_model)),
        "Wk": xavier(ks[1], (d_model, d_model)),
        "Wv": xavier(ks[2], (d_model, d_model)),
        "Wo": xavier(ks[3], (d_model, d_model)),
        "ln2_g": jnp.ones((d_model,), dtype),
        "ln2_b": jnp.zeros((d_model,), dtype),
        "W1": xavier(ks[4], (d_model, d_ff)),
        "b1": jnp.zeros((d_ff,), dtype),
        "W2": xavier(ks[5], (d_ff, d_model)),
        "b2": jnp.zeros((d_model,), dtype),
    }


# PartitionSpecs per param name: column-parallel weights shard their OUTPUT
# dim, row-parallel weights their INPUT dim; layernorm + output-side biases
# are replicated.
TP_BLOCK_SPECS: Dict[str, P] = {
    "ln1_g": P(), "ln1_b": P(),
    "Wq": P(None, MODEL_AXIS), "Wk": P(None, MODEL_AXIS),
    "Wv": P(None, MODEL_AXIS), "Wo": P(MODEL_AXIS, None),
    "ln2_g": P(), "ln2_b": P(),
    "W1": P(None, MODEL_AXIS), "b1": P(MODEL_AXIS),
    "W2": P(MODEL_AXIS, None), "b2": P(),
}


def shard_tp_params(params: Params, mesh: Mesh) -> Params:
    """Place block params on the mesh with Megatron shardings (device_put
    with NamedSharding — each chip holds 1/p of every sharded matrix)."""
    return {
        k: jax.device_put(v, NamedSharding(mesh, TP_BLOCK_SPECS[k]))
        for k, v in params.items()
    }


# ---------------------------------------------------------------------------
# Per-device bodies (run inside shard_map over the 'model' axis)
# ---------------------------------------------------------------------------


def _layer_norm(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _tp_block_body(p: Params, x, *, num_heads_local: int, causal: bool,
                   axis: str):
    """One transformer block on one device. x: [N, T, F] REPLICATED;
    sharded params arrive as local shards ([F, H/p] etc.)."""
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    n, t, _ = h.shape
    # column-parallel QKV: local heads only, no collective
    q = (h @ p["Wq"]).reshape(n, t, num_heads_local, -1)
    k = (h @ p["Wk"]).reshape(n, t, num_heads_local, -1)
    v = (h @ p["Wv"]).reshape(n, t, num_heads_local, -1)
    d = q.shape[-1]
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    att = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
    att = att.reshape(n, t, -1)
    # row-parallel output projection: psum #1 restores replication
    x = x + lax.psum(att @ p["Wo"], axis)
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    # column-parallel W1 (+ sharded bias), row-parallel W2: psum #2
    inner = jax.nn.gelu(h @ p["W1"] + p["b1"])
    x = x + lax.psum(inner @ p["W2"], axis) + p["b2"]
    return x


def tp_block_apply(params: Params, x, mesh: Mesh, *, num_heads: int,
                   causal: bool = True, axis: str = MODEL_AXIS):
    """Apply one tensor-parallel transformer block.

    x: [N, T, F] replicated; params sharded per TP_BLOCK_SPECS (global
    shapes — shard_map hands each device its shard). Output replicated."""
    p_size = mesh.shape[axis]
    if num_heads % p_size != 0:
        raise ValueError(f"num_heads {num_heads} not divisible by "
                         f"model-axis size {p_size}")
    in_specs = ({k: TP_BLOCK_SPECS[k] for k in params}, P())
    fn = shard_map(
        partial(_tp_block_body, num_heads_local=num_heads // p_size,
                causal=causal, axis=axis),
        mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )
    return fn(params, x)


def tp_block_reference(params: Params, x, *, num_heads: int,
                       causal: bool = True):
    """Single-device reference math for equivalence tests: identical block
    with unsharded params (the TP result must match this exactly up to
    reduction-order float noise)."""
    h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
    n, t, f = h.shape
    q = (h @ params["Wq"]).reshape(n, t, num_heads, -1)
    k = (h @ params["Wk"]).reshape(n, t, num_heads, -1)
    v = (h @ params["Wv"]).reshape(n, t, num_heads, -1)
    d = q.shape[-1]
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    att = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + att.reshape(n, t, f) @ params["Wo"]
    h = _layer_norm(x, params["ln2_g"], params["ln2_b"])
    inner = jax.nn.gelu(h @ params["W1"] + params["b1"])
    return x + inner @ params["W2"] + params["b2"]


# ---------------------------------------------------------------------------
# Standalone column/row-parallel dense (building blocks for other models)
# ---------------------------------------------------------------------------


def column_parallel_dense(W, b, x, mesh: Mesh, *, axis: str = MODEL_AXIS,
                          gather: bool = True):
    """y = x @ W + b with W [F, H] sharded on H. gather=True all_gathers the
    output back to full H (use gather=False to feed a row-parallel dense)."""
    def body(Wl, bl, xl):
        y = xl @ Wl + bl
        if gather:
            y = lax.all_gather(y, axis, axis=y.ndim - 1, tiled=True)
        return y

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(axis), P()),
        out_specs=P() if gather else P(*(None,) * (x.ndim - 1), axis),
        check_vma=False,
    )(W, b, x)


def local_head_columns(W, *, num_heads: int, head_dim: int,
                       n_devices: int, axis: str = MODEL_AXIS):
    """This device's head-columns of a REPLICATED projection W [F, H*hd]
    — the column-parallel partition of :data:`TP_BLOCK_SPECS` in its
    BYTEWISE form, for use inside a shard_map body (serving/mesh.py's
    decode tick).

    Column-parallel QKV is exact, not approximate: every output column
    of ``x @ W`` is an independent dot product, so
    ``(x @ W)[:, cols] == x @ W[:, cols]`` element-for-element — no
    float reduction is split or reordered. Slicing the replicated W at
    trace time by ``lax.axis_index`` keeps one params copy per device
    (no resharded second tree) while the compute still runs only the
    local ``num_heads / n_devices`` heads' columns. The serving tick
    needs this form (rather than `shard_tp_params` + row-parallel Wo)
    because its acceptance bar is BYTE-identity with the single-device
    program: a Megatron psum after Wo would reorder the output
    contraction's float sum."""
    cols = (num_heads // n_devices) * head_dim
    idx = lax.axis_index(axis)
    return lax.dynamic_slice_in_dim(W, idx * cols, cols, axis=1)


def row_parallel_dense(W, b, x_sharded, mesh: Mesh, *, axis: str = MODEL_AXIS):
    """y = x @ W + b with W [H, F] sharded on H and x [..., H] sharded on its
    last dim; ONE psum replicates the output."""
    def body(Wl, bl, xl):
        return lax.psum(xl @ Wl, axis) + bl

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(), P(*(None,) * (x_sharded.ndim - 1), axis)),
        out_specs=P(),
        check_vma=False,
    )(W, b, x_sharded)
