"""Pipeline parallelism: GPipe-style microbatch schedule over a 'pipe' axis.

The reference has NO pipeline parallelism (SURVEY.md section 2.7 — absent;
2016 model scale). Here depth-wise model sharding is first-class: the layer
stack is split into S shape-preserving stages, one per device along the
mesh's 'pipe' axis; a batch is split into M microbatches that flow through
the ring, activations hopping stage->stage via `ppermute` over ICI.

Schedule (GPipe): T = M + S - 1 ticks. At tick t, stage s processes
microbatch t - s (when 0 <= t - s < M). Every device computes every tick
(bubble ticks compute garbage that is masked out) — under jit this is a
single `lax.scan` whose body is pure SPMD compute + one ppermute, which XLA
overlaps with the next tick's compute.

The whole schedule is differentiable: `jax.grad` through `pipeline_apply`
yields the exact full-model gradient (scan transposes to the reverse
schedule; ppermute transposes to the reverse ring hop), so the backward
pipeline emerges from autodiff instead of hand-written 1F1B plumbing.

Stage params live as a pytree whose leaves carry a leading stage dim [S, ...]
sharded over 'pipe' — each device holds only its own stage's weights
(`shard_pipeline_params`), which is the point: the model can be S x larger
than one chip's HBM.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import PIPELINE_AXIS

StageFn = Callable[[Any, jax.Array], jax.Array]


def shard_pipeline_params(params: Any, mesh: Mesh,
                          axis: str = PIPELINE_AXIS) -> Any:
    """Place stage-stacked params ([S, ...] leaves) so each device along the
    pipe axis holds one stage's slice."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(
            a, NamedSharding(mesh, P(axis, *(None,) * (a.ndim - 1)))
        ),
        params,
    )


def _pipeline_body(params: Any, x: jax.Array, *, stage_fn: StageFn,
                   n_micro: int, axis: str, with_aux: bool = False,
                   data_axis: Optional[str] = None):
    """Per-device body. params leaves: [1, ...] (my stage, leading dim kept
    by shard_map); x: [M, mb, ...] microbatched input, replicated.

    with_aux: stage_fn returns (y, aux_scalar) and the body additionally
    returns the aux SUM over every valid (stage, microbatch) pair — the
    per-group MoE load-balance statistics (group = microbatch, or
    microbatch x data-slice under PP x DP), psum'd over the pipe axis and
    pmean'd over the data axis so the scalar is replicated."""
    my_params = jax.tree_util.tree_map(lambda a: a[0], params)
    stage = lax.axis_index(axis)
    n_stages = lax.psum(1, axis)
    n_ticks = n_micro + n_stages - 1  # static: mesh size is trace-constant

    outputs = jnp.zeros_like(x)
    recv = jnp.zeros_like(x[0])
    aux0 = jnp.zeros((), jnp.float32)
    # ring hop: stage s -> s+1 (last stage's send is dropped into stage 0's
    # recv buffer, where it is ignored — stage 0 reads from x instead)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        recv, outputs, aux_sum = carry
        mb_idx = jnp.clip(t - stage, 0, n_micro - 1)
        inp = jnp.where(stage == 0,
                        lax.dynamic_index_in_dim(x, jnp.clip(t, 0, n_micro - 1),
                                                 keepdims=False),
                        recv)
        if with_aux:
            y, aux = stage_fn(my_params, inp)
        else:
            y, aux = stage_fn(my_params, inp), aux0
        valid = (t - stage >= 0) & (t - stage < n_micro)
        outputs = jnp.where(
            valid,
            lax.dynamic_update_index_in_dim(outputs, y, mb_idx, 0),
            outputs,
        )
        # bubble ticks compute garbage — their aux must not enter the sum
        aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
        recv = lax.ppermute(y, axis, perm)
        return (recv, outputs, aux_sum), None

    (_, outputs, aux_sum), _ = lax.scan(
        tick, (recv, outputs, aux0), jnp.arange(n_ticks))
    # only the LAST stage's output buffer is the model output; mask + psum
    # replicates it to every device
    out = lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis,
    )
    if not with_aux:
        return out
    aux_total = lax.psum(aux_sum, axis)  # every stage's own layers
    if data_axis is not None:
        aux_total = lax.pmean(aux_total, data_axis)
    return out, aux_total


def pipeline_apply(params: Any, x: jax.Array, mesh: Mesh, *,
                   stage_fn: StageFn, n_micro: int,
                   axis: str = PIPELINE_AXIS,
                   data_axis: Optional[str] = None,
                   with_aux: bool = False):
    """Run the pipelined model.

    params: pytree with leading stage dim [S, ...] on every leaf (S = pipe
            axis size), sharded or shardable per `shard_pipeline_params`.
    x:      [B, ...] global batch; B must divide into n_micro microbatches.
    stage_fn(stage_params, mb) -> mb must preserve the microbatch shape
            (equal-width stages — the transformer-block case).
    data_axis: optional second mesh axis for PP x DP composition — each
            microbatch is additionally sharded over it (the per-device
            schedule is unchanged: the ppermute ring runs over `axis`
            independently per data slice, so every (pipe, data) device
            pipelines its own batch shard).
    with_aux: stage_fn returns (y, aux_scalar); pipeline_apply then returns
            (output, aux_sum) where aux_sum totals every (stage, microbatch)
            group's scalar (replicated) — the MoE per-group load-balance
            statistics channel.
    Returns [B, ...] output, replicated over `axis` (sharded over
    `data_axis` when given)."""
    s = mesh.shape[axis]
    bad = [a.shape[0] for a in jax.tree_util.tree_leaves(params)
           if a.shape[0] != s]
    if bad:
        raise ValueError(
            f"stage-stacked params have leading dims {bad}; every leaf must "
            f"have leading dim == pipe-axis size {s}")
    b = x.shape[0]
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    mb = b // n_micro
    if data_axis is not None and mb % mesh.shape[data_axis] != 0:
        raise ValueError(
            f"microbatch width {mb} not divisible by data-axis size "
            f"{mesh.shape[data_axis]} (global batch {b} / n_micro {n_micro})")
    xm = x.reshape((n_micro, mb) + x.shape[1:])
    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *(None,) * (a.ndim - 1)), params
    )
    # microbatches [M, mb, ...]: mb dim sharded over data_axis when present
    x_spec = (P(None, data_axis, *(None,) * (xm.ndim - 2))
              if data_axis is not None else P())
    fn = shard_map(
        partial(_pipeline_body, stage_fn=stage_fn, n_micro=n_micro,
                axis=axis, with_aux=with_aux, data_axis=data_axis),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(x_spec, P()) if with_aux else x_spec,
        check_vma=False,
    )
    if with_aux:
        out, aux = fn(params, xm)
        return out.reshape((b,) + out.shape[2:]), aux
    out = fn(params, xm)
    return out.reshape((b,) + out.shape[2:])


def pipeline_reference(params: Any, x: jax.Array, *, stage_fn: StageFn,
                       n_stages: int) -> jax.Array:
    """Serial reference: run the S stages in sequence on one device (the
    pipelined result must match this exactly)."""
    y = x
    for s in range(n_stages):
        my = jax.tree_util.tree_map(lambda a, s=s: a[s], params)
        y = stage_fn(my, y)
    return y
