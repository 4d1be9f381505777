"""Expert parallelism: mixture-of-experts FFN sharded over an 'expert' axis.

The reference has NO MoE / expert parallelism (SURVEY.md section 2.7 —
absent; 2016). Here it is first-class: E expert MLPs live sharded over the
mesh's 'expert' axis (each chip holds E/p experts), tokens are routed by a
learned top-k gate, and the dispatch/combine are exact einsum contractions
with ONE psum over ICI on the combine — the GShard/Switch formulation, which
keeps every shape static (capacity-bounded) so the whole layer jits into a
fixed SPMD program.

Routing math (capacity C per expert per device-batch):
  gate logits [T, E] -> softmax -> top-k (values renormalized to sum 1);
  slot-j one-hots are assigned positions by a running per-expert cumsum
  (earlier slots get priority, matching GShard); tokens past capacity are
  DROPPED (their combine weight is zero — the residual connection carries
  them, standard MoE semantics).
  dispatch [T, E, C] one-hot  : token t -> (expert e, slot c)
  combine  [T, E, C] weights  : gate mass for the same assignment
  expert inputs  = einsum('tec,tf->ecf', dispatch, x)   (sharded on e)
  expert outputs = per-expert MLP on [C, F]
  y              = psum_e einsum('tec,ecf->tf', combine, out)

Differentiable end-to-end (top_k indices are constant under grad; gate
values flow through combine), so `jax.grad` gives exact MoE gradients with
the reverse all-reduce inserted automatically.

Beside it, for serving: ``dropless_experts``, a gated expert layer that
drops no row. It routes over ALL ``E`` experts (router logits ``[T, E]``,
the ``top_k`` largest, softmax over the chosen), is told which experts it
holds (``first``, ``count``; their weights are what it is handed) and
computes the part of the result those give: the ``T * top_k`` (row, expert)
pairs are sorted by expert and each expert's rows meet its matrices in one
grouped product (``jax.lax.ragged_dot``: no ``[T, E, C]`` tensor, no
capacity). A row's output is a function of that row alone, whoever shares
its batch, which is what lets the decode pools (serving/paged.py) admit a
model built on it where they refuse the capacity-routed layer above. On one
chip a layer holds every expert and there is no exchange; over chips each
holds a share and the shares' parts add up to the whole layer
(tests/test_moe_dropless.py).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import EXPERT_AXIS

Params = Dict[str, jax.Array]


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32) -> Params:
    """Gate + E expert MLPs (leading expert dim on expert leaves)."""
    kg, k1, k2 = jax.random.split(key, 3)

    def xavier(k, shape, fan_in, fan_out):
        return (jax.random.normal(k, shape)
                * jnp.sqrt(2.0 / (fan_in + fan_out))).astype(dtype)

    return {
        "Wg": xavier(kg, (d_model, n_experts), d_model, n_experts),
        "W1": xavier(k1, (n_experts, d_model, d_ff), d_model, d_ff),
        "b1": jnp.zeros((n_experts, d_ff), dtype),
        "W2": xavier(k2, (n_experts, d_ff, d_model), d_ff, d_model),
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


MOE_SPECS: Dict[str, P] = {
    "Wg": P(),
    "W1": P(EXPERT_AXIS, None, None),
    "b1": P(EXPERT_AXIS, None),
    "W2": P(EXPERT_AXIS, None, None),
    "b2": P(EXPERT_AXIS, None),
}


def shard_moe_params(params: Params, mesh: Mesh) -> Params:
    return {
        k: jax.device_put(v, NamedSharding(mesh, MOE_SPECS[k]))
        for k, v in params.items()
    }


def _routing(gates: jax.Array, top_k: int, capacity: int
             ) -> Tuple[jax.Array, jax.Array]:
    """gates [T, E] -> (dispatch [T, E, C] 0/1, combine [T, E, C])."""
    t, e = gates.shape
    topv, topi = lax.top_k(gates, top_k)          # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    dispatch = jnp.zeros((t, e, capacity), gates.dtype)
    combine = jnp.zeros((t, e, capacity), gates.dtype)
    prior = jnp.zeros((e,), jnp.int32)            # slots used per expert
    for j in range(top_k):                        # static small loop
        onehot = jax.nn.one_hot(topi[:, j], e, dtype=jnp.int32)   # [T, E]
        pos = jnp.cumsum(onehot, axis=0) - 1 + prior[None, :]      # [T, E]
        prior = prior + onehot.sum(0)
        in_cap = (pos < capacity) & (onehot > 0)
        pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                                dtype=gates.dtype)                 # [T,E,C]
        slot = jnp.where(in_cap[..., None], pos_oh, 0.0)
        dispatch = dispatch + slot
        combine = combine + topv[:, j, None, None] * slot
    return dispatch, combine


def expert_mlp(W1, b1, W2, b2, dispatch, combine, x):
    """The GShard dispatch -> per-expert MLP -> combine einsum chain on
    [T(, E, C)] tensors (shared by the shard_map body, the serial
    reference, and the transformer flagship's inline MoE blocks)."""
    ex_in = jnp.einsum("tec,tf->ecf", dispatch, x)          # [E, C, F]
    h = jax.nn.gelu(jnp.einsum("ecf,efh->ech", ex_in, W1) + b1[:, None, :])
    out = jnp.einsum("ech,ehf->ecf", h, W2) + b2[:, None, :]
    return jnp.einsum("tec,ecf->tf", combine, out)


def aux_loss_from_gates(gates: jax.Array) -> jax.Array:
    """Switch-style load-balance loss from softmax gates [T, E]:
    E * sum_e f_e * P_e (f_e = argmax-count fraction, P_e = mean prob)."""
    e = gates.shape[-1]
    hard = jax.nn.one_hot(jnp.argmax(gates, -1), e, dtype=gates.dtype)
    return e * jnp.sum(hard.mean(0) * gates.mean(0))


def _moe_body(p: Params, dispatch, combine, x, *, axis: str):
    """Per-device body: local experts only. dispatch/combine arrive sliced
    on the expert dim ([T, E/p, C]); x replicated [T, F]."""
    y = expert_mlp(p["W1"], p["b1"], p["W2"], p["b2"], dispatch, combine, x)
    return lax.psum(y, axis)


def moe_apply(params: Params, x: jax.Array, mesh: Mesh, *, top_k: int = 2,
              capacity_factor: float = 1.25,
              axis: str = EXPERT_AXIS) -> jax.Array:
    """Apply the expert-parallel MoE FFN. x: [N, T, F] (or [T, F])
    replicated; returns same shape, replicated. Gate runs replicated (it is
    tiny); expert compute is sharded over the expert axis."""
    orig_shape = x.shape
    xt = x.reshape(-1, orig_shape[-1])
    n_tokens = xt.shape[0]
    n_experts = params["Wg"].shape[1]
    p_size = mesh.shape[axis]
    if n_experts % p_size != 0:
        raise ValueError(f"{n_experts} experts not divisible by "
                         f"expert-axis size {p_size}")
    capacity = max(1, int(capacity_factor * n_tokens * top_k / n_experts))
    gates = jax.nn.softmax(xt @ params["Wg"], axis=-1)
    dispatch, combine = _routing(gates, top_k, capacity)
    body_params = {k: v for k, v in params.items() if k != "Wg"}
    fn = shard_map(
        partial(_moe_body, axis=axis),
        mesh=mesh,
        in_specs=({k: MOE_SPECS[k] for k in body_params},
                  P(None, axis, None), P(None, axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )
    y = fn(body_params, dispatch, combine, xt)
    return y.reshape(orig_shape)


def moe_reference(params: Params, x: jax.Array, *, top_k: int = 2,
                  capacity_factor: float = 1.25) -> jax.Array:
    """Single-device reference with identical routing (equivalence oracle)."""
    orig_shape = x.shape
    xt = x.reshape(-1, orig_shape[-1])
    n_tokens = xt.shape[0]
    n_experts = params["Wg"].shape[1]
    capacity = max(1, int(capacity_factor * n_tokens * top_k / n_experts))
    gates = jax.nn.softmax(xt @ params["Wg"], axis=-1)
    dispatch, combine = _routing(gates, top_k, capacity)
    y = expert_mlp(params["W1"], params["b1"], params["W2"], params["b2"],
                   dispatch, combine, xt)
    return y.reshape(orig_shape)


# rows up to which every held expert is applied to every row in one batched
# product (the decode tick: at 32 to 64 rows of 6 pairs nearly every expert
# is hit whatever the routing, so its matrices are read either way; read
# once each by one product they ran at 89% of the chip's memory roofline,
# through the grouped kernel at 67%: PERF.md section 6, PR 37). Past it the
# E / top_k times more operations would bound the product, and the rows are
# grouped
DENSE_ROWS = 128


def dropless_experts(x: jax.Array, logits: jax.Array, w_in: jax.Array,
                     w_down: jax.Array, *, top_k: int, first: int = 0,
                     act=jax.nn.relu, live=None, scope: str = "moe"
                     ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a gated, dropless top-k expert layer.

    x [T, d] float32 rows; logits [T, E] float32 router logits over ALL E
    experts; w_in [count, d, 2f] (gate | up) and w_down [count, f, d] the
    matrices of experts ``first .. first + count - 1``, the ones held here.
    Row t takes the ``top_k`` largest of its logits, ``I``, with weights
    ``softmax(logits[I])``, and gets ``sum over e in I and held of w_e
    W_down,e (act(W_gate,e x) * W_up,e x)``. Returns (y [T, d] float32, hit:
    int32, how many held experts got a row of a ``live`` row; ``live`` [T]
    bool, every row where None).

    Two forms of the same sum, by the rows (static). Up to DENSE_ROWS every
    held expert meets every row in one batched product and the rows' weights
    (0 where an expert was not chosen) close the sum. Past it the (row,
    expert) pairs are sorted by expert (pairs whose expert is held elsewhere
    sort behind every group), each expert's rows meet its matrices in one
    grouped product, and the result is unsorted by a gather and summed over
    ``top_k`` in the pair's own order. Either way the products read a row in
    the weights' dtype and sum in float32 (HIGHEST with float32 weights
    only), and nothing of a row's result depends on which other rows stand
    in the batch. Under ``<scope>_route`` stand the top-k, the weights, the
    sort and the counts, under ``<scope>_experts`` the products."""
    t = x.shape[0]
    count, _, f2 = w_in.shape
    f = f2 // 2
    # HIGHEST is for float32 weights (the tests' strict policy); the chip's
    # grouped kernel takes no precision with bfloat16 operands
    hi = lax.Precision.HIGHEST if w_in.dtype == jnp.float32 else None
    with jax.named_scope(scope + "_route"):
        topv, topi = lax.top_k(logits, top_k)                 # [T, k]
        gate = jax.nn.softmax(topv, axis=-1)
        local = topi - first
        held = (local >= 0) & (local < count)
        seen = held if live is None else held & live[:, None]
        # [T, count]: a row's weight for each held expert, 0 where not chosen
        onto = lambda v: jnp.sum(
            jnp.where(held[..., None], v[..., None], 0)
            * jax.nn.one_hot(local, count, dtype=v.dtype), axis=1)
        hit = jnp.sum(jnp.sum(onto(seen.astype(jnp.int32)), axis=0) > 0,
                      dtype=jnp.int32)
    if t <= DENSE_ROWS:
        with jax.named_scope(scope + "_route"):
            weight = onto(gate)                               # [T, count]
        with jax.named_scope(scope + "_experts"):
            gu = jnp.einsum("td,edf->etf", x.astype(w_in.dtype), w_in,
                            precision=hi,
                            preferred_element_type=jnp.float32)
            mid = act(gu[..., :f]) * gu[..., f:] * weight.T[:, :, None]
            y = jnp.einsum("etf,efd->td", mid.astype(w_down.dtype), w_down,
                           precision=hi, preferred_element_type=jnp.float32)
        return y, hit
    with jax.named_scope(scope + "_route"):
        key = jnp.where(held, local, count).reshape(-1)       # [T * k]
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
        back = jnp.argsort(order)          # where each pair's row went
    with jax.named_scope(scope + "_experts"):
        xs = x.astype(w_in.dtype)[order // top_k]             # [T * k, d]
        gu = lax.ragged_dot(xs, w_in, sizes, precision=hi,
                            preferred_element_type=jnp.float32)
        mid = act(gu[:, :f]) * gu[:, f:]
        out = lax.ragged_dot(mid.astype(w_down.dtype), w_down, sizes,
                             precision=hi,
                             preferred_element_type=jnp.float32)
        # a pair whose expert is held elsewhere lies behind the last group:
        # no group writes its rows of the products
        pair = jnp.where(held, gate, 0.0).reshape(-1)
        y = (jnp.where(held.reshape(-1)[order][:, None], out, 0.0)[back]
             * pair[:, None]).reshape(t, top_k, -1).sum(axis=1)
    return y, hit


def load_balancing_loss(x: jax.Array, Wg: jax.Array) -> jax.Array:
    """Auxiliary load-balance loss over raw activations (see
    aux_loss_from_gates). Add to the task loss with a small coefficient."""
    xt = x.reshape(-1, x.shape[-1])
    return aux_loss_from_gates(jax.nn.softmax(xt @ Wg, axis=-1))
