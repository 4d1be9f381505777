"""ComputationGraph — the DAG network container.

Functional re-design of the reference's ``ComputationGraph`` (2,025 LoC,
deeplearning4j-core/.../nn/graph/ComputationGraph.java):

  reference mechanism                          -> here
  -------------------------------------------------------------------------
  topologicalSortOrder() (:279,511-540)        -> conf.topological_order()
  feedForward in topo order (:958-1000)        -> _forward over activation dict
  computeGradientAndScore (:884-908), score =
    sum of output-layer scores (:894-907)      -> _loss sums per-output losses
  calcBackpropGradients (:1061)                -> jax autodiff
  fit(MultiDataSet) (:676)                     -> fit(inputs, labels)
  rnnTimeStep (:1601)                          -> rnn_time_step()
  vertex impls (nn/graph/vertex/impl/*)        -> pure jnp vertex functions

The whole step (all vertices forward + backward + updaters) compiles to ONE
XLA program — vertex boundaries vanish under fusion, so DAG generality has
no runtime cost vs the sequential container.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import layers as conf_layers
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration,
    DuplicateToTimeSeriesVertex,
    ElementWiseVertex,
    GraphVertex,
    LastTimeStepVertex,
    MergeVertex,
    PreprocessorVertex,
    ScaleVertex,
    SubsetVertex,
)
from deeplearning4j_tpu.nn.layers.factory import (
    RNN_CONFS,
    STATEFUL_RNN_CONFS,
    create_layer,
)
from deeplearning4j_tpu.nn.layers.feedforward import OutputLayerImpl
from deeplearning4j_tpu.ops import dispatch, lowprec, rng as rng_mod
from deeplearning4j_tpu.optimize.updaters import LayerUpdater, apply_updates

logger = logging.getLogger("deeplearning4j_tpu")

_REG_PARAM_NAMES = ("W", "U")


def _as_list(x) -> List:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class ComputationGraph:
    """DAG of layer vertices and combining vertices over named inputs."""

    def __init__(self, conf: ComputationGraphConfiguration):
        conf.validate()
        self.conf = conf
        self.topo = conf.topological_order()
        self.layer_names = [
            n for n in self.topo if isinstance(conf.vertices[n], conf_layers.Layer)
        ]
        self.layers: Dict[str, Any] = {
            n: create_layer(conf.vertices[n]) for n in self.layer_names
        }
        self.updaters: Dict[str, LayerUpdater] = {
            n: LayerUpdater(conf.vertices[n], conf) for n in self.layer_names
        }
        self.params: Optional[Dict[str, Any]] = None
        self.states: Optional[Dict[str, Any]] = None
        self.updater_state: Optional[Dict[str, Any]] = None
        self.iteration = 0
        self.listeners: List[Any] = []
        self._score_dev = None
        self._rng = rng_mod.key(conf.seed)
        self._jit_cache: Dict[Any, Any] = {}
        # bf16 loss-scaled training state (DL4J_TPU_BF16, ops/lowprec.py)
        self._loss_scale = None
        self._input_shapes: Optional[Dict[str, Tuple[int, ...]]] = None
        self.dispatch_stats = dispatch.DispatchStats()
        from deeplearning4j_tpu.ops.memory import MemoryStats

        # AOT memory ledger beside dispatch_stats (ops/memory.py) —
        # populated on demand via the instrumented jits' .measure_memory
        self.memory_stats = MemoryStats()
        # ingest telemetry (etl/stats.py), adopted by fit_iterator from a
        # staged iterator — see MultiLayerNetwork.pipeline_stats
        self.pipeline_stats = None
        # see MultiLayerNetwork: BN batch statistics would absorb pad rows
        self._bucketing_blocked = any(
            isinstance(v, conf_layers.BatchNormalization)
            for v in conf.vertices.values()
        )
        # True while fit_iterator drives fit() — bucketing's "auto" scope
        self._bucket_scope = False
        # ledgers join the central MetricsRegistry (see MultiLayerNetwork)
        from deeplearning4j_tpu.obs.registry import register_net

        register_net(self)

    # ------------------------------------------------------------------ init
    def _infer_input_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Infer per-input feature shapes from first consumer layer confs
        (dense/rnn only; CNN-fed inputs need explicit shapes)."""
        shapes: Dict[str, Tuple[int, ...]] = {}
        for inp in self.conf.inputs:
            for name, ins in self.conf.vertex_inputs.items():
                if inp in ins:
                    v = self.conf.vertices[name]
                    if isinstance(v, RNN_CONFS):
                        shapes[inp] = (-1, v.n_in)
                        break
                    if isinstance(v, conf_layers.ConvolutionLayer):
                        raise ValueError(
                            f"input '{inp}' feeds a CNN; pass explicit "
                            "input_shapes to init()"
                        )
                    if isinstance(v, conf_layers.FeedForwardLayer):
                        shapes[inp] = (v.n_in,)
                        break
            if inp not in shapes:
                raise ValueError(
                    f"cannot infer shape for input '{inp}'; pass input_shapes"
                )
        return shapes

    def init(
        self,
        input_shapes: Optional[
            Union[Dict[str, Sequence[int]], Sequence[Sequence[int]]]
        ] = None,
    ) -> "ComputationGraph":
        """Initialize params/states by propagating shapes in topological
        order (role of reference init() + shape validation)."""
        if input_shapes is None:
            shapes = self._infer_input_shapes()
        elif isinstance(input_shapes, dict):
            shapes = {k: tuple(v) for k, v in input_shapes.items()}
        else:
            shapes = {
                n: tuple(s) for n, s in zip(self.conf.inputs, input_shapes)
            }
        self._input_shapes = dict(shapes)
        vshape: Dict[str, Tuple[int, ...]] = dict(shapes)
        params: Dict[str, Any] = {}
        states: Dict[str, Any] = {}
        for i, name in enumerate(self.topo):
            v = self.conf.vertices[name]
            in_shapes = [vshape[i_] for i_ in self.conf.vertex_inputs[name]]
            if isinstance(v, conf_layers.Layer):
                shape = in_shapes[0]
                pp = self.conf.input_preprocessors.get(name)
                if pp is not None:
                    shape = pp.out_shape(shape)
                k = rng_mod.layer_key(self._rng, i, "init")
                p, s, out_shape = self.layers[name].initialize(k, shape)
                params[name] = p
                states[name] = s
                vshape[name] = tuple(out_shape)
            else:
                vshape[name] = self._vertex_out_shape(v, name, in_shapes)
        self.params = params
        self.states = states
        self.updater_state = {
            n: self.updaters[n].init(params[n]) for n in self.layer_names
        }
        return self

    def _vertex_out_shape(self, v: GraphVertex, name: str, in_shapes) -> Tuple[int, ...]:
        if isinstance(v, MergeVertex):
            base = list(in_shapes[0])
            base[-1] = sum(s[-1] for s in in_shapes)
            return tuple(base)
        if isinstance(v, (ElementWiseVertex, ScaleVertex)):
            return tuple(in_shapes[0])
        if isinstance(v, SubsetVertex):
            base = list(in_shapes[0])
            base[-1] = v.to_index - v.from_index + 1
            return tuple(base)
        if isinstance(v, PreprocessorVertex):
            return tuple(v.preprocessor.out_shape(tuple(in_shapes[0])))
        if isinstance(v, LastTimeStepVertex):
            return tuple(in_shapes[0][1:])  # drop time axis -> (F,)
        if isinstance(v, DuplicateToTimeSeriesVertex):
            ref_shape = None
            if v.reference_input in (self._input_shapes or {}):
                ref_shape = self._input_shapes[v.reference_input]
            t = ref_shape[0] if ref_shape and len(ref_shape) >= 2 else -1
            return (t,) + tuple(in_shapes[0])
        raise ValueError(f"unknown vertex type {type(v).__name__} for '{name}'")

    def num_params(self) -> int:
        return sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params)
        )

    # --------------------------------------------------------------- forward
    def _apply_vertex(self, v: GraphVertex, xs: List, inputs: Dict, masks: Dict):
        if isinstance(v, MergeVertex):
            return jnp.concatenate(xs, axis=-1)
        if isinstance(v, ElementWiseVertex):
            y = xs[0]
            if v.op == "add":
                for x in xs[1:]:
                    y = y + x
            elif v.op == "subtract":
                for x in xs[1:]:
                    y = y - x
            elif v.op == "product":
                for x in xs[1:]:
                    y = y * x
            elif v.op == "average":
                y = sum(xs) / float(len(xs))
            elif v.op == "max":
                for x in xs[1:]:
                    y = jnp.maximum(y, x)
            return y
        if isinstance(v, SubsetVertex):
            return xs[0][..., v.from_index : v.to_index + 1]
        if isinstance(v, ScaleVertex):
            return xs[0] * v.scale
        if isinstance(v, PreprocessorVertex):
            return v.preprocessor(xs[0])
        if isinstance(v, LastTimeStepVertex):
            x = xs[0]  # [B,T,F]
            mask = masks.get(v.mask_input) if v.mask_input else None
            if mask is None:
                return x[:, -1, :]
            # last unmasked step per example
            idx = jnp.maximum(
                jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0
            )  # [B]
            return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :]
        if isinstance(v, DuplicateToTimeSeriesVertex):
            t = inputs[v.reference_input].shape[1]
            return jnp.broadcast_to(
                xs[0][:, None, :], (xs[0].shape[0], t, xs[0].shape[1])
            )
        raise ValueError(f"unknown vertex type {type(v).__name__}")

    def _forward(
        self,
        params,
        states,
        inputs: Dict[str, jax.Array],
        *,
        train: bool,
        rng=None,
        masks: Optional[Dict[str, jax.Array]] = None,
        carry_state: bool = False,
        backprop_window: Optional[int] = None,
        remat_prevent_cse: bool = True,
    ):
        """Forward all vertices in topo order. Returns (activations dict
        name->array incl. inputs, new states dict).

        Mask propagation: a vertex inherits the mask of its first masked
        input; LastTimeStep drops it (time axis removed) — the simplified
        equivalent of the reference's setLayerMaskArrays flow."""
        from deeplearning4j_tpu.nn.common import apply_layer

        masks = dict(masks or {})
        acts: Dict[str, jax.Array] = dict(inputs)
        new_states = dict(states)
        for i, name in enumerate(self.topo):
            v = self.conf.vertices[name]
            ins = self.conf.vertex_inputs[name]
            xs = [acts[i_] for i_ in ins]
            in_mask = next((masks[i_] for i_ in ins if i_ in masks), None)
            if isinstance(v, conf_layers.Layer):
                x = xs[0]
                pp = self.conf.input_preprocessors.get(name)
                if pp is not None:
                    x = pp(x)
                lrng = (
                    rng_mod.layer_key(rng, i, "dropout") if rng is not None else None
                )
                layer = self.layers[name]
                lmask = in_mask if isinstance(v, RNN_CONFS) else None
                kwargs = {}
                if carry_state and isinstance(v, STATEFUL_RNN_CONFS):
                    kwargs["carry_state"] = True
                if backprop_window is not None and isinstance(
                    v, STATEFUL_RNN_CONFS
                ):
                    kwargs["backprop_window"] = backprop_window
                y, ns = apply_layer(
                    layer, self.conf, params[name], states[name], x, lrng,
                    lmask, kwargs, train=train,
                    remat_prevent_cse=remat_prevent_cse,
                )
                new_states[name] = ns
                if in_mask is not None:
                    masks[name] = in_mask
                acts[name] = y
            else:
                y = self._apply_vertex(v, xs, inputs, masks)
                if in_mask is not None and not isinstance(v, LastTimeStepVertex):
                    masks[name] = in_mask
                acts[name] = y
        return acts, new_states

    def _regularization_penalty(self, params):
        total = jnp.asarray(0.0, jnp.float32)
        for name in self.layer_names:
            lc = self.conf.vertices[name]
            l1 = lc.l1 or 0.0
            l2 = lc.l2 or 0.0
            if l1 == 0.0 and l2 == 0.0:
                continue
            for path, leaf in jax.tree_util.tree_leaves_with_path(params[name]):
                pname = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
                if pname in _REG_PARAM_NAMES:
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(jnp.square(leaf))
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(leaf))
        return total

    def _loss(
        self,
        params,
        states,
        inputs: Dict[str, jax.Array],
        labels: List[jax.Array],
        *,
        train,
        rng,
        masks=None,
        label_masks: Optional[List] = None,
        carry_state: bool = False,
        backprop_window: Optional[int] = None,
        remat_prevent_cse: bool = True,
    ):
        """Sum of output-layer losses (reference computeGradientAndScore
        :894-907 sums per-output scores) + regularization."""
        # run up to (but excluding) output vertices: we need preout for fused
        # softmax-xent. Simplest correct approach: full forward, then redo the
        # loss from each output layer's input activation. The XLA compiler
        # CSEs the duplicated matmul away.
        acts, new_states = self._forward(
            params,
            states,
            inputs,
            train=train,
            rng=rng,
            masks=masks,
            remat_prevent_cse=remat_prevent_cse,
            carry_state=carry_state,
            backprop_window=backprop_window,
        )
        # mask propagated to each output vertex's input (label-mask fallback,
        # mirroring MLN: lmask = label_mask if set else feature mask)
        from deeplearning4j_tpu.nn.common import cast_loss_input

        prop_masks = dict(masks or {})
        for name in self.topo:
            ins = self.conf.vertex_inputs[name]
            m = next((prop_masks[i_] for i_ in ins if i_ in prop_masks), None)
            if m is not None and not isinstance(
                self.conf.vertices[name], LastTimeStepVertex
            ):
                prop_masks[name] = m
        total = jnp.asarray(0.0, jnp.float32)
        for oi, oname in enumerate(self.conf.outputs):
            impl = self.layers[oname]
            if not isinstance(impl, OutputLayerImpl):
                raise ValueError(
                    f"output vertex '{oname}' is not an OutputLayer/RnnOutputLayer"
                )
            in_name = self.conf.vertex_inputs[oname][0]
            x = acts[in_name]
            pp = self.conf.input_preprocessors.get(oname)
            if pp is not None:
                x = pp(x)
            oconf = self.conf.vertices[oname]
            if train and (oconf.dropout or 0.0) > 0 and rng is not None:
                x = impl._dropout_in(
                    x,
                    train,
                    rng_mod.layer_key(rng, self.topo.index(oname), "dropout"),
                )
            lm = label_masks[oi] if label_masks else None
            if lm is None:
                lm = prop_masks.get(in_name)
            x = cast_loss_input(x)
            total = total + impl.loss(params[oname], x, labels[oi], lm)
        return total + self._regularization_penalty(params), new_states

    # ------------------------------------------------------------- jit cache
    def _update_all(self, grads, upd_state, params, iteration):
        updates, new_state = {}, {}
        for n in self.layer_names:
            if not grads[n]:
                updates[n] = grads[n]
                new_state[n] = upd_state[n]
                continue
            u, s = self.updaters[n].update(
                grads[n], upd_state[n], params[n], iteration
            )
            updates[n] = u
            new_state[n] = s
        return updates, new_state

    def _get_train_step(self, n_labels: int, has_label_masks: bool,
                        carry_state=False, backprop_window=None):
        lp = lowprec.train_policy()
        key = ("train_step", n_labels, has_label_masks, carry_state,
               backprop_window, lp)
        if key in self._jit_cache:
            return self._jit_cache[key]

        def train_step(
            params, states, upd_state, inputs, labels, iteration, rng, masks, label_masks
        ):
            def loss_fn(p):
                return self._loss(
                    p,
                    states,
                    inputs,
                    labels,
                    train=True,
                    rng=rng,
                    masks=masks,
                    label_masks=label_masks,
                    carry_state=carry_state,
                    backprop_window=backprop_window,
                )

            (loss, new_states), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params
            )
            updates, upd_state = self._update_all(grads, upd_state, params, iteration)
            params = apply_updates(params, updates, self.conf.minimize)
            return params, new_states, upd_state, loss

        if lp:
            return self._build_lowprec_step(key, carry_state, backprop_window)

        # donation contract as in MultiLayerNetwork._get_train_step: every
        # caller re-binds params/states/upd_state from the returned triple
        fn = dispatch.instrumented_jit(
            train_step, "train_step", self.dispatch_stats,
            donate=(0, 1, 2), step=True, mem_stats=self.memory_stats)
        self._jit_cache[key] = fn
        return fn

    def _ensure_loss_scale(self):
        if self._loss_scale is None:
            self._loss_scale = lowprec.init_scale_state()
        return self._loss_scale

    @property
    def loss_scale(self):
        """Host snapshot of the dynamic loss-scale state (None when bf16
        training never ran); syncs dispatch_stats.loss_scale_skips."""
        snap = lowprec.scale_snapshot(self._loss_scale)
        if snap is not None:
            self.dispatch_stats.loss_scale_skips = snap["skipped"]
        return snap

    def _build_lowprec_step(self, key, carry_state, backprop_window):
        """bf16 master-weight train step for the DAG container — same
        scaled-loss / unscale / halve-and-skip discipline as
        MultiLayerNetwork._build_lowprec_step (Micikevicius et al., ICLR
        2018); the inner jit takes + donates the loss-scale tree, the
        wrapper keeps the original 9-arg signature."""

        def lp_step(params, states, upd_state, ls, inputs, labels,
                    iteration, rng, masks, label_masks):
            scale = ls["scale"]

            def loss_fn(p):
                loss, new_states = self._loss(
                    lowprec.cast_tree(p),
                    states,
                    {k: lowprec.cast_array(v) for k, v in inputs.items()}
                    if isinstance(inputs, dict)
                    else lowprec.cast_array(inputs),
                    labels,
                    train=True,
                    rng=rng,
                    masks=masks,
                    label_masks=label_masks,
                    carry_state=carry_state,
                    backprop_window=backprop_window,
                )
                return loss.astype(jnp.float32) * scale, (loss, new_states)

            (_, (loss, new_states)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = lowprec.unscale(grads, scale)
            finite = lowprec.finite_tree(grads)
            updates, new_upd = self._update_all(
                grads, upd_state, params, iteration)
            new_params = apply_updates(params, updates, self.conf.minimize)
            params = lowprec.select_trees(finite, new_params, params)
            upd_state = lowprec.select_trees(finite, new_upd, upd_state)
            states = lowprec.select_trees(finite, new_states, states)
            ls = lowprec.advance_scale(ls, finite)
            return params, states, upd_state, ls, loss.astype(jnp.float32)

        inner = dispatch.instrumented_jit(
            lp_step, "train_step", self.dispatch_stats,
            donate=(0, 1, 2, 3), step=True, mem_stats=self.memory_stats)
        net = self

        def wrapper(params, states, upd_state, inputs, labels, iteration,
                    rng, masks, label_masks):
            ls = net._ensure_loss_scale()
            params, states, upd_state, ls, loss = inner(
                params, states, upd_state, ls, inputs, labels, iteration,
                rng, masks, label_masks)
            net._loss_scale = ls
            return params, states, upd_state, loss

        def measure_memory(params, states, upd_state, inputs, labels,
                           iteration, rng, masks, label_masks):
            return inner.measure_memory(
                params, states, upd_state, net._ensure_loss_scale(),
                inputs, labels, iteration, rng, masks, label_masks)

        wrapper.measure_memory = measure_memory
        wrapper.lowprec = True
        self._jit_cache[key] = wrapper
        return wrapper

    def _get_fit_batches_fn(self, n_labels: int):
        """K train steps fused into ONE lax.scan (see
        MultiLayerNetwork._get_fit_batches_fn). Mask-free path: masked
        multi-step training uses the per-step fit()."""
        lp = lowprec.train_policy()
        key = ("fit_batches", n_labels, lp)
        if key in self._jit_cache:
            return self._jit_cache[key]

        n_iters = max(1, self.conf.iterations)

        def one_iter(params, states, upd_state, xs_k, ys_k, it, rng):
            def loss_fn(p):
                return self._loss(
                    p, states, xs_k, ys_k, train=True,
                    rng=rng_mod.step_key(rng, it),
                    masks=None, label_masks=None,
                    remat_prevent_cse=False,  # scan boundary blocks CSE
                )

            (loss, states), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            updates, upd_state = self._update_all(
                grads, upd_state, params, it
            )
            params = apply_updates(params, updates, self.conf.minimize)
            return params, states, upd_state, loss

        def one_iter_lp(params, states, upd_state, ls, xs_k, ys_k, it, rng):
            # _build_lowprec_step discipline inlined into the scan body
            scale = ls["scale"]

            def loss_fn(p):
                loss, new_states = self._loss(
                    lowprec.cast_tree(p), states,
                    {k: lowprec.cast_array(v) for k, v in xs_k.items()}
                    if isinstance(xs_k, dict) else lowprec.cast_array(xs_k),
                    ys_k, train=True,
                    rng=rng_mod.step_key(rng, it),
                    masks=None, label_masks=None,
                    remat_prevent_cse=False,
                )
                return loss.astype(jnp.float32) * scale, (loss, new_states)

            (_, (loss, new_states)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = lowprec.unscale(grads, scale)
            finite = lowprec.finite_tree(grads)
            updates, new_upd = self._update_all(
                grads, upd_state, params, it)
            new_params = apply_updates(params, updates, self.conf.minimize)
            params = lowprec.select_trees(finite, new_params, params)
            upd_state = lowprec.select_trees(finite, new_upd, upd_state)
            states = lowprec.select_trees(finite, new_states, states)
            ls = lowprec.advance_scale(ls, finite)
            return params, states, upd_state, ls, loss.astype(jnp.float32)

        def scan_fn(params, states, upd_state, inputs, labels, it0, rng):
            def body(carry, inp):
                params, states, upd_state, it = carry
                xs_k, ys_k = inp

                iter_losses = []
                for _ in range(n_iters):  # conf.iterations, like fit()
                    params, states, upd_state, loss = one_iter(
                        params, states, upd_state, xs_k, ys_k, it, rng)
                    it = it + 1
                    iter_losses.append(loss)
                return (params, states, upd_state, it), jnp.stack(iter_losses)

            (params, states, upd_state, _), losses = jax.lax.scan(
                body, (params, states, upd_state, it0), (inputs, labels)
            )
            return params, states, upd_state, losses.reshape(-1)

        if lp:
            def lp_scan_fn(params, states, upd_state, ls, inputs, labels,
                           it0, rng):
                def body(carry, inp):
                    params, states, upd_state, ls, it = carry
                    xs_k, ys_k = inp
                    iter_losses = []
                    for _ in range(n_iters):
                        params, states, upd_state, ls, loss = one_iter_lp(
                            params, states, upd_state, ls, xs_k, ys_k, it,
                            rng)
                        it = it + 1
                        iter_losses.append(loss)
                    return ((params, states, upd_state, ls, it),
                            jnp.stack(iter_losses))

                (params, states, upd_state, ls, _), losses = jax.lax.scan(
                    body, (params, states, upd_state, ls, it0),
                    (inputs, labels)
                )
                return params, states, upd_state, ls, losses.reshape(-1)

            inner = dispatch.instrumented_jit(
                lp_scan_fn, "fit_batches", self.dispatch_stats,
                donate=(0, 1, 2, 3), step=True,
                mem_stats=self.memory_stats)
            net = self

            def wrapper(params, states, upd_state, inputs, labels, it0,
                        rng):
                ls = net._ensure_loss_scale()
                params, states, upd_state, ls, losses = inner(
                    params, states, upd_state, ls, inputs, labels, it0,
                    rng)
                net._loss_scale = ls
                return params, states, upd_state, losses

            wrapper.lowprec = True
            self._jit_cache[key] = wrapper
            return wrapper

        fn = dispatch.instrumented_jit(
            scan_fn, "fit_batches", self.dispatch_stats,
            donate=(0, 1, 2), step=True, mem_stats=self.memory_stats)
        self._jit_cache[key] = fn
        return fn

    def _has_scanned_conv(self) -> bool:
        return any(isinstance(v, (conf_layers.ConvolutionLayer,
                                  conf_layers.SubsamplingLayer))
                   for v in self.conf.vertices.values())

    def _fit_batches_fallback(self, features, labels):
        """Per-step drain under the fusion policy (dispatch.fusion_enabled:
        XLA:CPU compiles scan-of-conv far slower than the per-step program);
        recorded in dispatch_stats.fused_fallbacks, DL4J_TPU_FUSE=force
        overrides. Same contract as MultiLayerNetwork's fallback."""
        from deeplearning4j_tpu.optimize.listeners import (
            CollectScoresIterationListener,
        )

        self.dispatch_stats.fused_fallbacks += 1
        feats = [jnp.asarray(f) for f in _as_list(features)]
        labs = [jnp.asarray(l) for l in _as_list(labels)]
        col = CollectScoresIterationListener(frequency=1)
        self.listeners.append(col)
        try:
            for k in range(feats[0].shape[0]):
                self.fit([f[k] for f in feats], [l[k] for l in labs])
        finally:
            self.listeners.remove(col)
        return np.asarray([s for _, s in col.scores], np.float32)

    def fit_batches(self, features, labels):
        """Fit each leading-axis slice ([K, N, ...]) inside a single
        compiled scan — K MultiDataSet fits (each with ``conf.iterations``
        optimizer iterations) without K host round-trips. Returns
        per-iteration losses [K*iterations]. SGD, non-TBPTT, mask-free
        path (same contract as MultiLayerNetwork.fit_batches)."""
        if self.params is None:
            self.init()
        if self.conf.backprop_type == "truncated_bptt":
            raise ValueError("fit_batches: use fit() for TBPTT training")
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            raise ValueError("fit_batches supports SGD-family training only")
        inputs = self._as_inputs(features)  # validates the input count
        labels_l = [jnp.asarray(l) for l in _as_list(labels)]
        if len(labels_l) != len(self.conf.outputs):
            raise ValueError(
                f"expected {len(self.conf.outputs)} label arrays, got {len(labels_l)}"
            )
        if not dispatch.fusion_enabled(scanned_conv=self._has_scanned_conv()):
            return self._fit_batches_fallback(features, labels)
        fn = self._get_fit_batches_fn(len(labels_l))
        self.params, self.states, self.updater_state, losses = fn(
            self.params, self.states, self.updater_state,
            inputs, labels_l,
            jnp.asarray(self.iteration, jnp.int32), self._rng,
        )
        self._score_dev = losses[-1]
        losses_np = np.asarray(losses)  # ONE bulk readback
        for k in range(losses_np.shape[0]):
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, float(losses_np[k]))
            self.iteration += 1
        return losses_np

    # ------------------------------------------------------------------- fit
    @property
    def score_value(self) -> float:
        return float("nan") if self._score_dev is None else float(self._score_dev)

    def _record_iteration(self, loss):
        self._score_dev = loss
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, float(loss))
        self.iteration += 1

    def _as_inputs(self, features) -> Dict[str, jax.Array]:
        feats = _as_list(features)
        if len(feats) != len(self.conf.inputs):
            raise ValueError(
                f"expected {len(self.conf.inputs)} inputs, got {len(feats)}"
            )
        return {n: jnp.asarray(f) for n, f in zip(self.conf.inputs, feats)}

    def fit(
        self, features, labels, masks=None, label_masks=None
    ) -> float:
        """One MultiDataSet fit (reference fit(MultiDataSet) :676).
        `features`/`labels`: array or list-of-arrays matching conf
        inputs/outputs order."""
        if self.params is None:
            self.init()
        inputs = self._as_inputs(features)
        labels_l = [jnp.asarray(l) for l in _as_list(labels)]
        if len(labels_l) != len(self.conf.outputs):
            raise ValueError(
                f"expected {len(self.conf.outputs)} label arrays, got {len(labels_l)}"
            )
        masks_d = self._as_masks(masks)
        lmasks = (
            [None if m is None else jnp.asarray(m) for m in _as_list(label_masks)]
            if label_masks is not None
            else None
        )
        if self.conf.backprop_type == "truncated_bptt":
            # before solver dispatch, same precedence as MultiLayerNetwork.fit
            return self._fit_tbptt(inputs, labels_l, masks_d, lmasks)
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            from deeplearning4j_tpu.optimize.solvers import Solver

            return Solver(self).optimize_graph(inputs, labels_l, masks_d, lmasks)
        inputs, labels_l, masks_d, lmasks = self._bucket_batch(
            inputs, labels_l, masks_d, lmasks
        )
        step = self._get_train_step(len(labels_l), lmasks is not None)
        loss = None
        for _ in range(max(1, self.conf.iterations)):
            srng = rng_mod.step_key(self._rng, self.iteration)
            self.params, self.states, self.updater_state, loss = step(
                self.params,
                self.states,
                self.updater_state,
                inputs,
                labels_l,
                jnp.asarray(self.iteration, jnp.int32),
                srng,
                masks_d,
                lmasks,
            )
            self._record_iteration(loss)
        return loss

    def _bucket_batch(self, inputs, labels_l, masks_d, lmasks):
        """Shape bucketing for the DAG container (see
        MultiLayerNetwork._bucket_batch): every input/label/mask is padded
        along the example axis up to dispatch.bucket_size, and each output
        gets a label mask that zeroes the pad rows out of its loss.

        Skipped when feature masks are present without a full set of
        explicit label masks: such outputs take their loss mask from
        _loss's mask PROPAGATION, and whether the propagated mask reaches a
        given output is a graph property this hook cannot cheaply verify —
        an unmasked padded output would divide by the padded row count.
        (The MLN container has no such ambiguity: its single output always
        falls back to the feature mask directly.)"""
        mode = dispatch.bucketing_mode()
        if (mode == "off" or (mode == "auto" and not self._bucket_scope)
                or self._bucketing_blocked):
            return inputs, labels_l, masks_d, lmasks
        explicit = (lmasks is not None
                    and all(m is not None for m in lmasks))
        if masks_d and not explicit:
            return inputs, labels_l, masks_d, lmasks
        n = next(iter(inputs.values())).shape[0]
        target = dispatch.bucket_size(n)
        if target != n:
            ik, mk = list(inputs), list(masks_d)
            padded = dispatch.pad_rows(
                self.dispatch_stats, target,
                [inputs[k] for k in ik] + labels_l + [masks_d[k] for k in mk],
            )
            inputs = dict(zip(ik, padded[:len(ik)]))
            labels_l = padded[len(ik):len(ik) + len(labels_l)]
            masks_d = dict(zip(mk, padded[len(ik) + len(labels_l):]))
        new_lmasks = []
        for oi, labels in enumerate(labels_l):
            lm = lmasks[oi] if lmasks is not None else None
            if lm is not None:
                lm = dispatch.pad_axis0(lm, target)
            else:
                # row-validity mask: all-ones for an exact-bucket batch, so
                # every bucket shares one jit signature (see MLN hook)
                lm = dispatch.row_validity_mask(
                    n, target,
                    labels.shape[1] if labels.ndim == 3 else None,
                )
            new_lmasks.append(lm)
        return inputs, labels_l, masks_d, new_lmasks

    def _reset_rnn_states(self, batch_n: int) -> None:
        """Zero recurrent state sized for this batch (sequence start — the
        graph analog of MLN's reset before doTruncatedBPTT :1162)."""
        for n in self.layer_names:
            lc = self.conf.vertices[n]
            if isinstance(lc, STATEFUL_RNN_CONFS):
                self.states[n] = {
                    k: jnp.zeros((batch_n, lc.n_out), jnp.float32)
                    for k in self.states[n]
                }

    def _fit_tbptt(self, inputs, labels_l, masks_d, lmasks,
                   state_placer=None) -> float:
        """Truncated BPTT over a DAG (reference ComputationGraph supports
        BackpropType.TruncatedBPTT the same way MLN does :1162-1233): slice
        the time axis into fwd-length windows, carry recurrent state across
        windows (stop-gradient at the boundary — state enters the next jitted
        step as data).

        A shorter tbptt_back_length truncates the backward pass inside each
        window via stop-gradient segments (reference
        LSTMHelpers.backpropGradientHelper:255)."""
        seq_inputs = {k: v for k, v in inputs.items() if v.ndim == 3}
        if not seq_inputs:
            raise ValueError(
                "backprop_type='truncated_bptt' requires at least one "
                "time-series ([B,T,F]) input"
            )
        first_seq = next(iter(seq_inputs.values()))
        t_total = first_seq.shape[1]
        w = self.conf.tbptt_fwd_length
        batch_n = first_seq.shape[0]
        self._reset_rnn_states(batch_n)
        if state_placer is not None:
            # DP path: place the freshly reset stream state on the mesh's
            # data axis before the first window step (avoids a replicated
            # full-batch state + GSPMD reshard)
            state_placer()
        from deeplearning4j_tpu.nn.common import tbptt_backprop_window

        bw = tbptt_backprop_window(self.conf)
        step = self._get_train_step(
            len(labels_l), lmasks is not None, carry_state=True,
            backprop_window=bw,
        )
        loss = float("nan")
        for window_start in range(0, t_total, w):
            sl = slice(window_start, min(window_start + w, t_total))
            in_w = {k: v[:, sl] if v.ndim == 3 else v for k, v in inputs.items()}
            lb_w = [l[:, sl] if l.ndim == 3 else l for l in labels_l]
            # slice a mask only when it spans the time axis (same guard the
            # labels/inputs get: per-example 2D masks pass through whole)
            mk_w = (
                {
                    k: (m[:, sl] if m.ndim >= 2 and m.shape[1] == t_total else m)
                    for k, m in masks_d.items()
                }
                if masks_d
                else masks_d
            )
            lm_w = (
                [
                    m[:, sl]
                    if m is not None and labels_l[i].ndim == 3
                    else m
                    for i, m in enumerate(lmasks)
                ]
                if lmasks
                else lmasks
            )
            srng = rng_mod.step_key(self._rng, self.iteration)
            self.params, self.states, self.updater_state, loss = step(
                self.params,
                self.states,
                self.updater_state,
                in_w,
                lb_w,
                jnp.asarray(self.iteration, jnp.int32),
                srng,
                mk_w,
                lm_w,
            )
            self._record_iteration(loss)
        return loss

    def fit_iterator(self, iterator, num_epochs: int = 1,
                     fused_batches: int = 1) -> "ComputationGraph":
        """fit over a MultiDataSetIterator (or DataSetIterator for
        single-input/single-output graphs).

        fused_batches=K > 1: stack K consecutive same-shape mask-free
        DataSets/MultiDataSets through fit_batches (one XLA program per K
        optimizer steps — MultiLayerNetwork.fit_iterator's fused path for
        the DAG container). Per-step fallback for masks, shape changes,
        ragged tails, TBPTT and non-SGD solvers.

        Input staging: DL4J_TPU_PIPELINE_WORKERS wraps a plain iterator
        in etl/pipeline.InputPipeline and the staged iterator's telemetry
        is adopted as ``net.pipeline_stats`` (see MultiLayerNetwork)."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.etl.pipeline import maybe_wrap

        iterator = maybe_wrap(iterator)
        if getattr(iterator, "pipeline_stats", None) is not None:
            self.pipeline_stats = iterator.pipeline_stats
            from deeplearning4j_tpu.obs.registry import register_net

            register_net(self)  # the freshly adopted ingest ledger
        fused = (fused_batches > 1
                 and self.conf.backprop_type != "truncated_bptt"
                 and self.conf.optimization_algo
                 == "stochastic_gradient_descent")
        from deeplearning4j_tpu.nn.common import fused_iterator_loop

        # bucketing's "auto" scope (see MultiLayerNetwork.fit_iterator)
        self._bucket_scope = True
        try:
            for _ in range(num_epochs):
                if not fused:
                    for ds in iterator:
                        self._fit_ds(ds)
                else:
                    fused_iterator_loop(
                        iterator, fused_batches,
                        can_stack=self._graph_stackable,  # fit_batches: no masks
                        same_shape=self._same_shapes,
                        fit_one=self._fit_ds,
                        fit_fused=self._fit_fused_graph,
                    )
                if hasattr(iterator, "reset"):
                    iterator.reset()
        finally:
            self._bucket_scope = False
        return self

    @staticmethod
    def _components(ds):
        """(features_list, labels_list, has_masks) for either container."""
        if hasattr(ds, "features_list"):  # MultiDataSet
            masks = any(m is not None for m in (ds.features_masks or [])) \
                or any(m is not None for m in (ds.labels_masks or []))
            return list(ds.features_list), list(ds.labels_list), masks
        return ([ds.features], [ds.labels],
                ds.features_mask is not None or ds.labels_mask is not None)

    def _graph_stackable(self, ds) -> bool:
        return not self._components(ds)[2]  # fit_batches is mask-free

    def _same_shapes(self, a, b) -> bool:
        fa, la, _ = self._components(a)
        fb, lb, _ = self._components(b)
        return (
            len(fa) == len(fb) and len(la) == len(lb)
            and all(np.asarray(x).shape == np.asarray(y).shape
                    for x, y in zip(fa + la, fb + lb))
        )

    def _fit_ds(self, ds) -> None:
        if hasattr(ds, "features_list"):  # MultiDataSet
            self.fit(ds.features_list, ds.labels_list, ds.features_masks,
                     ds.labels_masks)
        else:
            self.fit(ds.features, ds.labels, ds.features_mask,
                     ds.labels_mask)

    def _fit_fused_graph(self, buf) -> None:
        feats0, labs0, _ = self._components(buf[0])
        comps = [self._components(d) for d in buf]
        feats = [np.stack([np.asarray(c[0][i]) for c in comps])
                 for i in range(len(feats0))]
        labs = [np.stack([np.asarray(c[1][i]) for c in comps])
                for i in range(len(labs0))]
        self.fit_batches(feats, labs)

    # ------------------------------------------------------------- inference
    def _get_output_fn(self):
        key = "output"
        if key not in self._jit_cache:

            def out_fn(params, states, inputs):
                acts, _ = self._forward(params, states, inputs, train=False)
                return [acts[o] for o in self.conf.outputs]

            self._jit_cache[key] = dispatch.instrumented_jit(
                out_fn, "output", self.dispatch_stats,
                mem_stats=self.memory_stats)
        return self._jit_cache[key]

    def output(self, *features) -> List[jax.Array]:
        """Inference outputs in conf.outputs order (reference output()/
        feedForward). Ragged batches are bucket-padded and sliced back —
        inference-mode padding is unconditionally safe (BN running stats,
        no dropout), so arbitrary batch sizes compile O(log n) programs."""
        if self.params is None:
            self.init()
        if len(features) == 1 and isinstance(features[0], (list, tuple)):
            features = tuple(features[0])
        inputs = self._as_inputs(list(features))
        n = next(iter(inputs.values())).shape[0]
        target = dispatch.inference_bucket(self.dispatch_stats, n)
        if target is not None:
            inputs = {k: dispatch.pad_axis0(v, target)
                      for k, v in inputs.items()}
            outs = self._get_output_fn()(self.params, self.states, inputs)
            return [o[:n] for o in outs]
        return self._get_output_fn()(self.params, self.states, inputs)

    def feed_forward(self, *features) -> Dict[str, jax.Array]:
        """All vertex activations by name (reference feedForward map)."""
        if self.params is None:
            self.init()
        inputs = self._as_inputs(list(features))
        acts, _ = self._forward(self.params, self.states, inputs, train=False)
        return acts

    def _as_masks(self, masks) -> Dict[str, jax.Array]:
        """Normalize a masks argument (dict by input name, or list in conf
        input order) to the name-keyed dict _forward expects."""
        if masks is None:
            return {}
        if isinstance(masks, dict):
            return {k: jnp.asarray(m) for k, m in masks.items() if m is not None}
        return {
            n: jnp.asarray(m)
            for n, m in zip(self.conf.inputs, _as_list(masks))
            if m is not None
        }

    def _get_score_fn(self, n_labels: int, has_label_masks: bool):
        key = ("score", n_labels, has_label_masks)
        if key not in self._jit_cache:

            def score_fn(params, states, inputs, labels, masks, label_masks):
                loss, _ = self._loss(
                    params,
                    states,
                    inputs,
                    labels,
                    train=False,
                    rng=None,
                    masks=masks,
                    label_masks=label_masks,
                )
                return loss

            self._jit_cache[key] = dispatch.instrumented_jit(
                score_fn, "score", self.dispatch_stats)
        return self._jit_cache[key]

    def score(self, features, labels, masks=None, label_masks=None) -> float:
        if self.params is None:
            self.init()
        inputs = self._as_inputs(features)
        labels_l = [jnp.asarray(l) for l in _as_list(labels)]
        lmasks = (
            [None if m is None else jnp.asarray(m) for m in _as_list(label_masks)]
            if label_masks is not None
            else None
        )
        fn = self._get_score_fn(len(labels_l), lmasks is not None)
        loss = fn(
            self.params,
            self.states,
            inputs,
            labels_l,
            self._as_masks(masks),
            lmasks,
        )
        return float(loss)

    def evaluate(self, iterator):
        """Classification evaluation on the FIRST output (reference
        evaluate(DataSetIterator))."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation()
        for ds in iterator:
            feats = getattr(ds, "features_list", None) or ds.features
            labels = getattr(ds, "labels_list", None) or ds.labels
            out = self.output(*_as_list(feats))[0]
            first_labels = _as_list(labels)[0]
            ev.eval(np.asarray(first_labels), np.asarray(out))
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    # ------------------------------------------------------- rnn streaming
    def rnn_clear_previous_state(self):
        for n in self.layer_names:
            if isinstance(self.conf.vertices[n], STATEFUL_RNN_CONFS):
                self.states[n] = {
                    k: jnp.zeros_like(v) for k, v in self.states[n].items()
                }

    def rnn_time_step(self, *features) -> List[jax.Array]:
        """Single-step stateful inference (reference rnnTimeStep :1601):
        feeds one timestep, carries recurrent state across calls."""
        if len(features) == 1 and isinstance(features[0], (list, tuple)):
            features = tuple(features[0])
        feats = []
        for f in features:
            f = jnp.asarray(f)
            if f.ndim == 2:
                f = f[:, None, :]  # [B,F] -> [B,1,F]
            feats.append(f)
        inputs = self._as_inputs(feats)
        batch_n = feats[0].shape[0]
        # size/reset states lazily for this batch
        for n in self.layer_names:
            lc = self.conf.vertices[n]
            if isinstance(lc, STATEFUL_RNN_CONFS):
                st = self.states[n]
                if not st or next(iter(st.values())).shape[0] != batch_n:
                    self.states[n] = {
                        k: jnp.zeros((batch_n, lc.n_out), jnp.float32)
                        for k in (st or {"h": None, "c": None})
                    }
        key = ("rnn_step",)
        if key not in self._jit_cache:

            def step_fn(params, states, inputs):
                acts, new_states = self._forward(
                    params, states, inputs, train=False, carry_state=True
                )
                outs = [acts[o] for o in self.conf.outputs]
                return [
                    o[:, -1, :] if o.ndim == 3 else o for o in outs
                ], new_states

            self._jit_cache[key] = dispatch.instrumented_jit(
                step_fn, "rnn_step", self.dispatch_stats)
        outs, self.states = self._jit_cache[key](
            self.params, self.states, inputs
        )
        return outs

    def apply_lr_score_decay(self) -> None:
        """See MultiLayerNetwork.apply_lr_score_decay (reference
        Model.applyLearningRateScoreDecay for the 'score' LR policy)."""
        from deeplearning4j_tpu.nn.common import decay_lr_scale_entry

        rate = getattr(self.conf, "lr_policy_decay_rate", None)
        if rate is None:
            return
        self.updater_state = {
            n: decay_lr_scale_entry(s, rate)
            for n, s in self.updater_state.items()
        }

    def training_state(self) -> Dict[str, Any]:
        """Exact-resume extras (see MultiLayerNetwork.training_state —
        same contract for the DAG container, loss-scale state included)."""
        st = {
            "iteration": int(self.iteration),
            "rng": np.asarray(self._rng, np.uint32).tolist(),
        }
        snap = self.loss_scale  # property: also syncs loss_scale_skips
        if snap is not None:
            st["loss_scale"] = snap
        return st

    def restore_training_state(self, st: Dict[str, Any]) -> None:
        if st.get("iteration") is not None:
            self.iteration = int(st["iteration"])
        if st.get("rng") is not None:
            self._rng = jnp.asarray(np.asarray(st["rng"], dtype=np.uint32))
        if st.get("loss_scale") is not None:
            self._loss_scale = lowprec.scale_from_snapshot(st["loss_scale"])

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def clone(self) -> "ComputationGraph":
        other = ComputationGraph(self.conf)
        if self.params is not None:
            # real copies (not leaf-sharing): donation would delete shared
            # leaves on the original's next train step
            other.params = jax.tree_util.tree_map(jnp.copy, self.params)
            other.states = jax.tree_util.tree_map(jnp.copy, self.states)
            other.updater_state = jax.tree_util.tree_map(
                jnp.copy, self.updater_state
            )
            other._input_shapes = dict(self._input_shapes or {})
        other.iteration = self.iteration
        return other
