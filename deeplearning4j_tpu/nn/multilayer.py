"""MultiLayerNetwork — the sequential network container.

Functional re-design of the reference's ``MultiLayerNetwork`` (2,372 LoC,
deeplearning4j-core/.../nn/multilayer/MultiLayerNetwork.java):

  reference mechanism                        -> here
  -----------------------------------------------------------------------
  init() flat param view array (:349-440)    -> list-of-dicts param pytree
  computeGradientAndScore (:1786)            -> jax.value_and_grad of _loss
  backprop()/calcBackpropGradients (:1071)   -> autodiff (no hand backward)
  Solver/StochasticGradientDescent iteration -> ONE jitted train_step:
                                                forward+backward+updater+step
                                                compiled to a single XLA program
  fit(DataSetIterator) (:1017)               -> fit / fit_iterator
  pretrain() layerwise RBM/AE (:165-213)     -> pretrain()
  output()/feedForward (:619-704)            -> output()
  evaluate (:2316)                           -> evaluate()
  rnnTimeStep (:2152)                        -> rnn_time_step()  [stateful]
  setLayerMaskArrays (:1053)                 -> mask/label_mask arguments
  doTruncatedBPTT (:1162)                    -> fit with tbptt window slicing

The whole-step jit is the single biggest architectural win over the
reference's op-by-op dispatch (SURVEY.md section 7 "Architectural
translations").
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import layers as conf_layers
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.factory import (
    CNN_CONFS,
    RNN_CONFS,
    STATEFUL_RNN_CONFS,
    create_layer,
)
from deeplearning4j_tpu.nn.layers.feedforward import (
    AutoEncoderImpl,
    OutputLayerImpl,
    RBMImpl,
)
from deeplearning4j_tpu.ops import dispatch, lowprec, rng as rng_mod
from deeplearning4j_tpu.optimize.updaters import MultiLayerUpdater, apply_updates

logger = logging.getLogger("deeplearning4j_tpu")

# param leaf names regularized by l1/l2 (weights + recurrent weights, never
# biases — reference BaseLayer.calcL1/calcL2)
_REG_PARAM_NAMES = ("W", "U")


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = [create_layer(lc) for lc in conf.layers]
        self.updater = MultiLayerUpdater(conf.layers, conf)
        self.params: Optional[List[Dict[str, Any]]] = None
        self.states: Optional[List[Dict[str, Any]]] = None
        self.updater_state = None
        self.iteration = 0
        self.listeners = []
        self._score_dev = None  # device array; fetched lazily via score_value
        self._rng = rng_mod.key(conf.seed)
        self._jit_cache: Dict[Any, Any] = {}
        self._input_shape: Optional[Tuple[int, ...]] = None
        # bf16 loss-scaled training (DL4J_TPU_BF16, ops/lowprec.py):
        # device-side {scale, good, skipped} tree, created lazily by the
        # first lp train step and snapshotted through training_state()
        self._loss_scale = None
        self.dispatch_stats = dispatch.DispatchStats()
        from deeplearning4j_tpu.ops.memory import MemoryStats

        # AOT memory ledger beside dispatch_stats (ops/memory.py) —
        # populated on demand via measure_memory / .measure_memory on the
        # instrumented jits, never implicitly on the hot path
        self.memory_stats = MemoryStats()
        # ingest telemetry beside dispatch/memory stats (etl/stats.py):
        # adopted from the staged iterator the last fit_iterator consumed
        # (InputPipeline / AsyncDataSetIterator); None for direct fits
        self.pipeline_stats = None
        # batch-statistics layers make shape bucketing unsound in training:
        # the pad rows would enter the BN batch mean/var (loss masking
        # cannot undo that), so fit() skips bucketing for these nets
        self._bucketing_blocked = any(
            isinstance(lc, conf_layers.BatchNormalization)
            for lc in conf.layers
        )
        # True while fit_iterator drives fit(): the scope where bucketing's
        # "auto" mode applies (dispatch.bucketing_mode)
        self._bucket_scope = False
        # every *_stats ledger above joins the central MetricsRegistry
        # (obs/registry.py) — one Prometheus scrape covers them all; the
        # attach points for later ledgers (pipeline_stats adoption,
        # ResilientTrainer/fleet resilience_stats) re-register
        from deeplearning4j_tpu.obs.registry import register_net

        register_net(self)

    # ------------------------------------------------------------------ init
    def _infer_input_shape(self) -> Tuple[int, ...]:
        l0 = self.conf.layers[0]
        if isinstance(l0, RNN_CONFS):
            return (-1, l0.n_in)
        if isinstance(l0, conf_layers.ConvolutionLayer):
            raise ValueError(
                "CNN-first networks need an explicit input_shape=(h, w, c) "
                "(reference requires the same via ConvolutionLayerSetup)"
            )
        if isinstance(l0, conf_layers.FeedForwardLayer):
            return (l0.n_in,)
        raise ValueError(
            f"cannot infer input shape from first layer {type(l0).__name__}; "
            "pass input_shape to init()"
        )

    def init(self, input_shape: Optional[Sequence[int]] = None) -> "MultiLayerNetwork":
        """Initialize params/state, inferring per-layer shapes through the
        stack (role of reference init() :349-440 + ConvolutionLayerSetup)."""
        shape = tuple(input_shape) if input_shape else self._infer_input_shape()
        self._input_shape = shape
        params, states = [], []
        for i, layer in enumerate(self.layers):
            pp = self.conf.input_preprocessors.get(i)
            if pp is not None:
                shape = pp.out_shape(shape)
            k = rng_mod.layer_key(self._rng, i, "init")
            p, s, shape = layer.initialize(k, shape)
            params.append(p)
            states.append(s)
        self.params = params
        self.states = states
        self.updater_state = self.updater.init(params)
        return self

    def num_params(self) -> int:
        return sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params)
        )

    # --------------------------------------------------------------- forward
    def _apply_preprocessor(self, i, x, batch_n):
        pp = self.conf.input_preprocessors.get(i)
        if pp is None:
            return x
        from deeplearning4j_tpu.nn.conf.preprocessors import (
            CnnToRnnPreProcessor,
            FeedForwardToRnnPreProcessor,
        )

        if isinstance(pp, (FeedForwardToRnnPreProcessor, CnnToRnnPreProcessor)):
            return pp(x, time_steps=x.shape[0] // batch_n)
        return pp(x)

    def _forward(
        self,
        params,
        states,
        x,
        *,
        train: bool,
        rng=None,
        mask=None,
        upto: Optional[int] = None,
        carry_state: bool = False,
        backprop_window: Optional[int] = None,
        remat_prevent_cse: bool = True,
    ):
        """Forward through layers [0, upto). Returns (activations list incl.
        input, new_states). Mask is passed to recurrent-family layers only.
        carry_state=True resumes recurrent layers from their stored state
        (TBPTT window chaining). backprop_window truncates each recurrent
        layer's in-window backward pass (distinct tbptt_back_length,
        reference LSTMHelpers.backpropGradientHelper:255)."""
        from deeplearning4j_tpu.nn.common import apply_layer

        n_layers = len(self.layers) if upto is None else upto
        batch_n = x.shape[0]
        acts = [x]
        new_states = list(states)
        for i in range(n_layers):
            layer = self.layers[i]
            x = self._apply_preprocessor(i, x, batch_n)
            lrng = (
                rng_mod.layer_key(rng, i, "dropout") if rng is not None else None
            )
            lmask = mask if isinstance(self.conf.layers[i], RNN_CONFS) else None
            kwargs = {}
            if carry_state and isinstance(self.conf.layers[i], STATEFUL_RNN_CONFS):
                kwargs["carry_state"] = True
            if backprop_window is not None and isinstance(
                self.conf.layers[i], STATEFUL_RNN_CONFS
            ):
                kwargs["backprop_window"] = backprop_window
            y, ns = apply_layer(
                layer, self.conf, params[i], states[i], x, lrng, lmask,
                kwargs, train=train, remat_prevent_cse=remat_prevent_cse,
            )
            new_states[i] = ns
            acts.append(y)
            x = y
        return acts, new_states

    def _regularization_penalty(self, params):
        """0.5*l2*|W|^2 + l1*|W|_1 summed over layers (weights only)."""
        total = jnp.asarray(0.0, jnp.float32)
        for lc, p in zip(self.conf.layers, params):
            l1 = lc.l1 or 0.0
            l2 = lc.l2 or 0.0
            if l1 == 0.0 and l2 == 0.0:
                continue

            def visit(path, leaf, acc):
                name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
                if name in _REG_PARAM_NAMES:
                    if l2:
                        acc = acc + 0.5 * l2 * jnp.sum(jnp.square(leaf))
                    if l1:
                        acc = acc + l1 * jnp.sum(jnp.abs(leaf))
                return acc

            leaves = jax.tree_util.tree_leaves_with_path(p)
            for path, leaf in leaves:
                total = visit(path, leaf, total)
        return total

    def _loss(
        self,
        params,
        states,
        x,
        labels,
        *,
        train,
        rng,
        mask=None,
        label_mask=None,
        carry_state: bool = False,
        backprop_window: Optional[int] = None,
        remat_prevent_cse: bool = True,
    ):
        out_impl = self.layers[-1]
        if not isinstance(out_impl, OutputLayerImpl):
            raise ValueError("last layer must be an OutputLayer/RnnOutputLayer")
        acts, new_states = self._forward(
            params,
            states,
            x,
            train=train,
            rng=rng,
            mask=mask,
            upto=len(self.layers) - 1,
            carry_state=carry_state,
            backprop_window=backprop_window,
            remat_prevent_cse=remat_prevent_cse,
        )
        last_in = self._apply_preprocessor(
            len(self.layers) - 1, acts[-1], x.shape[0]
        )
        from deeplearning4j_tpu.nn.common import cast_loss_input

        last_in = cast_loss_input(last_in)
        if train and (self.conf.layers[-1].dropout or 0.0) > 0 and rng is not None:
            last_in = out_impl._dropout_in(
                last_in, train, rng_mod.layer_key(rng, len(self.layers) - 1, "dropout")
            )
        lmask = label_mask if label_mask is not None else mask
        loss = out_impl.loss(params[-1], last_in, labels, lmask)
        return loss + self._regularization_penalty(params), new_states

    # ------------------------------------------------------------- jit cache
    def _get_train_step(
        self,
        has_mask: bool,
        has_label_mask: bool,
        carry_state: bool = False,
        backprop_window: Optional[int] = None,
    ):
        lp = lowprec.train_policy()
        key = ("train_step", has_mask, has_label_mask, carry_state,
               backprop_window, lp)
        if key in self._jit_cache:
            return self._jit_cache[key]

        def train_step(params, states, upd_state, x, labels, iteration, rng, mask, label_mask):
            def loss_fn(p):
                return self._loss(
                    p,
                    states,
                    x,
                    labels,
                    train=True,
                    rng=rng,
                    mask=mask,
                    label_mask=label_mask,
                    carry_state=carry_state,
                    backprop_window=backprop_window,
                )

            (loss, new_states), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params
            )
            updates, upd_state = self.updater.update(
                grads, upd_state, params, iteration
            )
            params = apply_updates(params, updates, self.conf.minimize)
            return params, new_states, upd_state, loss

        if lp:
            return self._build_lowprec_step(key, carry_state, backprop_window)

        # params/states/upd_state are donated: every caller (fit,
        # _fit_tbptt, ParallelWrapper) re-binds them from the returned
        # triple, so the superseded buffers are never re-read and the
        # update happens in-place in HBM instead of copying the whole
        # training state each step
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
    def loss_scale(self) -> Optional[dict]:
        """Host snapshot of the dynamic loss-scale state (None when bf16
        training never ran). This is a deliberate sync point — it also
        refreshes dispatch_stats.loss_scale_skips."""
        snap = lowprec.scale_snapshot(self._loss_scale)
        if snap is not None:
            self.dispatch_stats.loss_scale_skips = snap["skipped"]
        return snap

    def _build_lowprec_step(self, key, carry_state, backprop_window):
        """bf16 master-weight train step (Micikevicius et al., ICLR 2018):
        f32 master params + updater state; the loss closure casts params
        and floating inputs to bf16 at the step boundary (the cast's
        transpose returns f32 grads); the loss is SCALED before the
        backward pass and the grads unscaled after; non-finite grads skip
        the update (select back the previous state) and halve the scale.

        The inner jit takes the loss-scale tree as a 4th donated arg; the
        returned wrapper keeps the ORIGINAL 9-arg signature (every caller
        — fit, _fit_tbptt, data_parallel, bench — re-binds the same
        4-tuple), injecting/rebinding ``self._loss_scale`` itself."""

        def lp_step(params, states, upd_state, ls, x, labels, iteration,
                    rng, mask, label_mask):
            scale = ls["scale"]

            def loss_fn(p):
                loss, new_states = self._loss(
                    lowprec.cast_tree(p),
                    states,
                    lowprec.cast_array(x),
                    labels,
                    train=True,
                    rng=rng,
                    mask=mask,
                    label_mask=label_mask,
                    carry_state=carry_state,
                    backprop_window=backprop_window,
                )
                return loss.astype(jnp.float32) * scale, (loss, new_states)

            (_, (loss, new_states)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = lowprec.unscale(grads, scale)
            finite = lowprec.finite_tree(grads)
            updates, new_upd = self.updater.update(
                grads, upd_state, params, iteration
            )
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

        def wrapper(params, states, upd_state, x, labels, iteration, rng,
                    mask, label_mask):
            ls = net._ensure_loss_scale()
            params, states, upd_state, ls, loss = inner(
                params, states, upd_state, ls, x, labels, iteration, rng,
                mask, label_mask)
            net._loss_scale = ls
            return params, states, upd_state, loss

        def measure_memory(params, states, upd_state, x, labels, iteration,
                           rng, mask, label_mask):
            return inner.measure_memory(
                params, states, upd_state, net._ensure_loss_scale(), x,
                labels, iteration, rng, mask, label_mask)

        wrapper.measure_memory = measure_memory
        wrapper.lowprec = True
        self._jit_cache[key] = wrapper
        return wrapper

    def measure_memory(self, features, labels, mask=None, label_mask=None):
        """AOT memory accounting for this net's train step on the given
        batch shape (ops/memory: lower + compile + memory_analysis, no
        execution) — recorded under 'train_step' in self.memory_stats.
        Returns the byte dict, or None when the backend exposes no
        memory stats."""
        if self.params is None:
            self.init()
        features = jnp.asarray(features)
        labels = jnp.asarray(labels)
        step = self._get_train_step(mask is not None, label_mask is not None)
        return step.measure_memory(
            self.params, self.states, self.updater_state, features, labels,
            jnp.asarray(self.iteration, jnp.int32), self._rng, mask,
            label_mask)

    def _get_output_fn(self, train: bool = False):
        key = ("output", train)
        if key not in self._jit_cache:

            def out_fn(params, states, x):
                acts, _ = self._forward(params, states, x, train=False)
                return acts[-1]

            self._jit_cache[key] = dispatch.instrumented_jit(
                out_fn, "output", self.dispatch_stats,
                mem_stats=self.memory_stats)
        return self._jit_cache[key]

    def _get_score_fn(self, has_mask: bool, has_label_mask: bool):
        key = ("score", has_mask, has_label_mask)
        if key not in self._jit_cache:

            def score_fn(params, states, x, labels, mask, label_mask):
                loss, _ = self._loss(
                    params,
                    states,
                    x,
                    labels,
                    train=False,
                    rng=None,
                    mask=mask,
                    label_mask=label_mask,
                )
                return loss

            self._jit_cache[key] = dispatch.instrumented_jit(
                score_fn, "score", self.dispatch_stats)
        return self._jit_cache[key]

    # ------------------------------------------------------------------- fit
    @property
    def score_value(self) -> float:
        """Last training loss. Syncing with the device happens HERE, not in
        the step loop — fit() stays async so steps pipeline on TPU (the
        reference's per-iteration score readback is a hidden sync point)."""
        return float("nan") if self._score_dev is None else float(self._score_dev)

    @score_value.setter
    def score_value(self, v):
        self._score_dev = v

    def _record_iteration(self, loss):
        self._score_dev = loss
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, float(loss))
        self.iteration += 1

    def fit(self, features, labels, mask=None, label_mask=None) -> float:
        """One DataSet fit: `conf.iterations` optimizer iterations on this
        batch (reference fit(DataSet) semantics with the Solver loop)."""
        if self.params is None:
            self.init()
        features = jnp.asarray(features)
        labels = jnp.asarray(labels)
        if self.conf.backprop_type == "truncated_bptt" and features.ndim == 3:
            return self._fit_tbptt(features, labels, mask, label_mask)
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            from deeplearning4j_tpu.optimize.solvers import Solver

            return Solver(self).optimize(features, labels, mask, label_mask)
        features, labels, mask, label_mask = self._bucket_batch(
            features, labels, mask, label_mask
        )
        step = self._get_train_step(mask is not None, label_mask is not None)
        loss = None
        for _ in range(max(1, self.conf.iterations)):
            srng = rng_mod.step_key(self._rng, self.iteration)
            self.params, self.states, self.updater_state, loss = step(
                self.params,
                self.states,
                self.updater_state,
                features,
                labels,
                jnp.asarray(self.iteration, jnp.int32),
                srng,
                mask,
                label_mask,
            )
            self._record_iteration(loss)
        return loss

    def _bucket_batch(self, features, labels, mask, label_mask):
        """Shape bucketing (dispatch.bucket_size): pad a ragged batch up to
        its bucket and mask the pad rows out of the loss, so fit() compiles
        once per BUCKET instead of once per batch shape. The reference's
        fit(DataSet) (MultiLayerNetwork.java:1017) accepts arbitrary shapes
        because a JVM re-dispatch is cheap; here every new shape is a full
        XLA retrace of the whole-step program.

        The row-validity mask rides the existing label-mask plumbing
        (nn/losses._masked_mean_per_example divides by the mask sum), which
        makes the padding semantically free; it is attached even when no
        padding happened so a padded 100-batch and an exact 128-batch share
        ONE jit signature. Applies per dispatch.bucketing_mode — by default
        only inside fit_iterator's loop (direct fit() stays byte-exact for
        the equivalence contracts). Skipped for BatchNormalization nets
        (pad rows would enter the batch statistics) and for the
        TBPTT/Solver paths, which dispatch before this hook."""
        mode = dispatch.bucketing_mode()
        if (mode == "off" or (mode == "auto" and not self._bucket_scope)
                or self._bucketing_blocked):
            return features, labels, mask, label_mask
        n = features.shape[0]
        target = dispatch.bucket_size(n)
        if target != n:
            features, labels, mask, label_mask = dispatch.pad_rows(
                self.dispatch_stats, target,
                [features, labels, mask, label_mask],
            )
        if label_mask is None:
            # the same fallback _loss applies (lmask = label_mask or mask),
            # made explicit so the padded and unpadded signatures agree;
            # pad rows of a padded feature mask are already all-zero
            label_mask = mask if mask is not None else (
                dispatch.row_validity_mask(
                    n, target,
                    labels.shape[1] if labels.ndim == 3 else None,
                )
            )
        return features, labels, mask, label_mask

    def _get_fit_batches_fn(self, has_mask: bool, has_label_mask: bool):
        """K train steps fused into ONE lax.scan — the reference's
        fit(DataSetIterator) hot loop (MultiLayerNetwork.java:1017) as a
        single XLA program. Per-step semantics (updater state, iteration
        counter, per-step rng stream) are identical to K fit() calls; the
        fusion removes the per-step host dispatch, which dominates step
        time for small/medium models on a remote-attached TPU."""
        lp = lowprec.train_policy()
        key = ("fit_batches", has_mask, has_label_mask, lp)
        if key in self._jit_cache:
            return self._jit_cache[key]

        n_iters = max(1, self.conf.iterations)

        def one_iter(params, states, upd_state, x, y, it, rng, mask, lmask):
            def loss_fn(p):
                return self._loss(
                    p, states, x, y, train=True,
                    rng=rng_mod.step_key(rng, it),
                    mask=mask, label_mask=lmask,
                    # inside lax.scan the loop boundary already
                    # prevents CSE; skip the remat barriers
                    remat_prevent_cse=False,
                )

            (loss, states), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            updates, upd_state = self.updater.update(
                grads, upd_state, params, it
            )
            params = apply_updates(params, updates, self.conf.minimize)
            return params, states, upd_state, loss

        def one_iter_lp(params, states, upd_state, ls, x, y, it, rng,
                        mask, lmask):
            # same scaled-loss/unscale/skip discipline as
            # _build_lowprec_step, inlined into the scan body
            scale = ls["scale"]

            def loss_fn(p):
                loss, new_states = self._loss(
                    lowprec.cast_tree(p), states, lowprec.cast_array(x), y,
                    train=True, rng=rng_mod.step_key(rng, it),
                    mask=mask, label_mask=lmask,
                    remat_prevent_cse=False,
                )
                return loss.astype(jnp.float32) * scale, (loss, new_states)

            (_, (loss, new_states)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = lowprec.unscale(grads, scale)
            finite = lowprec.finite_tree(grads)
            updates, new_upd = self.updater.update(
                grads, upd_state, params, it
            )
            new_params = apply_updates(params, updates, self.conf.minimize)
            params = lowprec.select_trees(finite, new_params, params)
            upd_state = lowprec.select_trees(finite, new_upd, upd_state)
            states = lowprec.select_trees(finite, new_states, states)
            ls = lowprec.advance_scale(ls, finite)
            return params, states, upd_state, ls, loss.astype(jnp.float32)

        def scan_fn(params, states, upd_state, xs, ys, it0, rng, masks, lmasks):
            def body(carry, inp):
                params, states, upd_state, it = carry
                x = inp[0]
                y = inp[1]
                mask = inp[2] if has_mask else None
                lmask = inp[3] if has_label_mask else None

                # conf.iterations optimizer iterations per batch, exactly
                # like fit()'s Solver loop (statically unrolled)
                iter_losses = []
                for _ in range(n_iters):
                    params, states, upd_state, loss = one_iter(
                        params, states, upd_state, x, y, it, rng, mask,
                        lmask)
                    it = it + 1
                    iter_losses.append(loss)
                return (params, states, upd_state, it), jnp.stack(iter_losses)

            zeros = jnp.zeros((xs.shape[0],), jnp.float32)
            inputs = (xs, ys, masks if has_mask else zeros,
                      lmasks if has_label_mask else zeros)
            (params, states, upd_state, _), losses = jax.lax.scan(
                body, (params, states, upd_state, it0), inputs
            )
            return params, states, upd_state, losses.reshape(-1)

        if lp:
            def lp_scan_fn(params, states, upd_state, ls, xs, ys, it0, rng,
                           masks, lmasks):
                def body(carry, inp):
                    params, states, upd_state, ls, it = carry
                    x = inp[0]
                    y = inp[1]
                    mask = inp[2] if has_mask else None
                    lmask = inp[3] if has_label_mask else None
                    iter_losses = []
                    for _ in range(n_iters):
                        params, states, upd_state, ls, loss = one_iter_lp(
                            params, states, upd_state, ls, x, y, it, rng,
                            mask, lmask)
                        it = it + 1
                        iter_losses.append(loss)
                    return ((params, states, upd_state, ls, it),
                            jnp.stack(iter_losses))

                zeros = jnp.zeros((xs.shape[0],), jnp.float32)
                inputs = (xs, ys, masks if has_mask else zeros,
                          lmasks if has_label_mask else zeros)
                (params, states, upd_state, ls, _), losses = jax.lax.scan(
                    body, (params, states, upd_state, ls, it0), inputs
                )
                return params, states, upd_state, ls, losses.reshape(-1)

            inner = dispatch.instrumented_jit(
                lp_scan_fn, "fit_batches", self.dispatch_stats,
                donate=(0, 1, 2, 3), step=True,
                mem_stats=self.memory_stats)
            net = self

            def wrapper(params, states, upd_state, xs, ys, it0, rng,
                        masks, lmasks):
                ls = net._ensure_loss_scale()
                params, states, upd_state, ls, losses = inner(
                    params, states, upd_state, ls, xs, ys, it0, rng,
                    masks, lmasks)
                net._loss_scale = ls
                return params, states, upd_state, losses

            wrapper.lowprec = True
            self._jit_cache[key] = wrapper
            return wrapper

        # same donation contract as the train step: fit_batches re-binds
        # params/states/upd_state from the scan's outputs
        fn = dispatch.instrumented_jit(
            scan_fn, "fit_batches", self.dispatch_stats,
            donate=(0, 1, 2), step=True, mem_stats=self.memory_stats)
        self._jit_cache[key] = fn
        return fn

    def _has_scanned_conv(self) -> bool:
        return any(isinstance(lc, (conf_layers.ConvolutionLayer,
                                   conf_layers.SubsamplingLayer))
                   for lc in self.conf.layers)

    def _fit_batches_fallback(self, features, labels, masks, label_masks):
        """Per-step drain for fit_batches when the fusion policy says the
        scanned program would lose (dispatch.fusion_enabled: XLA:CPU runs
        scan-of-conv far slower than the per-step program). Semantics are
        identical by construction — fit_batches is DEFINED as equivalent
        to K fit() calls — and the fallback is recorded in
        dispatch_stats.fused_fallbacks; DL4J_TPU_FUSE=force overrides."""
        from deeplearning4j_tpu.optimize.listeners import (
            CollectScoresIterationListener,
        )

        self.dispatch_stats.fused_fallbacks += 1
        col = CollectScoresIterationListener(frequency=1)
        self.listeners.append(col)
        try:
            for k in range(features.shape[0]):
                self.fit(features[k], labels[k],
                         masks[k] if masks is not None else None,
                         label_masks[k] if label_masks is not None else None)
        finally:
            self.listeners.remove(col)
        return np.asarray([s for _, s in col.scores], np.float32)

    def fit_batches(self, features, labels, masks=None, label_masks=None):
        """Fit each leading-axis slice of ``features`` [K, N, ...] /
        ``labels`` [K, ...] inside a single compiled scan — equivalent to
        ``for k in range(K): fit(features[k], labels[k], ...)`` (including
        ``conf.iterations`` optimizer iterations per batch) but without the
        per-step host round-trips. Returns the per-iteration losses as a
        length K*iterations numpy array. SGD-algorithm, non-TBPTT path."""
        if self.params is None:
            self.init()
        if self.conf.backprop_type == "truncated_bptt":
            raise ValueError("fit_batches: use fit() for TBPTT training")
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            raise ValueError("fit_batches supports SGD-family training only")
        features = jnp.asarray(features)
        labels = jnp.asarray(labels)
        if not dispatch.fusion_enabled(scanned_conv=self._has_scanned_conv()):
            return self._fit_batches_fallback(
                features, labels,
                jnp.asarray(masks) if masks is not None else None,
                jnp.asarray(label_masks) if label_masks is not None else None)
        fn = self._get_fit_batches_fn(masks is not None, label_masks is not None)
        zeros = jnp.zeros((features.shape[0],), jnp.float32)
        self.params, self.states, self.updater_state, losses = fn(
            self.params, self.states, self.updater_state,
            features, labels,
            jnp.asarray(self.iteration, jnp.int32),
            self._rng,
            jnp.asarray(masks) if masks is not None else zeros,
            jnp.asarray(label_masks) if label_masks is not None else zeros,
        )
        self._score_dev = losses[-1]
        # ONE bulk readback (per-element float() would be K sequential
        # device round-trips — the pattern loss_curve documents)
        losses_np = np.asarray(losses)
        for k in range(losses_np.shape[0]):
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, float(losses_np[k]))
            self.iteration += 1
        return losses_np

    def _reset_rnn_states(self, batch_n: int) -> None:
        """Zero recurrent state sized for this batch (sequence start —
        reference rnnClearPreviousState before doTruncatedBPTT)."""
        for i, lc in enumerate(self.conf.layers):
            if isinstance(lc, STATEFUL_RNN_CONFS):
                self.states[i] = {
                    k: jnp.zeros((batch_n, lc.n_out), jnp.float32)
                    for k in self.states[i]
                }

    def _tbptt_windows(self, features, labels, mask=None, label_mask=None):
        """Yield (f_w, l_w, m_w, lm_w) fwd-length window slices along time
        (reference doTruncatedBPTT :1183-1199 subset extraction)."""
        t_total = features.shape[1]
        w = self.conf.tbptt_fwd_length
        for window_start in range(0, t_total, w):
            sl = slice(window_start, min(window_start + w, t_total))
            f_w = features[:, sl]
            l_w = labels[:, sl] if labels.ndim == 3 else labels
            m_w = (
                mask[:, sl]
                if mask is not None and mask.ndim >= 2 and mask.shape[1] == t_total
                else mask
            )
            lm_w = (
                label_mask[:, sl]
                if label_mask is not None and labels.ndim == 3
                else label_mask
            )
            yield f_w, l_w, m_w, lm_w

    def _tbptt_backprop_window(self) -> Optional[int]:
        from deeplearning4j_tpu.nn.common import tbptt_backprop_window

        return tbptt_backprop_window(self.conf)

    def _fit_tbptt(self, features, labels, mask=None, label_mask=None) -> float:
        """Truncated BPTT: slice the time axis into fwd-length windows;
        recurrent state carries forward across windows (stop-gradient at the
        window boundary — state enters the next jitted step as data), matching
        reference doTruncatedBPTT :1162-1233. A shorter tbptt_back_length
        truncates the backward pass inside each window via stop-gradient
        segments (LSTMHelpers.backpropGradientHelper:255)."""
        if features.ndim != 3:
            raise ValueError(
                "backprop_type='truncated_bptt' requires [B,T,F] features"
            )
        loss = float("nan")
        self._reset_rnn_states(features.shape[0])
        bw = self._tbptt_backprop_window()
        for f_w, l_w, m_w, lm_w in self._tbptt_windows(
            features, labels, mask, label_mask
        ):
            step = self._get_train_step(
                m_w is not None, lm_w is not None, carry_state=True,
                backprop_window=bw,
            )
            srng = rng_mod.step_key(self._rng, self.iteration)
            self.params, self.states, self.updater_state, loss = step(
                self.params,
                self.states,
                self.updater_state,
                f_w,
                l_w,
                jnp.asarray(self.iteration, jnp.int32),
                srng,
                m_w,
                lm_w,
            )
            self._record_iteration(loss)
        return loss

    def fit_iterator(self, iterator, num_epochs: int = 1,
                     fused_batches: int = 1) -> "MultiLayerNetwork":
        """fit(DataSetIterator) equivalent (reference :1017). Async prefetch
        is provided by wrapping with datasets.AsyncDataSetIterator.

        fused_batches=K > 1: stack K consecutive same-shape DataSets and
        run them through fit_batches — ONE XLA program per K optimizer
        steps instead of K dispatches (the lenet5_fused bench leg
        measures the difference). Falls back
        to per-step fit() for ragged tails, shape changes, mixed mask
        presence, and TBPTT (whose window loop fit() already handles).

        Input staging: ``DL4J_TPU_PIPELINE_WORKERS`` > 0 wraps a plain
        iterator in ``etl/pipeline.InputPipeline`` (parallel off-thread
        assembly + device staging; value-identical stream, so the
        equivalence contracts hold); whichever staged iterator feeds the
        loop, its telemetry is adopted as ``net.pipeline_stats``."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.etl.pipeline import maybe_wrap

        iterator = maybe_wrap(iterator)
        if getattr(iterator, "pipeline_stats", None) is not None:
            self.pipeline_stats = iterator.pipeline_stats
            from deeplearning4j_tpu.obs.registry import register_net

            register_net(self)  # the freshly adopted ingest ledger
        if self.conf.pretrain:
            self.pretrain(iterator)
            if hasattr(iterator, "reset"):
                iterator.reset()
        fused = (fused_batches > 1
                 and self.conf.backprop_type != "truncated_bptt"
                 # fit_batches is SGD-family only; Solver algos (CG/LBFGS/
                 # line search) fall back to the per-step fit() they need
                 and self.conf.optimization_algo
                 == "stochastic_gradient_descent")
        from deeplearning4j_tpu.nn.common import fused_iterator_loop

        fit_one = lambda ds: self.fit(ds.features, ds.labels,
                                      ds.features_mask, ds.labels_mask)
        # the iterator loop is bucketing's "auto" scope: ragged tails and
        # shape drift land here, and each one costs a full XLA retrace
        # unless padded up to a bucket (dispatch.bucketing_mode)
        self._bucket_scope = True
        try:
            for _ in range(num_epochs):
                if not fused:
                    for ds in iterator:
                        fit_one(ds)
                else:
                    fused_iterator_loop(
                        iterator, fused_batches,
                        can_stack=lambda ds: True,  # fit_batches stacks masks
                        same_shape=self._stackable,
                        fit_one=fit_one,
                        fit_fused=self._fit_fused,
                    )
                if hasattr(iterator, "reset"):
                    iterator.reset()
        finally:
            self._bucket_scope = False
        return self

    @staticmethod
    def _stackable(a, b) -> bool:
        return (
            np.asarray(a.features).shape == np.asarray(b.features).shape
            and np.asarray(a.labels).shape == np.asarray(b.labels).shape
            and (a.features_mask is None) == (b.features_mask is None)
            and (a.labels_mask is None) == (b.labels_mask is None)
        )

    def _fit_fused(self, buf) -> None:
        stack = lambda get: (
            None if get(buf[0]) is None
            else np.stack([np.asarray(get(d)) for d in buf])
        )
        self.fit_batches(
            stack(lambda d: d.features), stack(lambda d: d.labels),
            stack(lambda d: d.features_mask),
            stack(lambda d: d.labels_mask),
        )

    # -------------------------------------------------------------- pretrain
    def pretrain(self, data, num_epochs: int = 1) -> None:
        """Greedy layerwise pretraining for AutoEncoder/RBM layers
        (reference pretrain(DataSetIterator) :165-213)."""
        if self.params is None:
            self.init()

        def batches():
            if hasattr(data, "__iter__") and not hasattr(data, "shape"):
                for ds in data:
                    yield jnp.asarray(ds.features)
                if hasattr(data, "reset"):
                    data.reset()
            else:
                yield jnp.asarray(data)

        for i, layer in enumerate(self.layers):
            if not isinstance(layer, (AutoEncoderImpl, RBMImpl)):
                continue
            lc = self.conf.layers[i]
            from deeplearning4j_tpu.optimize.updaters import LayerUpdater

            lu = LayerUpdater(lc, self.conf)
            lu_state = lu.init(self.params[i])

            if isinstance(layer, RBMImpl):

                def grads_fn(p, x, k):
                    return layer.cd_grads(p, x, k), None

            else:

                def grads_fn(p, x, k):
                    g = jax.grad(lambda pp: layer.pretrain_loss(pp, x, k))(p)
                    return g, None

            def _pretrain_step(p, s, x, it, k):
                g, _ = grads_fn(p, x, k)
                upd, s = lu.update(g, s, p, it)
                p = apply_updates(p, upd, True)
                return p, s

            # donated: self.params[i] and lu_state are re-bound from the
            # returned pair each call; earlier layers' params (read by the
            # inference forward above) are not arguments here
            pretrain_step = dispatch.instrumented_jit(
                _pretrain_step, "pretrain_step", self.dispatch_stats,
                donate=(0, 1), step=True)

            it_count = 0
            for _ in range(num_epochs):
                for xb in batches():
                    batch_n = xb.shape[0]
                    # forward through earlier layers in inference mode
                    if i > 0:
                        acts, _ = self._forward(
                            self.params, self.states, xb, train=False, upto=i
                        )
                        xb = acts[-1]
                    # apply this layer's input preprocessor (forward applies
                    # preprocessor i only when running layer i, which upto=i
                    # excludes)
                    xb = self._apply_preprocessor(i, xb, batch_n)
                    k = rng_mod.step_key(
                        rng_mod.layer_key(self._rng, i, "sample"), it_count
                    )
                    self.params[i], lu_state = pretrain_step(
                        self.params[i],
                        lu_state,
                        xb,
                        jnp.asarray(it_count, jnp.int32),
                        k,
                    )
                    it_count += 1
            logger.info("pretrained layer %d (%s)", i, type(lc).__name__)

    # ------------------------------------------------------------- inference
    def output(self, x) -> jax.Array:
        """Batch inference (reference output(INDArray) :619-704). Ragged
        batches are bucket-padded and sliced back — inference-mode padding
        is unconditionally safe (BN uses running stats, dropout is off), so
        a stream of arbitrary batch sizes compiles O(log n) programs."""
        fn = self._get_output_fn()
        x = jnp.asarray(x)
        n = x.shape[0]
        target = dispatch.inference_bucket(self.dispatch_stats, n)
        if target is not None:
            return fn(self.params, self.states,
                      dispatch.pad_axis0(x, target))[:n]
        return fn(self.params, self.states, x)

    def feed_forward(self, x, train: bool = False):
        """All layer activations (reference feedForward(train)). train=True
        applies dropout (fresh step key) and batch-stats normalization."""
        rng = rng_mod.step_key(self._rng, self.iteration) if train else None
        acts, _ = self._forward(
            self.params, self.states, jnp.asarray(x), train=train, rng=rng
        )
        return acts

    def score(self, features, labels, mask=None, label_mask=None) -> float:
        fn = self._get_score_fn(mask is not None, label_mask is not None)
        return float(
            fn(self.params, self.states, jnp.asarray(features), jnp.asarray(labels), mask, label_mask)
        )

    def evaluate(self, iterator):
        """Evaluate over an iterator (reference evaluate(DataSetIterator) :2316)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation()
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(np.asarray(ds.labels), np.asarray(out), mask=ds.labels_mask)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    # ------------------------------------------------- stateful rnn streaming
    def rnn_clear_previous_state(self):
        """Zero streaming RNN state WITHOUT touching params (reference
        rnnClearPreviousState just clears stateMap). State leaves go back to
        the lazily-sized empty form; the next rnn_time_step re-sizes them."""
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "step"):
                self.states[i] = {
                    k: jnp.zeros((0,) + v.shape[1:], v.dtype)
                    for k, v in self.states[i].items()
                }

    def _sized_rnn_states(self, states, n: int):
        """States with stream-state leaves sized for batch n. Only the
        intentionally cleared (0, ...) form is re-sized; any other batch
        mismatch raises (silently zeroing carried state would produce wrong
        predictions with no signal — call rnn_clear_previous_state first)."""
        out = list(states)
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "step"):
                sized = {}
                for k, v in states[i].items():
                    if v.shape[0] == n:
                        sized[k] = v
                    elif v.shape[0] == 0:
                        sized[k] = jnp.zeros((n,) + v.shape[1:], v.dtype)
                    else:
                        raise ValueError(
                            f"rnn_time_step batch {n} != carried state batch "
                            f"{v.shape[0]} (layer {i}); call "
                            "rnn_clear_previous_state() to start a new stream"
                        )
                out[i] = sized
        return out

    def _get_rnn_step_fn(self):
        """Jitted single-timestep forward through the whole stack with carried
        RNN state — the streaming-inference hot path (reference rnnTimeStep
        :2152 keeps a stateMap per layer; here state is an explicit pytree so
        the step is one compiled XLA program)."""
        key = ("rnn_step",)
        if key not in self._jit_cache:
            self._jit_cache[key] = dispatch.instrumented_jit(
                self._rnn_step_body, "rnn_step", self.dispatch_stats)
        return self._jit_cache[key]

    def _get_rnn_seq_fn(self):
        """Jitted [N,T,F] stepwise path: lax.scan of the single-step function
        over time (state carries across calls like repeated rnn_time_step)."""
        key = ("rnn_seq",)
        if key not in self._jit_cache:

            def seq_fn(params, states, x):
                def body(states, x_t):
                    y, new_states = self._rnn_step_body(params, states, x_t)
                    return new_states, y

                states, ys = jax.lax.scan(body, states, jnp.swapaxes(x, 0, 1))
                return jnp.swapaxes(ys, 0, 1), states

            self._jit_cache[key] = dispatch.instrumented_jit(
                seq_fn, "rnn_seq", self.dispatch_stats)
        return self._jit_cache[key]

    def _rnn_step_body(self, params, states, x):
        new_states = list(states)
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "step"):
                x, new_states[i] = layer.step(params[i], states[i], x)
            else:
                x, _ = layer.apply(params[i], states[i], x, train=False)
        return x, new_states

    def rnn_time_step(self, x_t) -> jax.Array:
        """Stateful streaming inference (reference rnnTimeStep :2152).
        x_t: [N, F] (single step) or [N, T, F] (scanned stepwise). State
        carries across calls; both paths are single jitted XLA programs."""
        x_t = jnp.asarray(x_t)
        n = x_t.shape[0]
        states = self._sized_rnn_states(self.states, n)
        if x_t.ndim == 3:
            ys, self.states = self._get_rnn_seq_fn()(self.params, states, x_t)
            return ys
        y, self.states = self._get_rnn_step_fn()(self.params, states, x_t)
        return y

    def apply_lr_score_decay(self) -> None:
        """Multiply the effective LR by lr_policy_decay_rate (reference
        Model.applyLearningRateScoreDecay — the event-driven 'score' LR
        policy, fired by BaseOptimizer.checkTerminalConditions:239 on an
        eps-plateau). The cumulative factor lives in updater state."""
        from deeplearning4j_tpu.nn.common import decay_lr_scale_entry

        rate = self.conf.lr_policy_decay_rate
        if rate is None:
            return
        self.updater_state = [
            decay_lr_scale_entry(s, rate) for s in self.updater_state
        ]

    # ------------------------------------------------------------ resilience
    def training_state(self) -> Dict[str, Any]:
        """Everything beyond params/states/updater that exact resume needs
        (resilience/checkpoint.py): the iteration counter (the per-step RNG
        stream and every LR schedule fold it in) and the base RNG key. The
        reference's ModelSerializer drops both (ModelSerializer.java:70-110
        writes config+coefficients+updater only), which is why a restored
        reference run drifts from the uninterrupted one. Under bf16
        training (DL4J_TPU_BF16) the dynamic loss-scale state rides along
        so kill/resume keeps the exact scale/skip trajectory."""
        st = {
            "iteration": int(self.iteration),
            "rng": np.asarray(self._rng, np.uint32).tolist(),
        }
        snap = self.loss_scale  # property: also syncs loss_scale_skips
        if snap is not None:
            st["loss_scale"] = snap
        return st

    def restore_training_state(self, st: Dict[str, Any]) -> None:
        """Inverse of :meth:`training_state`; tolerant of partial dicts so
        pre-resilience checkpoints (no rng section) keep loading."""
        if st.get("iteration") is not None:
            self.iteration = int(st["iteration"])
        if st.get("rng") is not None:
            self._rng = jnp.asarray(np.asarray(st["rng"], dtype=np.uint32))
        if st.get("loss_scale") is not None:
            self._loss_scale = lowprec.scale_from_snapshot(st["loss_scale"])

    # ------------------------------------------------------------- listeners
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def clone(self) -> "MultiLayerNetwork":
        import copy

        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        if self.params is not None:
            net._input_shape = self._input_shape
            # REAL copies, not leaf-sharing (tree_map identity): under
            # buffer donation the original's next train step would delete
            # shared leaves out from under the clone
            net.params = jax.tree_util.tree_map(jnp.copy, self.params)
            net.states = jax.tree_util.tree_map(jnp.copy, self.states)
            net.updater_state = jax.tree_util.tree_map(
                jnp.copy, self.updater_state
            )
            net.iteration = self.iteration
        return net
