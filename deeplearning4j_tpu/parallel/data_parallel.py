"""Data-parallel trainers.

Two modes, matching the reference's two semantics (SURVEY.md section 2.7):

1. :class:`ParallelWrapper` — synchronous gradient data parallelism. The
   batch is sharded over the mesh's data axis; params are replicated; the
   network's ordinary jitted train step is executed under GSPMD, which
   partitions the forward/backward and inserts the gradient all-reduce
   (psum over ICI) automatically. Numerically identical to single-device
   large-batch training. This supersedes the reference ParallelWrapper's
   replica threads + periodic averaging
   (core/.../parallelism/ParallelWrapper.java:58-95) with a strictly
   stronger (every-step, gradient-level) sync at wire speed.

2. :class:`ParameterAveragingTrainer` — exact reference semantics for the
   Spark ParameterAveragingTrainingMaster
   (dl4j-spark/.../paramavg/ParameterAveragingTrainingMaster.java:402-434):
   N workers train INDEPENDENTLY for `averaging_frequency` minibatches from
   the same broadcast params, then parameters AND updater state are averaged
   (:416-434 averages both). Implemented with shard_map: each device is a
   "worker", local steps run unsynced, then pmean replaces the
   broadcast+RDD.aggregate round trip. The distributed==serial equivalence
   test (TestCompareParameterAveragingSparkVsSingleMachine.java:115-262)
   is mirrored in tests/test_data_parallel.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import rng as rng_mod
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, device_mesh
from deeplearning4j_tpu.optimize.updaters import apply_updates


class ParallelWrapper:
    """Synchronous gradient DP via batch sharding + GSPMD."""

    def __init__(self, net, num_devices: Optional[int] = None, mesh: Optional[Mesh] = None):
        self.net = net
        self.mesh = mesh if mesh is not None else device_mesh(num_devices)
        self.n = int(np.prod(self.mesh.devices.shape))
        self.data_sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self.repl = NamedSharding(self.mesh, P())
        self._placed = False

    def _place_model(self):
        if self._placed:
            return
        if self.net.params is None:
            self.net.init()
        put = lambda t: jax.device_put(t, self.repl)
        self.net.params = put(self.net.params)
        self.net.states = put(self.net.states)
        self.net.updater_state = put(self.net.updater_state)
        self._placed = True

    def fit(self, features, labels, mask=None, label_mask=None) -> float:
        """One data-parallel train step across the mesh. Accepts either a
        MultiLayerNetwork (array features/labels) or a ComputationGraph
        (array-or-list features/labels) — the same duality as the reference's
        ParallelWrapper, which wraps Model (MLN or CG)."""
        self._place_model()
        net = self.net
        if hasattr(net, "_as_inputs"):  # ComputationGraph
            return self._fit_graph(features, labels, mask, label_mask)
        b = np.asarray(features).shape[0]
        self._check_divisible(b)
        from deeplearning4j_tpu.parallel.multihost import put_batch

        x = put_batch(features, self.data_sharding)
        y = put_batch(labels, self.data_sharding)
        m = None if mask is None else put_batch(mask, self.data_sharding)
        lm = (None if label_mask is None
              else put_batch(label_mask, self.data_sharding))
        if net.conf.backprop_type == "truncated_bptt" and x.ndim == 3:
            return self._fit_tbptt_mln(x, y, m, lm)
        step = net._get_train_step(m is not None, lm is not None)
        loss = None
        for _ in range(max(1, net.conf.iterations)):  # same loop as net.fit
            srng = rng_mod.step_key(net._rng, net.iteration)
            net.params, net.states, net.updater_state, loss = step(
                net.params, net.states, net.updater_state, x, y,
                jnp.asarray(net.iteration, jnp.int32), srng, m, lm,
            )
            net._record_iteration(loss)
        return loss

    def fit_batches(self, features, labels):
        """Data-parallel fused multi-step training: K stacked batches
        [K, N, ...] run through the container's fit_batches scan with the
        example axis sharded over the mesh — one XLA program containing
        the whole K-step loop AND the per-step gradient psum (GSPMD). The
        equivalent of the reference ParallelWrapper iterating fit() over a
        DataSetIterator, minus every host round-trip."""
        self._place_model()
        net = self.net

        def shard_stacked(a):
            from deeplearning4j_tpu.parallel.multihost import put_batch

            a = jnp.asarray(a)
            self._check_divisible(a.shape[1])
            spec = P(*((None, DATA_AXIS) + (None,) * (a.ndim - 2)))
            return put_batch(a, NamedSharding(self.mesh, spec))

        if hasattr(net, "_as_inputs"):  # ComputationGraph
            feats = features if isinstance(features, (list, tuple)) else [features]
            labs = labels if isinstance(labels, (list, tuple)) else [labels]
            return net.fit_batches(
                [shard_stacked(f) for f in feats],
                [shard_stacked(l) for l in labs],
            )
        return net.fit_batches(shard_stacked(features), shard_stacked(labels))

    def _check_divisible(self, b: int) -> None:
        # multi-process runs feed the PROCESS-LOCAL shard (multihost
        # .put_batch), so the divisibility bar is the local device share —
        # counted from the mesh itself, not self.n // process_count():
        # a mesh over a device subset, or devices spread unevenly across
        # processes, would make the quotient wrong in both directions
        # (ADVICE r4)
        n = self.n
        pc = jax.process_count()
        if pc > 1:
            pi = jax.process_index()
            n = sum(1 for d in self.mesh.devices.flat
                    if d.process_index == pi)
            if n == 0:
                # fail HERE with the real cause — clamping to 1 (the old
                # max(1, ...)) let any batch pass the divisibility gate and
                # the failure surfaced later as an opaque
                # make_array_from_process_local_data error (ADVICE r5)
                raise ValueError(
                    f"process {pi} owns none of the mesh's devices: this "
                    "process cannot feed a data-parallel shard. Build the "
                    "mesh over devices of every participating process, or "
                    "exclude this process from the trainer."
                )
        if b % n != 0:
            raise ValueError(
                f"batch {b} not divisible by {n} "
                f"{'local ' if pc > 1 else ''}devices "
                "(pad or trim — static shapes keep the step compiled once)"
            )

    def _shard_rnn_states(self):
        """Place recurrent stream state (batch-dim leaves) on the data axis;
        everything else stays replicated. Called after a state reset sized
        for the global batch. Handles both containers (MLN list states /
        graph dict states)."""
        net = self.net
        from deeplearning4j_tpu.nn.layers.factory import STATEFUL_RNN_CONFS

        put = lambda t: jax.device_put(t, self.data_sharding)
        if isinstance(net.states, dict):  # ComputationGraph
            net.states = {
                n: (
                    {k: put(v) for k, v in s.items()}
                    if isinstance(net.conf.vertices[n], STATEFUL_RNN_CONFS)
                    else s
                )
                for n, s in net.states.items()
            }
        else:
            net.states = [
                (
                    {k: put(v) for k, v in s.items()}
                    if isinstance(net.conf.layers[i], STATEFUL_RNN_CONFS)
                    else s
                )
                for i, s in enumerate(net.states)
            ]

    def _fit_tbptt_mln(self, x, y, m, lm) -> float:
        """Data-parallel truncated BPTT: the same fwd-window loop as
        MultiLayerNetwork._fit_tbptt, with the batch (and the carried
        recurrent state) sharded over the mesh — each window step is one
        GSPMD program with the gradient psum inside (reference
        doTruncatedBPTT :1162-1233 under ParallelWrapper)."""
        net = self.net
        net._reset_rnn_states(x.shape[0])
        self._shard_rnn_states()
        bw = net._tbptt_backprop_window()
        loss = None
        for f_w, l_w, m_w, lm_w in net._tbptt_windows(x, y, m, lm):
            step = net._get_train_step(
                m_w is not None, lm_w is not None, carry_state=True,
                backprop_window=bw,
            )
            srng = rng_mod.step_key(net._rng, net.iteration)
            net.params, net.states, net.updater_state, loss = step(
                net.params, net.states, net.updater_state, f_w, l_w,
                jnp.asarray(net.iteration, jnp.int32), srng, m_w, lm_w,
            )
            net._record_iteration(loss)
        return loss

    def _fit_graph(self, features, labels, masks=None, label_masks=None) -> float:
        from deeplearning4j_tpu.nn.graph import _as_list

        net = self.net
        if net.conf.optimization_algo != "stochastic_gradient_descent":
            raise NotImplementedError(
                "ParallelWrapper shards the SGD train step; "
                f"optimization_algo={net.conf.optimization_algo!r} requires "
                "the serial Solver path (net.fit)"
            )
        inputs = net._as_inputs(features)
        labels_l = [jnp.asarray(l) for l in _as_list(labels)]
        if len(labels_l) != len(net.conf.outputs):
            raise ValueError(
                f"expected {len(net.conf.outputs)} label arrays, got {len(labels_l)}"
            )
        self._check_divisible(next(iter(inputs.values())).shape[0])
        from deeplearning4j_tpu.parallel.multihost import put_batch

        # process-local feeding under multi-process runs, same as the MLN
        # path (plain device_put requires identical values on every
        # process — put_batch docstring)
        put = lambda t: put_batch(t, self.data_sharding)
        inputs = {k: put(v) for k, v in inputs.items()}
        labels_l = [put(l) for l in labels_l]
        masks_d = net._as_masks(masks)
        masks_d = {k: put(v) for k, v in masks_d.items()}
        lmasks = (
            [None if m is None else put(jnp.asarray(m)) for m in label_masks]
            if label_masks is not None
            else None
        )
        if net.conf.backprop_type == "truncated_bptt":
            return self._fit_tbptt_graph(inputs, labels_l, masks_d, lmasks)
        step = net._get_train_step(len(labels_l), lmasks is not None)
        loss = None
        for _ in range(max(1, net.conf.iterations)):  # same loop as net.fit
            srng = rng_mod.step_key(net._rng, net.iteration)
            net.params, net.states, net.updater_state, loss = step(
                net.params, net.states, net.updater_state, inputs, labels_l,
                jnp.asarray(net.iteration, jnp.int32), srng, masks_d, lmasks,
            )
            net._record_iteration(loss)
        return loss

    def _fit_tbptt_graph(self, inputs, labels_l, masks_d, lmasks) -> float:
        """DP truncated BPTT over a DAG: delegate to the graph's own window
        loop — inputs/labels arrive batch-sharded and time-slicing preserves
        that sharding, so every window step runs under GSPMD with the
        gradient psum inside (reference ComputationGraph TBPTT under
        ParallelWrapper)."""
        return self.net._fit_tbptt(
            inputs, labels_l, masks_d, lmasks,
            state_placer=self._shard_rnn_states,
        )

    def fit_iterator(self, iterator, num_epochs: int = 1):
        for _ in range(num_epochs):
            for ds in iterator:
                self.fit(ds.features, ds.labels, ds.features_mask, ds.labels_mask)
            if hasattr(iterator, "reset"):
                iterator.reset()
        return self.net


def stack_rounds(a, averaging_frequency: int):
    """[freq*gb, ...] -> [freq, gb, ...] minibatch stacking (the
    reference's one-split-feeds-freq-minibatches rule,
    ParameterAveragingTrainingMaster.java:148). ONE copy shared by the
    mesh trainer and the elastic fleet — the stacking rule must stay
    identical or the ==serial / bit-exact-replay contracts silently
    diverge between the two trainers."""
    if a is None:
        return None
    a = jnp.asarray(a)
    if a.ndim >= 2 and a.shape[0] != averaging_frequency:
        gb = a.shape[0] // averaging_frequency
        a = a[: gb * averaging_frequency].reshape(
            (averaging_frequency, gb) + a.shape[1:])
    return a


def round_step_rngs(net, averaging_frequency: int):
    """The round's per-step RNG keys [freq, 2] — every worker of a round
    consumes the SAME sequence (the shard_map trainer replicates it;
    the fleet ships it in the round state), derived from the net's key
    at the current iteration. Shared for the same reason as
    stack_rounds."""
    return jax.vmap(lambda i: rng_mod.step_key(net._rng, i))(
        jnp.arange(net.iteration, net.iteration + averaging_frequency))


def container_calls(net):
    """The two container-specific callables every parameter-averaging
    worker needs — the loss invocation and the updater application —
    for either container (the reference drives MLN and CG through the
    same ParameterAveragingTrainingMaster). Returns
    ``(loss_call, update_call, is_graph)``; shared by the shard_map
    trainer below and the elastic fleet (parallel/fleet.py)."""
    if hasattr(net, "_as_inputs"):  # ComputationGraph
        return (
            lambda p, st, x, y, r, m, lm: net._loss(
                p, st, x, y, train=True, rng=r, masks=m or None,
                label_masks=lm),
            net._update_all,
            True,
        )
    return (
        lambda p, st, x, y, r, m, lm: net._loss(
            p, st, x, y, train=True, rng=r, mask=m, label_mask=lm),
        net.updater.update,
        False,
    )


def local_round_scan(net, loss_call, update_call):
    """The UNsynchronized device-side half of one averaging worker:
    `averaging_frequency` independent train steps scanned over this
    worker's minibatches from the broadcast params (processMinibatch on
    executors, ExecuteWorkerFlatMap.java:35-100). Returns
    ``(params, states, upd_state, iteration), losses``. Two consumers:
    ParameterAveragingTrainer wraps it in shard_map and closes the round
    with a pmean (single-controller mesh path); the elastic fleet
    (parallel/fleet.py) jits it bare, per split, and averages the
    survivor results on the host — which is what makes a round's outcome
    a deterministic function of (broadcast params, split data) alone,
    independent of WHICH worker executed the split."""

    def worker(params, states, upd_state, xs, ys, ms, lms, iteration, rngs):
        def body(carry, inp):
            params, st, upd_state, it = carry
            (x, y, m, lm), r = inp
            (loss, new_states), grads = jax.value_and_grad(
                lambda p: loss_call(p, st, x, y, r, m, lm), has_aux=True
            )(params)
            updates, upd_state2 = update_call(grads, upd_state, params, it)
            params = apply_updates(params, updates, net.conf.minimize)
            return (params, new_states, upd_state2, it + 1), loss

        return jax.lax.scan(
            body, (params, states, upd_state, iteration),
            ((xs, ys, ms, lms), rngs),
        )

    return worker


class ParameterAveragingTrainer:
    """Reference-exact parameter averaging over mesh 'workers'.

    Semantics (ParameterAveragingTrainingMaster.java):
      - split each global batch into `n` worker shards of
        `batch_size_per_worker` examples x `averaging_frequency` minibatches;
      - every worker runs `averaging_frequency` INDEPENDENT train steps from
        the same starting params (processMinibatch on executors,
        ExecuteWorkerFlatMap.java:35-100);
      - params and updater state are then averaged (:407-434).
    """

    def __init__(
        self,
        net,
        num_workers: Optional[int] = None,
        averaging_frequency: int = 5,
        save_updater: bool = True,
        mesh: Optional[Mesh] = None,
    ):
        self.net = net
        self.mesh = mesh if mesh is not None else device_mesh(num_workers)
        self.n = int(np.prod(self.mesh.devices.shape))
        self.averaging_frequency = max(1, int(averaging_frequency))
        self.save_updater = save_updater
        self._step_fns = {}

    def _build_worker(self, loss_call, update_call, combine_states,
                      m_spec, lm_spec):
        """ONE copy of the averaging semantics, shared by both containers
        (the reference drives MLN and CG through the same
        ParameterAveragingTrainingMaster — ExecuteWorkerFlatMap.java:35-100):
        local minibatch scan, then pmean of params (+ updater state if
        save_updater — reference saveUpdater flag, :416-434 averages both).
        Container-specific pieces arrive as callables: the loss invocation,
        the updater application, and the state-averaging rule.

        States rule (combine_states): batch-statistics states (BN running
        mean/var — params in the reference, so they ARE averaged,
        BatchNormalizationParamInitializer) are pmean'd; recurrent stream
        states are NOT (workers are rebuilt from broadcast each split —
        worker RNN state never crosses the averaging boundary)."""
        save_updater = self.save_updater
        scan = local_round_scan(self.net, loss_call, update_call)

        def worker(params, states, upd_state, xs, ys, ms, lms, iteration,
                   rngs):
            # xs: [freq, local_b, ...] leaves — this worker's minibatches
            (params, out_states, upd_state, _), losses = scan(
                params, states, upd_state, xs, ys, ms, lms, iteration, rngs,
            )
            # averaging round: params (and updater state) pmean'd over workers
            params = jax.lax.pmean(params, DATA_AXIS)
            if save_updater:
                upd_state = jax.lax.pmean(upd_state, DATA_AXIS)
            return (
                params,
                combine_states(states, out_states),
                upd_state,
                jax.lax.pmean(jnp.mean(losses), DATA_AXIS),
            )

        repl = P()
        sharded = P(None, DATA_AXIS)  # [freq, global_b, ...]: batch sharded
        fn = shard_map(
            worker,
            mesh=self.mesh,
            in_specs=(repl, repl, repl, sharded, sharded, m_spec, lm_spec,
                      repl, P(None)),
            out_specs=(repl, repl, repl, repl),
            check_vma=False,
        )
        # params/states/upd_state donated: fit() re-binds all three from
        # the averaging round's outputs (the recurrent stream-state leaves
        # that pass through unaveraged alias input to output, which is
        # exactly what donation expresses)
        from deeplearning4j_tpu.ops import dispatch

        return dispatch.instrumented_jit(
            fn, "param_avg_worker", self.net.dispatch_stats,
            donate=(0, 1, 2), step=True)

    def _build_step(self, has_mask: bool, has_label_mask: bool):
        """MultiLayerNetwork worker (list states, one shared updater)."""
        net = self.net
        from deeplearning4j_tpu.nn.layers.factory import STATEFUL_RNN_CONFS

        def combine(states, out_states):
            return [
                (
                    st_in  # recurrent stream state: local, not averaged
                    if isinstance(net.conf.layers[i], STATEFUL_RNN_CONFS)
                    else jax.lax.pmean(st_out, DATA_AXIS)
                )
                for i, (st_in, st_out) in enumerate(zip(states, out_states))
            ]

        sharded, repl = P(None, DATA_AXIS), P()
        loss_call, update_call, _ = container_calls(net)
        return self._build_worker(
            loss_call=loss_call,
            update_call=update_call,
            combine_states=combine,
            m_spec=sharded if has_mask else repl,
            lm_spec=sharded if has_label_mask else repl,
        )

    def _build_step_graph(self, n_labels: int, has_label_masks: bool):
        """ComputationGraph worker (SparkComputationGraph.java:68 fit drives
        the same master): dict inputs/masks keyed by input name, per-output
        label lists, per-vertex state dicts and updaters (net._update_all)."""
        net = self.net
        from deeplearning4j_tpu.nn.layers.factory import STATEFUL_RNN_CONFS

        def combine(states, out_states):
            return {
                n: (
                    states[n]  # recurrent stream state: local, not averaged
                    if isinstance(net.conf.vertices[n], STATEFUL_RNN_CONFS)
                    else jax.lax.pmean(out_states[n], DATA_AXIS)
                )
                for n in out_states
            }

        sharded, repl = P(None, DATA_AXIS), P()  # prefix spec: every leaf
        loss_call, update_call, _ = container_calls(net)
        return self._build_worker(
            loss_call=loss_call,
            update_call=update_call,
            combine_states=combine,
            m_spec=sharded,
            lm_spec=sharded if has_label_masks else repl,
        )

    def _to_rounds(self, a):
        return stack_rounds(a, self.averaging_frequency)

    def _step_rngs(self):
        return round_step_rngs(self.net, self.averaging_frequency)

    def _fit_graph(self, features, labels, masks=None,
                   label_masks=None) -> float:
        """One ComputationGraph averaging round (SparkComputationGraph.fit
        semantics): features/labels may be single arrays or per-input /
        per-output lists; masks a per-input dict-or-list; label_masks a
        per-output list."""
        from deeplearning4j_tpu.nn.graph import _as_list

        net = self.net
        inputs = net._as_inputs(features)
        labels_l = [jnp.asarray(l) for l in _as_list(labels)]
        if len(labels_l) != len(net.conf.outputs):
            raise ValueError(
                f"expected {len(net.conf.outputs)} label arrays, "
                f"got {len(labels_l)}"
            )
        x = {k: self._to_rounds(v) for k, v in inputs.items()}
        y = [self._to_rounds(l) for l in labels_l]
        ms = {k: self._to_rounds(v)
              for k, v in net._as_masks(masks).items()}
        lms = (
            [None if m is None else self._to_rounds(m) for m in label_masks]
            if label_masks is not None
            else None
        )
        first = next(iter(x.values()))
        if hasattr(net, "_reset_rnn_states"):
            net._reset_rnn_states(first.shape[1] // self.n)
        key = ("graph", len(y), lms is not None)
        if key not in self._step_fns:
            self._step_fns[key] = self._build_step_graph(
                len(y), lms is not None)
        net.params, net.states, net.updater_state, loss = self._step_fns[key](
            net.params, net.states, net.updater_state, x, y, ms, lms,
            jnp.asarray(net.iteration, jnp.int32), self._step_rngs(),
        )
        net.iteration += self.averaging_frequency
        net._score_dev = loss  # CG exposes score via the score_value property
        return loss

    def fit(self, features, labels, mask=None, label_mask=None) -> float:
        """One averaging round: features [freq*n*b, ...] or [freq, n*b, ...].
        Feature/label masks (variable-length sequences) shard with the batch
        (reference workers pass the DataSet's mask arrays to net.fit).
        Accepts both containers — MultiLayerNetwork (array features/labels)
        and ComputationGraph (array-or-list features/labels), the same
        duality as ParallelWrapper.fit."""
        net = self.net
        if net.params is None:
            net.init()
        if hasattr(net, "_as_inputs"):  # ComputationGraph
            return self._fit_graph(features, labels, mask, label_mask)
        x = self._to_rounds(features)
        y = self._to_rounds(labels)
        m = self._to_rounds(mask)
        lm = self._to_rounds(label_mask)
        # worker RNN stream state is per-round local (reference workers are
        # rebuilt from broadcast each split): size it for the LOCAL batch so
        # the scan carry is shape-stable
        if hasattr(net, "_reset_rnn_states"):
            net._reset_rnn_states(x.shape[1] // self.n)
        key = (m is not None, lm is not None)
        if key not in self._step_fns:
            self._step_fns[key] = self._build_step(*key)
        net.params, net.states, net.updater_state, loss = self._step_fns[key](
            net.params,
            net.states,
            net.updater_state,
            x,
            y,
            m,
            lm,
            jnp.asarray(net.iteration, jnp.int32),
            self._step_rngs(),
        )
        net.iteration += self.averaging_frequency
        net.score_value = loss
        return loss
