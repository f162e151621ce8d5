"""ComputationGraph: the DAG-network runtime (multi-input / multi-output).

Reference capability: org.deeplearning4j.nn.graph.ComputationGraph
(SURVEY.md §2.5, call stack §3.2). As with MultiLayerNetwork, the DAG is
lowered to one pure function over the precomputed topological order and
trained with a single donated-buffer XLA step per minibatch.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.autodiff.samediff import (
    _as_batches, _host_array, _ones_mask, _pad_to_bucket, _prepare_batches,
    _split_dataset_full)
from deeplearning4j_tpu.evaluation import Evaluation
from deeplearning4j_tpu.ndarray import INDArray
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration, GraphVertex)
from deeplearning4j_tpu.nn.conf.layers import (
    BaseLayer, OUTPUT_LAYER_TYPES)
from deeplearning4j_tpu.nn.multilayer import _normalize_grads, _unwrap


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        for out in conf.outputs:
            node, _ = conf.nodes[out]
            if not isinstance(node, OUTPUT_LAYER_TYPES):
                raise ValueError(f"output node {out!r} must be an "
                                 f"OutputLayer/LossLayer")
        self._params: dict[str, dict] = {}
        self._states: dict[str, dict] = {}
        self._opt_states: dict = {}
        self._prec_state: dict = {}  # loss-scaler state (ISSUE 4); {} = off
        self._listeners: list = []
        self._train_step = None
        self._train_step_plan = None  # health BuildPlan compiled into it
        self._multi_step = None
        self._bucket = None  # fit batch-size bucket (pad ragged tail)
        self._infer_fn_cache = {}
        self._iteration = 0
        self._epoch = 0
        self._score = None
        self._initialized = False

    def init(self):
        # master weights in the policy's param dtype (fp32 under any
        # *_mixed policy); exactly conf.dtype without a policy
        pol = self._precision_policy()
        dtype = pol.param_jnp
        key = jax.random.key(self.conf.seed)
        for i, name in enumerate(self.conf.topo_order):
            node, _ = self.conf.nodes[name]
            if isinstance(node, BaseLayer):
                self._params[name] = node.init_params(
                    jax.random.fold_in(key, i), dtype)
                self._states[name] = node.init_state(dtype)
            else:
                self._params[name] = {}
                self._states[name] = {}
        self._opt_states = {
            name: (self._updater(name).init_state(p) if p else ())
            for name, p in self._params.items()
        }
        scaler = self._loss_scaler()
        self._prec_state = scaler.init_state() if scaler else {}
        self._initialized = True
        return self

    def _precision_policy(self):
        return self.conf.precision_policy

    def _loss_scaler(self):
        from deeplearning4j_tpu.precision import DynamicLossScaler

        if not hasattr(self, "_scaler_cache"):
            self._scaler_cache = DynamicLossScaler.for_policy(
                self._precision_policy())
        return self._scaler_cache

    def _updater(self, name):
        node, _ = self.conf.nodes[name]
        u = getattr(node, "updater", None)
        return u if u is not None else self.conf.defaults["updater"]

    def _check_init(self):
        if not self._initialized:
            raise RuntimeError("call init() first")

    # -- pure forward over the DAG ------------------------------------------
    def _forward(self, params, states, inputs: dict, training, rng,
                 stop_before_output=False):
        # float inputs follow the policy's compute dtype (== the
        # configured dataType without a policy); int inputs (embedding
        # ids) pass through, and f64 is left alone — the gradient-check
        # harness runs fp64
        dt = self._precision_policy().compute_jnp
        env = {}
        for k, v in inputs.items():
            v = jnp.asarray(v)
            if jnp.issubdtype(v.dtype, jnp.floating) and v.dtype != dt \
                    and v.dtype != jnp.float64:
                v = v.astype(dt)
            env[k] = v
        new_states = {}
        for i, name in enumerate(self.conf.topo_order):
            node, ins = self.conf.nodes[name]
            xs = [env[n] for n in ins]
            if isinstance(node, GraphVertex):
                env[name] = node.apply(*xs)
                new_states[name] = {}
            elif stop_before_output and name in self.conf.outputs:
                # leave the pre-output input available for the loss
                env[name] = xs[0]
                new_states[name] = states[name]
            else:
                lrng = jax.random.fold_in(rng, i) if rng is not None else None
                arg = xs if getattr(node, "MULTI_INPUT", False) else xs[0]
                y, st = node.apply(params[name], states[name], arg,
                                   training, lrng)
                env[name] = y
                new_states[name] = st
        return env, new_states

    def _loss_from(self, params, states, inputs, labels: dict, training, rng,
                   masks: dict | None = None):
        from deeplearning4j_tpu.precision import cast_floating

        pol = self._precision_policy()
        if pol.is_mixed:
            # cast INSIDE whatever is differentiated: the transpose
            # upcasts gradients back to the master dtype
            params = cast_floating(params, pol.compute_jnp)
        env, new_states = self._forward(params, states, inputs, training, rng,
                                        stop_before_output=True)
        loss = 0.0
        for out in self.conf.outputs:
            node, _ = self.conf.nodes[out]
            mask = None if masks is None else masks.get(out)
            if training and getattr(node, "LOSS_UPDATES_STATE", False):
                # loss-state channel (see MultiLayerNetwork._loss_from)
                term, new_states[out] = node.compute_loss_with_state(
                    params[out], env[out], labels[out], mask, states[out])
                loss = loss + term
            else:
                loss = loss + node.compute_loss(params[out], env[out],
                                                labels[out], mask)
        # regularization
        for name, (node, _) in self.conf.nodes.items():
            p = params.get(name)
            if not p:
                continue
            l2 = getattr(node, "l2", None) or 0.0
            l1 = getattr(node, "l1", None) or 0.0
            if l2:
                loss = loss + 0.5 * l2 * sum(
                    jnp.sum(w * w) for w in jax.tree_util.tree_leaves(p))
            if l1:
                loss = loss + l1 * sum(
                    jnp.sum(jnp.abs(w)) for w in jax.tree_util.tree_leaves(p))
        return loss, new_states

    # -- training ------------------------------------------------------------
    def _layer_labels(self):
        """Health-row labels (one per node + the trailing loss row),
        row-aligned with the health array the step returns (same
        iteration order as _step_math)."""
        from deeplearning4j_tpu.telemetry import health as _health

        return _health.with_loss_row(
            f"{name}:{type(node).__name__}"
            for name, (node, _) in self.conf.nodes.items())

    def _step_math(self, params, states, opt_states, prec, inputs, labels,
                   masks, rng, it, health_plan=None):
        """One optimizer step as a pure traced function (shared by the
        single-step jit and the scan-of-K-steps jit). Health stats ride
        along per node when the plan collects, and the precision
        policy's loss scaler (scale/unscale/finite-gate/state-advance)
        compiles in exactly as in MultiLayerNetwork._step_math."""
        from deeplearning4j_tpu.telemetry import health as _health

        plan = health_plan or _health.INACTIVE
        scaler = self._loss_scaler()
        scaling = scaler is not None and bool(prec)

        def loss_fn(p):
            loss, ns = self._loss_from(p, states, inputs, labels, True,
                                       rng, masks)
            if scaling:
                return scaler.scale_loss(loss, prec), (loss, ns)
            return loss, (loss, ns)

        (_, (loss, new_states)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if scaling:
            grads = scaler.unscale(grads, prec)
            finite = scaler.all_finite(grads)
        new_params, new_opts, stats = {}, {}, []
        for name, (node, _) in self.conf.nodes.items():
            g = grads.get(name)
            if not g:
                new_params[name] = params[name]
                new_opts[name] = opt_states[name]
                if plan.collect:
                    stats.append(_health.zero_stats())
                continue
            g = _normalize_grads(
                g, getattr(node, "gradientNormalization", None),
                getattr(node, "gradientNormalizationThreshold", None)
                or 1.0)
            upd, new_opt = self._updater(name).apply_mixed(
                g, opt_states[name], params[name], it)
            new_params[name] = jax.tree_util.tree_map(
                lambda p, u: p - u, params[name], upd)
            new_opts[name] = new_opt
            if plan.collect:
                stats.append(_health.layer_stats(g, upd, new_params[name]))
        if plan.collect:
            stats.append(_health.loss_stats(loss))
        health = _health.stack_stats(stats) if plan.collect else None
        if scaling:
            new_params = _health.keep_if(finite, new_params, params)
            new_opts = _health.keep_if(finite, new_opts, opt_states)
            new_states = _health.keep_if(finite, new_states, states)
            new_prec = scaler.next_state(prec, finite)
        else:
            new_prec = prec
        if plan.skip:
            ok = _health.step_ok(health)
            new_params = _health.keep_if(ok, new_params, params)
            new_opts = _health.keep_if(ok, new_opts, opt_states)
            new_states = _health.keep_if(ok, new_states, states)
        return loss, new_params, new_states, new_opts, health, new_prec

    def _build_train_step(self, health_plan=None):
        def step(params, states, opt_states, prec, inputs, labels, masks,
                 rng, it):
            return self._step_math(params, states, opt_states, prec,
                                   inputs, labels, masks, rng, it,
                                   health_plan=health_plan)

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _policy_label(self, plan):
        return (f"{self._precision_policy().name}"
                f"/h{int(plan.collect)}{int(plan.skip)}")

    def _refresh_train_step(self):
        """(re)build the compiled step when missing or when the health
        build plan changed (see MultiLayerNetwork._refresh_train_step)."""
        from deeplearning4j_tpu import compilestore
        from deeplearning4j_tpu.telemetry import health as _health

        plan = _health.build_plan(self._listeners)
        if self._train_step is None or \
                getattr(self, "_train_step_plan", None) != plan:
            step = self._build_train_step(plan)
            if compilestore.enabled():
                # ISSUE 13: warm restarts deserialize instead of
                # recompiling (program digest = full graph conf)
                step = compilestore.StoredJit(
                    step, "graph",
                    program=(f"train:ComputationGraph:"
                             f"{self.conf.to_json()}"
                             f":policy={self._policy_label(plan)}"),
                    policy=self._policy_label(plan),
                    donation=(0, 1, 2))
            self._train_step = step
            self._train_step_plan = plan
        return plan

    def _build_multi_step(self, health_plan=None):
        from deeplearning4j_tpu.telemetry import health as _health

        plan = health_plan or _health.INACTIVE

        def many(params, states, opts, prec, inputs_k, labels_k, masks_k,
                 rng0, it0):
            def body(carry, xs):
                params, states, opts, prec, it = carry
                inputs, labels, masks = xs
                rng = jax.random.fold_in(rng0, it)
                loss, params, states, opts, health, prec = self._step_math(
                    params, states, opts, prec, inputs, labels, masks,
                    rng, it, health_plan=plan)
                ys = (loss, health) if plan.collect else loss
                return (params, states, opts, prec, it + 1), ys

            carry, ys = jax.lax.scan(
                body, (params, states, opts, prec, it0),
                (inputs_k, labels_k, masks_k))
            losses, healths = ys if plan.collect else (ys, None)
            params, states, opts, prec, _ = carry
            return losses, params, states, opts, healths, prec

        return jax.jit(many, donate_argnums=(0, 1, 2))

    def fitMultiBatch(self, features_k, labels_k):
        """K optimizer steps in ONE device launch over stacked [K, B, ...]
        minibatches via lax.scan (see MultiLayerNetwork.fitMultiBatch).
        Single-input single-output graphs only. Returns the [K] losses."""
        self._check_init()
        from deeplearning4j_tpu.telemetry import health as _health

        plan = _health.build_plan(self._listeners)
        if not isinstance(getattr(self, "_multi_step", None), dict):
            self._multi_step = {}
        if plan not in self._multi_step:
            self._multi_step[plan] = self._build_multi_step(plan)
        # keep device-resident stacks on device (a _host_array bounce
        # would round-trip the whole [K,B,...] block D2H then H2D)
        f_k = _unwrap(features_k) if isinstance(
            features_k, (jax.Array, INDArray)) else _host_array(features_k)
        l_k = _unwrap(labels_k) if isinstance(
            labels_k, (jax.Array, INDArray)) else _host_array(labels_k)
        inputs_k = {self.conf.inputs[0]: f_k}
        labels_k = {self.conf.outputs[0]: l_k}
        masks_k = {self.conf.outputs[0]: np.ones(
            (l_k.shape[0],) + _ones_mask(l_k[0]).shape, np.float32)}
        rng0 = jax.random.key(self.conf.seed + 1)
        it0 = self._iteration
        from deeplearning4j_tpu import precision as _precision

        pm = _precision.monitor_for("graph", self._precision_policy())
        if pm is not None:
            pm.baseline_from(self._prec_state)
        (losses, self._params, self._states, self._opt_states, healths,
         self._prec_state) = self._multi_step[plan](
                self._params, self._states, self._opt_states,
                self._prec_state, inputs_k, labels_k, masks_k, rng0,
                jnp.asarray(self._iteration, jnp.int32))
        self._iteration += int(f_k.shape[0])
        self._score = float(losses[-1])
        if pm is not None:
            pm.on_launch(range(it0, self._iteration), self._prec_state)
        if healths is not None:
            hm = _health.monitor_for("graph", self._layer_labels(),
                                     self._listeners)
            if hm is not None:
                hm.precision = pm
                for k in range(int(f_k.shape[0])):
                    hm.on_step(it0 + k, healths[k])
                hm.flush()
        return losses

    def _feeds(self, ds, with_ones_masks=False):
        """Host-side feed dicts (numpy throughout: committed-vs-uncommitted
        inputs key separate jit cache entries even at identical avals, and
        a jnp bounce would cost a device round-trip per batch)."""
        feats, labels, _, lmasks = _split_dataset_full(ds)
        inputs = {n: _host_array(f) for n, f in zip(self.conf.inputs, feats)}
        lab = {n: _host_array(l) for n, l in zip(self.conf.outputs, labels)}
        masks = {}
        for n, m in zip(self.conf.outputs, lmasks):
            if m is not None:
                masks[n] = _host_array(m, np.float32)
            elif with_ones_masks:
                masks[n] = _ones_mask(lab[n])
        return inputs, lab, masks

    # -- TBPTT + streaming state (reference: ComputationGraph truncated
    # BPTT + rnnTimeStep; same chunked-segment scheme as
    # MultiLayerNetwork._fit_tbptt, over the DAG's recurrent nodes) ---------
    def _recurrent_nodes(self, forbid_bidirectional=False):
        from deeplearning4j_tpu.nn.conf.layers import Bidirectional

        out = []
        for name, (node, _ins) in self.conf.nodes.items():
            if isinstance(node, Bidirectional):
                if forbid_bidirectional:
                    raise ValueError(
                        f"node {name!r} is Bidirectional: streaming "
                        f"rnnTimeStep/TBPTT cannot carry state through a "
                        f"layer that consumes the whole sequence")
                continue
            if getattr(node, "IS_RECURRENT", False) or getattr(
                    getattr(node, "rnn", None), "IS_RECURRENT", False):
                out.append(name)
        return out

    def _seed_rnn_states(self, states, batch_size):
        dtype = self.conf.dtype
        out = dict(states)
        for name in self._recurrent_nodes():
            node, _ = self.conf.nodes[name]
            target = node.rnn if hasattr(node, "rnn") and getattr(
                node.rnn, "IS_RECURRENT", False) and not getattr(
                node, "IS_RECURRENT", False) else node
            out[name] = target.streaming_state(batch_size, dtype)
        return out

    def _strip_rnn_states(self, states):
        out = dict(states)
        for name in self._recurrent_nodes():
            out[name] = {}
        return out

    def _fit_tbptt(self, params, states, opts, prec, inputs, labels, masks,
                   base_key, hm=None, pm=None):
        from deeplearning4j_tpu.nn.conf.configuration import BackpropType

        assert self.conf.backpropType == BackpropType.TruncatedBPTT
        L = self.conf.tbpttLength
        T = max(v.shape[2] for v in inputs.values() if v.ndim == 3)
        n = next(iter(inputs.values())).shape[0]
        self._recurrent_nodes(forbid_bidirectional=True)
        states = self._seed_rnn_states(states, n)
        loss = None
        for t0 in range(0, T, L):
            def chunk(v, is_mask=False):
                if is_mask:
                    return v[:, t0:t0 + L] if v.ndim == 2 else v
                return v[:, :, t0:t0 + L] if v.ndim == 3 else v

            ic = {k: chunk(v) for k, v in inputs.items()}
            lc = {k: chunk(v) for k, v in labels.items()}
            mc = {k: chunk(v, is_mask=True) for k, v in masks.items()}
            seg = min(L, T - t0)
            if seg < L:
                # zero-pad the tail segment to the fixed tbptt shape and
                # mask the padded timesteps out of the loss
                pad = L - seg
                ic = {k: (np.concatenate(
                    [v, np.zeros(v.shape[:2] + (pad,), v.dtype)], axis=2)
                    if v.ndim == 3 else v) for k, v in ic.items()}
                lc = {k: (np.concatenate(
                    [v, np.zeros(v.shape[:2] + (pad,), v.dtype)], axis=2)
                    if v.ndim == 3 else v) for k, v in lc.items()}
                mc = {k: (np.concatenate(
                    [v, np.zeros((v.shape[0], pad), v.dtype)], axis=1)
                    if v.ndim == 2 else v) for k, v in mc.items()}
            it_used = self._iteration
            rng = jax.random.fold_in(base_key, it_used)
            loss, params, states, opts, health, prec = self._train_step(
                params, states, opts, prec, ic, lc, mc, rng, it_used)
            self._iteration += 1
            if hm is not None or pm is not None:
                # rebind first: on_step may raise (HALT) and the caller
                # must not be left holding this step's donated buffers
                self._params, self._states, self._opt_states = (
                    params, self._strip_rnn_states(states), opts)
                self._prec_state = prec
                if pm is not None:
                    pm.on_step(it_used, prec)
                if hm is not None:
                    hm.on_step(it_used, health)
        return loss, params, self._strip_rnn_states(states), opts, prec

    def rnnTimeStep(self, *xs):
        """Streaming inference with carried recurrent state; each x is
        [N, C] (one timestep) or [N, C, T] (a chunk)."""
        self._check_init()
        arrs = [_unwrap(x) for x in xs]
        single = arrs[0].ndim == 2
        if single:
            arrs = [a[:, :, None] for a in arrs]
        n = arrs[0].shape[0]
        rec = set(self._recurrent_nodes(forbid_bidirectional=True))
        if getattr(self, "_stream_states", None) is None or \
                getattr(self, "_stream_batch", None) != n:
            seeded = self._seed_rnn_states(self._states, n)
            self._stream_states = {k: seeded[k] for k in rec}
            self._stream_batch = n
        # only the recurrent carry is cached; everything else (BN running
        # stats, ...) comes fresh from self._states so an interleaved
        # fit() (which rebinds self._states after donation) can't leave
        # stale or deleted buffers behind
        states = {k: (self._stream_states[k] if k in rec else v)
                  for k, v in self._states.items()}
        inputs = {k: v for k, v in zip(self.conf.inputs, arrs)}
        key = "stream"
        if key not in self._infer_fn_cache:
            def fn(params, states, inputs):
                params = self._cast_for_inference(params)
                env, ns = self._forward(params, states, inputs, False, None)
                return [self._cast_output(env[o])
                        for o in self.conf.outputs], ns

            self._infer_fn_cache[key] = jax.jit(fn)
        ys, new_states = self._infer_fn_cache[key](
            self._params, states, inputs)
        self._stream_states = {k: new_states[k] for k in rec}
        outs = [INDArray(y[:, :, 0]) if single and y.ndim == 3
                else INDArray(y) for y in ys]
        return outs[0] if len(outs) == 1 else outs

    def rnnClearPreviousState(self):
        self._stream_states = None
        self._stream_batch = None

    def fit(self, data, epochs: int = 1):
        self._check_init()
        import time as _time

        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.telemetry import health as _health

        plan = self._refresh_train_step()
        policy_label = self._policy_label(plan)
        params, states, opts = self._params, self._states, self._opt_states
        prec = self._prec_state
        base_key = jax.random.key(self.conf.seed + 1)
        last = None
        # one flag check per fit(): with telemetry disabled both are
        # None and the loop body makes zero registry calls per step
        tele = telemetry.loop_instruments("graph")
        hm = _health.monitor_for("graph", self._layer_labels(),
                                 self._listeners)
        from deeplearning4j_tpu import precision as _precision

        pm = _precision.monitor_for("graph", self._precision_policy())
        if pm is not None:
            pm.baseline_from(prec)
        if hm is not None:
            hm.precision = pm
        # sampled trace root + step-time-throttled XLA cost attribution
        # (ISSUE 10) — the MultiLayerNetwork.fit treatment, graph loop
        from deeplearning4j_tpu.telemetry import (
            compile_ledger, costmodel, memledger, tracing)
        import sys as _sys

        # HBM ownership claim (ISSUE 14): same contract as the
        # multilayer loop — per-net key, None when disabled, one
        # gauge-set per step
        mem = None if tele is None else memledger.claim_for_owner(
            self, "train", "graph",
            tree={"p": params, "s": states, "o": opts, "prec": prec},
            model=type(self).__name__)

        tspan = tracing.trace_or_span("train.graph", loop="graph")
        tspan.__enter__()
        steps_seen = 0
        try:
            for epoch_i in range(epochs):
                batches, data = _prepare_batches(data, epoch_i, epochs)
                for ds in batches:
                    # explicit ones masks keep the jit signature stable
                    # across masked/unmasked and padded batches (one
                    # executable)
                    inputs, labels, masks = self._feeds(
                        ds, with_ones_masks=True)
                    n = next(iter(inputs.values())).shape[0]
                    if self._bucket is None or n > self._bucket:
                        self._bucket = n
                    if n < self._bucket:
                        for k in inputs:
                            (inputs[k],), _, _ = _pad_to_bucket(
                                [inputs[k]], np.ones((n,), np.float32),
                                self._bucket)
                        for k in labels:
                            (labels[k],), masks[k], _ = _pad_to_bucket(
                                [labels[k]], masks[k], self._bucket)
                    from deeplearning4j_tpu.nn.conf.configuration import (
                        BackpropType)

                    tbptt = (self.conf.backpropType ==
                             BackpropType.TruncatedBPTT
                             and self.conf.tbpttLength
                             and any(v.ndim == 3
                                     and v.shape[2] > self.conf.tbpttLength
                                     for v in inputs.values()))
                    if tele is not None:
                        t_step = _time.perf_counter()
                    try:
                        if tbptt:
                            loss, params, states, opts, prec = \
                                self._fit_tbptt(
                                    params, states, opts, prec, inputs,
                                    labels, masks, base_key, hm=hm, pm=pm)
                        else:
                            it_used = self._iteration
                            rng = jax.random.fold_in(base_key, it_used)
                            (loss, params, states, opts, health,
                             prec) = self._train_step(
                                params, states, opts, prec, inputs,
                                labels, masks, rng, it_used)
                            self._iteration += 1
                    except Exception as e:
                        # OOM forensics (ISSUE 14): typed error + flight
                        # event naming this seam and the top HBM claims
                        memledger.raise_if_oom(e, site="train.graph",
                                               step=self._iteration)
                        raise
                    if tele is not None:
                        dt_step = _time.perf_counter() - t_step
                        tele.record_step(dt_step, n,
                                         exemplar=tspan.trace_id)
                        if mem is not None:
                            # steady state: ONE gauge-set per step
                            mem.touch()
                        if tspan and not tbptt:
                            tracing.emit("train.step", tspan.ctx(),
                                         t_step, t_step + dt_step,
                                         step=it_used)
                        steps_seen += 1
                        if not tbptt:
                            costmodel.maybe_attribute(
                                tele, "graph", self._train_step,
                                (params, states, opts, prec, inputs,
                                 labels, masks, rng, it_used),
                                self, steps_seen, dt_step)
                            # recompile forensics (ISSUE 11): one
                            # thread-local read unless this step
                            # actually compiled
                            compile_ledger.note_step(
                                "graph", self._train_step,
                                (params, states, opts, prec, inputs,
                                 labels, masks, rng, it_used),
                                policy=policy_label,
                                window=(t_step, t_step + dt_step))
                    # rebind BEFORE the health monitor runs: its HALT
                    # policy raises out of fit() and the caller must find
                    # live params, not the buffers this step donated
                    self._params, self._states, self._opt_states = (
                        params, states, opts)
                    self._prec_state = prec
                    if not tbptt:
                        if pm is not None:
                            pm.on_step(it_used, prec)  # before hm
                        if hm is not None:
                            hm.on_step(it_used, health)
                    last = loss
                    if self._listeners:
                        self._score = float(loss)
                        for listener in self._listeners:
                            listener.iterationDone(self, self._iteration,
                                                   self._epoch)
                self._epoch += 1
            if pm is not None:
                pm.flush()   # before hm.flush: same-step skip handshake
            if hm is not None:
                hm.flush()   # drain the one-behind slot (HALT may raise)
            if last is not None:
                self._score = float(last)
            return self
        finally:
            tspan.__exit__(*_sys.exc_info())

    # -- inference -----------------------------------------------------------
    def _cast_for_inference(self, params):
        """Mixed policy: inference runs in the compute dtype too (the
        input cast in _forward already truncates, so casting the params
        is what actually buys the bf16 matmuls); identity otherwise."""
        from deeplearning4j_tpu.precision import cast_floating

        pol = self._precision_policy()
        return cast_floating(params, pol.compute_jnp) if pol.is_mixed \
            else params

    def _cast_output(self, y):
        pol = self._precision_policy()
        if jnp.issubdtype(y.dtype, jnp.floating) and \
                y.dtype != pol.output_jnp:
            return y.astype(pol.output_jnp)
        return y

    def output(self, *xs, train=False):
        """output(x1, x2, ...) -> list of output arrays (one per configured
        output)."""
        self._check_init()
        inputs = {n: _unwrap(x) for n, x in zip(self.conf.inputs, xs)}
        key = ("out", train)
        if key not in self._infer_fn_cache:
            def fn(params, states, inputs):
                params = self._cast_for_inference(params)
                env, _ = self._forward(params, states, inputs, train, None)
                return [self._cast_output(env[o])
                        for o in self.conf.outputs]

            self._infer_fn_cache[key] = jax.jit(fn)
        ys = self._infer_fn_cache[key](self._params, self._states, inputs)
        return [INDArray(y) for y in ys]

    def outputSingle(self, *xs, train=False) -> INDArray:
        return self.output(*xs, train=train)[0]

    def score(self, dataset=None) -> float:
        self._check_init()
        if dataset is None:
            if self._score is None:
                raise ValueError("no score yet")
            return self._score
        inputs, labels, masks = self._feeds(dataset)
        loss, _ = self._loss_from(self._params, self._states, inputs, labels,
                                  False, None, masks)
        return float(loss)

    def evaluate(self, iterator, numClasses=None) -> Evaluation:
        """Ragged final batches pad up to the running bucket (serving
        `pad_rows`) and slice back, so eval compiles ONE executable."""
        from deeplearning4j_tpu.serving.buckets import pad_rows

        self._check_init()
        ev = Evaluation(numClasses)
        bucket = None
        for ds in _as_batches(iterator):
            feats, labels, _, lmasks = _split_dataset_full(ds)
            fs = [_host_array(f) for f in feats]
            n = fs[0].shape[0]
            if bucket is None or n > bucket:
                bucket = n
            out = self.output(*[pad_rows(f, bucket) for f in fs])[0]
            ev.eval(labels[0], out.toNumpy()[:n], mask=lmasks[0])
        return ev

    def numParams(self) -> int:
        return sum(int(np.prod(v.shape)) for p in self._params.values()
                   for v in p.values())

    def params(self) -> INDArray:
        leaves = []
        for name in self.conf.topo_order:
            p = self._params[name]
            for k in sorted(p):
                leaves.append(jnp.ravel(p[k]))
        if not leaves:
            return INDArray(jnp.zeros((0,)))
        return INDArray(jnp.concatenate(leaves))

    def setParams(self, flat):
        """Install a flat vector in params() order (topo order, sorted
        param names per node)."""
        flat = jnp.asarray(flat).reshape(-1)
        off = 0
        for name in self.conf.topo_order:
            p = self._params[name]
            for k in sorted(p):
                n = int(np.prod(p[k].shape)) if p[k].shape else 1
                p[k] = flat[off: off + n].reshape(p[k].shape).astype(
                    p[k].dtype)
                off += n
        self._train_step = None
        self._multi_step = None

    def getParam(self, node: str, name: str) -> INDArray:
        return INDArray(self._params[node][name])

    def setListeners(self, *listeners):
        self._listeners = list(listeners)
        return self

    def gradients(self, inputs_and_labels) -> dict:
        """Per-node analytic gradients for the gradient-check harness."""
        self._check_init()
        inputs, labels, masks = self._feeds(inputs_and_labels)

        def loss_fn(p):
            loss, _ = self._loss_from(p, self._states, inputs, labels, False,
                                      None, masks)
            return loss

        return jax.grad(loss_fn)(self._params)

    def summary(self) -> str:
        lines = [f"{'name':<24}{'type':<26}{'nParams':<10}{'inputs'}"]
        for name in self.conf.topo_order:
            node, ins = self.conf.nodes[name]
            n = sum(int(np.prod(v.shape))
                    for v in self._params.get(name, {}).values())
            lines.append(f"{name:<24}{type(node).__name__:<26}{n:<10}{ins}")
        lines.append(f"Total params: {self.numParams()}")
        return "\n".join(lines)
