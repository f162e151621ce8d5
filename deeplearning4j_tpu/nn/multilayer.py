"""MultiLayerNetwork: the sequential-network runtime.

Reference capability: org.deeplearning4j.nn.multilayer.MultiLayerNetwork
(SURVEY.md §2.5, call stack §3.1). The reference's fit() walks layers
calling activate/backpropGradient with a JNI dispatch per op and assembles
a flat gradient for the Solver. Here the whole network lowers to ONE pure
function and fit() runs ONE compiled XLA step per minibatch:
forward + backward (jax.grad) + every per-layer updater fused, with
parameter/updater-state buffers donated (device-resident params — the
PJRT equivalent of the reference's flat-param views, SURVEY.md §7 hard
part 2). No Solver, no per-layer workspaces: XLA owns scheduling.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.autodiff.samediff import (
    _as_batches, _host_array, _ones_mask, _pad_to_bucket, _prepare_batches,
    _split_dataset_full)
from deeplearning4j_tpu.evaluation import Evaluation, RegressionEvaluation
from deeplearning4j_tpu.ndarray import INDArray
from deeplearning4j_tpu.nn.conf.configuration import (
    BackpropType, MultiLayerConfiguration, _apply_preprocessor)
from deeplearning4j_tpu.nn.conf.layers import OUTPUT_LAYER_TYPES


def _unwrap(x):
    if isinstance(x, INDArray):
        return x.jax()
    return jnp.asarray(x)


class GradientNormalization:
    ClipL2PerLayer = "clip_l2_per_layer"
    ClipL2PerParamType = "clip_l2_per_param"
    ClipElementWiseAbsoluteValue = "clip_elementwise"
    RenormalizeL2PerLayer = "renorm_l2_per_layer"


def _normalize_grads(grads, mode, threshold):
    if mode is None:
        return grads
    leaves = jax.tree_util.tree_leaves(grads)
    if mode == GradientNormalization.ClipElementWiseAbsoluteValue:
        return jax.tree_util.tree_map(
            lambda g: jnp.clip(g, -threshold, threshold), grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves) + 1e-12)
    if mode == GradientNormalization.RenormalizeL2PerLayer:
        return jax.tree_util.tree_map(lambda g: g / norm, grads)
    scale = jnp.minimum(1.0, threshold / norm)
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        if not self.layers:
            raise ValueError("configuration has no layers")
        out = self.layers[-1]
        if not isinstance(out, OUTPUT_LAYER_TYPES):
            raise ValueError("last layer must be an OutputLayer/LossLayer")
        self._params: list[dict] = []
        self._states: list[dict] = []
        self._opt_states: list = []
        self._prec_state: dict = {}  # loss-scaler state (ISSUE 4); {} = off
        self._listeners: list = []
        self._train_step = None
        self._train_step_plan = None  # health BuildPlan compiled into it
        self._multi_step = None
        self._bucket = None  # fit batch-size bucket (pad ragged tail to it)
        self._infer_fns: dict = {}
        self._profiler_cfg = None
        self._stream_states = None   # rnnTimeStep carried state per layer
        self._stream_batch = None
        self._iteration = 0
        self._epoch = 0
        self._score = None
        self._initialized = False

    # -- init ----------------------------------------------------------------
    def init(self):
        # master weights follow the precision policy's param dtype (fp32
        # under any *_mixed policy — the compute cast happens inside the
        # step); without a policy this is exactly conf.dtype as before
        pol = self._precision_policy()
        dtype = pol.param_jnp
        key = jax.random.key(self.conf.seed)
        self._params, self._states = [], []
        for i, lr in enumerate(self.layers):
            self._params.append(lr.init_params(jax.random.fold_in(key, i),
                                               dtype))
            self._states.append(lr.init_state(dtype))
        self._opt_states = [
            self._layer_updater(i).init_state(p) if p else ()
            for i, p in enumerate(self._params)
        ]
        scaler = self._loss_scaler()
        self._prec_state = scaler.init_state() if scaler else {}
        self._initialized = True
        return self

    def _precision_policy(self):
        return self.conf.precision_policy

    def _loss_scaler(self):
        """The policy's loss scaler (built once per net), or None."""
        from deeplearning4j_tpu.precision import DynamicLossScaler

        if not hasattr(self, "_scaler_cache"):
            self._scaler_cache = DynamicLossScaler.for_policy(
                self._precision_policy())
        return self._scaler_cache

    def _layer_updater(self, i):
        u = self.layers[i].updater
        return u if u is not None else self.conf.defaults["updater"]

    def _check_init(self):
        if not self._initialized:
            raise RuntimeError("call init() first")

    # -- pure forward --------------------------------------------------------
    def _forward(self, params, states, x, training, rng, upto=None,
                 compute_dtype=None):
        # float inputs follow the policy's COMPUTE dtype (== the
        # configured dataType without a policy, so bf16 nets accept
        # f32-fed batches exactly as before); int inputs (embedding ids)
        # pass through, and f64 is left alone — the gradient-check
        # harness runs the whole net in fp64. compute_dtype overrides
        # the policy for callers that pick their own activation dtype.
        dt = compute_dtype if compute_dtype is not None \
            else self._precision_policy().compute_jnp
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dt \
                and x.dtype != jnp.float64:
            x = x.astype(dt)
        new_states = []
        n = len(self.layers) if upto is None else upto
        for i in range(n):
            lr = self.layers[i]
            x = _apply_preprocessor(self.conf.preprocessors[i], x)
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            x, st = lr.apply(params[i], states[i], x, training, lrng)
            new_states.append(st)
        new_states.extend(states[n:])
        return x, new_states

    def _loss_from(self, params, states, f, l, training, rng, mask=None):
        """Forward to the last hidden activation, then the output layer's
        fused pre-activation loss (stable logits path). Under a mixed
        precision policy the (master-dtype) params are cast to the
        compute dtype HERE — inside whatever is being differentiated —
        so the cast's transpose upcasts gradients back to the master
        dtype and Adam/SGD moments stay fp32."""
        from deeplearning4j_tpu.precision import cast_floating

        pol = self._precision_policy()
        if pol.is_mixed:
            params = cast_floating(params, pol.compute_jnp)
        out_idx = len(self.layers) - 1
        h, new_states = self._forward(params, states, f, training, rng,
                                      upto=out_idx)
        h = _apply_preprocessor(self.conf.preprocessors[out_idx], h)
        out_layer = self.layers[out_idx]
        if training and getattr(out_layer, "LOSS_UPDATES_STATE", False):
            # loss-state channel (e.g. OCNN's r threshold): the output
            # layer's apply() never runs during training, so its state
            # updates ride along with the loss
            loss, new_states[out_idx] = out_layer.compute_loss_with_state(
                params[out_idx], h, l, mask, states[out_idx])
        else:
            loss = out_layer.compute_loss(params[out_idx], h, l, mask)
        # hidden-layer aux-loss channel: any layer may store a scalar under
        # "_aux_loss" in its state (e.g. MoELayer's load-balancing loss);
        # summed into the training objective so gradients flow through the
        # layer's forward computation
        if training:
            for st in new_states:
                if isinstance(st, dict) and "_aux_loss" in st:
                    loss = loss + st["_aux_loss"]
        # L1/L2 regularization per layer (reference: BaseLayer.calcRegularizationScore)
        reg = 0.0
        for i, lr in enumerate(self.layers):
            if not params[i]:
                continue
            l2 = lr.l2 or 0.0
            l1 = lr.l1 or 0.0
            if l2:
                reg = reg + l2 * sum(jnp.sum(w * w)
                                     for w in jax.tree_util.tree_leaves(
                                         params[i])) * 0.5
            if l1:
                reg = reg + l1 * sum(jnp.sum(jnp.abs(w))
                                     for w in jax.tree_util.tree_leaves(
                                         params[i]))
        return loss + reg, new_states

    # -- compiled train step -------------------------------------------------
    def _layer_labels(self):
        """Health-row labels (one per layer + the trailing loss row),
        row-aligned with the health array the step returns
        (telemetry.health, ISSUE 3)."""
        from deeplearning4j_tpu.telemetry import health as _health

        return _health.with_loss_row(
            f"{i}:{type(lr).__name__}"
            for i, lr in enumerate(self.layers))

    def _step_math(self, updaters, params, states, opt_states, prec, f, l,
                   lmask, rng, it, health_plan=None):
        """One optimizer step as a pure traced function (shared by the
        single-step jit and the scan-of-K-steps jit). When the health
        plan collects, per-layer stats ride along as one small [L, 5]
        array (fused reductions — no extra dispatch); with the
        SKIP_BATCH policy a non-finite step keeps the old
        params/states/opts via an in-graph select. When the precision
        policy enables loss scaling, `prec` carries the scaler state:
        the loss is scaled before the backward pass, gradients are
        unscaled (exactly — powers of two), a fused finite check gates
        the whole update through the same keep-old-params jnp.where,
        and the scaler state advances — all on device, zero host syncs
        for an overflow step."""
        from deeplearning4j_tpu.telemetry import health as _health

        plan = health_plan or _health.INACTIVE
        scaler = self._loss_scaler()
        scaling = scaler is not None and bool(prec)

        def loss_fn(p):
            loss, ns = self._loss_from(p, states, f, l, True, rng,
                                       mask=lmask)
            if scaling:
                return scaler.scale_loss(loss, prec), (loss, ns)
            return loss, (loss, ns)

        (_, (loss, new_states)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if scaling:
            grads = scaler.unscale(grads, prec)
            finite = scaler.all_finite(grads)
        new_params, new_opts, stats = [], [], []
        for i, lr in enumerate(self.layers):
            g = grads[i]
            if not g:
                new_params.append(params[i])
                new_opts.append(opt_states[i])
                if plan.collect:
                    stats.append(_health.zero_stats())
                continue
            g = _normalize_grads(g, lr.gradientNormalization,
                                 lr.gradientNormalizationThreshold or 1.0)
            upd, new_opt = updaters[i].apply_mixed(g, opt_states[i],
                                                   params[i], it)
            new_params.append(jax.tree_util.tree_map(
                lambda p, u: p - u, params[i], upd))
            new_opts.append(new_opt)
            if plan.collect:
                stats.append(_health.layer_stats(g, upd, new_params[-1]))
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
        updaters = [self._layer_updater(i) for i in range(len(self.layers))]

        def step(params, states, opt_states, prec, f, l, lmask, rng, it):
            return self._step_math(updaters, params, states, opt_states,
                                   prec, f, l, lmask, rng, it,
                                   health_plan=health_plan)

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _policy_label(self, plan):
        """The compile-ledger/executable-store policy label: precision
        policy + health build plan, both compiled INTO the step — a
        change in either recompiles, and forensics should name it
        policy_change."""
        return (f"{self._precision_policy().name}"
                f"/h{int(plan.collect)}{int(plan.skip)}")

    def _step_program(self, plan, kind="train"):
        """Executable-store program digest: the configuration JSON is
        the full architecture + updater spec (weights are arguments),
        and the policy label covers what else is compiled in."""
        return (f"{kind}:MultiLayerNetwork:{self.conf.to_json()}"
                f":policy={self._policy_label(plan)}")

    def _refresh_train_step(self):
        """(re)build the compiled step when missing or when the health
        build plan changed (telemetry/health toggled, policy changed) —
        the plan is compiled into the step, so it must invalidate."""
        from deeplearning4j_tpu import compilestore
        from deeplearning4j_tpu.telemetry import health as _health

        plan = _health.build_plan(self._listeners)
        if self._train_step is None or \
                getattr(self, "_train_step_plan", None) != plan:
            step = self._build_train_step(plan)
            if compilestore.enabled():
                # ISSUE 13: a warm restart's first step deserializes
                # this signature's executable from the persistent
                # store (milliseconds) instead of recompiling
                step = compilestore.StoredJit(
                    step, "fit", program=self._step_program(plan),
                    policy=self._policy_label(plan),
                    donation=(0, 1, 2))
            self._train_step = step
            self._train_step_plan = plan
        return plan

    def _build_multi_step(self, health_plan=None):
        from deeplearning4j_tpu.telemetry import health as _health

        plan = health_plan or _health.INACTIVE
        updaters = [self._layer_updater(i) for i in range(len(self.layers))]

        def many(params, states, opts, prec, f_k, l_k, m_k, rng0, it0):
            def body(carry, xs):
                params, states, opts, prec, it = carry
                f, l, m = xs
                rng = jax.random.fold_in(rng0, it)
                loss, params, states, opts, health, prec = self._step_math(
                    updaters, params, states, opts, prec, f, l, m, rng, it,
                    health_plan=plan)
                ys = (loss, health) if plan.collect else loss
                return (params, states, opts, prec, it + 1), ys

            carry, ys = jax.lax.scan(
                body, (params, states, opts, prec, it0), (f_k, l_k, m_k))
            losses, healths = ys if plan.collect else (ys, None)
            params, states, opts, prec, _ = carry
            return losses, params, states, opts, healths, prec

        return jax.jit(many, donate_argnums=(0, 1, 2))

    def fitMultiBatch(self, features_k, labels_k):
        """K optimizer steps in ONE device launch: features_k/labels_k are
        stacked [K, batch, ...] minibatches consumed by a lax.scan. This
        amortizes per-dispatch host latency the way an on-device input
        pipeline would; semantics match K
        successive fit() calls on the K slices. Returns the [K] losses."""
        self._check_init()
        from deeplearning4j_tpu.telemetry import health as _health

        plan = _health.build_plan(self._listeners)
        if not isinstance(self._multi_step, dict):
            self._multi_step = {}
        # a plan's FIRST launch compiles inside the timed region, so its
        # per-step wall is useless for MFU (10-100x understated)
        warm = plan in self._multi_step
        if not warm:
            many = self._build_multi_step(plan)
            from deeplearning4j_tpu import compilestore

            if compilestore.enabled():
                many = compilestore.StoredJit(
                    many, "fit:multi",
                    program=self._step_program(plan, kind="multi"),
                    policy=self._policy_label(plan),
                    donation=(0, 1, 2))
            self._multi_step[plan] = many
        # keep device-resident stacks on device (a _host_array bounce
        # would round-trip the whole [K,B,...] block D2H then H2D)
        f_k = _unwrap(features_k) if isinstance(
            features_k, (jax.Array, INDArray)) else _host_array(features_k)
        l_k = _unwrap(labels_k) if isinstance(
            labels_k, (jax.Array, INDArray)) else _host_array(labels_k)
        m_k = np.ones((l_k.shape[0],) + _ones_mask(l_k[0]).shape,
                      np.float32)
        rng0 = jax.random.key(self.conf.seed + 1)
        it0 = self._iteration
        from deeplearning4j_tpu import precision as _precision

        pm = _precision.monitor_for("fit", self._precision_policy())
        if pm is not None:
            pm.baseline_from(self._prec_state)   # pre-launch count
        import time as _time

        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.telemetry import costmodel

        t_launch = _time.perf_counter() if telemetry.enabled() else None
        try:
            (losses, self._params, self._states, self._opt_states,
             healths, self._prec_state) = self._multi_step[plan](
                    self._params, self._states, self._opt_states,
                    self._prec_state, f_k, l_k, m_k, rng0,
                    jnp.asarray(self._iteration, jnp.int32))
        except Exception as e:
            from deeplearning4j_tpu.telemetry import memledger

            memledger.raise_if_oom(e, site="train.fitMultiBatch",
                                   step=self._iteration)
            raise
        self._iteration += int(f_k.shape[0])
        self._score = float(losses[-1])
        if t_launch is not None:
            # float(losses[-1]) materialized the launch, so this wall
            # time covers the device work
            n_steps = int(f_k.shape[0])
            per_step = (_time.perf_counter() - t_launch) / max(1, n_steps)
            costmodel.attribute_launch(
                "fit", self._multi_step[plan],
                (self._params, self._states, self._opt_states,
                 self._prec_state, f_k, l_k, m_k, rng0,
                 jnp.asarray(it0, jnp.int32)),
                self, per_step, warm)
        if pm is not None:
            # publish from the launch's FINAL scaler state (already
            # materialized — we just read losses): scale gauge + the
            # overflow-count delta accumulated across the K steps
            pm.on_launch(range(it0, self._iteration), self._prec_state)
        if healths is not None:
            hm = _health.monitor_for("fit", self._layer_labels(),
                                     self._listeners)
            if hm is not None:
                hm.precision = pm
                # the [K, L, 5] stack is already materialized (we just
                # read losses), so processing here adds no sync
                for k in range(int(f_k.shape[0])):
                    hm.on_step(it0 + k, healths[k])
                hm.flush()
        return losses

    def _prefetch_prepare(self):
        """The host-side half of the input pipeline, run in the
        DevicePrefetcher's producer thread: split + pad-to-bucket +
        mask build + device_put, so the fit loop's per-batch host work
        collapses to a queue pop. Falls back to the raw DataSet (and
        the classic host path) for shapes it does not understand."""
        from deeplearning4j_tpu.datasets.prefetch import DeviceBatch

        def prepare(ds):
            feats, labels, _, lmasks = _split_dataset_full(ds)
            if len(feats) != 1 or len(labels) != 1:
                return ds
            f = _host_array(feats[0])
            l = _host_array(labels[0])
            lmask = (_host_array(lmasks[0], np.float32)
                     if lmasks[0] is not None else _ones_mask(l))
            real = f.shape[0]
            bucket = max(real, self._bucket or 0)
            if real < bucket:
                (f, l), lmask, _ = _pad_to_bucket([f, l], lmask, bucket)
            if f.dtype != np.float32:
                f = f.astype(np.float32)
            return DeviceBatch(jax.device_put(f), jax.device_put(l),
                               jax.device_put(lmask), bucket=bucket,
                               real=real)

        return prepare

    def _wrap_prefetch(self, data):
        """Auto-wrap a plain DataSetIterator in a DevicePrefetcher
        (ISSUE 6: transfer overlaps compute on every consumption path).
        Returns (data, prefetcher-or-None); callers close() it."""
        from deeplearning4j_tpu.datasets import prefetch as _prefetch
        from deeplearning4j_tpu.datasets.iterator import (
            DataSetIterator as _DSI)

        if (isinstance(data, _DSI)
                and not isinstance(data, _prefetch.DevicePrefetcher)
                and data.asyncSupported()
                and _prefetch.default_depth() > 0
                and self.conf.backpropType != BackpropType.TruncatedBPTT):
            wrapped = _prefetch.DevicePrefetcher(
                data, prepare=self._prefetch_prepare(), loop="fit")
            return wrapped, wrapped
        return data, None

    def fit(self, data, epochs: int | None = None):
        """fit(iterator) / fit(iterator, nEpochs) / fit(features, labels) /
        fit(DataSet)."""
        self._check_init()
        if epochs is not None and not isinstance(epochs, int):
            # fit(features, labels)
            data, epochs = (data, epochs), 1
        epochs = epochs or 1

        import time as _time

        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.datasets.prefetch import DeviceBatch
        from deeplearning4j_tpu.telemetry import (
            compile_ledger, costmodel, memledger, tracing)
        from deeplearning4j_tpu.telemetry import health as _health

        plan = self._refresh_train_step()
        policy_label = self._policy_label(plan)
        data, _prefetcher = self._wrap_prefetch(data)
        params, states, opts = self._params, self._states, self._opt_states
        prec = self._prec_state
        base_key = jax.random.key(self.conf.seed + 1)
        last_loss = None
        # one flag check per fit(): with telemetry disabled tele is None
        # and the loop body makes zero registry calls per step
        tele = telemetry.loop_instruments("fit")
        # HBM ownership claim (ISSUE 14): params + updater state +
        # loss-scale state, keyed to THIS net (two nets fitting through
        # the same loop label must not re-state one claim). None when
        # disabled — the loop guards on the handle, so the per-step
        # touch() (ONE gauge-set) compiles out
        mem = None if tele is None else memledger.claim_for_owner(
            self, "train", "fit",
            tree={"p": params, "s": states, "o": opts, "prec": prec},
            model=type(self).__name__)
        # same contract for health: hm is None when health/telemetry is
        # off, and the jitted step then returns no health array at all
        hm = _health.monitor_for("fit", self._layer_labels(),
                                 self._listeners)
        # loss-scaler publication (None unless the policy scales AND
        # telemetry is on; the on-device gate runs regardless). The
        # health monitor defers its SKIP_BATCH accounting to pm for
        # steps the scaler already skipped (no double counting).
        from deeplearning4j_tpu import precision as _precision

        pm = _precision.monitor_for("fit", self._precision_policy())
        if pm is not None:
            pm.baseline_from(prec)
        if hm is not None:
            hm.precision = pm
        # sampled trace root (ISSUE 10): NULL (falsy, no tracer calls)
        # when telemetry/tracing is off or the head sampler said no;
        # nests under an enclosing context (ElasticTrainer root) so
        # checkpoints and ETL spans land in the same tree. Entered
        # manually: the epoch loop below must stay at its indentation,
        # and the finally below closes the span on every exit path.
        import sys as _sys

        tspan = tracing.trace_or_span("train.fit", loop="fit")
        tspan.__enter__()
        steps_seen = 0
        try:
            for epoch_i in range(epochs):
                batches, data = _prepare_batches(data, epoch_i, epochs)
                batch_iter = iter(batches)
                while True:
                    if tele is not None:
                        t_etl = _time.perf_counter()
                    ds = next(batch_iter, None)
                    if ds is None:
                        break
                    if tele is not None:
                        tele.record_etl_wait(_time.perf_counter() - t_etl)
                    if isinstance(ds, DeviceBatch) and (
                            self._bucket is None
                            or ds.bucket >= self._bucket):
                        # prefetched: pad/mask/transfer already happened in
                        # the producer thread, arrays are device-resident
                        f, l, lmask = ds.features, ds.labels, ds.mask
                        self._bucket = ds.bucket
                    elif isinstance(ds, DeviceBatch):
                        # staged against a smaller bucket than the
                        # compiled executable's (producer raced a bucket
                        # growth): rejoin the host pad path, KEEPING the
                        # staged mask so already-padded rows stay
                        # zero-weighted
                        f = np.asarray(ds.features)
                        l = np.asarray(ds.labels)
                        lmask = np.asarray(ds.mask)
                        if f.shape[0] < self._bucket:
                            (f, l), lmask, _ = _pad_to_bucket(
                                [f, l], lmask, self._bucket)
                    else:
                        feats, labels, _, lmasks = _split_dataset_full(ds)
                        f = _host_array(feats[0])
                        l = _host_array(labels[0])
                        # always train with an explicit mask so the jit
                        # signature (and hence the ONE compiled executable)
                        # is stable whether or not the batch is ragged/masked
                        lmask = (_host_array(lmasks[0], np.float32)
                                 if lmasks[0] is not None else _ones_mask(l))
                        if self._bucket is None or f.shape[0] > self._bucket:
                            self._bucket = f.shape[0]
                        if f.shape[0] < self._bucket:
                            (f, l), lmask, _ = _pad_to_bucket([f, l], lmask,
                                                              self._bucket)
                    tbptt = (self.conf.backpropType == BackpropType.TruncatedBPTT
                             and self.conf.tbpttLength and f.ndim == 3
                             and f.shape[2] > self.conf.tbpttLength)
                    if tele is not None:
                        t_step = _time.perf_counter()
                    try:
                        if tbptt:
                            loss, params, states, opts, prec = \
                                self._fit_tbptt(
                                    params, states, opts, prec, f, l,
                                    lmask, base_key, hm=hm, pm=pm)
                        else:
                            it_used = self._iteration
                            rng = jax.random.fold_in(base_key, it_used)
                            (loss, params, states, opts, health,
                             prec) = self._train_step(
                                params, states, opts, prec, f, l, lmask,
                                rng, it_used)
                            self._iteration += 1
                    except Exception as e:
                        # OOM forensics (ISSUE 14): an allocation
                        # failure inside the step becomes a typed
                        # DeviceOomError naming this seam and the top
                        # HBM claims; everything else re-raises as-is
                        memledger.raise_if_oom(e, site="train.fit",
                                               step=self._iteration)
                        raise
                    if tele is not None:
                        dt_step = _time.perf_counter() - t_step
                        tele.record_step(dt_step, f.shape[0],
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
                            # locals were rebound to the step's
                            # outputs, so shapes match what dispatched
                            costmodel.maybe_attribute(
                                tele, "fit", self._train_step,
                                (params, states, opts, prec, f, l,
                                 lmask, rng, it_used),
                                self, steps_seen, dt_step)
                            # recompile forensics (ISSUE 11): steady
                            # state is one thread-local read — only a
                            # backend compile during this step builds
                            # and diffs the signature
                            compile_ledger.note_step(
                                "fit", self._train_step,
                                (params, states, opts, prec, f, l,
                                 lmask, rng, it_used),
                                policy=policy_label,
                                window=(t_step, t_step + dt_step))
                    # rebind before anything can observe donated buffers —
                    # including the health monitor, whose HALT policy raises
                    # out of fit(): the caller must find live params to
                    # checkpoint/inspect, not the buffers this step donated
                    self._params, self._states, self._opt_states = (
                        params, states, opts)
                    self._prec_state = prec
                    if not tbptt:
                        if pm is not None:
                            # pm BEFORE hm: the skip set must be populated
                            # when hm's SKIP_BATCH accounting asks
                            pm.on_step(it_used, prec)
                        if hm is not None:
                            # one step behind: processes the PREVIOUS step's
                            # (already materialized) stats — no added sync
                            hm.on_step(it_used, health)
                    last_loss = loss
                    if self._profiler_cfg is not None:
                        from deeplearning4j_tpu.utils.profiler import (
                            nan_panic_check)

                        nan_panic_check(
                            self._profiler_cfg, loss, params,
                            context=f" at iteration {self._iteration}")
                    if self._listeners:
                        lv = float(loss)
                        self._score = lv
                        for listener in self._listeners:
                            listener.iterationDone(self, self._iteration,
                                                   self._epoch)
                self._epoch += 1
            if pm is not None:
                pm.flush()   # before hm.flush: same-step skip handshake
            if hm is not None:
                hm.flush()   # drain the one-behind slot (HALT may raise here)
            if last_loss is not None:
                self._score = float(last_loss)
            return self
        finally:
            tspan.__exit__(*_sys.exc_info())
            # deterministic producer shutdown: a fit that raises
            # (HALT, preemption) must not leave a prefetch thread
            # racing the next attempt for the same base iterator
            if _prefetcher is not None:
                _prefetcher.close()

    # -- layerwise unsupervised pretraining (reference:
    # MultiLayerNetwork.pretrain/pretrainLayer over AutoEncoder / VAE
    # layers, SURVEY.md §2.5 "Layer impls"; here the unsupervised loss +
    # updater fuse into one jitted step per layer) ---------------------------
    def pretrainLayer(self, layer_idx: int, data, epochs: int = 1):
        """Unsupervised pretraining of ONE layer: inputs forward through
        layers [0, layer_idx) in inference mode, then the layer's
        pretrain_loss is minimized with the layer's own updater."""
        self._check_init()
        lr = self.layers[layer_idx]
        if not getattr(lr, "HAS_PRETRAIN_LOSS", False):
            raise ValueError(
                f"layer {layer_idx} ({type(lr).__name__}) has no "
                f"unsupervised pretrain loss")
        updater = self._layer_updater(layer_idx)

        # the below-stack is FROZEN during this layer's pretraining, so its
        # forward runs once per batch outside the differentiated step
        def fwd(below, states, f):
            h, _ = self._forward(below, states, f, False, None,
                                 upto=layer_idx)
            return _apply_preprocessor(self.conf.preprocessors[layer_idx], h)

        def step(lp, opt, h, rng, it):
            loss, g = jax.value_and_grad(
                lambda p: lr.pretrain_loss(p, h, rng))(lp)
            g = _normalize_grads(g, lr.gradientNormalization,
                                 lr.gradientNormalizationThreshold or 1.0)
            upd, opt = updater.apply(g, opt, lp, it)
            lp = jax.tree_util.tree_map(lambda p, u: p - u, lp, upd)
            return loss, lp, opt

        fkey = ("pretrain_fwd", layer_idx)
        skey = ("pretrain", layer_idx)
        if skey not in self._infer_fns:
            self._infer_fns[fkey] = jax.jit(fwd)
            self._infer_fns[skey] = jax.jit(step, donate_argnums=(0, 1))
        jfwd, jstep = self._infer_fns[fkey], self._infer_fns[skey]
        base_key = jax.random.key(self.conf.seed + 2 + layer_idx)
        loss = None
        for epoch_i in range(epochs):
            batches, data = _prepare_batches(data, epoch_i, epochs)
            for ds in batches:
                feats, _, _, _ = _split_dataset_full(ds)
                f = _host_array(feats[0])
                # layer 0 included: fwd still applies the dtype cast and
                # the layer's input preprocessor
                h = jfwd(self._params[:layer_idx], self._states, f)
                rng = jax.random.fold_in(base_key, self._iteration)
                loss, lp, opt = jstep(
                    self._params[layer_idx], self._opt_states[layer_idx],
                    h, rng, self._iteration)
                # rebind immediately: the step DONATED the old buffers
                self._params[layer_idx] = lp
                self._opt_states[layer_idx] = opt
                self._iteration += 1
        if loss is not None:
            self._score = float(loss)
        return self

    def pretrain(self, data, epochs: int = 1):
        """Pretrain every pretrainable layer in order (reference:
        MultiLayerNetwork.pretrain(DataSetIterator))."""
        # materialize one-shot iterables ONCE so the second pretrainable
        # layer doesn't see an exhausted generator
        if not hasattr(data, "reset") and not isinstance(
                data, (list, tuple)):
            data = list(_as_batches(data))
        for i, lr in enumerate(self.layers):
            if getattr(lr, "HAS_PRETRAIN_LOSS", False):
                self.pretrainLayer(i, data, epochs)
        return self

    # -- TBPTT (reference: MultiLayerNetwork truncated BPTT, SURVEY.md §2.5:
    # tBPTTLength splits each minibatch sequence into segments; hidden state
    # carries ACROSS segments (no gradient flow — states enter the next
    # compiled step as inputs), and resets at minibatch boundaries) --------
    def _recurrent_indices(self, forbid_bidirectional=False):
        from deeplearning4j_tpu.nn.conf.layers import Bidirectional

        out = []
        for i, lr in enumerate(self.layers):
            if isinstance(lr, Bidirectional):
                if forbid_bidirectional:
                    # the backward direction needs the FULL sequence; DL4J
                    # likewise rejects rnnTimeStep/TBPTT on bidirectional
                    raise ValueError(
                        f"layer {i} is Bidirectional: streaming rnnTimeStep"
                        f"/TBPTT cannot carry state through a layer that "
                        f"consumes the whole sequence")
                continue
            if getattr(lr, "IS_RECURRENT", False) or getattr(
                    getattr(lr, "rnn", None), "IS_RECURRENT", False):
                out.append(i)
        return out

    def _seed_rnn_states(self, states, batch_size):
        dtype = self.conf.dtype
        out = list(states)
        for i in self._recurrent_indices():
            lr = self.layers[i]
            target = lr.rnn if hasattr(lr, "rnn") and getattr(
                lr.rnn, "IS_RECURRENT", False) and not getattr(
                lr, "IS_RECURRENT", False) else lr
            out[i] = target.streaming_state(batch_size, dtype)
        return out

    def _strip_rnn_states(self, states):
        out = list(states)
        for i in self._recurrent_indices():
            out[i] = {}
        return out

    def _fit_tbptt(self, params, states, opts, prec, f, l, lmask, base_key,
                   hm=None, pm=None):
        L = self.conf.tbpttLength
        T = f.shape[2]
        self._recurrent_indices(forbid_bidirectional=True)
        states = self._seed_rnn_states(states, f.shape[0])
        loss = None
        for t0 in range(0, T, L):
            fc = f[:, :, t0:t0 + L]
            lc = l[:, :, t0:t0 + L] if l.ndim == 3 else l
            mc = lmask[:, t0:t0 + L] if lmask.ndim == 2 else lmask
            if fc.shape[2] < L:
                # zero-pad the tail segment to the fixed tbptt shape and
                # mask the padded timesteps out of the loss
                pad = L - fc.shape[2]
                fc = np.concatenate(
                    [fc, np.zeros(fc.shape[:2] + (pad,), fc.dtype)], axis=2)
                if lc.ndim == 3:
                    lc = np.concatenate(
                        [lc, np.zeros(lc.shape[:2] + (pad,), lc.dtype)],
                        axis=2)
                if mc.ndim == 2:
                    mc = np.concatenate(
                        [mc, np.zeros((mc.shape[0], pad), mc.dtype)], axis=1)
            it_used = self._iteration
            rng = jax.random.fold_in(base_key, it_used)
            loss, params, states, opts, health, prec = self._train_step(
                params, states, opts, prec, fc, lc, mc, rng, it_used)
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

    # -- streaming inference (reference: rnnTimeStep / rnnClearPreviousState,
    # SURVEY.md §2.5 TBPTT row) ---------------------------------------------
    def rnnTimeStep(self, x):
        """Streaming inference with carried hidden state: x is [N, C]
        (one timestep) or [N, C, T] (a chunk). Successive calls continue
        the sequence; rnnClearPreviousState() resets."""
        self._check_init()
        x = _unwrap(x)
        single = x.ndim == 2
        if single:
            x = x[:, :, None]
        n = x.shape[0]
        rec = set(self._recurrent_indices(forbid_bidirectional=True))
        if self._stream_states is None or self._stream_batch != n:
            seeded = self._seed_rnn_states(self._states, n)
            self._stream_states = {i: seeded[i] for i in rec}
            self._stream_batch = n
        # only the recurrent carry is cached; BN running stats etc. come
        # fresh from self._states so an interleaved fit() (which rebinds
        # self._states after donating the old buffers) can't leave stale
        # or deleted arrays behind
        states = [self._stream_states[i] if i in rec else s
                  for i, s in enumerate(self._states)]
        key = "stream"
        if key not in self._infer_fns:
            def fn(params, states, x):
                return self._forward(params, states, x, False, None)

            self._infer_fns[key] = jax.jit(fn)
        y, new_states = self._infer_fns[key](self._params, states, x)
        self._stream_states = {i: new_states[i] for i in rec}
        y = INDArray(y[:, :, 0]) if single and y.ndim == 3 else INDArray(y)
        return y

    def rnnClearPreviousState(self):
        self._stream_states = None
        self._stream_batch = None

    def rnnGetPreviousState(self, layer_idx: int) -> dict:
        if self._stream_states is None:
            return {}
        return {k: INDArray(v)
                for k, v in self._stream_states.get(layer_idx, {}).items()}

    def rnnSetPreviousState(self, layer_idx: int, state: dict):
        """Install carried state (e.g. restoring a saved streaming session).
        Works after rnnClearPreviousState: a fresh session is seeded from
        the given state's batch size."""
        vals = {k: _unwrap(v) for k, v in state.items()}
        if self._stream_states is None:
            if not vals:
                raise ValueError("cannot infer batch size from empty state")
            n = next(iter(vals.values())).shape[0]
            rec = set(self._recurrent_indices())
            seeded = self._seed_rnn_states(self._states, n)
            self._stream_states = {i: seeded[i] for i in rec}
            self._stream_batch = n
        self._stream_states[layer_idx] = vals

    # -- inference -----------------------------------------------------------
    def _infer_fn(self, training=False):
        key = ("out", training)
        if key not in self._infer_fns:
            from deeplearning4j_tpu.precision import cast_floating

            pol = self._precision_policy()

            def fn(params, states, x):
                # mixed policy: inference ALSO runs in the compute dtype
                # (the MXU payoff applies to serving too) and returns
                # output_dtype at the boundary; identity without a policy
                if pol.is_mixed:
                    params = cast_floating(params, pol.compute_jnp)
                y, _ = self._forward(params, states, x, training, None)
                return y.astype(pol.output_jnp) \
                    if y.dtype != pol.output_jnp and \
                    jnp.issubdtype(y.dtype, jnp.floating) else y

            self._infer_fns[key] = jax.jit(fn)
        return self._infer_fns[key]

    def output(self, x, train: bool = False) -> INDArray:
        self._check_init()
        y = self._infer_fn(train)(self._params, self._states, _unwrap(x))
        return INDArray(y)

    def feedForward(self, x, train: bool = False) -> list:
        """All layer activations (reference returns input + each layer's
        activation)."""
        self._check_init()
        x = _unwrap(x)
        acts = [INDArray(x)]
        states = self._states
        for i, lr in enumerate(self.layers):
            x = _apply_preprocessor(self.conf.preprocessors[i], x)
            x, _ = lr.apply(self._params[i], states[i], x, train, None)
            acts.append(INDArray(x))
        return acts

    # -- scoring / eval ------------------------------------------------------
    def score(self, dataset=None) -> float:
        self._check_init()
        if dataset is None:
            if self._score is None:
                raise ValueError("no score yet: call fit() or score(dataset)")
            return self._score
        feats, labels, _, lmasks = _split_dataset_full(dataset)
        lmask = None if lmasks[0] is None else _unwrap(lmasks[0])
        loss, _ = self._loss_from(self._params, self._states,
                                  _unwrap(feats[0]), _unwrap(labels[0]),
                                  False, None, mask=lmask)
        return float(loss)

    def _eval_outputs(self, iterator):
        """Yield (labels, predictions, mask) per batch with the ragged
        final batch padded UP to the running batch-size bucket (the
        serving-side `pad_rows`), so an eval pass compiles ONE inference
        executable instead of one per distinct tail size. Padding rows
        are sliced back off before scoring — masks stay untouched and
        results are bit-identical to unpadded inference (row-wise
        networks)."""
        from deeplearning4j_tpu.serving.buckets import pad_rows

        bucket = None
        for ds in _as_batches(iterator):
            feats, labels, _, lmasks = _split_dataset_full(ds)
            f = _host_array(feats[0])
            n = f.shape[0]
            if bucket is None or n > bucket:
                bucket = n
            out = self.output(pad_rows(f, bucket))
            yield labels[0], out.toNumpy()[:n], lmasks[0]

    def evaluate(self, iterator, numClasses=None) -> Evaluation:
        self._check_init()
        ev = Evaluation(numClasses)
        for labels, out, mask in self._eval_outputs(iterator):
            ev.eval(labels, out, mask=mask)
        return ev

    def evaluateRegression(self, iterator) -> RegressionEvaluation:
        self._check_init()
        ev = RegressionEvaluation()
        for labels, out, mask in self._eval_outputs(iterator):
            ev.eval(labels, out, mask=mask)
        return ev

    # -- params --------------------------------------------------------------
    def params(self) -> INDArray:
        """Flat parameter vector in layer order (reference:
        MultiLayerNetwork.params() flat view)."""
        self._check_init()
        leaves = []
        for p in self._params:
            for k in sorted(p):
                leaves.append(jnp.ravel(p[k]))
        if not leaves:
            return INDArray(jnp.zeros((0,)))
        return INDArray(jnp.concatenate(leaves))

    def setParams(self, flat):
        self._check_init()
        flat = _unwrap(flat).reshape(-1)
        off = 0
        for p in self._params:
            for k in sorted(p):
                n = int(np.prod(p[k].shape)) if p[k].shape else 1
                p[k] = flat[off: off + n].reshape(p[k].shape).astype(
                    p[k].dtype)
                off += n
        self._train_step = None
        self._multi_step = None

    def numParams(self) -> int:
        return sum(int(np.prod(v.shape)) for p in self._params
                   for v in p.values())

    def getParam(self, layer_idx: int, name: str) -> INDArray:
        return INDArray(self._params[layer_idx][name])

    def setParam(self, layer_idx: int, name: str, value):
        if isinstance(value, dict):  # nested group (Bidirectional fwd/bwd)
            self._params[layer_idx][name] = {
                k: _unwrap(v) for k, v in value.items()}
        else:
            self._params[layer_idx][name] = _unwrap(value)

    def paramTable(self) -> dict:
        return {f"{i}_{k}": INDArray(v)
                for i, p in enumerate(self._params) for k, v in p.items()}

    def gradients(self, features, labels) -> list[dict]:
        """Per-layer analytic gradients (for the gradient-check harness,
        SURVEY.md §4)."""
        self._check_init()
        f, l = _unwrap(features), _unwrap(labels)

        def loss_fn(p):
            loss, _ = self._loss_from(p, self._states, f, l, False, None)
            return loss

        return jax.grad(loss_fn)(self._params)

    def computeGradientAndScore(self, features, labels):
        f, l = _unwrap(features), _unwrap(labels)

        def loss_fn(p):
            loss, _ = self._loss_from(p, self._states, f, l, False, None)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(self._params)
        self._score = float(loss)
        return grads, self._score

    # -- profiler / debug (reference: OpProfiler NAN_PANIC, SURVEY.md §2.3)
    def setProfilerConfig(self, cfg):
        """ProfilerConfig with checkForNaN/checkForInf enables a per-step
        finite check that raises naming the offending parameter."""
        self._profiler_cfg = cfg
        return self

    # -- listeners / misc ----------------------------------------------------
    def setListeners(self, *listeners):
        self._listeners = list(listeners)
        return self

    def addListeners(self, *listeners):
        self._listeners.extend(listeners)
        return self

    def getListeners(self):
        return list(self._listeners)

    def getIterationCount(self):
        return self._iteration

    def getEpochCount(self):
        return self._epoch

    def clone(self) -> "MultiLayerNetwork":
        other = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(self.conf.to_json()))
        if self._initialized:
            other.init()
            # real copies, not aliases: the source's next fit() DONATES its
            # buffers, which would invalidate shared references
            copy = lambda x: jnp.array(x, copy=True)  # noqa: E731
            other._params = jax.tree_util.tree_map(copy, self._params)
            other._states = jax.tree_util.tree_map(copy, self._states)
            other._opt_states = jax.tree_util.tree_map(copy, self._opt_states)
            other._prec_state = jax.tree_util.tree_map(copy,
                                                       self._prec_state)
        return other

    def summary(self) -> str:
        lines = [f"{'idx':<4}{'layer':<28}{'nParams':<10}{'shape'}"]
        for i, (lr, p) in enumerate(zip(self.layers, self._params)):
            n = sum(int(np.prod(v.shape)) for v in p.values())
            shapes = {k: tuple(v.shape) for k, v in p.items()}
            lines.append(f"{i:<4}{type(lr).__name__:<28}{n:<10}{shapes}")
        lines.append(f"Total params: {self.numParams()}")
        return "\n".join(lines)
