"""Sharded training: the TPU-native replacement for the reference's entire
scale-out stack.

Reference capability: ParallelWrapper + SharedTrainingMaster +
VoidParameterServer/Aeron (SURVEY.md §2.6, call stack §3.5). The reference
clones the model per device thread, trains asynchronously, and exchanges
threshold-compressed updates over UDP. Here ONE jitted train step is
compiled with GSPMD shardings over a named mesh:

  - batch sharded over the 'data' axis, params replicated (DP) or sharded
    per the param_specs pytree (TP);
  - XLA emits the gradient all-reduce (psum over 'data') INSIDE the step
    HLO, riding ICI — there is no transport layer to port, and sync is
    exact (vs the reference's stale-tolerant async updates, a convergence
    semantics difference SURVEY.md §3.5 flags);
  - donation keeps params device-resident across steps.
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, MeshConfig, global_batch, host_sharded_batch, spec_for)


def _host_scalar(x) -> float:
    """float(x) that also works on multi-process replicated outputs (not
    fully addressable -> read this process's shard, which holds the full
    replicated value)."""
    if getattr(x, "is_fully_addressable", True):
        return float(x)
    return float(np.asarray(x.addressable_data(0)))


def _pad_batch(arr, multiple):
    """Pad the batch axis up to a multiple by repeating the last row, and
    return (padded, real_count). The loss weighting uses real_count so
    padding rows do not bias gradients."""
    n = arr.shape[0]
    rem = n % multiple
    if rem == 0:
        return arr, n
    pad = multiple - rem
    reps = np.repeat(arr[-1:], pad, axis=0)
    return np.concatenate([arr, reps], axis=0), n


class ShardedTrainer:
    """Data/tensor-parallel trainer around a MultiLayerNetwork.

    param_specs: optional pytree (same structure as net._params) of
    PartitionSpec for tensor parallelism; default fully replicated."""

    def __init__(self, net, mesh: Mesh | None = None, param_specs=None):
        self.net = net
        self.mesh = mesh or MeshConfig.data_parallel()
        self.param_specs = param_specs
        self._step_fn = None
        self._step_plan = None   # health BuildPlan compiled into it
        self._n_data = self.mesh.shape.get(DATA_AXIS, 1)

    def _shardings(self):
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        if self.param_specs is None:
            p_shard = jax.tree_util.tree_map(lambda _: repl,
                                             self.net._params)
        else:
            p_shard = jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec), self.param_specs,
                is_leaf=lambda x: isinstance(x, P))
        s_shard = jax.tree_util.tree_map(lambda _: repl, self.net._states)
        # optimizer state mirrors param sharding (TP memory savings depend
        # on m/v being sharded like their params); updater states are
        # param-shaped subtrees ({"m": params_like, ...}), so map each
        # state entry through the layer's param shardings
        o_shard = []
        for i, ost in enumerate(self.net._opt_states):
            if not ost:
                o_shard.append(())
                continue
            try:
                o_shard.append({
                    k: jax.tree_util.tree_map(lambda _, s: s, v, p_shard[i])
                    for k, v in ost.items()})
            except (ValueError, TypeError):
                o_shard.append(jax.tree_util.tree_map(lambda _: repl, ost))
        batch = NamedSharding(mesh, spec_for(mesh, DATA_AXIS))
        return p_shard, s_shard, o_shard, batch, repl

    def _build_step(self, health_plan=None):
        net = self.net
        updaters = [net._layer_updater(i) for i in range(len(net.layers))]
        p_sh, s_sh, o_sh, b_sh, repl = self._shardings()

        from deeplearning4j_tpu import kernels
        from deeplearning4j_tpu.nn.multilayer import _normalize_grads
        from deeplearning4j_tpu.telemetry import health as _health

        plan = health_plan or _health.INACTIVE
        scaler = net._loss_scaler()
        scaling = scaler is not None and bool(net._prec_state)
        # scaler state is a few replicated scalars; the finite-check
        # reduction over the sharded grads gets its psum from GSPMD just
        # like the health stats — the policy survives sharding intact
        prec_sh = jax.tree_util.tree_map(lambda _: repl, net._prec_state)

        def step(params, states, opt_states, prec, f, l, mask, rng, it):
            def loss_fn(p):
                loss, ns = net._loss_from(p, states, f, l, True, rng,
                                          mask=mask)
                if scaling:
                    return scaler.scale_loss(loss, prec), (loss, ns)
                return loss, (loss, ns)

            # a Mosaic kernel cannot be partitioned by GSPMD (jax raises
            # at lowering): inside this scope the recurrence kernels
            # run per batch shard under shard_map
            with kernels.batch_sharded(self.mesh, DATA_AXIS):
                (_, (loss, new_states)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            if scaling:
                grads = scaler.unscale(grads, prec)
                finite = scaler.all_finite(grads)
            new_params, new_opts, stats = [], [], []
            for i, lr in enumerate(net.layers):
                g = grads[i]
                if not g:
                    new_params.append(params[i])
                    new_opts.append(opt_states[i])
                    if plan.collect:
                        stats.append(_health.zero_stats())
                    continue
                g = _normalize_grads(g, lr.gradientNormalization,
                                     lr.gradientNormalizationThreshold
                                     or 1.0)
                upd, new_opt = updaters[i].apply_mixed(g, opt_states[i],
                                                       params[i], it)
                new_params.append(jax.tree_util.tree_map(
                    lambda p, u: p - u, params[i], upd))
                new_opts.append(new_opt)
                if plan.collect:
                    # fused reductions over the SHARDED grads/params —
                    # XLA inserts the cross-device psum inside the step
                    stats.append(_health.layer_stats(g, upd,
                                                     new_params[-1]))
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

        out_health = (repl,) if plan.collect else (None,)
        return jax.jit(
            step,
            in_shardings=(p_sh, s_sh, o_sh, prec_sh, b_sh, b_sh, b_sh,
                          repl, repl),
            out_shardings=(repl, p_sh, s_sh, o_sh) + out_health
            + (prec_sh,),
            donate_argnums=(0, 1, 2),
        )

    def place_params(self):
        """Device_put params/states/opt with their shardings (replicates or
        shards across the mesh; multi-process assembles global arrays from
        the identical host copies every process initialized)."""
        p_sh, s_sh, o_sh, _, repl = self._shardings()
        net = self.net
        if jax.process_count() > 1:
            def put(tree, sh_tree):
                def one(a, s):
                    host = np.asarray(jax.device_get(a))
                    return jax.make_array_from_callback(
                        host.shape, s, lambda idx, h=host: h[idx])
                return jax.tree_util.tree_map(one, tree, sh_tree)
        else:
            put = jax.device_put
        net._params = put(net._params, p_sh)
        net._states = put(net._states, s_sh)
        net._opt_states = put(net._opt_states, o_sh)
        if net._prec_state:
            net._prec_state = put(
                net._prec_state,
                jax.tree_util.tree_map(lambda _: repl, net._prec_state))

    def _prefetch_prepare(self):
        """Host-side batch prep (split + pad-to-multiple + mask) plus
        the sharded device_put, run in the DevicePrefetcher's producer
        thread so the H2D transfer of batch k+1 overlaps the step of
        batch k. Single-process only (the multi-host path assembles
        global arrays inline)."""
        from deeplearning4j_tpu.autodiff.samediff import _split_dataset
        from deeplearning4j_tpu.datasets.prefetch import DeviceBatch

        batch_sh = self._shardings()[3]

        def prepare(ds):
            feats, labels = _split_dataset(ds)
            if len(feats) != 1 or len(labels) != 1:
                return ds
            f = np.asarray(feats[0])
            l = np.asarray(labels[0])
            if f.dtype != np.float32:
                f = f.astype(np.float32)
            f, real = _pad_batch(f, self._n_data)
            l, _ = _pad_batch(l, self._n_data)
            mshape = ((l.shape[0], l.shape[2]) if l.ndim == 3
                      else (l.shape[0],))
            mask = np.ones(mshape, np.float32)
            mask[real:] = 0.0
            return DeviceBatch(jax.device_put(f, batch_sh),
                               jax.device_put(l, batch_sh),
                               jax.device_put(mask, batch_sh),
                               real=real)

        return prepare

    def _wrap_prefetch(self, data):
        from deeplearning4j_tpu.datasets import prefetch as _prefetch
        from deeplearning4j_tpu.datasets.iterator import (
            DataSetIterator as _DSI)

        if (jax.process_count() == 1
                and isinstance(data, _DSI)
                and not isinstance(data, _prefetch.DevicePrefetcher)
                and data.asyncSupported()
                and _prefetch.default_depth() > 0):
            wrapped = _prefetch.DevicePrefetcher(
                data, prepare=self._prefetch_prepare(), loop="sharded")
            return wrapped, wrapped
        return data, None

    def fit(self, data, epochs: int = 1):
        import time

        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.autodiff.samediff import (
            _as_batches, _split_dataset)
        from deeplearning4j_tpu.datasets.prefetch import DeviceBatch
        from deeplearning4j_tpu.telemetry import health as _health

        net = self.net
        if self._step_fn is None:
            self.place_params()
        plan = _health.build_plan(net._listeners)
        if self._step_fn is None or self._step_plan != plan:
            step = self._build_step(plan)
            from deeplearning4j_tpu import compilestore

            if compilestore.enabled():
                # ISSUE 13: the mesh topology is part of the program
                # digest — a sharded executable bakes in its device
                # assignment, so a differently-shaped mesh must miss
                step = compilestore.StoredJit(
                    step, "sharded",
                    program=(f"train:ShardedTrainer:"
                             f"{net.conf.to_json()}"
                             f":mesh={sorted(self.mesh.shape.items())}"
                             f":ndev={self.mesh.devices.size}"
                             f":specs={self.param_specs!r}"
                             f":policy={net._precision_policy().name}"
                             f"/h{int(plan.collect)}{int(plan.skip)}"),
                    policy=(f"{net._precision_policy().name}"
                            f"/h{int(plan.collect)}{int(plan.skip)}"),
                    donation=(0, 1, 2))
            self._step_fn = step
            self._step_plan = plan
        data, _prefetcher = self._wrap_prefetch(data)
        assemble = (host_sharded_batch
                    if getattr(data, "hostSharded", False)
                    else global_batch)
        params, states, opts = net._params, net._states, net._opt_states
        prec = net._prec_state
        base_key = jax.random.key(net.conf.seed + 1)
        last = None
        # one flag check per fit(): tele is None when telemetry is
        # disabled, and the loop body then makes zero registry calls
        tele = telemetry.loop_instruments("sharded")
        hm = _health.monitor_for("sharded", net._layer_labels(),
                                 net._listeners)
        from deeplearning4j_tpu import precision as _precision

        pm = _precision.monitor_for("sharded", net._precision_policy())
        if pm is not None:
            pm.baseline_from(prec)
        if hm is not None:
            hm.precision = pm
        # sampled trace root + cost attribution (ISSUE 10): same
        # treatment as MultiLayerNetwork.fit, loop="sharded"
        import sys as _sys

        from deeplearning4j_tpu.telemetry import (
            compile_ledger, costmodel, tracing)

        # compile-ledger policy label (ISSUE 11): precision policy +
        # health build plan, both compiled into the sharded step
        policy_label = (f"{net._precision_policy().name}"
                        f"/h{int(plan.collect)}{int(plan.skip)}")

        from deeplearning4j_tpu.telemetry import memledger

        # HBM ownership claim (ISSUE 14): the sharded replicas of
        # params/updater/loss-scale state, keyed to the NET — None when
        # disabled, one gauge-set per step (the multilayer contract)
        mem = None if tele is None else memledger.claim_for_owner(
            net, "train", "sharded",
            tree={"p": params, "s": states, "o": opts, "prec": prec},
            mesh=str(sorted(self.mesh.shape.items())))

        tspan = tracing.trace_or_span("train.sharded", loop="sharded")
        tspan.__enter__()
        steps_seen = 0
        try:
            for _ in range(epochs):
                batch_iter = iter(_as_batches(data))
                while True:
                    if tele is not None:
                        t_etl = time.perf_counter()
                    ds = next(batch_iter, None)
                    if ds is None:
                        break
                    if tele is not None:
                        tele.record_etl_wait(time.perf_counter() - t_etl)
                    if isinstance(ds, DeviceBatch):
                        # prefetched: pad/mask/sharded-placement already
                        # happened in the producer thread
                        f, l, mask, real = (ds.features, ds.labels, ds.mask,
                                            ds.real)
                    else:
                        feats, labels = _split_dataset(ds)
                        f = np.asarray(feats[0])
                        l = np.asarray(labels[0])
                        f, real = _pad_batch(f, self._n_data)
                        l, _ = _pad_batch(l, self._n_data)
                        # zero-weight the padding rows so repeated examples
                        # do not bias gradients ([N] for 2D labels, [N,T]
                        # for NCW labels)
                        mshape = ((l.shape[0], l.shape[2]) if l.ndim == 3
                                  else (l.shape[0],))
                        mask = np.ones(mshape, np.float32)
                        mask[real:] = 0.0
                        if jax.process_count() > 1:
                            # multi-host SPMD. Host-sharded pipelines
                            # (shardByHost) feed per-process-DISTINCT
                            # batches that concatenate into the global
                            # batch; everything else follows the
                            # identical-copy convention where each
                            # device takes its own slice
                            f = assemble(self.mesh, f)
                            l = assemble(self.mesh, l)
                            mask = assemble(self.mesh, mask)
                    it_used = net._iteration
                    rng = jax.random.fold_in(base_key, it_used)
                    if tele is None:
                        try:
                            loss, params, states, opts, health, prec = \
                                self._step_fn(params, states, opts, prec,
                                              f, l, mask, rng, it_used)
                        except Exception as e:
                            # OOM forensics (ISSUE 14): typed error +
                            # flight event naming this seam
                            memledger.raise_if_oom(
                                e, site="train.sharded", step=it_used)
                            raise
                    else:
                        # the span is also a TraceAnnotation, so the host
                        # step region lines up with XPlane device traces;
                        # dispatch-queue backpressure makes its wall time
                        # equal the device step time in steady state (no
                        # sync added)
                        sp = tele.step_span()
                        sp.exemplar = tspan.trace_id
                        t_step = time.perf_counter()
                        try:
                            with sp:
                                loss, params, states, opts, health, \
                                    prec = self._step_fn(
                                        params, states, opts, prec, f,
                                        l, mask, rng, it_used)
                        except Exception as e:
                            memledger.raise_if_oom(
                                e, site="train.sharded", step=it_used)
                            raise
                        dt_step = time.perf_counter() - t_step
                        if mem is not None:
                            # steady state: ONE gauge-set per step
                            mem.touch()
                        if tspan:
                            tracing.emit("train.step", tspan.ctx(),
                                         t_step, t_step + dt_step,
                                         step=it_used)
                        tele.examples.inc(real)
                        if tele.step_flops:
                            # this loop records through the Timer span,
                            # not record_step, so the live MFU gauge
                            # refreshes here
                            costmodel.publish_mfu("sharded",
                                                  tele.step_flops,
                                                  dt_step)
                        steps_seen += 1
                        costmodel.maybe_attribute(
                            tele, "sharded", self._step_fn,
                            (params, states, opts, prec, f, l, mask,
                             rng, it_used), self, steps_seen, dt_step)
                        # recompile forensics (ISSUE 11): one
                        # thread-local read unless this step compiled
                        compile_ledger.note_step(
                            "sharded", self._step_fn,
                            (params, states, opts, prec, f, l, mask,
                             rng, it_used), policy=policy_label,
                            window=(t_step, t_step + dt_step))
                    # rebind BEFORE the health monitor runs: its HALT policy
                    # raises out of fit() and the caller must find live
                    # params, not the buffers this step donated
                    net._params, net._states, net._opt_states = (
                        params, states, opts)
                    net._prec_state = prec
                    if pm is not None:
                        pm.on_step(it_used, prec)   # before hm (skip set)
                    if hm is not None:
                        hm.on_step(it_used, health)
                    net._iteration += 1
                    last = loss
                    if net._listeners:
                        net._score = _host_scalar(loss)
                        for listener in net._listeners:
                            listener.iterationDone(net, net._iteration,
                                                   net._epoch)
                net._epoch += 1
        finally:
            tspan.__exit__(*_sys.exc_info())
            # deterministic producer shutdown (see
            # MultiLayerNetwork.fit): a raising fit must not
            # leave a prefetch thread racing the next attempt
            if _prefetcher is not None:
                _prefetcher.close()
        if pm is not None:
            pm.flush()   # before hm.flush: same-step skip handshake
        if hm is not None:
            hm.flush()   # drain the one-behind slot (HALT may raise here)
        if last is not None:
            net._score = _host_scalar(last)
        return net


# ---------------------------------------------------------------------------
# facades with the reference's API shapes
# ---------------------------------------------------------------------------


_WARNED_KNOBS: set = set()


def _warn_noop_knob(knob, why):
    """One-time notice that a parity knob is accepted but has no effect
    here (VERDICT.md round-1 weak item 7: silent no-ops surprise users)."""
    if knob in _WARNED_KNOBS:
        return
    _WARNED_KNOBS.add(knob)
    import warnings

    warnings.warn(f"{knob} is accepted for DL4J API parity but has no "
                  f"effect on TPU: {why}", stacklevel=3)


class ParallelWrapper:
    """Reference: org.deeplearning4j.parallelism.ParallelWrapper.Builder
    (SURVEY.md §2.6). workers() picks how many devices join the data axis;
    averaging/gradient-sharing knobs are accepted for API parity but the
    sync is always the exact in-step all-reduce."""

    class Builder:
        def __init__(self, net):
            self._net = net
            self._workers = None
            self._prefetch = 2

        def workers(self, n):
            self._workers = n
            return self

        def prefetchBuffer(self, n):
            self._prefetch = n
            return self

        def averagingFrequency(self, n):
            _warn_noop_knob("ParallelWrapper.averagingFrequency",
                            "gradients all-reduce exactly every step "
                            "inside the compiled executable")
            return self

        def trainingMode(self, *_):
            return self

        def workspaceMode(self, *_):
            return self

        def build(self):
            devices = jax.devices()
            n = self._workers or len(devices)
            mesh = MeshConfig(data=n, devices=devices[:n]).build()
            return ParallelWrapper(self._net, mesh, self._prefetch)

    def __init__(self, net, mesh, prefetch=2):
        self.net = net
        self.mesh = mesh
        self.prefetch = prefetch
        self._trainer = ShardedTrainer(net, mesh)

    def fit(self, iterator, epochs: int = 1):
        from deeplearning4j_tpu.datasets.iterator import (
            AsyncDataSetIterator, DataSetIterator)

        data = iterator
        if isinstance(iterator, DataSetIterator) and self.prefetch > 0 \
                and iterator.asyncSupported():
            data = AsyncDataSetIterator(iterator, self.prefetch)
        self._trainer.fit(data, epochs)
        return self.net

    def shutdown(self):
        pass


class ParallelInference:
    """Reference: org.deeplearning4j.parallelism.ParallelInference —
    batched inference over all devices (batch sharded over 'data')."""

    class Builder:
        def __init__(self, net):
            self._net = net
            self._batch_limit = 32

        def inferenceMode(self, *_):
            return self

        def batchLimit(self, n):
            self._batch_limit = n
            return self

        def workers(self, n):
            return self

        def build(self):
            return ParallelInference(self._net, self._batch_limit)

    def __init__(self, net, batch_limit=32):
        self.net = net
        self.batch_limit = batch_limit
        self.mesh = MeshConfig.data_parallel()
        self._fn = None
        self._n_data = self.mesh.shape.get(DATA_AXIS, 1)

    def output(self, x):
        from deeplearning4j_tpu.ndarray import INDArray

        net = self.net
        if self._fn is None:
            mesh = self.mesh
            repl = NamedSharding(mesh, P())
            b_sh = NamedSharding(mesh, spec_for(mesh, DATA_AXIS))
            p_sh = jax.tree_util.tree_map(lambda _: repl, net._params)
            s_sh = jax.tree_util.tree_map(lambda _: repl, net._states)

            from deeplearning4j_tpu import kernels

            def fn(params, states, xb):
                with kernels.batch_sharded(mesh, DATA_AXIS):
                    y, _ = net._forward(params, states, xb, False, None)
                return y

            self._fn = jax.jit(fn, in_shardings=(p_sh, s_sh, b_sh),
                               out_shardings=b_sh)
        xb = np.asarray(x)
        xb, real = _pad_batch(xb, self._n_data)
        y = self._fn(net._params, net._states, xb)
        return INDArray(y[:real])


class ParameterAveragingTrainingMaster:
    """Reference: dl4j-spark ParameterAveragingTrainingMaster.Builder —
    kept as a mesh-size configuration facade (averaging IS all-reduce when
    done every step)."""

    class Builder:
        def __init__(self, *_args):
            self._batch = 32

        def batchSizePerWorker(self, n):
            self._batch = n
            return self

        def averagingFrequency(self, n):
            _warn_noop_knob("TrainingMaster.averagingFrequency",
                            "averaging IS the in-step all-reduce here")
            return self

        def workerPrefetchNumBatches(self, n):
            return self

        def build(self):
            return ParameterAveragingTrainingMaster(self._batch)

    def __init__(self, batch_per_worker=32):
        self.batch_per_worker = batch_per_worker


class SharedTrainingMaster(ParameterAveragingTrainingMaster):
    """Reference: gradient-sharing SharedTrainingMaster (threshold-
    compressed async updates). The compression knobs are accepted and
    ignored: dense synchronous all-reduce over ICI replaces sparse async
    UDP (SURVEY.md §2.6 item 'Gradient sharing')."""

    class Builder(ParameterAveragingTrainingMaster.Builder):
        def thresholdAlgorithm(self, *_):
            _warn_noop_knob("SharedTrainingMaster.thresholdAlgorithm",
                            "dense synchronous all-reduce over ICI "
                            "replaces threshold-compressed async updates")
            return self

        def residualPostProcessor(self, *_):
            return self

        def build(self):
            return SharedTrainingMaster(self._batch)


class SparkDl4jMultiLayer:
    """Reference: org.deeplearning4j.spark.impl.multilayer
    .SparkDl4jMultiLayer — the Spark driver role collapses to 'shard the
    batch over the mesh'; `sc` is accepted for signature parity."""

    def __init__(self, sc, net_or_conf, training_master=None):
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        if hasattr(net_or_conf, "layers") and not hasattr(net_or_conf,
                                                          "fit"):
            net = MultiLayerNetwork(net_or_conf)
            net.init()
        else:
            net = net_or_conf
        self.net = net
        self.training_master = training_master
        self._trainer = ShardedTrainer(net)

    def fit(self, data, epochs: int = 1):
        self._trainer.fit(data, epochs)
        return self.net

    def getNetwork(self):
        return self.net
