"""Numeric-vs-analytic gradient checking.

Reference capability: org.deeplearning4j.gradientcheck.GradientCheckUtil
(SURVEY.md §4 "Gradient checks" — the backbone of DL4J correctness): central
finite differences in fp64 against analytic gradients on tiny nets. Here the
analytic side is jax.grad of the lowered net function; fp64 is enabled
per-call via jax.enable_x64 so the check is immune to bf16/f32
matmul drift (SURVEY.md §7 "Numerics")."""

from __future__ import annotations

import numpy as np
import jax


class GradientCheckUtil:
    @staticmethod
    def checkGradients(net, features, labels, epsilon=1e-5, maxRelError=1e-3,
                       minAbsError=1e-8, subset=None, seed=0,
                       print_results=False) -> bool:
        """net: MultiLayerNetwork (initialized). Perturbs each parameter
        (or a random subset of `subset` per array) and compares
        (f(x+e)-f(x-e))/2e with the analytic gradient."""
        f = np.asarray(features, np.float64)
        l = np.asarray(labels, np.float64)

        # TPUs have no native fp64 — running the check there silently
        # degrades precision until finite differences underflow to zero.
        # Pin everything to the host CPU backend (the reference equivalently
        # runs gradient checks on the fp64-capable CPU backend).
        import contextlib

        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            cpu = None
        device_scope = (jax.default_device(cpu) if cpu is not None
                        else contextlib.nullcontext())

        with device_scope, jax.enable_x64():
            # ascontiguousarray is load-bearing: XLA buffers can expose
            # non-C-contiguous layouts through np.asarray, making
            # reshape(-1) below return a COPY and perturbations silently
            # no-ops
            to64 = lambda x: np.ascontiguousarray(  # noqa: E731
                np.asarray(x, np.float64))
            params64 = jax.tree_util.tree_map(to64, net._params)
            states64 = jax.tree_util.tree_map(to64, net._states)

            def loss_fn(p):
                loss, _ = net._loss_from(p, states64, f, l, False, None)
                return loss

            analytic = jax.grad(loss_fn)(params64)
            base_loss = float(loss_fn(params64))
            if base_loss != base_loss:
                raise ValueError("loss is NaN at the test point")

            rng = np.random.default_rng(seed)
            failures = []
            total_checked = 0
            for li, p in enumerate(params64):
                for k, arr in p.items():
                    flat = arr.reshape(-1)
                    assert np.shares_memory(flat, arr), \
                        "perturbation view must alias the param array"
                    n = flat.shape[0]
                    idxs = (range(n) if subset is None or subset >= n
                            else rng.choice(n, subset, replace=False))
                    an = np.asarray(analytic[li][k], np.float64).reshape(-1)
                    for i in idxs:
                        orig = flat[i]
                        flat[i] = orig + epsilon
                        lp = float(loss_fn(params64))
                        flat[i] = orig - epsilon
                        lm = float(loss_fn(params64))
                        flat[i] = orig
                        numeric = (lp - lm) / (2 * epsilon)
                        a = an[i]
                        denom = max(abs(numeric), abs(a))
                        abs_err = abs(numeric - a)
                        rel = abs_err / denom if denom > 0 else 0.0
                        total_checked += 1
                        if rel > maxRelError and abs_err > minAbsError:
                            failures.append(
                                (li, k, int(i), float(a), float(numeric),
                                 float(rel)))
            if print_results or failures:
                print(f"gradient check: {total_checked} params checked, "
                      f"{len(failures)} failures")
                for li, k, i, a, nmr, rel in failures[:20]:
                    print(f"  layer {li} {k}[{i}]: analytic={a:.3e} "
                          f"numeric={nmr:.3e} rel={rel:.3e}")
            return not failures
