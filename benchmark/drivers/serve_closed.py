"""Driver `serve_closed`: a closed loop of callers against the decode engine,
in-process: `InferenceSession.register_decoder(name, model)` with the engine's
own defaults, then `session.decoder(name).submit(prompt, n)` and `.tokens()`
from `callers` threads, each sending its next request when its last ended and
stamping every token as it reaches it. No JAX in the callers' threads.

The load starts in set-up; the window opens once every slot is taken and as
many requests as there are callers have ended. Once it has closed, a sample of
the requests it finished (the longest among them) is run through the plain
reference with the tokens that were served."""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark.lib import arith, compare, traffic
from benchmark.reference import bert_plain

NAME = "bench-decoder"


def _sizes(config):
    m = config["model"]
    return {"vocab_size": m["vocab_size"], "hidden": m["hidden"],
            "ffn": m["ffn"], "num_layers": m["num_layers"],
            "num_heads": m["num_heads"], "max_len": m["max_len"],
            "layer_norm_eps": m["layer_norm_eps"]}


class State:
    pass


def _weights(params, gain):
    """The program's own initialiser, then the two FFN matrices of every
    layer times `ffn_matrix_gain` (why: the configuration's file)."""
    for lp in params["layers"]:
        for w in ("ffn_in_w", "ffn_out_w"):
            lp[w] = lp[w] * gain
    return params


def setup(ctx):
    import jax

    from deeplearning4j_tpu.models.bert import BertConfig, init_params
    from deeplearning4j_tpu.serving import (InferenceSession,
                                            TransformerDecodeModel)

    st = State()
    st.sizes, tr, eng = _sizes(ctx.config), ctx.cell["traffic"], \
        ctx.config["engine"]
    s, gain = st.sizes, ctx.config["model"]["ffn_matrix_gain"]
    cfg = BertConfig(vocab_size=s["vocab_size"], hidden=s["hidden"],
                     num_layers=s["num_layers"], num_heads=s["num_heads"],
                     ffn=s["ffn"], max_len=s["max_len"])
    params = jax.jit(lambda key: _weights(init_params(cfg, key), gain))(
        jax.random.key(ctx.seed31))
    model = TransformerDecodeModel(
        params, n_heads=s["num_heads"], max_slots=eng["max_slots"],
        page=eng["page"], max_pages_per_slot=eng["max_pages_per_slot"],
        eps=s["layer_norm_eps"])
    st.session = InferenceSession()
    st.session.register_decoder(NAME, model, **eng.get("options", {}))
    st.engine = st.session.decoder(NAME)
    # the engine's warm-up leaves its throwaway step running, and that step's
    # output pool lives until it ends: wait for the device, or the first real
    # step finds three pools alive and `memory_peak_bytes` reads one too many
    jax.block_until_ready(jax.device_put(np.int32(0), ctx.devices[0]) + 1)
    ctx.say("decoder registered and warmed")

    st.stream, st.requests = traffic.requests(tr, ctx.seed), []
    st.records, st.lock, st.stop = [], threading.Lock(), threading.Event()
    st.samples, st.max_slots = [], eng["max_slots"]
    st.threads = [threading.Thread(target=_caller, args=(st,), daemon=True,
                                   name=f"bench:caller-{i}")
                  for i in range(tr["callers"])]
    st.threads.append(threading.Thread(target=_sampler, args=(st,),
                                       daemon=True, name="bench:sampler"))
    for t in st.threads:
        t.start()
    deadline = time.perf_counter() + tr["ramp_timeout_s"]
    full = False
    while True:
        full = full or st.engine.active_slots >= st.max_slots
        ended = sum(1 for r in list(st.records) if r.get("t_end"))
        if full and ended >= tr["callers"]:
            break
        if time.perf_counter() > deadline:
            raise RuntimeError(f"ramp not over after {tr['ramp_timeout_s']}s:"
                               f" slots full {full}, {ended} requests ended")
        time.sleep(0.05)
    ctx.say(f"ramp over: {ended} requests ended")
    return st


def _caller(st):
    import jax

    while not st.stop.is_set():
        with st.lock:
            i = len(st.requests)
            st.requests.append(next(st.stream))
        prompt, n_new = st.requests[i]
        rec = {"i": i, "stamps": [], "tokens": [], "error": None,
               "t_submit": time.perf_counter()}
        st.records.append(rec)
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                req = st.engine.submit(prompt, n_new)
            for tok in req.tokens(timeout=120.0):
                rec["stamps"].append(time.perf_counter())
                rec["tokens"].append(int(tok))
        except Exception as e:   # a refused or failed request is a result
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["t_end"] = time.perf_counter()


def _sampler(st):
    while not st.stop.is_set():
        st.samples.append((time.perf_counter(), st.engine.active_slots))
        time.sleep(0.1)


def measure(ctx, st):
    t0 = time.perf_counter()
    t1 = t0 + ctx.seconds
    t_traced = None
    if ctx.trace:
        time.sleep(max(0.0, t1 - ctx.cell["trace_seconds"]
                       - time.perf_counter()))
        t_traced = time.perf_counter()
        ctx.start_trace()
    time.sleep(max(0.0, t1 - time.perf_counter()))
    t1 = time.perf_counter()
    if t_traced is not None:
        ctx.stop_trace()
    st.stop.set()
    st.session.close()
    for t in st.threads:
        t.join(timeout=30.0)
    alive = [t.name for t in st.threads if t.is_alive()]
    if alive:
        raise RuntimeError(f"threads still alive after the window: {alive}")

    recs = list(st.records)
    tokens, ttft, gaps = arith.request_stats(recs, t0, t1)
    attempted = [r for r in recs if t0 <= r["t_submit"] <= t1]
    failed = [r for r in recs if _failed(r, t1) and t0 <= r["t_end"]]
    st.finished = [r for r in recs if not r["error"]
                   and t0 <= r["t_end"] <= t1
                   and len(r["tokens"]) == st.requests[r["i"]][1]]
    ctx.say(f"window {t1 - t0:.3f}s: {tokens} tokens, {len(ttft)} first "
            f"tokens, {len(gaps)} gaps, {len(st.finished)} requests finished,"
            f" {len(failed)} failed")
    # per-layer counters over the part of the window before the tracer
    c1 = t_traced if t_traced is not None else t1
    _, _, c_gaps = arith.request_stats(recs, t0, c1)
    step_s = arith.percentile(c_gaps, 50)
    occ = [n for t, n in st.samples if t0 <= t <= c1]
    contexts, positions = _positions(recs, st.requests, t0, c1, step_s)
    s = st.sizes
    return {"t0": t0, "t1": t1, "attempted": len(attempted),
            "failed": len(failed),
            "end_to_end": {
                "decode_tokens_per_s": arith.rate(tokens, t0, t1),
                "ttft_p95_ms": _ms(arith.percentile(ttft, 95)),
                "itl_p95_ms": _ms(arith.percentile(gaps, 95))},
            "counters": {
                "itl_p50_s": step_s,
                "slot_occupancy": (float(np.mean(occ)) / st.max_slots
                                   if occ else None),
                "positions": positions, "seconds": c1 - t0,
                "mean_context": contexts,
                "live_slots": float(np.mean(occ)) if occ else None,
                "sizes": s, "step_executable": "jit__fn",
                "w_itemsize": 4, "kv_itemsize": 4}}


def _failed(r, t1):
    """Refused or errored inside the window; what the engine's shutdown
    ended after the window was in flight, not failed."""
    return bool(r["error"]) and r["t_end"] <= t1


def _ms(x):
    return None if x is None else 1e3 * x


def _positions(recs, requests, t0, t1, step_s):
    """(mean context length of a live slot, positions advanced in [t0, t1]),
    from the callers' own stamps. Every engine iteration advances every live
    request by one position, prompt or answer, so a request's position at a
    time is the time since its submit over the iteration time (the median
    gap between tokens), up to its prompt and answer."""
    if not step_s:
        return None, None
    positions, ctx_sum, ctx_n = 0.0, 0.0, 0
    times = np.arange(t0, t1, 0.1)
    for r in recs:
        if _failed(r, t1):
            continue
        total = len(requests[r["i"]][0]) + requests[r["i"]][1] - 1
        a, b = max(r["t_submit"], t0), min(r["t_end"], t1)
        if b <= a:
            continue
        at = lambda t: min(total, max(0.0, t - r["t_submit"]) / step_s)  # noqa: E731
        positions += at(b) - at(a)
        live = times[(times >= a) & (times <= b)]
        ctx_sum += float(sum(at(t) for t in live))
        ctx_n += len(live)
    return (ctx_sum / ctx_n if ctx_n else None), positions


def free(st):
    st.engine = st.session = st.threads = None
    gc.collect()


def sample(ctx, st):
    """The requests to compare: the longest the window finished and others
    drawn from the seed, `check_requests` in all."""
    fin = sorted(st.finished, key=lambda r: -(len(st.requests[r["i"]][0])
                                              + len(r["tokens"])))
    k = min(ctx.cell["check_requests"], len(fin))
    if k == 0:
        return []
    rest = fin[1:]
    pick = np.random.default_rng([ctx.seed, 3]).permutation(len(rest))[:k - 1]
    return [fin[0]] + [rest[i] for i in pick]


def reference_gaps(ctx, sizes, requests, chosen, control=None):
    """The widest and the mean gap of the served tokens under the reference
    (or, for the control, of the tokens that the reference computed in the
    lower precision `control` puts first at the same positions)."""
    import jax
    import jax.numpy as jnp

    n, t = ctx.cell["check_requests"], sizes["max_len"]
    seqs = np.zeros((n, t), np.int32)
    served = np.zeros((n, t), np.int32)
    first, counts = np.zeros(n, int), np.zeros(n, int)
    for j, r in enumerate(chosen):
        prompt, toks = requests[r["i"]][0], r["tokens"]
        whole = prompt + toks[:-1]
        seqs[j, :len(whole)] = whole
        first[j], counts[j] = len(prompt) - 1, len(toks)
        served[j, first[j]:first[j] + counts[j]] = toks
    params = bert_plain.init_params(
        jax.random.key(ctx.seed31), sizes["vocab_size"], sizes["hidden"],
        sizes["ffn"], sizes["num_layers"], sizes["max_len"],
        ctx.config["model"]["ffn_matrix_gain"])
    ref = bert_plain.decoder_logits(params, sizes, seqs)
    if control:
        low = bert_plain.decoder_logits(params, sizes, seqs, mode=control)
        served = np.asarray(jnp.argmax(low, axis=-1))
    return compare.serving(ref, served, first, counts)


def check(ctx, st):
    sizes, requests = st.sizes, st.requests
    chosen = sample(ctx, st)
    free(st)
    if not chosen:
        ctx.say("no request finished inside the window: nothing to compare")
        return {}
    numbers, notes = reference_gaps(ctx, sizes, requests, chosen)
    ctx.say(f"compared {len(chosen)} requests: {notes}")
    return numbers
