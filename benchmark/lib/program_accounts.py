"""What the program says of the host's side of a token-step boundary over a
whole run, read from outside it: the decode engine's five phase histograms and
the time between them (`dl4j_decode_between_phases_seconds`, ISSUE 36).

Pure functions over the registry's flat snapshot, as `program_spans`' counts
are, so that a test can hand them samples written by hand. A series is summed
over the `model` label. Every count runs the engine's life, the ramp before
the window included, as `serve.live_page_share` and its siblings do. In a
traced run, the only kind that reads them, that life goes on while the tracer
stops: the drivers stop the callers after `stop_trace()`, which takes 6 s in
the two LM cells and 55 s in `bert-base-decoder.closed-64`, where these two
means read 11% and 20% above an untraced run's (PERF.md sections 5 and 7; the
engine's maxima and its histogram's tail read the tracer outright there and
have no metric until `run.py` hands the readers a snapshot taken at the
window's end). Where the program has no such series (a commit before they
were added, or telemetry off), every function returns None and the metric is
left out."""

from benchmark.lib.program_spans import ratio, sample_sum

BETWEEN = "dl4j_decode_between_phases_seconds"
PHASES = "dl4j_decode_boundary_seconds"


def mean_ms(snap, histogram):
    """Sum over count of a histogram of seconds, in ms."""
    mean = ratio(sample_sum(snap, histogram + "_sum"),
                 sample_sum(snap, histogram + "_count"))
    return None if mean is None else 1e3 * mean


def host_chain_ms(snap):
    """What the engine's thread does for one token-step boundary beside
    waiting for the device, mean in ms: the sums of the `admit`, `build`,
    `dispatch` and `emit` phase histograms and of the time between phases,
    over the token-step boundaries delivered. `readback` is left out: it is
    the wait for the launch in flight, and with it the five phases and the
    time between them add up to the dispatch interval itself. Against the
    launch's device time it says which of the two sets the boundary, which
    `serve.prepare_ms_p50` + `serve.retire_ms_p50` were meant to say."""
    host = [sample_sum(snap, PHASES + "_sum", phase=p)
            for p in ("admit", "build", "dispatch", "emit")]
    between = sample_sum(snap, BETWEEN + "_sum")
    if None in host or between is None:
        return None
    per = ratio(sum(host) + between, sample_sum(
        snap, "dl4j_decode_boundaries_total", executable="step"))
    return None if per is None else 1e3 * per
