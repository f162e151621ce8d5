"""HLO fusion/remat audit: what XLA actually compiled (ISSUE 11
tentpole, part c).

ROADMAP item 4 ("close the MFU gap") names an XLA fusion/remat audit as
the next instrument: the cuDNN paper (PAPERS.md) defines which
primitives must fuse to hit roofline, and an unfused dot or a
rematerialized block is invisible in step-time metrics — the step is
just "slow". This module parses the *optimized* HLO of a compiled
executable (``compiled.as_text()``) into the handful of structural
facts an operator acts on:

- **fusion count** and how many dot/convolution ops were left
  *outside* any fused computation (an unfused dot at a hot site is the
  classic roofline miss);
- **collective ops** (all-reduce / all-gather / reduce-scatter /
  collective-permute / all-to-all) — the sharded-trainer overlap work
  (ROADMAP item 4) needs to know how many and where;
- **remat markers**: ``opt-barrier`` ops and ops whose names carry the
  ``.remat`` suffix jax.checkpoint leaves behind — rematerialization
  trades FLOPs for memory and should be a *decision*, not a surprise;
- **largest buffers** by result-type byte size — the first question
  when ``memory_analysis()`` temp bytes look wrong;
- **custom calls by target** — a Pallas/Mosaic kernel shows up as
  ``tpu_custom_call``; chip_smoke.py reads this to prove the compiled
  ``fit()`` step holds the recurrence kernels and not the ``lax.scan``.

The parser is a line-oriented state machine over HLO text — no XLA
bindings, so it audits a dumped module in a test as happily as a live
Compiled object. Consumers: the compile ledger attaches an audit to
every AOT serving executable at warmup, ``GET /debug/hlo/<key>``
(ui/server.py) serves it per ledgered executable, and
``tools/hloaudit.py`` emits the per-model report committed to
docs/HLO_AUDIT.md.
"""

from __future__ import annotations

import hashlib
import re

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

# "%name = <type> opcode(..." — the opcode is the first lowercase token
# immediately followed by "(" on the right-hand side (types like
# f32[64,64]{1,0} never touch a "(", tuple types open with "(" before
# any token)
_OPCODE_RE = re.compile(r"\b([a-z][a-zA-Z0-9\-_]*)\(")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
# computation headers: "%fused_computation.1 (p: f32[..]) -> .. {" /
# "ENTRY %main.5 (...) -> .. {"
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
# the target every Pallas/Mosaic TPU kernel compiles to
MOSAIC_TARGET = "tpu_custom_call"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}


def _result_bytes(rhs: str):
    """Byte size of an op line's result when it is a single array: the
    one shape token between '=' and the opcode. Tuple-typed results
    (while-loop carries, multi-output fusions) return 0 — they
    aggregate the whole carried state and would drown every real
    buffer in the largest-buffer ranking."""
    m = _OPCODE_RE.search(rhs)
    head = rhs[:m.start()] if m else rhs
    shapes = _SHAPE_RE.findall(head)
    if len(shapes) != 1:
        return 0, None
    dtype, dims = shapes[0]
    width = _DTYPE_BYTES.get(dtype)
    if width is None:
        return 0, None
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * width, f"{dtype}[{dims}]"


def audit_text(hlo: str) -> dict:
    """Parse one HLO module's text into the audit summary dict. Pure
    and total: malformed lines are skipped, never raised on."""
    fusions = 0
    unfused = {"dot": 0, "convolution": 0}
    fused = {"dot": 0, "convolution": 0}
    collectives = {op: 0 for op in COLLECTIVE_OPS}
    opt_barriers = 0
    remat_ops = 0
    custom_calls = 0
    custom_call_targets: dict = {}
    mosaic_results: list = []
    ops = 0
    computations = 0
    fused_computations = 0
    opcode_hist: dict = {}
    buffers: list = []
    in_fused = False
    for raw in hlo.splitlines():
        line = raw.strip()
        if line == "}":
            in_fused = False
            continue
        comp = _COMP_RE.match(raw)
        if comp is not None:
            computations += 1
            in_fused = "fused" in comp.group(2)
            fused_computations += int(in_fused)
            continue
        if line.startswith("ROOT "):
            # computation roots are instructions too — a fusion's root
            # IS the fused op, and a small module's only dot is often
            # the entry root
            line = line[len("ROOT "):]
        if "=" not in line or not line.startswith("%"):
            continue
        name, _, rhs = line.partition("=")
        m = _OPCODE_RE.search(rhs)
        if m is None:
            continue
        opcode = m.group(1)
        ops += 1
        opcode_hist[opcode] = opcode_hist.get(opcode, 0) + 1
        if opcode == "fusion":
            fusions += 1
        if opcode in unfused:
            (fused if in_fused else unfused)[opcode] += 1
        if opcode in collectives:
            collectives[opcode] += 1
        if opcode == "opt-barrier":
            opt_barriers += 1
        if opcode == "custom-call":
            custom_calls += 1
            found = _TARGET_RE.search(rhs)
            target = found.group(1) if found is not None else None
            if target is not None:
                custom_call_targets[target] = \
                    custom_call_targets.get(target, 0) + 1
            if target == MOSAIC_TARGET:
                # the kernel's first result, e.g. "f32[100,1024,256]":
                # under a mesh this is the PER-DEVICE shape, which says
                # whether the kernel saw its batch shard or the whole
                first = _SHAPE_RE.search(rhs[:m.start()])
                if first is not None:
                    mosaic_results.append(first.group(0))
        if ".remat" in name:
            remat_ops += 1
        nbytes, label = _result_bytes(rhs)
        if nbytes:
            buffers.append((nbytes, label, name.strip().rstrip(" ")))
    buffers.sort(key=lambda b: -b[0])
    top_ops = dict(sorted(opcode_hist.items(),
                          key=lambda kv: (-kv[1], kv[0]))[:12])
    return {
        "ops": ops,
        "computations": computations,
        "fused_computations": fused_computations,
        "fusions": fusions,
        "unfused_dots": unfused["dot"],
        "unfused_convolutions": unfused["convolution"],
        "fused_dots": fused["dot"],
        "fused_convolutions": fused["convolution"],
        "collectives": {**collectives,
                        "total": sum(collectives.values())},
        "remat": {"opt_barriers": opt_barriers, "remat_ops": remat_ops},
        "custom_calls": custom_calls,
        "custom_call_targets": custom_call_targets,
        "mosaic_results": mosaic_results,
        "opcode_histogram": top_ops,
        "largest_buffers": [
            {"bytes": b, "type": t, "op": n}
            for b, t, n in buffers[:5]],
    }


def fingerprint(text: str) -> str:
    """Stable short identity for one HLO/StableHLO module text."""
    return hashlib.sha1(text.encode()).hexdigest()[:12]


def audit_compiled(compiled) -> dict:
    """Audit a live jax Compiled object: ``as_text()`` through
    :func:`audit_text`, plus the cost/memory analyses the executable
    already carries. Degrades field-by-field — a backend without
    ``memory_analysis`` still gets the structural audit."""
    text = compiled.as_text()
    out = audit_text(text)
    out["hlo_fingerprint"] = fingerprint(text)
    out["module_bytes"] = len(text)
    try:
        analysis = compiled.cost_analysis()
        if analysis is not None:
            out["flops"] = float(analysis.get("flops", 0.0))
            out["bytes_accessed"] = float(
                analysis.get("bytes accessed", 0.0))
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["memory"] = {
                kind: getattr(mem, attr)
                for kind, attr in (
                    ("argument_bytes", "argument_size_in_bytes"),
                    ("output_bytes", "output_size_in_bytes"),
                    ("temp_bytes", "temp_size_in_bytes"),
                    ("code_bytes", "generated_code_size_in_bytes"))
                if getattr(mem, attr, None) is not None}
    except Exception:
        pass
    return out
