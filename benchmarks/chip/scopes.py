"""Device time by the program's own named scopes.

The program names its layers with ``jax.named_scope`` (``VOCABULARY``).
XLA keeps each scope path in the ``op_name`` metadata of every instruction
of the compiled step, fusions included, and a profiler trace names each
device op by its instruction.  Instruction names are unique in a module, so
the map from instruction to ``op_name`` that ``instruction_scopes`` reads
from ``compiled.as_text()`` gives each traced op its scope path exactly.
The scopes are metadata: they cost nothing at run time.

``layer_of`` parses an ``op_name`` into the phase of the step it ran in
(``forward``; ``backward``; ``remat``, the forward that ``jax.checkpoint``
recomputes for the backward) and the program's scope segments.
``from_profile`` reads a trace as ``trace.from_profile`` does, with each
op's ``op_name`` as a fifth field; ``scope_seconds`` sums, per steady step,
the device time of the ops whose phase and segments a predicate accepts.
"""
from __future__ import annotations

import collections
import re

from benchmarks.chip import trace

#: The segments the program's named scopes use (``src/repro``):
#: ``optimizer`` (train/optimizer.py::update), ``gather``
#: (sharding.quantized_all_gather, trainer.make_train_step), ``embed``,
#: ``patch_embed``, ``span_head``, ``cls_head``, ``loss``
#: (models/paper_models.py), ``blocks`` and the modules inside it, named by
#: their quantization scope (models/blocks.py: ``ln1``, ``attn``, ``ln2``,
#: ``mlp``, plus ``embed_ln`` and ``final_ln``), and ``quantize``
#: (core/int_ops.py, core/dfx.py).
VOCABULARY = frozenset({
    "optimizer", "gather", "embed", "patch_embed", "embed_ln", "blocks",
    "ln1", "attn", "ln2", "mlp", "final_ln", "span_head", "cls_head", "loss",
    "quantize"})
#: JAX's own segments: loops, calls and the remat region.
STRUCTURAL = frozenset({"while", "body", "cond", "closed_call", "checkpoint",
                        "rematted_computation"})

_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')
_OPCODE = re.compile(r"^(?:\([^=]*?\)|\S+) [\w\-]+\(")
_CALLED = re.compile(
    r"\b(?:body|condition|calls|to_apply|branch_computations)="
    r"(\{[^}]*\}|%[\w.\-]+)")
_NAME = re.compile(r"%([\w.\-]+)")
_WRAPPER = re.compile(r"^([a-z_]+)\((.*)\)$")


def _operands(rest: str) -> list:
    """The ``%`` names in the operand list of an instruction's text."""
    m = _OPCODE.match(rest)
    if m is None:
        return []
    depth, i = 1, m.end()
    for j in range(i, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[j], 0)
        if depth == 0:
            return _NAME.findall(rest[i:j])
    return []


def instruction_scopes(hlo_text: str) -> dict:
    """Instruction name -> ``op_name``, for every instruction of the module.

    An instruction that the compiler inserted without an ``op_name`` (a
    copy, a prefetch or slice and its ``-done``, a ``ConcatBitcast``) takes
    that of its first operand that has one: it moves that op's data.  Where
    no operand has one (a loop's own carried buffers), it takes that of the
    instruction whose computation it runs in (the loop)."""
    text, home, caller = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c and line.rstrip().endswith("{") else comp
            continue
        name, rest = m.groups()
        text[name], home[name] = rest, comp
        for called in _CALLED.findall(rest):
            for c in _NAME.findall(called):
                caller.setdefault(c, name)
    out = {}

    def resolve(name, seen):
        if name not in out:
            m = _OP_NAME.search(text[name])
            found = m.group(1) if m else next(
                (s for o in _operands(text[name])
                 if o in text and o not in seen
                 for s in [resolve(o, seen | {name})] if s), "")
            up = caller.get(home[name])
            if not found and up is not None and up not in seen:
                found = resolve(up, seen | {name})
            out[name] = found
        return out[name]

    for name in text:
        resolve(name, frozenset())
    return {n: s for n, s in out.items() if s}


def layer_of(op_name: str):
    """(phase, segments) of an ``op_name`` such as
    ``jit(step)/transpose(jvp(blocks))/while/body/closed_call/checkpoint/
    attn/quantize/jit(frexp)/reduce_max`` -> ("backward", ("blocks", "attn",
    "quantize")).  Transform wrappers are unwrapped, ``jit(...)`` and
    structural segments dropped, and the trailing primitive cut off."""
    parts = op_name.split("/")[:-1]
    if "rematted_computation" in parts:
        phase = "remat"
    elif any(p.startswith("transpose(") for p in parts):
        phase = "backward"
    else:
        phase = "forward"
    segments = []
    for p in parts:
        m = _WRAPPER.match(p)
        while m and m.group(1) != "jit":
            p = m.group(2)
            m = _WRAPPER.match(p)
        if p and m is None and p not in STRUCTURAL:
            segments.append(p)
    return phase, tuple(segments)


def top_scope(segments) -> str:
    """The outermost segment of the program's vocabulary, or ``""``."""
    return next((s for s in segments if s in VOCABULARY), "")


def _instruction(hlo: str) -> str:
    return hlo.partition(" = ")[0].strip().lstrip("%")


def from_profile(path: str, device_ids, module_prefix: str,
                 scopes: dict) -> dict:
    """``trace.from_profile``'s plain form with each op's ``op_name`` (from
    ``scopes``, ``""`` where the instruction has none) as a fifth field."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    wanted = {f"/device:TPU:{i}": i for i in device_ids}
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name in wanted:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [[e.start_ns, e.duration_ns]
                                      for e in line.events
                                      if e.name.startswith(module_prefix)]
                elif line.name == "XLA Ops":
                    dev["ops"] = [[*trace.op_name(e.name), e.start_ns,
                                   e.duration_ns,
                                   scopes.get(_instruction(e.name), "")]
                                  for e in line.events]
            out["devices"][str(wanted[plane.name])] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(trace.HOST_PREFIX)]
    return out


def scope_ns(plain: dict) -> list:
    """Per device with steady steps: (steps, {(op, op_name): ns}) over the
    steady span of ``trace.steady_span``, containers left out.  Ops of four
    fields (no ``op_name``) count under ``""``."""
    out = []
    for _, dev in sorted(plain["devices"].items()):
        span = trace.steady_span({"modules": dev["modules"],
                                  "ops": [op[:4] for op in dev["ops"]]})
        if span is None:
            continue
        ns = collections.Counter()
        for op in dev["ops"]:
            name, kind, s, d = op[:4]
            lo, hi = max(s, span.start), min(s + d, span.end)
            if hi > lo and kind not in trace.CONTAINERS:
                ns[name, op[4] if len(op) > 4 else ""] += hi - lo
        out.append((span.steps, ns))
    return out


def scope_seconds(plain: dict, match) -> float | None:
    """Device seconds per steady step of the ops whose ``layer_of`` phase
    and segments ``match(phase, segments)`` accepts, averaged over devices;
    None where no device has steady steps."""
    per_dev = [sum(n for (_, s), n in ns.items() if match(*layer_of(s)))
               / steps for steps, ns in scope_ns(plain)]
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else None


#: What each scope reading sums: ``unscoped_ms`` is time in ops with no
#: segment of ``VOCABULARY``; "under a scope" is inclusive of kernels.
READINGS = {
    "remat_ms": lambda phase, segs: phase == "remat",
    "quantize_path_ms": lambda phase, segs: "quantize" in segs,
    "optimizer_ms": lambda phase, segs: "optimizer" in segs,
    "unscoped_ms": lambda phase, segs: not VOCABULARY.intersection(segs),
    "backward_ms": lambda phase, segs: phase == "backward",
}


def readings(plain: dict) -> dict:
    """Each of ``READINGS`` in ms per steady step; empty where the traced
    program has no named scope at all (nothing to attribute)."""
    spans = scope_ns(plain)
    if not any(top_scope(layer_of(s)[1]) for _, ns in spans for _, s in ns):
        return {}
    return {name: 1e3 * scope_seconds(plain, match)
            for name, match in READINGS.items()}


def by_layer(plain: dict) -> dict:
    """ms per steady step by (top-level scope, phase, op), averaged over
    devices; ``""`` is the scope of unscoped ops."""
    table = collections.Counter()
    spans = scope_ns(plain)
    for steps, ns in spans:
        for (name, s), n in ns.items():
            phase, segs = layer_of(s)
            table[top_scope(segs), phase, name] += n / steps / len(spans) / 1e6
    return dict(table)
