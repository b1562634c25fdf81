"""Device time by the program's named scopes (``benchmarks/chip/scopes.py``).

The hand-checked reductions use the same steady span as
``test_bench_trace.py``: four 100 ns steps, of which the 2nd and 3rd
(110 .. 320 ns) are steady.  The compiled tiny bert cell checks that the
scopes reach the ``op_name`` of the step's instructions.
"""
import gzip
import json
import pathlib
import re
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness, scopes, trace  # noqa: E402
from test_bench_rehearsal import SEED, tiny_cell  # noqa: E402,F401

DATA = pathlib.Path(__file__).with_name("data") / "bert_int8_trace.json.gz"

BLOCKS_FWD = "jit(step)/jvp(blocks)/while/body/closed_call"
BLOCKS_BWD = "jit(step)/transpose(jvp(blocks))/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name, want", [
    (BLOCKS_FWD + "/attn/quantize/jit(frexp)/reduce_max",
     ("forward", ("blocks", "attn", "quantize"))),
    (BLOCKS_BWD + "/mlp/jit(bfp_matmul_tn)/bfp_matmul_tn/pallas_call",
     ("backward", ("blocks", "mlp", "bfp_matmul_tn"))),
    ("jit(step)/transpose(jvp(blocks))/mlp/dot_general",
     ("backward", ("blocks", "mlp"))),
    (BLOCKS_BWD + "/rematted_computation/ln1/quantize/jit(frexp)/abs",
     ("remat", ("blocks", "ln1", "quantize"))),
    ("jit(step)/optimizer/sqrt", ("forward", ("optimizer",))),
    ("jit(step)/transpose(jvp(jit(_uniform)))/while/body/closed_call/add",
     ("backward", ())),
    ("reduce_max", ("forward", ())),
    ("", ("forward", ())),
], ids=["forward", "backward", "custom_vjp_backward", "remat", "optimizer",
        "unscoped", "primitive_only", "no_op_name"])
def test_layer_of(op_name, want):
    assert scopes.layer_of(op_name) == want


HLO = """\
%body.5 (wide.arg_tuple.3: (f32[8], s32[])) -> (f32[8], s32[]) {
  %wide.arg_tuple.3 = (f32[8]{0}, s32[]) parameter(0)
  %get-tuple-element.7 = f32[8]{0} get-tuple-element(%wide.arg_tuple.3), index=0
  %copy-start.4 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]{:S(2)}) copy-start(%get-tuple-element.7)
  ROOT %tuple.2 = (f32[8]{0}, s32[]) tuple(%get-tuple-element.7, %c.1)
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0:T(128)} fusion(f32[8]{0:T(128)} %p.1), kind=kLoop, calls=%fused_computation, metadata={op_type="mul" op_name="jit(step)/optimizer/mul" source_file="o.py" source_line=3}
  %copy-start.2 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]{:S(2)}) copy-start(%fusion.3)
  %copy-done.2 = f32[8]{0:S(1)} copy-done(%copy-start.2)
  %while.1 = (f32[8]{0}, s32[]) while(%tuple.1), condition=%cond.2, body=%body.5, metadata={op_name="jit(step)/jvp(blocks)/while"}
  %copy.1 = f32[8]{0} copy(f32[8]{0} %p.1)
  ROOT %bfp_matmul_tn.80 = f32[8]{0} custom-call(%copy-done.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(span_head))/bfp_matmul_tn/pallas_call"}, backend_config={"a":"b"}
}
"""


def test_instruction_scopes():
    """Each instruction's own ``op_name``; a compiler-inserted copy takes
    its operand's, and a loop's carried buffers the loop's; a copy of a
    step argument has none."""
    loop = "jit(step)/jvp(blocks)/while"
    assert scopes.instruction_scopes(HLO) == {
        "fusion.3": "jit(step)/optimizer/mul",
        "copy-start.2": "jit(step)/optimizer/mul",
        "copy-done.2": "jit(step)/optimizer/mul",
        "while.1": loop,
        "wide.arg_tuple.3": loop,
        "get-tuple-element.7": loop,
        "copy-start.4": loop,
        "tuple.2": loop,
        "bfp_matmul_tn.80":
            "jit(step)/transpose(jvp(span_head))/bfp_matmul_tn/pallas_call"}


def synthetic():
    """The steps of ``test_bench_trace.synthetic`` with an ``op_name`` per
    op: forward, backward and remat time inside ``blocks``, an optimizer
    fusion, an op with no ``op_name`` and one that straddles the start."""
    mods = [[0, 100], [110, 100], [220, 100], [330, 100]]
    ops = [
        ["bfp_matmul", "custom-call", 110, 40,
         BLOCKS_FWD + "/mlp/jit(bfp_matmul)/bfp_matmul/pallas_call"],
        ["bfp_matmul_nt", "custom-call", 150, 30,
         BLOCKS_BWD + "/mlp/jit(bfp_matmul_nt)/bfp_matmul_nt/pallas_call"],
        ["while", "while", 110, 100, "jit(step)/transpose(jvp(blocks))/while"],
        ["dfx_quantize", "custom-call", 190, 20,
         BLOCKS_BWD + "/rematted_computation/attn/quantize/dfx_quantize/"
         "pallas_call"],
        ["fusion", "fusion", 220, 50, "jit(step)/optimizer/mul"],
        ["copy", "copy", 300, 20, ""],
        ["add_maximum_fusion", "fusion", 90, 30,
         "jit(step)/transpose(jvp(embed))/quantize/jit(_uniform)/add"],
    ]
    return {"devices": {"0": {"modules": mods, "ops": ops}}, "host": []}


def test_scope_reduction_by_hand():
    plain = synthetic()
    ((steps, ns),) = scopes.scope_ns(plain)
    assert steps == 2
    assert sum(ns.values()) == 40 + 30 + 20 + 50 + 20 + 10   # no while
    assert ns["copy", ""] == 20
    mlp = scopes.scope_seconds(plain, lambda phase, segs: "mlp" in segs)
    assert mlp == pytest.approx((40 + 30) / 2 * 1e-9)
    assert scopes.scope_seconds(
        plain, lambda phase, segs: "gather" in segs) == 0.0
    assert scopes.readings(plain) == pytest.approx({
        "remat_ms": 20 / 2 * 1e-6,
        "quantize_path_ms": (20 + 10) / 2 * 1e-6,
        "optimizer_ms": 50 / 2 * 1e-6,
        "unscoped_ms": 20 / 2 * 1e-6,
        "backward_ms": (30 + 10) / 2 * 1e-6})
    assert scopes.by_layer(plain) == pytest.approx({
        ("blocks", "forward", "bfp_matmul"): 20e-6,
        ("blocks", "backward", "bfp_matmul_nt"): 15e-6,
        ("blocks", "remat", "dfx_quantize"): 10e-6,
        ("optimizer", "forward", "fusion"): 25e-6,
        ("", "forward", "copy"): 10e-6,
        ("embed", "backward", "add_maximum_fusion"): 5e-6})
    # the 4-field view of the same trace reduces as before
    four = {"devices": {"0": {"modules": plain["devices"]["0"]["modules"],
                              "ops": [o[:4] for o in
                                      plain["devices"]["0"]["ops"]]}},
            "host": []}
    assert trace.reduce(four).spans[0].op_ns == {
        "bfp_matmul": 40, "bfp_matmul_nt": 30, "dfx_quantize": 20,
        "fusion": 50, "copy": 20, "add_maximum_fusion": 10}


def test_recorded_four_field_trace():
    """The recorded int8 trace (no ``op_name``s) keeps its numbers, and the
    scope reduction covers the same device time, all of it unscoped."""
    plain = json.loads(gzip.open(DATA, "rt").read())
    r = trace.reduce(plain)
    assert r.op_seconds_per_step(
        lambda n: n.startswith("bfp_matmul")) == pytest.approx(0.5253,
                                                               abs=2e-3)
    ((steps, ns),) = scopes.scope_ns(plain)
    assert steps == r.steps
    assert ns == {(name, ""): n for name, n in r.spans[0].op_ns.items()}
    assert scopes.readings(plain) == {}


def test_tiny_bert_step_names_its_layers(tiny_cell):  # noqa: F811
    """Every scope the readings use names at least one fusion of the
    compiled tiny step, and so do the backward and remat phases."""
    import jax
    prog = harness.Program(tiny_cell, jax.devices()[:1])
    prog.first_steps(SEED)
    text = prog.compiled.as_text()
    prog.close()
    op_scopes = scopes.instruction_scopes(text)
    fusions = re.findall(r"^\s*(?:ROOT\s+)?%(\S+) = \S+ fusion\(", text,
                         re.M)
    layers = [scopes.layer_of(op_scopes.get(f, "")) for f in fusions]
    segments = set().union(*(segs for _, segs in layers))
    assert {"optimizer", "quantize", "attn", "mlp", "embed", "blocks",
            "loss", "span_head"} <= segments
    assert {"forward", "backward", "remat"} <= {ph for ph, _ in layers}
