"""The benchmark's reduction from a profiler trace to device numbers.

``data/bert_int8_trace.json.gz`` is three steps of bert-base int8 span
fine-tuning (B=32, S=384) traced on one TPU v5e and cut down to
``trace.from_profile``'s plain form.  A hand reduction of that trace (sum
of the ``bfp_matmul*`` events per step) gave 525.3 ms of limb matmuls in an
811.7 ms step.
"""
import gzip
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.chip import trace  # noqa: E402

DATA = pathlib.Path(__file__).with_name("data") / "bert_int8_trace.json.gz"


@pytest.mark.parametrize("hlo, want", [
    ("%bfp_matmul_tn.80 = f32[12,768,3072]{2,1,0:T(8,128)} fusion(f32[12,"
     "768,3072]{2,1,0:T(8,128)} %get-tuple-element.2486), kind=kCustom",
     ("bfp_matmul_tn", "fusion")),
    ("%int_attn_fwd.21 = (f32[768,256,128]{2,1,0:T(8,128)S(1)}, f32[768,"
     "256,1]{2,1,0:T(8,128)}) custom-call(s8[2,768,256,128]{3,2,1,0} %b)",
     ("int_attn_fwd", "custom-call")),
    ("%pad.99.clone = f32[64,256]{1,0} pad(f32[64,197]{1,0} %x, f32[] %c)",
     ("pad", "pad")),
    ("%while.12 = (s32[]{:T(128)}, f32[64]{0}) while((s32[], f32[64]) %t)",
     ("while", "while")),
])
def test_op_name(hlo, want):
    assert trace.op_name(hlo) == want


def synthetic():
    """Four steps on one device, each 100 ns, with hand-placed ops."""
    mods = [[0, 100], [110, 100], [220, 100], [330, 100]]
    ops = [
        ["bfp_matmul", "custom-call", 110, 40],      # step 2
        ["bfp_matmul_nt", "custom-call", 150, 30],
        ["while", "while", 110, 100],                # container: no op time
        ["int_attn_fwd", "custom-call", 190, 20],
        ["bfp_matmul", "custom-call", 220, 50],      # step 3, idle 270-300
        ["fusion", "fusion", 300, 20],
        ["dfx_quantize", "custom-call", 90, 30],     # straddles the start
    ]
    host = [["bench.read_loss", 260, 50], ["bench.dispatch", 95, 20]]
    return {"devices": {"0": {"modules": mods, "ops": ops}}, "host": host}


def test_steady_span_by_hand():
    r = trace.reduce(synthetic())
    (span,) = r.spans
    # steady steps: the 2nd and 3rd, 110 .. 320 ns
    assert (span.start, span.end, span.steps) == (110, 320, 2)
    assert span.busy == 100 + 50 + 20            # 110-210, 220-270, 300-320
    assert span.gaps == [[210, 220], [270, 300]]
    assert span.op_ns == {"bfp_matmul": 90, "bfp_matmul_nt": 30,
                          "int_attn_fwd": 20, "fusion": 20,
                          "dfx_quantize": 10}
    assert r.window_s == pytest.approx(210e-9)
    assert r.busy_s == pytest.approx(170e-9)
    assert r.op_seconds_per_step(
        lambda n: n.startswith("bfp_matmul")) == pytest.approx(60e-9)
    assert r.op_seconds_per_step(lambda n: n == "absent") is None
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["bfp_matmul", pytest.approx(90e-9)]
    # the longest gap overlaps bench.read_loss; the 10 ns one no host span
    assert bd["idle_gaps"] == [["bench.read_loss", pytest.approx(30e-9)],
                               ["host.other", pytest.approx(10e-9)]]


def test_too_few_steps_gives_nothing():
    plain = synthetic()
    plain["devices"]["0"]["modules"] = plain["devices"]["0"]["modules"][:2]
    assert trace.reduce(plain) is None


def test_recorded_bert_int8_trace():
    plain = json.loads(gzip.open(DATA, "rt").read())
    r = trace.reduce(plain)
    assert r.steps == 1
    assert r.window_s == pytest.approx(0.8117, abs=1e-3)
    assert 0.999 < r.busy_s / r.window_s <= 1.0
    matmul = r.op_seconds_per_step(lambda n: n.startswith("bfp_matmul"))
    assert matmul == pytest.approx(0.5253, abs=2e-3)
    attention = r.op_seconds_per_step(lambda n: n.startswith("int_attn_"))
    assert attention == pytest.approx(0.1159, abs=2e-3)
    bd = r.breakdown()
    assert [n for n, _ in bd["device_ops"][:3]] == [
        "bfp_matmul", "bfp_matmul_tn", "bfp_matmul_nt"]
    assert len(bd["idle_gaps"]) == 10
    assert {label for label, _ in bd["idle_gaps"]} <= {
        "bench.dispatch", "bench.read_loss", "bench.next_batch",
        "host.other"}
