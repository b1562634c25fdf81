"""Needed-work counts and the peaks table against hand counts."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import work  # noqa: E402
from benchmarks.chip.families import bert, vit  # noqa: E402

CONF = ROOT / "benchmarks" / "chip" / "configs"
BERT = json.loads((CONF / "bert-base.json").read_text())
VIT = json.loads((CONF / "vit-base.json").read_text())
SQUAD = {"batch": 32, "seq_len": 384}
IMG = {"batch": 64}


def test_bert_base_counts_by_hand():
    # per layer 4·768² (Q, K, V, O) + 2·768·3072 (FFN), 12 layers
    encoder = sum(k * n * c for name, _, k, n, c in bert.linears(BERT, SQUAD)
                  if name != "span_head")
    assert encoder == 12 * (4 * 768 ** 2 + 2 * 768 * 3072) == 84_934_656
    tokens = 32 * 384
    assert bert.positions(BERT, SQUAD) == tokens == 12_288
    assert work.linear_ops(bert, BERT, SQUAD) == 6 * tokens * (
        84_934_656 + 768 * 2)
    assert work.linear_ops(bert, BERT, SQUAD) == pytest.approx(6.26e12,
                                                               rel=1e-3)
    attention = 12 * 32 * 384 ** 2 * 768 * 12
    assert work.attention_ops(bert, BERT, SQUAD) == attention
    assert work.attention_ops(bert, BERT, SQUAD) == pytest.approx(5.22e11,
                                                                  rel=1e-3)
    assert work.step_ops(bert, BERT, SQUAD) == pytest.approx(6.78e12,
                                                             rel=1e-3)


def test_vit_base_counts_by_hand():
    assert vit.positions(VIT, IMG) == 64 * 197
    patch = 6 * 64 * 196 * (16 * 16 * 3) * 768
    head = 6 * 64 * 768 * 1000
    encoder = 6 * 64 * 197 * 84_934_656
    attention = 12 * 64 * 197 ** 2 * 768 * 12
    assert work.linear_ops(vit, VIT, IMG) == patch + encoder + head
    assert work.attention_ops(vit, VIT, IMG) == attention
    assert work.step_ops(vit, VIT, IMG) == pytest.approx(6.74e12, rel=1e-3)


def test_matmul_products_and_least_time():
    bits = {"weight": 8, "act": 12, "grad": 8}
    prods = work.matmul_products(bert, BERT, SQUAD, bits)
    assert len(prods) == 3 * len(bert.linears(BERT, SQUAD))
    fwd, dx, dw = prods[3:6]                      # mlp.w1
    assert fwd == ("mlp.w1.fwd", 12288, 768, 3072, 12, 8, 12)
    assert dx == ("mlp.w1.dx", 12288, 3072, 768, 8, 8, 12)
    assert dw == ("mlp.w1.dw", 768, 12288, 3072, 12, 8, 12)
    peak = work.peaks("TPU v5 lite")
    # one forward FFN product: 2·12288·768·3072 ops at 393e12/s is 59.0 us;
    # 12288·768·1.5 + 768·3072·1 + 12288·3072·4 = 167.5e6 bytes at 819e9
    # B/s is 204.5 us, so the bytes bound it
    one = work.least_seconds([fwd[:6] + (1,)], peak)
    nbytes = 12288 * 768 * 1.5 + 768 * 3072 + 12288 * 3072 * 4
    assert one == pytest.approx(nbytes / 819e9)
    assert one == pytest.approx(204.5e-6, rel=1e-3)
    compute_bound = [("x", 8192, 8192, 8192, 8, 8, 1)]
    assert work.least_seconds(compute_bound, peak) == pytest.approx(
        2 * 8192 ** 3 / 393e12)


def test_peaks_table():
    peak = work.peaks("TPU v5 lite")
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
