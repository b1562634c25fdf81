"""The benchmark's plain float32 references against the program's models
with every integer layer off (``QuantConfig.fp32()``), at a tiny size on
the CPU, both at ``precision="highest"``.

The references follow the program where it departs from the published
models (pre-LN, tanh GeLU, no attention biases, layer norm epsilon 1e-5),
so the two agree to float32 rounding: the loss to 1e-5 and every gradient
leaf to 1e-4 of its norm.
"""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip.families import bert, vit  # noqa: E402
from repro.core.qconfig import QuantConfig  # noqa: E402
from repro.models import paper_models as pm  # noqa: E402

CONF = ROOT / "benchmarks" / "chip" / "configs"
TINY = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 2,
        "intermediate_size": 128}


def tiny(name, **extra):
    conf = json.loads((CONF / name).read_text())
    conf.update(TINY, **extra)
    return conf


CASES = {
    "bert": (bert, tiny("bert-base.json", vocab_size=512),
             {"batch": 4, "seq_len": 32},
             lambda c: jax.eval_shape(lambda: pm.bert_init(
                 jax.random.PRNGKey(0), bert.program(c, None)[0],
                 span_head=True))),
    "vit": (vit, tiny("vit-base.json", image_size=32, patch_size=8,
                      num_labels=10),
            {"batch": 4},
            lambda c: jax.eval_shape(lambda: pm.vit_init(
                jax.random.PRNGKey(0), vit.program(c, None)[0],
                num_classes=10, img=32, patch=8))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_params_have_the_programs_layout(name):
    family, conf, _, program_shapes = CASES[name]
    ours = jax.eval_shape(lambda: family.init(jax.random.PRNGKey(0), conf))
    assert jax.tree.structure(ours) == jax.tree.structure(
        program_shapes(conf))
    assert [x.shape for x in jax.tree.leaves(ours)] == [
        x.shape for x in jax.tree.leaves(program_shapes(conf))]


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_matches_program_in_float32(name):
    family, conf, traffic, _ = CASES[name]
    arch, loss_fn = family.program(conf, traffic)
    params = family.init(jax.random.PRNGKey(1), conf)
    batch = family.make_batch(jax.random.PRNGKey(2), conf, traffic)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_g = jax.value_and_grad(family.reference_loss)(
            params, batch, conf)
        (prog_loss, _), prog_g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, arch, QuantConfig.fp32(), jax.random.PRNGKey(3))
    assert float(prog_loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for r, p in zip(jax.tree.leaves(ref_g), jax.tree.leaves(prog_g)):
        r, p = np.asarray(r, np.float64), np.asarray(p, np.float64)
        assert np.linalg.norm(p - r) <= 1e-4 * max(np.linalg.norm(r), 1e-12)


def test_span_batch_marks_its_answer():
    _, conf, traffic, _ = CASES["bert"]
    b = bert.make_batch(jax.random.PRNGKey(5), conf, traffic)
    rows = jnp.arange(traffic["batch"])
    assert bool(jnp.all(b["tokens"][rows, b["span_start"]] == 510))
    assert bool(jnp.all(b["tokens"][rows, b["span_end"]] == 511))
    assert bool(jnp.all((b["span_end"] - b["span_start"] >= 1)
                        & (b["span_end"] - b["span_start"] <= 5)))
    assert bool(jnp.all(b["span_end"] < traffic["seq_len"]))
