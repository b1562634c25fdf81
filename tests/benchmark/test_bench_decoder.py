"""The decoder family (Mellum2-shaped) on the CPU at a tiny size: the
program through the registry entry, ``lm.lm_loss`` and the integer layers
against the family's plain float32 reference, the expert share against the
uncut layer, YaRN against Hugging Face's formula written out by hand, and
the drop counter.

The tiny configuration keeps the published structure: one period of three
sliding-window layers (window 16) and one full YaRN layer at S=64, GQA
4:1, 16 experts with 8 held (0-7) and top-4.
"""
import dataclasses
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import check  # noqa: E402
from benchmarks.chip.families import decoder  # noqa: E402
from repro.core.qconfig import QuantConfig  # noqa: E402
from repro.models import blocks, lm  # noqa: E402

TRAFFIC = {"batch": 2, "seq_len": 64}
OPT = {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
       "weight_decay": 0.01, "grad_clip": 1.0}


def tiny_conf(**extra):
    conf = json.loads(
        (ROOT / "benchmarks/chip/configs/mellum2-12b-a2.5b.json").read_text())
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
                head_dim=16, moe_intermediate_size=32, router_experts=16,
                num_experts=8, num_experts_per_tok=4, sliding_window=16,
                vocab_size=256, **extra)
    return conf


def readings(conf, qcfg=None):
    """Loss, per-leaf gradient norms and per-leaf norms of one AdamW step's
    change, of the reference (``qcfg`` None) or the program."""
    arch, loss_fn = decoder.program(conf, TRAFFIC)
    params = decoder.init(jax.random.PRNGKey(1), conf)
    batch = decoder.make_batch(jax.random.PRNGKey(2), conf, TRAFFIC)
    with jax.default_matmul_precision("highest"):
        if qcfg is None:
            loss, grads = jax.value_and_grad(decoder.reference_loss)(
                params, batch, conf)
        else:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, arch, qcfg, jax.random.PRNGKey(3))
        zeros = jax.tree.map(jnp.zeros_like, params)
        new, _, _, clipped = check.adamw(OPT, params, grads, zeros, zeros,
                                         1.0)
    return {"loss": float(loss), "grads": grads,
            "grad": np.asarray(check.leaf_norms(clipped), np.float64),
            "change": np.asarray(check.change_norms(new, params), np.float64)}


@pytest.fixture(scope="module")
def reference():
    return readings(tiny_conf())


def test_params_have_the_programs_layout():
    conf = tiny_conf()
    arch, _ = decoder.program(conf, TRAFFIC)
    ours = jax.eval_shape(lambda: decoder.init(jax.random.PRNGKey(0), conf))
    theirs = jax.eval_shape(lambda: lm.lm_init(jax.random.PRNGKey(0), arch))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == [
        x.shape for x in jax.tree.leaves(theirs)]
    assert arch.layer_pattern == ("sliding",) * 3 + ("full",)
    assert arch.moe_shard == (0, 8) and arch.moe_experts == 16


def test_reference_matches_program_in_float32(reference):
    """Every integer layer off: the two agree to float32 rounding, the loss
    to 1e-5 and every gradient leaf to 1e-4 of its norm."""
    prog = readings(tiny_conf(), QuantConfig.fp32())
    assert prog["loss"] == pytest.approx(reference["loss"], rel=1e-5)
    for r, p in zip(jax.tree.leaves(reference["grads"]),
                    jax.tree.leaves(prog["grads"])):
        r, p = np.asarray(r, np.float64), np.asarray(p, np.float64)
        assert np.linalg.norm(p - r) <= 1e-4 * max(np.linalg.norm(r), 1e-12)


@pytest.mark.parametrize("backend", ["sim", "pallas"])
def test_program_tracks_reference_at_int16(reference, backend):
    """w16-a16-g16 on both backends (pallas in interpret mode), by the
    numbers of ``check.compare`` over one step.  Each tolerance sits between
    what the program reads and what every bit-width four bits lower reads
    (both backends alike, with this file's keys):

    * loss within 1e-6 relative (int16 1.7e-7; 12-bit 3.3e-6);
    * ``grad_gap`` within 1e-3 (int16 1.5e-4, the attention Q/K leaves,
      which carry the dS exponent of the attention backward; 12-bit 4.2e-3);
    * ``update_gap`` over one AdamW step within 8e-3 (int16 3.9e-3, set by
      the expert FFNs whose small gradients Adam normalises; 12-bit 1.2e-2).
    """
    cfg = dataclasses.replace(QuantConfig.int16(), backend=backend)
    prog = readings(tiny_conf(), cfg)
    gaps = check.compare(
        {"losses": [prog["loss"]], "grad": prog["grad"],
         "change": prog["change"]},
        {"losses": np.asarray([reference["loss"]]), "grad": reference["grad"],
         "change": reference["change"]})
    assert gaps["loss_gap"] <= 1e-6, gaps
    assert gaps["grad_gap"] <= 1e-3, gaps
    assert gaps["update_gap"] <= 8e-3, gaps


def _expert_inputs(E=16, D=64, F=32, T=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, D))
    p = {"router": jax.random.normal(ks[1], (D, E)) * 0.3,
         "wg_e": jax.random.normal(ks[2], (E, D, F)) * 0.1,
         "wu_e": jax.random.normal(ks[3], (E, D, F)) * 0.1,
         "wd_e": jax.random.normal(ks[4], (E, F, D)) * 0.1}
    return x, p


def _share(p, first, count):
    return {"router": p["router"],
            **{n: p[n][first:first + count] for n in ("wg_e", "wu_e", "wd_e")}}


def _layer(x, p, conf, qcfg, first, count):
    arch, _ = decoder.program(dict(conf, experts_first=first,
                                   num_experts=count), TRAFFIC)
    y, stats = blocks.moe_apply(p, x[None], arch, qcfg, None)
    return y[0], stats


def test_expert_shares_sum_to_the_whole_layer():
    """Every share of 8 of the 16 experts computes its own experts' part;
    the parts of all shares add up to the uncut reference layer (float32,
    1e-5 of the largest output), and to the program's uncut layer at int16
    (the same per-expert exponents in both, so only the order of the
    final float32 sum differs: 1e-6).  The load-balancing term is the
    router's alone, the same in every share."""
    conf = tiny_conf()
    x, p = _expert_inputs()
    with jax.default_matmul_precision("highest"):
        want, want_aux = decoder.experts(x, p, dict(conf, num_experts=16,
                                                    experts_first=0))
    for qcfg, target, tol in [
            (QuantConfig.fp32(), want, 1e-5),
            (dataclasses.replace(QuantConfig.int16(), backend="pallas"),
             _layer(x, p, conf, dataclasses.replace(
                 QuantConfig.int16(), backend="pallas"), 0, 16)[0], 1e-6)]:
        parts = [_layer(x, _share(p, f, 8), conf, qcfg, f, 8)
                 for f in (0, 8)]
        total = parts[0][0] + parts[1][0]
        scale = float(jnp.max(jnp.abs(target)))
        assert float(jnp.max(jnp.abs(total - target))) <= tol * scale
        for _, (aux, dropped) in parts:
            assert float(aux) == pytest.approx(float(want_aux), rel=1e-5)
            assert float(dropped) == 0


def test_no_pair_is_dropped_when_every_token_picks_held_experts():
    """A router that sends every token's top 4 to the 8 held experts fills
    the expert rows to their bound (tokens x 4): the drop counter reads 0
    and the output is the reference's."""
    conf = tiny_conf()
    x, p = _expert_inputs(seed=1)
    x = jnp.abs(x) + 0.5
    p["router"] = p["router"] * 0.1 + jnp.concatenate(
        [jnp.full((64, 8), 0.5), jnp.full((64, 8), -0.5)], axis=1)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    assert bool(jnp.all(jax.lax.top_k(probs, 4)[1] < 8))
    held = _share(p, 0, 8)
    y, (_, dropped) = _layer(x, held, conf, QuantConfig.fp32(), 0, 8)
    with jax.default_matmul_precision("highest"):
        want, _ = decoder.experts(x, p, conf)
    assert float(dropped) == 0
    assert float(jnp.max(jnp.abs(y - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))


def _yarn_by_hand(base, hd, factor, orig, beta_fast, beta_slow):
    """Hugging Face's ``_compute_yarn_parameters``, written out."""
    def correction_dim(rotations):
        return (hd * math.log(orig / (rotations * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), hd - 1)
    pos_freqs = [base ** (i / hd) for i in range(0, hd, 2)]
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(hd // 2)]
    extrapolation_factor = [1 - r for r in ramp]
    inv = [(1 / (factor * pf)) * (1 - e) + (1 / pf) * e
           for pf, e in zip(pos_freqs, extrapolation_factor)]
    return low, high, np.asarray(inv)


def test_yarn_matches_the_hf_formula():
    """Mellum2's full layers: base 5e5, factor 16 over 8192 positions,
    beta 32 / 1 at head_dim 128 -> the correction range is dims 18..35
    (18.08 floored, 34.98 ceiled); inverse frequencies to 1e-6 relative;
    cos and sin both scaled by the attention factor 0.1 ln 16 + 1."""
    conf = json.loads(
        (ROOT / "benchmarks/chip/configs/mellum2-12b-a2.5b.json").read_text())
    rope = conf["rope_parameters"]["full_attention"]
    low, high, want = _yarn_by_hand(500000.0, 128, 16.0, 8192, 32.0, 1.0)
    assert (low, high) == (18, 35)
    arch, _ = decoder.program(conf, {"batch": 1, "seq_len": 8192})
    rc = arch.rope_for("full")
    assert rc.kind == "yarn"
    assert rc.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    np.testing.assert_allclose(blocks.yarn_inv_freq(rc, 128), want,
                               rtol=1e-6)
    np.testing.assert_allclose(decoder.inv_freq(rope, 128)[0], want,
                               rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 128))
    y = blocks.apply_rope(x, jnp.arange(3), rc)
    np.testing.assert_allclose(y[:, 0], x[:, 0] * rc.attention_factor,
                               rtol=1e-6)
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1)
        * rc.attention_factor, rtol=1e-5)


def test_tiny_cell_runs_through_the_harness(tmp_path, monkeypatch):
    """The whole timed path of a run (registry entry, ``lm.lm_loss``,
    ``trainer.make_train_step``, the window, the reference and the check)
    on the tiny decoder cell, the device check steered here.  Limits from
    tiny readings on the CPU (sim, seed 2**31 + 77): loss 1.4e-6, grad
    7.6e-5, update 9.4e-4."""
    from benchmarks.chip import harness, work
    chip = tmp_path / "benchmarks" / "chip"
    (chip / "configs").mkdir(parents=True)
    (chip / "traffic").mkdir()
    (chip / "configs/mellum-tiny.json").write_text(
        json.dumps(dict(tiny_conf(), name="mellum-tiny")))
    traffic = json.loads(
        (ROOT / "benchmarks/chip/traffic/code8k-int16.json").read_text())
    traffic.update(TRAFFIC, limits={"loss_gap": 1e-5, "grad_gap": 3e-3,
                                    "update_gap": 5e-3})
    (chip / "traffic/tiny.json").write_text(json.dumps(traffic))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "mellum-tiny", "source": "test",
                             "file": "benchmarks/chip/configs/mellum-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "mellum-tiny.tiny",
                               "config": "mellum-tiny", "traffic": "tiny",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_peaks",
                        lambda d: work.peaks("TPU v5 lite"))
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    cell = harness.load_cell("mellum-tiny.tiny", tmp_path)
    result = harness.run(cell, 2 ** 31 + 77, 1.0, False, 0.0)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
