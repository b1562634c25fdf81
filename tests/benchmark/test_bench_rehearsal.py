"""A whole run of one cell on the CPU at a tiny size, the device check
steered here in the test, and the same run with the timed path broken
underneath: the control (every bit-width four bits lower) and each fault a
training cell can have must come out not ``correct``.

The tiny cell is bert-base's configuration at width 64 and depth 2 with
the ``squad384-int16`` traffic at B=8, S=32, added as files and entries of
its own.  Its limits come from tiny readings on the CPU (sim backend, seeds
1-3): the program read at most loss 1e-5, grad 6.2e-3, update 1.4e-3; the
control grad 8.5e-2 or more, update 1.2e-2 or more; half of the batch left
out loss 6.7e-3 or more; a step that returns its state unchanged reads 1
for grad and update.
"""
import json
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import calibrate, harness, work  # noqa: E402

TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 3e-2, "update_gap": 5e-3}
SEED = 2 ** 31 + 77


@pytest.fixture
def tiny_cell(tmp_path, monkeypatch):
    chip = tmp_path / "benchmarks" / "chip"
    (chip / "configs").mkdir(parents=True)
    (chip / "traffic").mkdir()
    conf = json.loads(
        (ROOT / "benchmarks/chip/configs/bert-base.json").read_text())
    conf.update(name="bert-tiny", hidden_size=64, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=128, vocab_size=512)
    (chip / "configs/bert-tiny.json").write_text(json.dumps(conf))
    traffic = json.loads(
        (ROOT / "benchmarks/chip/traffic/squad384-int16.json").read_text())
    traffic.update(batch=8, seq_len=32, limits=TINY_LIMITS)
    (chip / "traffic/tiny.json").write_text(json.dumps(traffic))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "bert-tiny", "source": "test",
                             "file": "benchmarks/chip/configs/bert-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "bert-tiny.tiny", "config": "bert-tiny",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_peaks",
                        lambda d: work.peaks("TPU v5 lite"))
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    return harness.load_cell("bert-tiny.tiny", tmp_path)


def state_unchanged(monkeypatch, cell):
    from repro.train import trainer
    real = trainer.make_train_step

    def broken(*a, **k):
        step = real(*a, **k)

        def same_state(params, opt, batch, key):
            _, _, metrics = step(params, opt, batch, key)
            return params, opt, metrics
        return same_state
    monkeypatch.setattr(trainer, "make_train_step", broken)


def half_of_the_batch(monkeypatch, cell):
    real = cell.family.program
    monkeypatch.setattr(cell.family, "program", lambda c, t: (
        real(c, t)[0], calibrate.half_batch(real(c, t)[1])))


def control(monkeypatch, cell):
    real = harness.quant_config
    monkeypatch.setattr(harness, "quant_config", lambda t, bits=None: real(
        t, t["control_bits"]))


@pytest.mark.parametrize("breakage, correct", [
    (None, True),
    (control, False),
    (state_unchanged, False),
    (half_of_the_batch, False),
], ids=["program", "control", "state_unchanged", "half_of_the_batch"])
def test_run_decides_correct(tiny_cell, monkeypatch, breakage, correct):
    if breakage is not None:
        breakage(monkeypatch, tiny_cell)
    result = harness.run(tiny_cell, SEED, 1.0, False, 0.0)
    assert result["correct"] is correct, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    assert result["device"]["count"] >= 1


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "bert-base.squad384-int16", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert e.value.code != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "TPU" in out.err
