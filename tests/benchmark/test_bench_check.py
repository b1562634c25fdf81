"""The comparison that decides ``correct``, on hand-made readings."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.chip import check  # noqa: E402

LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.1, "update_gap": 0.05}


def ref():
    # five leaves; the last has no gradient (an unused head) and moves by
    # weight decay alone
    return {"losses": np.array([6.0, 5.0, 4.0]),
            "grad": np.array([1.0, 2.0, 0.5, 4.0, 0.0]),
            "change": np.array([3.0, 3.0, 3.0, 3.0, 1e-4])}


def test_equal_readings_pass():
    r = ref()
    numbers = check.compare(r, r)
    assert numbers == {"loss_gap": 0.0, "grad_gap": 0.0, "update_gap": 0.0}
    ok, checks = check.judge(numbers, LIMITS, failed=0)
    assert ok and list(checks) == [*check.NUMBERS, "failed_steps"]


def test_gaps_by_hand():
    r = ref()
    p = {"losses": [6.0, 5.01, 4.0],
         "grad": [1.0, 2.0, 0.6, 4.0, 0.0],          # small leaf: / median 1.0
         "change": [3.0, 2.7, 3.0, 3.0, 3.0]}        # dead leaf is left out
    numbers = check.compare(p, r)
    assert numbers["loss_gap"] == pytest.approx(0.01 / 5.0)
    assert numbers["grad_gap"] == pytest.approx(0.1 / 1.0)
    assert numbers["update_gap"] == pytest.approx(0.3 / 3.0)
    ok, _ = check.judge(numbers, LIMITS, failed=0)
    assert not ok


@pytest.mark.parametrize("where", ["losses", "grad", "change"])
def test_non_finite_never_passes(where):
    p = {k: np.array(v, dtype=float) for k, v in ref().items()}
    p[where][1] = np.nan
    ok, _ = check.judge(check.compare(p, ref()), LIMITS, failed=0)
    assert not ok


def test_a_failed_step_or_a_missing_limit_fails():
    r = ref()
    numbers = check.compare(r, r)
    assert not check.judge(numbers, LIMITS, failed=1)[0]
    assert not check.judge(numbers, {**LIMITS, "grad_gap": None}, 0)[0]


def test_unchanged_state_reads_one():
    """A step that returns its state: no first moment, no change."""
    r = ref()
    p = {"losses": r["losses"], "grad": np.zeros(5), "change": np.zeros(5)}
    numbers = check.compare(p, r)
    assert numbers["grad_gap"] == pytest.approx(1.0)
    assert numbers["update_gap"] == pytest.approx(1.0)


def test_leaf_names_split_stacked_layers():
    import jax.numpy as jnp
    tree = {"blocks": {"w": jnp.ones((3, 2, 2))}, "embed": jnp.ones((4, 2))}
    assert check.leaf_names(tree) == ["blocks.w[0]", "blocks.w[1]",
                                      "blocks.w[2]", "embed"]
    norms = check.leaf_norms({"blocks": {"w": jnp.arange(12.0).reshape(
        3, 2, 2)}, "embed": jnp.full((4, 2), 0.5)})
    np.testing.assert_allclose(
        norms, [np.sqrt(14.0), np.sqrt(126.0), np.sqrt(366.0), np.sqrt(2.0)],
        rtol=1e-6)


def test_seed_keys_differ_above_32_bits():
    a, b = check.seed_key(5), check.seed_key(5 + 2 ** 32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
