"""Quantization configuration for b-bit dynamic fixed-point (DFX) training.

The paper's control knobs are the bit-widths of the three tensor classes that
flow through an integer layer:

* ``weight_bits``  — parameters (paper: 8..16)
* ``act_bits``     — input activations (paper: must be >= 12 when weights are 8-bit)
* ``grad_bits``    — upstream gradients quantized in the backward pass

plus the rounding mode of the backward pass (paper: stochastic rounding, which
makes the DFX gradient an unbiased estimator — Assumption 2).

``QuantConfig`` is a frozen pytree-leafless dataclass threaded through every
integer layer; ``enabled=False`` short-circuits to the FP32 baseline so the
same model code runs both the paper's method and its baseline.
"""
from __future__ import annotations

import dataclasses
import os
import warnings


class StabilityWarning(UserWarning):
    """The paper's empirical stability constraint is violated: Figure 4
    shows w8·a8·g8 diverging while w8·a12·g8 tracks FP32 — 8-bit weights
    need >= 12-bit activations.  A warning (not an error) because the
    diverging configuration is itself a paper experiment
    (``int8_naive``)."""


def stability_violated(cfg: "QuantConfig") -> bool:
    """Paper's empirical stability constraint (Fig. 4): 8-bit weights need
    >= 12-bit activations."""
    return cfg.enabled and cfg.weight_bits == 8 and cfg.act_bits < 12


def _env_default_backend() -> str:
    """Default execution backend: ``"pallas"`` on a TPU, ``"sim"`` elsewhere.

    Decided when a config is built (never at import), from the platform JAX
    runs on, so a chip run takes the kernels without being told to.
    ``REPRO_BACKEND`` overrides it — CI runs the whole tier-1 suite as a
    ``{sim, pallas}`` backend matrix (``.github/workflows/ci.yml``) that
    way.  Invalid values fail fast in ``__post_init__``.
    """
    env = os.environ.get("REPRO_BACKEND")
    if env:
        return env
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "sim"


def _env_default_kept_ops() -> str:
    """Default kept-ops mode; ``REPRO_KEPT_OPS`` overrides it.

    Same pattern as ``_env_default_backend``: the CI kept-ops matrix leg
    exports ``REPRO_KEPT_OPS=integer`` and every ``QuantConfig`` built
    without an explicit ``kept_ops=`` picks it up.  An empty value counts
    as unset (the CI matrix passes ``REPRO_KEPT_OPS=""`` on other legs).
    Invalid values fail fast in ``__post_init__``.
    """
    return os.environ.get("REPRO_KEPT_OPS") or "fp32"


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Static configuration of the b-bit dynamic fixed-point mapping."""

    enabled: bool = True
    weight_bits: int = 16
    act_bits: int = 16
    grad_bits: int = 16
    #: stochastic rounding for gradient quantization (paper requires it for
    #: the unbiasedness assumption; forward uses round-to-nearest).
    stochastic_grad: bool = True
    #: also stochastically round the forward mappings (off in the paper).
    stochastic_fwd: bool = False
    #: execution backend for the integer layers: "sim" runs the mantissa
    #: contractions through XLA with float accumulators (exactness governed
    #: by ``dfx.acc_dtype``); "pallas" routes quantization and both matmul
    #: directions (forward q(X)·q(W), backward dX/dW) through the Pallas
    #: kernels in ``repro.kernels`` — bit-exact int32 limb accumulation,
    #: interpret mode off-TPU.  Defaults to $REPRO_BACKEND, else "pallas" on
    #: a TPU and "sim" elsewhere.
    backend: str = dataclasses.field(default_factory=_env_default_backend)
    #: what the paper's *kept* FP32 ops (softmax exp, GeLU/SiLU, the norm
    #: rsqrt, the pooler tanh) compute with: "fp32" is the paper's setting;
    #: "integer" swaps each for its fixed-point form in ``core/iapprox.py``
    #: (I-BERT-style, DESIGN.md §10) — in-kernel on the pallas backend, the
    #: bit-identical XLA trace on sim.  Per-scope resolvable through
    #: ``QuantPolicy`` like every other field.  Only meaningful with
    #: ``enabled=True``: a disabled config is the FP32 *baseline* and keeps
    #: the stock float ops everywhere.  Defaults to $REPRO_KEPT_OPS (else
    #: "fp32") so CI can run a kept-ops matrix leg.
    kept_ops: str = dataclasses.field(default_factory=_env_default_kept_ops)
    #: emit a ``StabilityWarning`` when the paper's "act_bits >= 12 when
    #: weight_bits == 8" constraint is violated (Fig. 4's divergence).
    #: Opt-out knob, not an error — ``int8_naive`` is a paper experiment.
    warn_stability: bool = True

    def __post_init__(self):
        for name in ("weight_bits", "act_bits", "grad_bits"):
            b = getattr(self, name)
            if not (2 <= b <= 24):
                raise ValueError(f"{name}={b} outside supported range [2, 24]")
        if self.warn_stability and stability_violated(self):
            warnings.warn(
                f"weight_bits=8 with act_bits={self.act_bits} < 12 violates "
                "the paper's stability constraint (Fig. 4: w8-a8-g8 diverges "
                "while w8-a12-g8 matches FP32); pass warn_stability=False to "
                "silence", StabilityWarning, stacklevel=2)
        if self.backend not in ("sim", "pallas"):
            raise ValueError(
                f"backend={self.backend!r} not in ('sim', 'pallas')")
        if self.kept_ops not in ("fp32", "integer"):
            raise ValueError(
                f"kept_ops={self.kept_ops!r} not in ('fp32', 'integer')")

    # -- presets matching the paper's experimental grid -------------------
    @staticmethod
    def fp32() -> "QuantConfig":
        """FP32 baseline (quantization disabled)."""
        return QuantConfig(enabled=False)

    @staticmethod
    def int16() -> "QuantConfig":
        return QuantConfig(weight_bits=16, act_bits=16, grad_bits=16)

    @staticmethod
    def int12() -> "QuantConfig":
        return QuantConfig(weight_bits=12, act_bits=12, grad_bits=12)

    @staticmethod
    def int10() -> "QuantConfig":
        return QuantConfig(weight_bits=10, act_bits=10, grad_bits=10)

    @staticmethod
    def int8() -> "QuantConfig":
        """Paper's headline low-bit setting: int8 weights/grads, int12 acts."""
        return QuantConfig(weight_bits=8, act_bits=12, grad_bits=8)

    @staticmethod
    def int8_naive() -> "QuantConfig":
        """w8 a8 g8 — the diverging configuration of Figure 4."""
        return QuantConfig(weight_bits=8, act_bits=8, grad_bits=8)

    @staticmethod
    def preset(name: str):
        """Config preset by name.  Policy-preset names (``"int8_embed16"``,
        ...) return a ``QuantPolicy`` — every model entry point accepts
        either, so ``--quant int8_embed16`` works wherever ``--quant int8``
        does."""
        table = {
            "fp32": QuantConfig.fp32,
            "int16": QuantConfig.int16,
            "int12": QuantConfig.int12,
            "int10": QuantConfig.int10,
            "int8": QuantConfig.int8,
            "int8_naive": QuantConfig.int8_naive,
        }
        if name in table:
            return table[name]()
        from repro.core import qpolicy  # lazy: qpolicy imports this module
        if name in qpolicy.POLICY_PRESETS:
            return qpolicy.preset(name)
        raise KeyError(f"unknown quant preset {name!r}; have "
                       f"{sorted(table) + sorted(qpolicy.POLICY_PRESETS)}")


PRESETS = ("fp32", "int16", "int12", "int10", "int8", "int8_naive")
