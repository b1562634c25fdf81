"""Paper reproduction driver: BERT-style bit-width sweep (Tables 1-2, Figs
3-4) on the synthetic GLUE/SQuAD proxies.

Besides the metric sweep, prints a per-step dispatch table on the pallas
backend: since the single-dispatch limb fusion the traced ``pallas_call``
count per train step is IDENTICAL across bit-widths (one launch per matmul
direction regardless of limb count), so the 16-bit configuration pays no
dispatch overhead over int8 — the end-to-end shape of the paper's headline
"16-bit matches FP32" claim.  Step times come from the chip benchmark
(``benchmarks/chip/``), not from here.

    PYTHONPATH=src python examples/finetune_bitwidth_sweep.py --task span \
        --steps 200
"""
import argparse
import dataclasses
import sys

sys.path.insert(0, ".")

from benchmarks.tasks import FtConfig, finetune, step_stats, sweep  # noqa: E402
from repro.core.qconfig import QuantConfig  # noqa: E402


def dispatch_report(task: str, presets, ft: FtConfig) -> None:
    """Per-step traced-dispatch deltas between bit-widths."""
    print("per-step dispatch (pallas backend):")
    rows = {}
    for p in presets:
        q = dataclasses.replace(QuantConfig.preset(p), backend="pallas") \
            if QuantConfig.preset(p).enabled else QuantConfig.preset(p)
        rows[p] = step_stats(task, q, ft)
    base = rows[presets[-1]]          # narrowest width = dispatch baseline
    for p, s in rows.items():
        d_calls = s["pallas_calls"] - base["pallas_calls"]
        print(f"  {p:7s} pallas_calls/step={s['pallas_calls']:4d} "
              f"(delta vs {presets[-1]}: {d_calls:+d})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="cls", choices=["cls", "span", "img"])
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--fig4", action="store_true",
                    help="activation-bit-width sweep at w8/g8 (paper Fig. 4)")
    ap.add_argument("--no-dispatch-report", action="store_true",
                    help="skip the per-step dispatch table")
    args = ap.parse_args()

    ft = FtConfig(steps=args.steps)
    if args.fig4:
        print("Fig. 4 — w8/g8, varying activation bits on the span task:")
        for ab in (8, 10, 12, 16):
            q = QuantConfig(weight_bits=8, act_bits=ab, grad_bits=8)
            metric, _ = finetune("span", q, ft)
            print(f"  act_bits={ab:<3d} EM={metric:.2f}")
        return
    presets = ["fp32", "int16", "int12", "int10", "int8"]
    if not args.no_dispatch_report:
        dispatch_report(args.task, presets[1:], ft)
    print(f"bit-width sweep on task={args.task} ({args.steps} steps/point):")
    res = sweep(args.task, presets, ft)
    base = res["fp32"]
    for p, m in res.items():
        print(f"  {p:7s} metric={m:6.2f} drop={base - m:+.2f}")


if __name__ == "__main__":
    main()
