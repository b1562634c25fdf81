"""Device time of one cell by the program's named scopes and by phase.

    python3 benchmarks/chip/scope_profile.py --workload <name> --seed <n> \
        --seconds <s>

On the cell's chips, like ``run.py``.  Set-up is the harness's own (the
compiled step, its state, three steps); then one window of ``--seconds``
with the profiler off and one with it on.  The last line of standard
output is a JSON object:

* ``tokens_per_s``: positions per second in each window, so the cost of
  tracing reads as their ratio;
* ``step_ms``: device time per steady traced step;
* ``readings``: each of ``scopes.READINGS`` in ms per steady step;
* ``by_layer``: ms per steady step by top-level scope and phase, and the
  longest ops of each (``""``: ops outside every named scope);
* ``reading_ops``: the longest ops that each reading sums;
* ``kernels``: ms per steady step of each Pallas kernel's instruction
  name, as the ``*_ms`` readers match them.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

def summarize(by_layer: dict, top: int = 6) -> dict:
    """``scopes.by_layer`` as {"<scope>.<phase>": {"ms": total, "ops":
    [[op, ms], ...]}}, longest first."""
    rows = {}
    for (scope, phase, op), ms in by_layer.items():
        row = rows.setdefault(f"{scope}.{phase}", {"ms": 0.0, "ops": {}})
        row["ms"] += ms
        row["ops"][op] = row["ops"].get(op, 0.0) + ms
    return {k: {"ms": r["ms"], "ops": sorted(
                r["ops"].items(), key=lambda kv: -kv[1])[:top]}
            for k, r in sorted(rows.items(), key=lambda kv: -kv[1]["ms"])}


def reading_ops(plain: dict, top: int = 8) -> dict:
    """The ``top`` longest op names (ms per steady step) that each of
    ``scopes.READINGS`` sums."""
    from benchmarks.chip import scopes
    spans = scopes.scope_ns(plain)
    out = {}
    for reading, match in scopes.READINGS.items():
        ops = {}
        for steps, ns in spans:
            for (name, op_name), n in ns.items():
                if match(*scopes.layer_of(op_name)):
                    ms = n / steps / len(spans) / 1e6
                    ops[name] = ops.get(name, 0.0) + ms
        out[reading] = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return out


def main():
    import argparse
    import glob
    import json
    import shutil
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    from benchmarks.chip import harness, scopes, trace
    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    harness.use_compile_cache()
    prog = harness.Program(cell, devices)
    prog.first_steps(args.seed)
    op_scopes = scopes.instruction_scopes(prog.compiled.as_text())
    positions = cell.family.positions(cell.conf, cell.traffic)

    def rate(window):
        return window["attempted"] * positions / (window["t_end"]
                                                  - window["t_start"])

    untraced = prog.measure(args.seconds, harness.Tracer(False).annotate)
    tracer = harness.Tracer(True)
    tracer.start()
    traced = prog.measure(args.seconds, tracer.annotate)
    tracer.stop()
    prog.close()
    try:
        path = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        plain = scopes.from_profile(path, [d.id for d in devices],
                                    harness.STEP_MODULE, op_scopes)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    red = trace.reduce({"devices": {
        k: {"modules": d["modules"], "ops": [op[:4] for op in d["ops"]]}
        for k, d in plain["devices"].items()}, "host": plain["host"]})
    prefixes = sum((harness.load_metric(m).PREFIXES for m in (
        "matmul_ms", "attention_ms", "quantize_ms", "norm_ms")), ())
    kernels = {}
    for (scope, phase, op), ms in scopes.by_layer(plain).items():
        if op.startswith(prefixes):
            kernels[op] = kernels.get(op, 0.0) + ms
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "tokens_per_s": {"untraced": rate(untraced), "traced": rate(traced)},
        "step_ms": 1e3 * red.window_s / red.steps,
        "busy_ms": 1e3 * red.busy_s / red.steps,
        "readings": scopes.readings(plain),
        "by_layer": summarize(scopes.by_layer(plain)),
        "reading_ops": reading_ops(plain),
        "kernels": kernels,
    }), flush=True)


if __name__ == "__main__":
    main()
