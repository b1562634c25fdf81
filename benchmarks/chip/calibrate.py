"""Readings that the limits of ``correct`` are set from, at a cell's own
size, in one process:

* the program as the cell runs it, on every seed of ``--seeds``;
* the control, the program with every bit-width of the traffic's
  ``control_bits`` (four bits below the stated ones), on ``--control-seeds``;
* the fault "half of the batch left out, the mean taken over the rest",
  planted in the program's loss, on ``--fault-seeds``.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,3 --control-seeds 1,2,3 --fault-seeds 1,2,3

Each reading is one JSON line on standard output (and in ``--out``).  The
fault "a step that returns its state unchanged" needs no run: it leaves the
first moment at zero and the parameters where they were, so ``grad_gap``
and ``update_gap`` read 1.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def half_batch(loss):
    """The loss over the first half of the rows only."""
    import jax

    def wrapped(params, batch, cfg, qcfg, key):
        return loss(params, jax.tree.map(lambda x: x[:x.shape[0] // 2],
                                         batch), cfg, qcfg, key)
    return wrapped


def main():
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json, or <config>.<traffic> "
                         "for one that is not listed there")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args()

    from benchmarks.chip import check, harness
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    listed = {w["name"]: w for w in bench["workloads"]}
    config, _, traffic = args.workload.partition(".")
    cell = harness.make_cell(listed.get(args.workload) or {
        "name": args.workload, "config": config, "traffic": traffic,
        "chips": 1}, bench)
    devices = harness.require_devices(cell.chips)
    harness.use_compile_cache()
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    variants = [
        ("program", {}, seeds(args.seeds)),
        ("control", {"qcfg": harness.quant_config(
            cell.traffic, cell.traffic["control_bits"])},
         seeds(args.control_seeds)),
        ("half_batch", {"loss_wrap": half_batch}, seeds(args.fault_seeds)),
    ]
    reference = check.Reference(cell.family, cell.conf, cell.traffic)
    refs = {}
    out = open(args.out, "a") if args.out else None
    for variant, kw, vseeds in variants:
        if not vseeds:
            continue
        prog = harness.Program(cell, devices, **kw)
        for seed in vseeds:
            t0 = time.perf_counter()
            readings = prog.first_steps(seed)
            t1 = time.perf_counter()
            batches = prog.batches[:check.STEPS]
            prog.params = prog.opt = None
            if seed not in refs:
                refs[seed] = reference.run(check.keys(seed)[0], batches)
            t2 = time.perf_counter()
            ref = refs[seed]
            line = {"workload": cell.name, "variant": variant, "seed": seed,
                    "numbers": check.compare(readings, ref),
                    "worst": check.worst_leaves(readings, ref, prog.names),
                    "losses": readings["losses"],
                    "ref_losses": ref["losses"].tolist(),
                    "program_s": t1 - t0, "reference_s": t2 - t1}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
        prog.close()
    if out:
        out.close()


if __name__ == "__main__":
    main()
