"""One run of one cell: set-up, a measured window of training steps, the
correctness check, and the result line.

Everything that belongs to one cell is found by name: the cell's entry in
``BENCHMARK.json`` names a configuration (a file listed there) and a
traffic mix (``traffic/<name>.json``); the configuration names a family
(``families/<family>.py``); each per-layer metric is ``metrics/<name>.py``.
Adding a cell, a configuration, a family or a metric adds files and
entries and edits none of this.

The timed path is the program's own: ``trainer.init_train_state``,
``make_train_step`` and ``jit_train_step`` on a data-parallel mesh over the
cell's chips, with the family's loss from ``repro.models.paper_models``.
Set-up builds that compiled step and its state once, drives it through the
first three steps (on three different batches, through the same call and
feed as the window), and hands the same step and state to the window.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import glob
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmarks.chip import check, trace as trace_lib, work

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
KEY_POOL = 256
STEP_MODULE = "jit_step"


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    traffic: dict
    family: object
    end_to_end: list
    per_layer: list


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    return make_cell(cells[name], bench, root)


def make_cell(w: dict, bench: dict, root: pathlib.Path = ROOT) -> Cell:
    """The cell of a ``workloads`` entry ``w`` (name, config, traffic,
    chips), which need not be listed in ``bench``."""
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = json.loads((root / cfg_entry["file"]).read_text())
    traffic_file = root / "benchmarks/chip/traffic" / f"{w['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    family = importlib.import_module(
        f"benchmarks.chip.families.{conf['family']}")
    name = w["name"]
    return Cell(name, w["chips"], conf, traffic, family,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def load_metric(name: str):
    """The reader module ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; exits without a result otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX has {len(devs)} "
              f"{devs[0].platform} device(s) ({devs[0].device_kind})",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def device_peaks(device) -> dict:
    return work.peaks(device.device_kind)


def use_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or the fixed ``<checkout>/.jax_cache``), holding every program however
    quickly it compiled, so that a run's set-up repeats."""
    import jax
    from repro import utils
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return utils.use_compile_cache()


def quant_config(traffic: dict, bits: dict | None = None):
    """The program's preset named by the traffic, held to the bit-widths
    the traffic states; ``bits`` replaces them (the control)."""
    from repro.core.qconfig import QuantConfig
    q = QuantConfig.preset(traffic["preset"])
    have = {"weight": q.weight_bits, "act": q.act_bits, "grad": q.grad_bits}
    if have != traffic["bits"]:
        raise ValueError(f"preset {traffic['preset']!r} has bits {have}, "
                         f"the traffic states {traffic['bits']}")
    if bits is None:
        return q
    return dataclasses.replace(q, weight_bits=bits["weight"],
                               act_bits=bits["act"], grad_bits=bits["grad"],
                               warn_stability=False)


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class Program:
    """The compiled train step of one cell, its state, and its feed.

    ``qcfg`` replaces the traffic's preset and ``loss_wrap`` wraps the
    program's loss; both serve only the calibration of the limits.
    """

    def __init__(self, cell: Cell, devices, qcfg=None, loss_wrap=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import sharding
        from repro.train import optimizer as opt_lib, trainer

        fam, conf, traffic = cell.family, cell.conf, cell.traffic
        self.mesh = sharding.make_mesh((len(devices), 1), ("data", "model"),
                                       devices=devices)
        sharding.set_mesh(self.mesh)
        arch, loss = fam.program(conf, traffic)
        if loss_wrap is not None:
            loss = loss_wrap(loss)
        self.opt_cfg = opt_lib.OptimizerConfig(**traffic["optimizer"])
        init = functools.partial(fam.init, conf=conf)
        self._trainer = trainer
        self._init_fn = init
        shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
        self.pspecs = sharding.param_pspecs(shapes, self.mesh, fsdp=False)
        self.init = jax.jit(init, out_shardings=self.pspecs)
        self.names = check.leaf_names(shapes)
        self.step = trainer.make_train_step(
            loss, arch, qcfg or quant_config(traffic), self.opt_cfg)
        self.batch_sharding = NamedSharding(
            self.mesh, P(sharding.batch_axes(self.mesh)))
        self.replicated = NamedSharding(self.mesh, P())
        n = traffic["distinct_batches"]
        self.make_batches = jax.jit(
            lambda k: [fam.make_batch(jax.random.fold_in(k, i), conf, traffic)
                       for i in range(n)],
            out_shardings=self.batch_sharding)
        self.compiled = None
        self.params = self.opt = None

    def feed(self, i: int):
        return self.batches[i % len(self.batches)], self.keys[i % KEY_POOL]

    def first_steps(self, seed: int) -> dict:
        """Fresh state from ``seed`` and three steps; returns the readings
        ``check.compare`` takes.  Leaves the state ready for step 4."""
        import jax
        k_init, k_batch, k_step = check.keys(seed)
        self.params = self.opt = None
        self.params, self.opt, _ = self._trainer.init_train_state(
            self._init_fn, k_init, self.mesh, fsdp=False,
            opt_cfg=self.opt_cfg)
        self.batches = self.make_batches(k_batch)
        self.keys = [jax.device_put(k, self.replicated) for k in
                     np.asarray(jax.random.split(k_step, KEY_POOL))]
        if self.compiled is None:
            self.compiled = self._trainer.jit_train_step(
                self.step, self.mesh, self.pspecs, opt_state_like=self.opt
            ).lower(self.params, self.opt, *self.feed(0)).compile()
        losses, grad = [], None
        for i in range(check.STEPS):
            self.params, self.opt, m = self.compiled(self.params, self.opt,
                                                     *self.feed(i))
            losses.append(float(m["loss"]))
            if grad is None:
                grad = np.asarray(check.leaf_norms_jit(self.opt.m),
                                  np.float64) / (1 - self.opt_cfg.beta1)
        p0 = self.init(k_init)
        change = np.asarray(check.change_norms_jit(self.params, p0),
                            np.float64)
        del p0
        self.i = check.STEPS
        return {"losses": losses, "grad": grad, "change": change}

    def measure(self, seconds: float, annotate) -> dict:
        """Steps for ``seconds``, one kept in flight: step i+1 is
        dispatched before step i's loss is read.  A step is attempted when
        dispatched in the window and failed when its loss is not finite."""
        import jax
        attempted = failed = 0
        pending = None
        done = []
        # what set-up made stays alive: frozen, a full collection in the
        # window does not walk it
        gc.collect()
        gc.freeze()
        t_start = time.perf_counter()
        deadline = t_start + seconds
        while time.perf_counter() < deadline:
            with annotate("bench.next_batch"):
                batch, key = self.feed(self.i)
            with annotate("bench.dispatch"):
                self.params, self.opt, m = self.compiled(
                    self.params, self.opt, batch, key)
            self.i += 1
            attempted += 1
            if pending is not None:
                with annotate("bench.read_loss"):
                    failed += not math.isfinite(float(pending))
                done.append(time.perf_counter())
            pending = m["loss"]
        if pending is not None:
            with annotate("bench.read_loss"):
                failed += not math.isfinite(float(pending))
        jax.block_until_ready((self.params, self.opt))
        t_end = time.perf_counter()
        gc.unfreeze()
        return {"attempted": attempted, "failed": failed,
                "t_start": t_start, "t_end": t_end,
                "step_s": np.diff([t_start, *done, t_end]).tolist()}

    def close(self):
        """Free the program's state; the batches stay for the reference."""
        from repro import sharding
        self.params = self.opt = self.compiled = None
        sharding.set_mesh(None)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    cell: Cell
    chips: int
    peak: dict
    reduction: object          # trace.Reduction or None
    memory_peak_bytes: int

    @functools.cached_property
    def step_ops(self) -> int:
        c = self.cell
        return work.step_ops(c.family, c.conf, c.traffic)

    @functools.cached_property
    def matmul_least_s(self) -> float:
        c = self.cell
        return work.least_seconds(
            work.matmul_products(c.family, c.conf, c.traffic,
                                 c.traffic["bits"]), self.peak)

    def op_seconds(self, prefixes) -> float | None:
        """Device seconds per steady step of ops named with a prefix."""
        if self.reduction is None:
            return None
        return self.reduction.op_seconds_per_step(
            lambda n: n.startswith(tuple(prefixes)))


class Tracer:
    """The profiler around the window, writing under ``$TMPDIR``; with
    ``enabled`` False every method does nothing."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled else None

    def start(self):
        if self.enabled:
            import jax
            jax.profiler.start_trace(self.dir)

    def stop(self):
        if self.enabled:
            import jax
            jax.profiler.stop_trace()

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def read(self, device_ids) -> dict:
        """The trace in ``trace.from_profile``'s plain form; the files go."""
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            return trace_lib.from_profile(path, device_ids, STEP_MODULE)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_process: float) -> dict:
    """Everything after argument parsing; returns the result object."""
    devices = require_devices(cell.chips)
    import jax
    peak = device_peaks(devices[0])
    use_compile_cache()

    prog = Program(cell, devices)
    prog_readings = prog.first_steps(seed)
    tracer = Tracer(traced)
    tracer.start()
    window = prog.measure(seconds, tracer.annotate)
    tracer.stop()
    setup_s = window["t_start"] - t_process
    mem = peak_memory(devices)
    batches = prog.batches[:check.STEPS]
    prog.close()
    del prog

    ref = check.Reference(cell.family, cell.conf, cell.traffic).run(
        check.keys(seed)[0], batches)
    numbers = check.compare(prog_readings, ref)
    correct, checks = check.judge(numbers, cell.traffic["limits"],
                                  window["failed"])

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    print(f"bench: seconds between steps read in the window "
          f"{[round(x, 4) for x in window['step_s']]}", file=sys.stderr)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"]}
    if traced:
        red = trace_lib.reduce(tracer.read([d.id for d in devices]))
        ctx = Context(cell, len(devices), peak, red, mem)
        metrics = {}
        for entry in cell.per_layer:
            value = load_metric(entry["name"]).read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        if red is not None:
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            result["breakdown"] = red.breakdown()
    else:
        positions = cell.family.positions(cell.conf, cell.traffic)
        elapsed = window["t_end"] - window["t_start"]
        values = {"tokens_per_s": window["attempted"] * positions / elapsed,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device)
    result["checks"] = checks
    return result


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_process)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
