#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  The cell is an entry of ``BENCHMARK.json``
at the root of the checkout; its configuration is the file that entry of
``configs`` names; its traffic is ``chip_bench/traffic/<traffic>.json``,
whose ``path`` names the module ``chip_bench/paths/<path>.py`` that
builds the system under test; the configuration's ``arch`` names the
module ``chip_bench/archs/<arch>.py`` that makes the inputs and holds
the plain reference step and the work counts; the cell's correctness
limits are ``chip_bench/limits/<cell>.json``; each metric is read by
``chip_bench/metrics/<metric>.py``.

A run: weights and a ring of distinct batches from ``--seed``; the
system under test built; three checked steps through the entry the
window drives, then warm-up steps until one compiles nothing; the
window of whole steps, which closes at the end of the first step that
ends after ``--seconds``; then, with the program's state freed, the
plain reference over the three checked steps and the comparison that
decides ``correct``.  ``--trace 1`` profiles the window and reports the
per-layer metrics; ``--trace 0`` reports the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (steps in the window), ``failed`` (steps that raised or
gave a non-finite loss), ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its
limit.  Without a TPU, with fewer chips than the cell asks for, or
without the system under test beside this directory, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chip_bench import reference  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot give a result here (no chip, too few chips)."""


def log(msg: str) -> None:
    print(f"[chip_bench {time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_root: str = ROOT):
    """``chip_bench/<kind>/<name>.py`` under ``bench_root``, by name."""
    file = os.path.join(bench_root, "chip_bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chip_bench.{kind}.{name}@{bench_root}", file)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    """One cell of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits: dict
    metrics: list
    arch: object  # chip_bench/archs/<cfg["arch"]>.py
    root: str = ROOT  # where BENCHMARK.json is

    @classmethod
    def load(cls, name: str, trace: bool, bench_root: str = ROOT) -> "Cell":
        bench = load_json(bench_root, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
        w = cells[name]
        cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
        metrics = [m for m in bench["per_layer" if trace else "end_to_end"]
                   if applies(m, name)]
        here = os.path.join(bench_root, "chip_bench")
        cfg = load_json(bench_root, cfg_entry["file"])
        return cls(
            name=name, chips=int(w["chips"]), cfg=cfg,
            traffic=load_json(here, "traffic", f"{w['traffic']}.json"),
            limits=load_json(here, "limits", f"{name}.json"),
            metrics=metrics, arch=load_module("archs", cfg["arch"], bench_root),
            root=bench_root,
        )

    def path_class(self):
        return load_module("paths", self.traffic["path"], self.root).Path

    def reader(self, metric: str):
        return load_module("metrics", metric, self.root)


class CompileCounter:
    """Counts XLA executables compiled or loaded from the persistent
    cache, through a ``jax.monitoring`` listener, until ``close``."""

    def __init__(self):
        import jax

        self.count = 0
        self._monitoring = jax.monitoring
        self._monitoring.register_event_duration_secs_listener(self._listener)

    def _listener(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._listener)


@dataclass
class Measurement:
    """What a run measured; the metric readers take their numbers from it."""

    cell: Cell
    device_kind: str
    setup_s: float
    steps: int
    samples: int
    window_s: float
    compiles: int
    counters: dict
    shard_work: dict  # device id -> (flops, bytes) of the chip's kernel work in the window
    trace: Optional[dict] = None

    def peaks(self) -> dict:
        from chip_bench.peaks import peaks_for

        return peaks_for(self.device_kind)


def timed_step(path, params, x, y, jax):
    """One step of the window, in the benchmark's own host spans."""
    with jax.profiler.TraceAnnotation("bench.input"):
        feed = (x, y)
    with jax.profiler.TraceAnnotation("bench.step"):
        params, loss = path.step(params, *feed)
    with jax.profiler.TraceAnnotation("bench.sync"):
        loss = float(loss)
        jax.block_until_ready(params)
    return params, loss


def add_work(total: dict, arch, widths, cfg, batch, with_input_dx) -> None:
    for dev, w, calls in widths:
        f, b = arch.shard_work(cfg, batch, w, calls, with_input_dx)
        tf, tb = total.get(dev, (0.0, 0.0))
        total[dev] = (tf + f, tb + b)


def run_cell(cell: Cell, path_cls, devices, seed: int, seconds: float, trace: bool):
    """Set-up, checked steps, warm-up, window, reference.  Returns
    ``(measurement, checks, failed, memory_peak_bytes)``."""
    import jax

    tr = cell.traffic
    batch, ring, checked, lr = tr["batch"], tr["ring"], tr["checked_steps"], tr["lr"]
    compiles = CompileCounter()
    params0, images, labels = cell.arch.make_inputs(cell.cfg, batch, ring, seed)
    p0 = reference.to_host(params0)
    path = path_cls(cell.cfg, tr, devices)
    log("inputs made, system built" + (f"; {path.note()}" if hasattr(path, "note") else ""))
    try:
        params, feed = path.place(params0, images, labels)
        states, losses = [], []
        for i in range(checked):
            params, loss = path.step(params, *feed[i % ring])
            losses.append(float(loss))
            if i in (0, checked - 1):
                states.append(reference.to_host(params))
        i = checked
        for _ in range(tr["warmup_steps_max"]):
            before = compiles.count
            params, loss = timed_step(path, params, *feed[i % ring], jax)
            i += 1
            if compiles.count == before:
                break
        log(f"set-up: {i} steps, {compiles.count} compiles, losses {losses}")

        counters0, compiles0 = path.counters(), compiles.count
        work: dict = {}
        tdir = tempfile.mkdtemp(prefix="chip_bench_trace_") if trace else None
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the benchmark's spans, not every Python call
            jax.profiler.start_trace(tdir, profiler_options=options)
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        steps = failed = 0
        step_s = []
        while True:
            t_step = time.perf_counter()
            add_work(work, cell.arch, path.step_conv_widths(), cell.cfg, batch,
                     path.with_input_dx)
            with jax.profiler.StepTraceAnnotation("bench.train", step_num=steps):
                try:
                    params, loss = timed_step(path, params, *feed[i % ring], jax)
                    failed += not math.isfinite(loss)
                except Exception:
                    traceback.print_exc()
                    failed += 1
            steps += 1
            i += 1
            step_s.append(time.perf_counter() - t_step)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        compiles_in_window = compiles.count - compiles0
        counters1 = path.counters()
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        log(f"device 0 memory stats: {stats[0]}")
    finally:
        path.close()
        compiles.close()
    del params, feed, path
    gc.collect()
    log(f"window: {steps} steps in {window_s:.3f} s, {failed} failed; system closed")
    slow = max(range(steps), key=step_s.__getitem__)
    log(f"window steps: median {sorted(step_s)[steps // 2]:.4f} s, "
        f"slowest {step_s[slow]:.4f} s (step {slow})")

    ref_states, ref_losses = reference.run_reference(
        cell.arch.reference_step(cell.cfg, lr), params0, images[:checked], labels[:checked],
        steps=checked)
    detail: dict = {}
    checks = reference.compare(
        p0, lr, states, losses, [ref_states[0], ref_states[-1]], ref_losses, detail)
    log(f"reference done; losses (program, reference) {detail['losses']}")
    for k in ("grad_gap", "change_gap"):
        log(f"{k} by leaf {({n: float(f'{g:.3g}') for n, g in detail[k].items()})}")

    summary = None
    if trace:
        from chip_bench import trace_reduce

        paths = [os.path.join(r, f) for r, _, fs in os.walk(tdir) for f in fs
                 if f.endswith(".xplane.pb")]
        if paths:
            from jax.profiler import ProfileData

            prof = ProfileData.from_file(paths[0])
            summary = trace_reduce.summarize(*trace_reduce.load_events(prof))
            if summary is not None and summary["collectives"]:
                log(f"collective ops in the trace: {summary['collectives']}")
        shutil.rmtree(tdir, ignore_errors=True)
        log("trace reduced")
    meas = Measurement(
        cell=cell, device_kind=devices[0].device_kind, setup_s=setup_s, steps=steps,
        samples=steps * batch, window_s=window_s, compiles=compiles_in_window,
        counters={k: counters1[k] - counters0[k] for k in counters1},
        shard_work=work, trace=summary,
    )
    return meas, checks, failed, peak


def result_line(cell: Cell, meas: Measurement, checks: dict, failed: int, peak: int,
                devices, trace: bool) -> dict:
    metrics = {}
    for m in cell.metrics:
        value = cell.reader(m["name"]).read(meas)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checked = {k: {"value": v, "limit": cell.limits[k]} for k, v in checks.items()}
    correct = all(c["value"] <= c["limit"] for c in checked.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": meas.steps, "failed": failed,
            "metrics": metrics, "device": device}
    if trace and meas.trace is not None:
        devs = meas.trace["devices"].values()
        device["busy_s"] = sum(d["busy_ns"] for d in devs) / len(devs) / 1e9
        device["window_s"] = meas.trace["window_ns"] / 1e9
        line["breakdown"] = {"device_ops": meas.trace["device_ops"],
                             "idle_gaps": meas.trace["idle_gaps"]}
    line["checks"] = checked
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    trace = bool(args.trace)
    cell = Cell.load(args.workload, trace)
    path_cls = cell.path_class()
    for m in cell.metrics:
        cell.reader(m["name"])

    from repro.compile_cache import configure_compile_cache

    log(f"compile cache: {configure_compile_cache()}")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision", cell.cfg["matmul_precision"])
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX's default device is {devices[0].platform!r}, not a TPU")
    if len(devices) < cell.chips:
        raise Refused(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
    log(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}")
    meas, checks, failed, peak = run_cell(
        cell, path_cls, devices[:cell.chips], args.seed, args.seconds, trace)
    line = result_line(cell, meas, checks, failed, peak, devices, trace)
    for k, c in line["checks"].items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


def exit_now(code: int) -> None:
    """Leave through ``os._exit`` after flushing: runtime threads of the
    system under test must not hang interpreter finalization."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    try:
        rc = main()
    except Refused as e:
        log(f"refused: {e}")
        rc = 3
    except BaseException:
        traceback.print_exc()
        rc = 1
    exit_now(rc)
