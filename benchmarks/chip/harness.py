"""The benchmark's general part: find a cell's files by name, run its
driver, read its per-layer metrics and print the result.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Their files are found by name:

* ``configs/<config>.json`` — the sizes as run, and the limits of the
  correctness comparison; ``configs/<config>.ref.py`` — its plain
  reference;
* ``traffic/<traffic>.json`` — the mix's parameters; its ``kind`` names
  the generator that reads it, ``<kind>_cell.py`` (`train_cell`,
  `serve_cell`);
* ``metrics/<metric>.py`` — one reader per per-layer metric, with a
  ``read(run)`` that returns a number or None (nothing to read).

Adding a cell, a configuration, a mix or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_module(path: str, name: str | None = None):
    name = name or "chipbench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> tuple[dict, dict, dict, dict]:
    """(benchmark spec, cell, configuration, traffic) for ``workload``."""
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg = read_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    traffic = read_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return spec, cell, cfg, traffic


def reference(cfg: dict):
    return load_module(os.path.join(HERE, "configs", cfg["name"] + ".ref.py"))


@dataclass
class Run:
    """What one run of a cell leaves for the metric readers."""
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace_on: bool
    t_start: float                 # perf_counter at process start
    peak: dict | None = None       # this device's row of peaks.json
    trace: object = None           # xplane.Trace of the traced window
    spans: list = field(default_factory=list)  # [(name, t0_ns, dur_ns)]
    facts: dict = field(default_factory=dict)  # counts the driver knows
    end_to_end: dict = field(default_factory=dict)   # name → value
    checks: dict = field(default_factory=dict)  # name → (value, limit)
    attempted: int = 0
    failed: int = 0
    ok: bool = True                # no malformed or missing answer
    notes: list = field(default_factory=list)  # lines for stderr

    def span_s(self, name: str) -> list:
        return [d * 1e-9 for n, _, d in self.spans if n == name]

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = (float(value), float(limit))

    def read_memory_peak(self, devices) -> None:
        """Peak device memory of the fullest chip; read after the window
        and before the reference runs (a process's peak never falls)."""
        self.facts["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices)


def peaks_for(kind: str) -> dict:
    table = read_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def read_per_layer(spec: dict, cell: dict, run: Run) -> dict:
    out = {}
    for m in spec["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        v = load_module(path).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def end_to_end(spec: dict, cell: dict, run: Run) -> dict:
    out = {}
    for m in spec["end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if m["name"] in run.end_to_end:
            out[m["name"]] = {"value": float(run.end_to_end[m["name"]]),
                              "unit": m["unit"]}
    return out


def device_info(devices, run: Run) -> dict:
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": run.facts["memory_peak_bytes"]}
    if run.trace is not None:
        info["busy_s"] = run.trace.busy_s
        info["window_s"] = run.trace.window_s
    return info


def execute(workload: str, seed: int, seconds: float, trace: bool,
            t_start: float, devices, *, root: str = ROOT,
            overrides=None) -> dict:
    """Run one cell and return its result line (a dict).  ``overrides``
    lets the benchmark's own tests shrink the configuration and patch
    the traffic; the command line never passes it."""
    spec, cell, cfg, traffic = load_cell(workload, root)
    if overrides:
        cfg, traffic = overrides(cfg, traffic)
    run = Run(cfg=cfg, traffic=traffic, seed=seed, seconds=seconds,
              trace_on=trace, t_start=t_start)
    if trace:
        run.peak = peaks_for(devices[0].device_kind)
    driver = load_module(os.path.join(HERE, traffic["kind"] + "_cell.py"))
    # the profiler's trace is read before the run ends, then deleted
    traces = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        driver.run(run, traces, devices[:cell["chips"]])
    finally:
        shutil.rmtree(traces, ignore_errors=True)
    within = all(v <= lim for v, lim in run.checks.values())
    correct = bool(run.ok and within)
    result = {
        "correct": correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": (read_per_layer(spec, cell, run) if trace
                    else end_to_end(spec, cell, run)),
        "device": device_info(devices, run),
    }
    if trace and run.trace is not None:
        result["breakdown"] = {
            "device_ops": run.trace.top_ops(10),
            "idle_gaps": run.trace.idle_gaps(
                10, spans=tuple(run.facts.get("span_names", ()))),
        }
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    for line in run.notes:
        print(line, file=sys.stderr)
    for k, (v, lim) in run.checks.items():
        print(f"check {k} = {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    print(f"check answers_ok = {run.ok} attempted = {run.attempted} "
          f"failed = {run.failed} correct = {correct}", file=sys.stderr,
          flush=True)
    return result
