"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced window.  On a TPU
its device planes are named ``/device:TPU:<n>``; each has a line
``XLA Modules`` (one event per program execution) and a line ``XLA Ops``
(one event per HLO instruction, named by the instruction's HLO text, e.g.
``%culsh_sgd_step.3 = (f32[33,512]...) custom-call(...)``).  Host spans
written through `jax.profiler.TraceAnnotation` land on the ``/host:CPU``
plane on the same clock.

`Trace` keeps, for the traced window:

* ``modules`` and ``ops`` of every device plane, as (name, start, end)
  in nanoseconds;
* ``busy_s`` — the union of the program executions, averaged over the
  devices; ``window_s`` — the length of the traced window;
* ``kernel_calls(name)`` — every execution of an instruction whose HLO
  name is ``name`` or ``name.<n>``, with its HLO text (so a reader can
  take the operand shapes from it);
* ``idle_gaps`` — the device's idle intervals, each named by the host
  span that was open at its middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

# dtype[dims]{layout}; a layout ending in S(n), n > 0, is an on-chip
# memory space (VMEM on a TPU), not HBM
_SHAPE = re.compile(r"(\w+)\[([0-9,]*)\](\{[^}]*\})?")
_ON_CHIP = re.compile(r"S\([1-9]\d*\)")
# instructions whose interval encloses other instructions' intervals
_CONTAINERS = ("while", "conditional", "call")


def hlo_name(text: str) -> str:
    """``%name.3 = ...`` → ``name.3``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def base_name(name: str) -> str:
    """``culsh_sgd_step.3`` → ``culsh_sgd_step``."""
    return re.sub(r"\.\d+$", "", name)


def _matching(text: str, i: int) -> int:
    """Index of the parenthesis that closes the one at ``text[i]``."""
    depth = 0
    for k in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[k], 0)
        if depth == 0:
            return k
    return len(text) - 1


def operand_shapes(text: str, *, hbm_only: bool = False) -> tuple[list,
                                                                  list]:
    """(result shapes, operand shapes) of an instruction's HLO text, each
    a list of (dtype, dims).  ``hbm_only`` leaves out the arrays the
    compiler placed in on-chip memory (layout ``S(n)``), which an
    instruction reads or writes without HBM traffic."""
    rest = text.partition(" = ")[2]
    if rest.startswith("("):                      # a tuple result
        k = _matching(rest, 0) + 1
    else:
        k = rest.find(" ")
    result, call = rest[:k], rest[k:]
    o = call.find("(")
    args = call[o:_matching(call, o) + 1] if o >= 0 else ""
    shapes = lambda t: [(d, tuple(int(x) for x in s.split(",") if x))
                        for d, s, lay in _SHAPE.findall(t)
                        if not (hbm_only and _ON_CHIP.search(lay))]
    return shapes(result), shapes(args)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Trace:
    modules: dict = field(default_factory=dict)   # plane → [(name, s, e)]
    ops: dict = field(default_factory=dict)       # plane → [(text, s, e)]
    host: list = field(default_factory=list)      # [(name, s, e)]
    t0: float = 0.0
    t1: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _busy(self, plane) -> list:
        return [(max(s, self.t0), min(e, self.t1))
                for s, e in _union((s, e) for _, s, e in self.modules[plane])
                if e > self.t0 and s < self.t1]

    @property
    def busy_s(self) -> float:
        if not self.modules:
            return 0.0
        tot = sum(sum(e - s for s, e in self._busy(p)) for p in self.modules)
        return tot / len(self.modules) * 1e-9

    def kernel_calls(self, name: str) -> list:
        """[(hlo text, seconds)] of every call of kernel ``name``."""
        return [(t, (e - s) * 1e-9) for p in self.ops.values()
                for t, s, e in p if base_name(hlo_name(t)) == name]

    def module_calls(self, prefix: str) -> list:
        """[(module name, seconds)] of program executions whose name
        starts with ``prefix`` (e.g. ``jit_train_epoch_scheduled``)."""
        return [(n, (e - s) * 1e-9) for p in self.modules.values()
                for n, s, e in p if n.startswith(prefix)]

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` instructions that took the most device time (loops
        and calls, whose interval holds other instructions, left out),
        each named with its result shapes."""
        tot: dict = {}
        for p in self.ops.values():
            for t, s, e in p:
                nm = hlo_name(t)
                if base_name(nm) in _CONTAINERS:
                    continue
                res = " ".join(f"{d}[{','.join(map(str, dims))}]"
                               for d, dims in operand_shapes(t)[0][:2])
                nm = f"{nm} {res}".strip()
                tot[nm] = tot.get(nm, 0.0) + (e - s) * 1e-9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10, spans: tuple = ()) -> list:
        """The ``n`` longest idle intervals of the first device, each
        named by the innermost host span (of the names in ``spans``, or
        any annotation when empty) open at its middle."""
        if not self.modules:
            return []
        busy = self._busy(sorted(self.modules)[0])
        gaps, prev = [], self.t0
        for s, e in busy + [(self.t1, self.t1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for s, e in gaps[:n]:
            mid = 0.5 * (s + e)
            open_ = [(hs, nm) for nm, hs, he in self.host
                     if hs <= mid <= he and (not spans or nm in spans)]
            what = max(open_)[1] if open_ else "(no host span)"
            named.append([what, (e - s) * 1e-9])
        return named


def load(trace_dir: str, window: str | None = None) -> Trace:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``.  ``window``
    names the host annotation that bounds the measured window; without
    it the window runs from the first to the last device event."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]), window)


def from_profile(pd, window: str | None = None) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
                if line.name == "XLA Modules":
                    tr.modules[plane.name] = ev
                elif line.name == "XLA Ops":
                    tr.ops[plane.name] = ev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if not e.name.startswith("$")]
    bounds = [(s, e) for n, s, e in tr.host if n == window] if window else []
    if bounds:
        tr.t0, tr.t1 = min(s for s, _ in bounds), max(e for _, e in bounds)
    else:
        allev = [x for p in tr.modules.values() for x in p]
        if allev:
            tr.t0 = min(s for _, s, _ in allev)
            tr.t1 = max(e for _, _, e in allev)
    inside = lambda evs: [x for x in evs if x[2] > tr.t0 and x[1] < tr.t1]
    tr.modules = {p: inside(v) for p, v in tr.modules.items()}
    tr.ops = {p: inside(v) for p, v in tr.ops.items()}
    return tr
