"""Registry spans on a profiler trace's clock.

A `Registry` stamps its spans with `time.perf_counter_ns`.  The JAX
profiler stamps its host annotations and device events on a clock of its
own, counted from the start of the profiling session.  A context span
recorded while the registry writes `jax.profiler.TraceAnnotation`s
(``jax_annotations=True``) exists on both clocks; those twins fix the
affine map between them, so a span that only the registry holds
(`Registry.record_span`: an interval that crosses calls) can be laid
over the device's program executions.

The registry is never re-stamped: readers that compare its spans with
other `perf_counter` times keep working, and whoever needs trace time
maps a span through the `TraceClock` that `trace_clock` returns.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class TraceClock:
    """``trace time = at_ref + rate · (registry ns − ref_ns)``."""
    ref_ns: int             # a registry time (perf_counter_ns)
    at_ref: float           # its time on the trace's clock, ns
    rate: float             # trace ns per registry ns
    residual_us: float      # worst |fitted − recorded| over twins' ends
    twins: int              # spans matched on both clocks

    @property
    def offset_ns(self) -> float:
        """Trace time of registry time 0."""
        return self.at_ref - self.rate * self.ref_ns

    def __call__(self, t_ns):
        """Registry nanoseconds (scalar or array) → trace nanoseconds."""
        return self.at_ref + self.rate * (
            np.asarray(t_ns, np.int64) - self.ref_ns).astype(np.float64)

    def place(self, spans, name: str) -> list:
        """[(start, end)] on the trace's clock of every span ``name``."""
        t = np.asarray([(s[1], s[1] + s[2]) for s in spans if s[0] == name],
                       np.int64).reshape(-1, 2)
        return [(float(a), float(b)) for a, b in self(t)]


def _by_name(rows, ref: int = 0) -> dict:
    out: dict = {}
    for name, a, b in rows:
        out.setdefault(name, []).append((a - ref, b - ref))
    return {k: np.asarray(sorted(v), np.float64) for k, v in out.items()}


def _shift(reg: np.ndarray, host: np.ndarray, probe: int = 32
           ) -> float | None:
    """Offset (host − registry) that lines up the first ``probe`` host
    events of one name with the run of that name's registry spans whose
    durations match theirs best."""
    m = min(probe, len(host))
    if m == 0 or len(reg) < m:
        return None
    dr = reg[:, 1] - reg[:, 0]
    dh = host[:m, 1] - host[:m, 0]
    runs = np.lib.stride_tricks.sliding_window_view(dr, m)
    k = int(np.argmin(np.abs(runs - dh).sum(axis=1)))
    return float(np.median(host[:m, 0] - reg[k:k + m, 0]))


def _pairs(reg: np.ndarray, host: np.ndarray, shift: float):
    """Index of the registry span whose shifted start lies nearest each
    host event's start, and whether it lies within half the gap to its
    neighbours (else the host event has no twin)."""
    r0, h0 = reg[:, 0] + shift, host[:, 0]
    n = len(r0)
    j = np.searchsorted(r0, h0)
    cand = np.stack([np.clip(j - 1, 0, n - 1), np.clip(j, 0, n - 1)])
    i = cand[np.argmin(np.abs(r0[cand] - h0), axis=0), np.arange(len(h0))]
    pad = np.concatenate([[-np.inf], r0, [np.inf]])
    room = 0.5 * np.minimum(pad[i + 1] - pad[i], pad[i + 2] - pad[i + 1])
    return i, np.abs(r0[i] - h0) < room


def trace_clock(spans, host_events) -> TraceClock | None:
    """The affine map from registry time to a profiler trace's time.

    ``spans`` are registry spans ``(name, t0_ns, dur_ns, ...)``
    (`Registry.spans`, or the first three fields of each);
    ``host_events`` are the trace's host annotations
    ``(name, start_ns, end_ns)``.  Twins are matched by name and order:
    the profiler may cover only part of the registry's spans, so each
    name's first host events are lined up with the run of registry spans
    whose durations fit them, and every host event is then paired with
    the registry span of its name that starts nearest it.  The rate and
    offset are a least-squares fit over the twins' ends: a span's
    annotation closes right after the registry's stamp, while it opens
    before it by as much as the profiler takes to record the opening
    (up to 0.2 ms on a v5e's host, once in a few thousand spans).
    None when fewer than two twins are found."""
    host = _by_name(host_events)
    rows = [(s[0], s[1], s[1] + s[2]) for s in spans if s[0] in host]
    if not rows:
        return None
    ref = min(r[1] for r in rows)      # registry ns stay exact as ints
    reg = _by_name(rows, ref)
    names = sorted(reg)
    shifts = [x for x in (_shift(reg[n], host[n]) for n in names)
              if x is not None]
    if not shifts:
        return None
    coarse = float(np.median(shifts))
    xs, ys = [], []
    for n in names:
        i, ok = _pairs(reg[n], host[n], coarse)
        xs.append(reg[n][i[ok], 1])
        ys.append(host[n][ok, 1])
    x, y = np.concatenate(xs), np.concatenate(ys)
    if x.size < 2:
        return None
    xm, ym = x.mean(), y.mean()
    var = float(((x - xm) ** 2).sum())
    rate = float(((x - xm) * (y - ym)).sum() / var) if var else 1.0
    at_ref = float(ym - rate * xm)
    worst = float(np.abs(y - (at_ref + rate * x)).max())
    return TraceClock(ref_ns=int(ref), at_ref=at_ref, rate=rate,
                      residual_us=worst * 1e-3, twins=x.size)
