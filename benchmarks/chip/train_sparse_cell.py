"""Generator of the ``train_sparse`` mixes: the ``train`` mix of
`train_cell` (the same set-up, window, metrics and checks, through
`repro.train.trainer.fit`) for a configuration whose reference keeps its
ratings sparse and cannot search neighbours itself.

Two things differ, each put in place for the run alone:

* the comparison passes the program's neighbour lists to the
  reference's ``train``, and adds two checks: ``rmse_lag``, how far
  `fit`'s held-out RMSE after the last compared epoch lies above the
  reference's, as a share of it (negative where `fit`'s is lower), which
  a training precision too low for the decayed steps reads above 0
  while the program's conflict-free rounds, whole steps where the
  reference's blocks average a heavy user's collisions, read below it;
  and ``nb_sim_gap``: the reference's ``neighbour_gap`` of those lists,
  how far their exact similarity falls short of the exact top-K's on a
  sample of items;
* a held-out RMSE that is not finite ends the run at once, with a
  non-zero exit: the program cannot train the configuration, and the
  epochs left would only repeat it.
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import harness
import train_cell
from control import patched

_train_compare = train_cell.compare


def compare(run, p0, train, test, hist, p_prog, jk_prog) -> None:
    """`train_cell.compare` with the reference trained on ``jk_prog``,
    then ``rmse_lag`` and ``nb_sim_gap``."""
    ref = harness.reference(run.cfg)
    curves = []

    def train_on_lists(*args, **kw):
        out = ref.train(*args, JK=jk_prog, **kw)
        curves.append(out[1])
        return out

    view = SimpleNamespace(**vars(ref))
    view.train = train_on_lists
    with patched(harness, "reference", lambda cfg: view):
        _train_compare(run, p0, train, test, hist, p_prog, jk_prog)
    lim = run.cfg["limits"]
    curve = curves[-1]
    run.check("rmse_lag", (hist[len(curve) - 1] - curve[-1]) / curve[-1],
              lim["rmse_lag"])
    gap = ref.neighbour_gap(train, jk_prog, run.cfg["data"]["N"], run.seed)
    run.check("nb_sim_gap", gap, lim["nb_sim_gap"])


def _guarded(fit, notes: list):
    """``fit`` that stops at the first held-out RMSE that is not finite,
    and notes the counters of what the configuration works."""
    def guarded(train, test, shape, cfg, log=None, registry=None):
        def hook(msg: str) -> None:
            evals = [f for _, n, f in registry.events if n == "train.eval"]
            if evals and not math.isfinite(evals[-1]["rmse"]):
                raise SystemExit(f"held-out RMSE {evals[-1]['rmse']} after "
                                 f"epoch {evals[-1]['epoch']}: the program "
                                 "does not train this configuration")
            if log:
                log(msg)
        res = fit(train, test, shape, cfg, log=hook, registry=registry)
        notes.append("fit counters: " + ", ".join(
            f"{k} {v:g}" for k, v in sorted(registry.counters.items())))
        return res
    return guarded


def run(run: harness.Run, trace_dir: str, devices) -> None:
    from repro.train import trainer
    with patched(train_cell, "compare", compare), \
            patched(trainer, "fit", _guarded(trainer.fit, run.notes)):
        train_cell.run(run, trace_dir, devices)
