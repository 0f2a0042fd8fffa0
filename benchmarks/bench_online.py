"""The always-on loop under a drifting stream — the paper's online claim
measured end to end (ISSUE 10).

Three arms over one deterministic zipf-drift schedule (new users arrive
every slice, rating a drifting hot set of items; every slice also grows
the catalog by a few cold-start items):

  * **fault-free** — `OnlineLoop` slices serve/train/drift/publish on one
    device budget; records held-out RMSE-over-time (the model tracking
    the drift), serve staleness p99 under concurrent training, publishes,
    and end-of-run recall.
  * **fault**      — the same schedule killed (simulated kill -9: the
    injected fault propagates out of `run_slice`) at each installed loop
    fault site; `OnlineLoop.recover()` must resume with an `OnlineState`
    bit-identical to the fault-free arm at the same WAL seq, and the
    post-recovery RMSE curve must rejoin the fault-free curve within one
    slice.  Records time-to-recover (checkpoint restore + WAL replay +
    service rebuild + warmup).
  * **oracle**     — rebuild-on-every-delta: a service rebuilt fresh from
    the final state (no tail inserts, no publish lag).  The loop's
    serving recall under drift must stay within ``ORACLE_RECALL_DELTA``.

Gated floors (--check): every kill site recovered and bit-identical,
``rejoin_slices <= 1``, ``staleness_p99 <= max_staleness_s``,
``recall_delta <= 0.02``, and the service dropped nobody (degraded > 0
is fine — that is what degraded serving is for).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_serve import CatalogSpec, drift_stream, make_catalog, recall_at
from common import emit

from repro import compile_cache, obs
from repro.core import model, online, simlsh, topk
from repro.core.sgd import Hyper
from repro.loop import LoopConfig, OnlineLoop
from repro.resil import FaultSpec, InjectedFault, OnlineUpdater, faults, wal
from repro.serve.service import ServeConfig

# ---------------------------------------------------------------------------
# floors (--check) — regression gates, deliberately loose; see ISSUE 10
# ---------------------------------------------------------------------------
# staleness p99 must stay under the loop's configured wall-clock cap: the
# publish cadence (max_lag=2 slice mutations) bounds it far below the cap
# on a healthy run, so hitting the cap means publishing stopped working
CHECK_STALENESS_P99_S = 30.0
# after a kill + recover, the RMSE curve must rejoin the fault-free arm
# within one slice — replay is bit-identical, so it rejoins immediately;
# the slack is for the slice in flight at the kill
CHECK_REJOIN_SLICES = 1
# serving recall under drift vs the rebuild-on-every-delta oracle
CHECK_ORACLE_RECALL_DELTA = 0.02

ONLINE_N = 4000            # full-run catalog (items); smoke uses 1500
LSH = simlsh.SimLSHConfig(G=8, p=2, q=8, band_cap=16)
K_NEIGH = 8
SERVE = ServeConfig(topn=10, micro_batch=128, C=256, n_seeds=8, cap=8,
                    n_popular=64, band_budget=512, max_pending=1024,
                    deadline_s=0.5)
LOOP = LoopConfig(serve_flushes=2, micro_epochs=1, micro_batch=4096,
                  deltas_per_slice=2, backpressure_queue=4, max_lag=2,
                  max_staleness_s=CHECK_STALENESS_P99_S, ckpt_every=2,
                  drift_every=4, drift_window=8, drift_tol=0.15,
                  watchdog_s=120.0, tail_cap=256, seed=0)
HOLDOUT_WINDOW = 4         # holdout batches the rolling RMSE probe keeps


# ---------------------------------------------------------------------------
# the deterministic drift schedule (same for every arm)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Schedule:
    """Everything the run needs, precomputed so the fault-free, fault and
    oracle arms replay the *same* stream: planted factors over the full
    growth horizon, per-slice ΔΩ triples, per-slice holdout batches, and
    per-slice serving traffic."""
    state0: online.OnlineState
    deltas: list          # per slice: (rows, cols, vals, key, M_new, N_new)
    holdouts: list        # per slice: (rows, cols, vals) in that slice's
                          # pre-growth id space
    traffic: list         # per slice: user id batch
    M0: int
    N0: int


def build_schedule(*, N: int, n_slices: int, grow_users: int,
                   grow_items: int, ratings_per_user: int,
                   batch: int, seed: int) -> Schedule:
    """Plant a group catalog, extend its factor model over the whole
    growth horizon, and cut a drifting rating stream into slices.

    Drift: arriving users rate a zipf(1.3) hot set whose permutation
    rolls every 3 slices (the trending cycle of bench_serve's
    `drift_stream`, applied to the rating stream itself).  New users are
    planted off the group directions, so ratings follow a consistent
    ground truth and held-out RMSE-over-time is meaningful."""
    rng = np.random.default_rng(seed)
    spec = CatalogSpec(N=N)
    params, sp, _ = make_catalog(spec, seed=seed)
    M0, N0 = int(params.U.shape[0]), int(params.V.shape[0])
    F = int(params.U.shape[1])
    # make_catalog's params are serve-only (width-1 W/C placeholders);
    # the loop *trains* them, so the neighbourhood planes must be K-wide
    params = dataclasses.replace(
        params, W=jnp.zeros((N0, K_NEIGH), jnp.float32),
        C=jnp.zeros((N0, K_NEIGH), jnp.float32))

    # planted factors over the full horizon (U for users yet to arrive,
    # V for items yet to be listed) — the stream's ground truth
    M_end = M0 + n_slices * grow_users
    N_end = N0 + n_slices * grow_items
    U_all = np.asarray(params.U)
    V_all = np.asarray(params.V)
    U_ext = np.concatenate(
        [U_all, U_all[rng.integers(0, M0, M_end - M0)]
         + 0.12 * rng.normal(0, 1, (M_end - M0, F)).astype(np.float32)])
    V_ext = np.concatenate(
        [V_all, V_all[rng.integers(0, N0, N_end - N0)]
         + 0.12 * rng.normal(0, 1, (N_end - N0, F)).astype(np.float32)])

    def rate(rows, cols):
        dots = np.einsum("ef,ef->e", U_ext[rows], V_ext[cols])
        return np.clip(3.0 + 1.5 * dots, 1.0, 5.0).astype(np.float32)

    key = jax.random.PRNGKey(seed)
    sigs, S = simlsh.encode(sp, LSH, key, return_accumulators=True)
    JK = topk.topk_from_signatures(sigs, jax.random.fold_in(key, 1),
                                   K=K_NEIGH, band_cap=LSH.band_cap)
    state0 = online.OnlineState(params=params, S=S, JK=JK, sp=sp,
                                M=M0, N=N0, hash_key=key)

    perm = rng.permutation(N0)      # the drifting item hot set
    deltas, holdouts, traffic = [], [], []
    M, Ncur = M0, N0
    for s in range(n_slices):
        if s and s % 3 == 0:
            perm = np.roll(perm, N0 // 7)

        def zipf_items(n):
            z = np.minimum(rng.zipf(1.3, n).astype(np.int64) - 1, N0 - 1)
            return perm[z].astype(np.int32)

        # holdout in the *pre-growth* id space: scoreable by the state
        # the loop holds when this slice's RMSE probe runs
        h_rows = rng.integers(0, M, 200).astype(np.int32)
        h_cols = zipf_items(200)
        holdouts.append((h_rows, h_cols, rate(h_rows, h_cols)))

        M_new, N_new = M + grow_users, Ncur + grow_items
        nr = np.repeat(np.arange(M, M_new, dtype=np.int32),
                       ratings_per_user)
        nc = zipf_items(nr.shape[0])
        # every new item gets a few cold-start ratings from the new users
        cold_r = nr[rng.integers(0, nr.shape[0],
                                 3 * grow_items)].astype(np.int32)
        cold_c = np.repeat(np.arange(Ncur, N_new, dtype=np.int32), 3)
        dr = np.concatenate([nr, cold_r])
        dc = np.concatenate([nc, cold_c])
        # new users may hit the same (user, item) pair twice under zipf —
        # dedup so merge_coo sees unique pairs
        uniq = np.unique(dr.astype(np.int64) * N_end + dc)
        dr = (uniq // N_end).astype(np.int32)
        dc = (uniq % N_end).astype(np.int32)
        deltas.append((dr, dc, rate(dr, dc),
                       np.asarray(jax.random.fold_in(key, 1000 + s)),
                       M_new, N_new))
        M, Ncur = M_new, N_new
        # traffic over the founding user base: arriving users become
        # servable only after the loop publishes, so the request stream
        # sticks to ids every published state can score
        traffic.append(next(drift_stream(
            np.random.default_rng(seed + 7000 + s), M0, batch, 1)))
    return Schedule(state0=state0, deltas=deltas, holdouts=holdouts,
                    traffic=traffic, M0=M0, N0=N0)


# ---------------------------------------------------------------------------
# the arms
# ---------------------------------------------------------------------------

def _build_loop(root: str, sched: Schedule) -> OnlineLoop:
    st0 = sched.state0
    up = OnlineUpdater(st0, LSH, Hyper(), root=root, K=K_NEIGH, epochs=1,
                       batch=4096)
    svc = OnlineLoop.build_service(st0, SERVE, tail_cap=LOOP.tail_cap)
    reg = obs.Registry(enabled=True, mirror=obs.get())
    return OnlineLoop(up, svc, LOOP, registry=reg)


def _hold_window(sched: Schedule, s: int):
    lo = max(0, s - HOLDOUT_WINDOW + 1)
    hr = np.concatenate([sched.holdouts[i][0] for i in range(lo, s + 1)])
    hc = np.concatenate([sched.holdouts[i][1] for i in range(lo, s + 1)])
    hv = np.concatenate([sched.holdouts[i][2] for i in range(lo, s + 1)])
    return hr, hc, hv


def _probe_rmse(loop: OnlineLoop, sched: Schedule, s: int) -> float:
    st = loop.state
    hr, hc, hv = _hold_window(sched, s)
    return float(model.rmse(st.params, st.sp, st.JK, jnp.asarray(hr),
                            jnp.asarray(hc), jnp.asarray(hv)))


def run_arm(loop: OnlineLoop, sched: Schedule, *, start: int = 0,
            kill_site: str | None = None, kill_call: int = 0):
    """Drive the schedule from slice ``start``.  Returns
    (rmse_over_time, snapshots {seq: state}, killed_at_slice | None)."""
    curve, snaps = [], {}
    plan = None
    if kill_site:
        plan = faults.install(faults.FaultPlan(
            {kill_site: FaultSpec(at_calls=(kill_call,))}))
    try:
        for s in range(start, len(sched.deltas)):
            loop.svc.submit(sched.traffic[s])
            loop.offer_delta(*sched.deltas[s][:4],
                             M_new=sched.deltas[s][4],
                             N_new=sched.deltas[s][5])
            # the rolling holdout feeds the loop's own drift detector too
            loop.holdout = _hold_window(sched, s)
            try:
                loop.run_slice()
            except InjectedFault:
                return curve, snaps, s
            snaps[loop.updater.seq] = loop.state
            curve.append(dict(slice=s, rmse=_probe_rmse(loop, sched, s)))
        return curve, snaps, None
    finally:
        if plan is not None:
            faults.uninstall()


def _bit_identical(a, b) -> bool:
    ta, tb = wal.state_tree(a), wal.state_tree(b)
    return all(np.asarray(ta[k]).dtype == np.asarray(tb[k]).dtype
               and np.array_equal(np.asarray(ta[k]), np.asarray(tb[k]))
               for k in ta)


def fault_arm(sched: Schedule, site: str, kill_call: int,
              free_curve: list, free_snaps: dict, workdir: str) -> dict:
    """Kill the loop at ``site``, recover, finish the schedule, and
    compare against the fault-free arm."""
    root = f"{workdir}/loop-{site.replace('.', '-')}"
    shutil.rmtree(root, ignore_errors=True)
    loop = _build_loop(root, sched)
    pre_curve, _, killed_at = run_arm(loop, sched, kill_site=site,
                                      kill_call=kill_call)
    if killed_at is None:
        return dict(site=site, kill_call=kill_call, killed=False,
                    recovered=False, state_bit_identical=False,
                    rejoin_slices=-1, recover_seconds=-1.0)
    del loop                        # the "killed" process

    t0 = time.perf_counter()
    rec = OnlineLoop.recover(root, LSH, Hyper(), SERVE, K=K_NEIGH,
                             epochs=1, batch=4096, cfg=LOOP,
                             base_state=sched.state0,
                             registry=obs.Registry(enabled=True,
                                                   mirror=obs.get()))
    recover_s = time.perf_counter() - t0
    seq = rec.updater.seq
    bit = seq in free_snaps and _bit_identical(rec.state, free_snaps[seq])

    # resume where the recovered cursor says, not where the kill landed:
    # for loop.ckpt / loop.drift the killed slice's WAL entry was already
    # appended, so replay re-applied it and the cursor sits past it
    post_curve, _, _ = run_arm(rec, sched, start=rec.slice_count)
    # rejoin: first post-recovery slice whose RMSE matches the fault-free
    # curve (replay is bit-identical, so this is immediate on a healthy
    # recovery; > CHECK_REJOIN_SLICES means replay diverged)
    free = {c["slice"]: c["rmse"] for c in free_curve}
    rejoin = -1
    for i, c in enumerate(post_curve):
        if c["slice"] in free and abs(c["rmse"] - free[c["slice"]]) < 1e-6:
            rejoin = i
            break
    st = rec.svc.stats()
    out = dict(site=site, kill_call=kill_call, killed=True,
               killed_at_slice=killed_at, recovered=True,
               recovered_seq=int(seq), state_bit_identical=bool(bit),
               recover_seconds=float(recover_s),
               rejoin_slices=int(rejoin),
               wal_replayed=int(rec.obs.counter("resil.wal.replayed")),
               rmse_over_time=pre_curve + post_curve,
               degraded=st["degraded"], dropped=st["dropped"])
    emit(f"online.fault.{site}.recover_seconds", recover_s,
         f"replayed={out['wal_replayed']};bit_identical={bit}")
    return out


def oracle_recall(sched: Schedule, final_state, probe) -> float:
    """Rebuild-on-every-delta oracle: a fresh service from the final
    state — no tail inserts, no publish lag, index always current."""
    svc = OnlineLoop.build_service(final_state, SERVE,
                                   tail_cap=LOOP.tail_cap)
    return recall_at(svc, final_state.params, probe, SERVE.topn)


# ---------------------------------------------------------------------------
# checks + main
# ---------------------------------------------------------------------------

def check(doc: dict) -> list:
    fails = []
    ff = doc["fault_free"]
    if ff["staleness_p99_s"] > CHECK_STALENESS_P99_S:
        fails.append(f"staleness p99 {ff['staleness_p99_s']:.2f}s exceeds "
                     f"the {CHECK_STALENESS_P99_S}s cap")
    if ff["dropped"] != 0:
        fails.append(f"{ff['dropped']} users dropped — degraded serving "
                     f"must answer everyone")
    for fa in doc["fault"]["sites"]:
        tag = fa["site"]
        if not fa.get("recovered"):
            fails.append(f"{tag}: loop did not recover after the kill")
            continue
        if not fa["state_bit_identical"]:
            fails.append(f"{tag}: recovered OnlineState is not "
                         f"bit-identical to the fault-free run")
        if not 0 <= fa["rejoin_slices"] <= CHECK_REJOIN_SLICES:
            fails.append(f"{tag}: RMSE rejoined after {fa['rejoin_slices']} "
                         f"slices (cap {CHECK_REJOIN_SLICES})")
    if doc["recall_delta"] > CHECK_ORACLE_RECALL_DELTA:
        fails.append(f"recall under drift {doc['recall_under_drift']:.3f} "
                     f"trails the rebuild-on-every-delta oracle "
                     f"{doc['recall_oracle']:.3f} by {doc['recall_delta']:.3f} "
                     f"(cap {CHECK_ORACLE_RECALL_DELTA})")
    return fails


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, default=ONLINE_N)
    ap.add_argument("--slices", type=int, default=12)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--probe", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_online.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small catalog, fewer slices, one kill site "
                         "(CI gate; still writes --out)")
    ap.add_argument("--check", action="store_true",
                    help="assert the recovery/staleness/recall floors "
                         "(exit 1 on regression)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write loop spans as Chrome trace-event JSON")
    args = ap.parse_args(argv)
    if args.trace:
        obs.enable()

    items, n_slices = args.items, args.slices
    sites = [("loop.slice", 3), ("loop.ckpt", 1), ("loop.drift", 1)]
    if args.smoke:
        items, n_slices = 1500, 8
        sites = [("loop.ckpt", 1)]

    t0 = time.perf_counter()
    sched = build_schedule(N=items, n_slices=n_slices, grow_users=16,
                           grow_items=8, ratings_per_user=20,
                           batch=args.batch, seed=args.seed)
    emit(f"online.setup.N{items}", time.perf_counter() - t0,
         f"M0={sched.M0};slices={n_slices}")

    workdir = tempfile.mkdtemp(prefix="bench_online_")
    try:
        # fault-free arm
        t0 = time.perf_counter()
        loop = _build_loop(f"{workdir}/loop-free", sched)
        free_curve, free_snaps, _ = run_arm(loop, sched)
        free_s = time.perf_counter() - t0
        stale = loop.obs.hist_summary("loop.staleness_s")
        st = loop.svc.stats()
        rng = np.random.default_rng(args.seed + 3)
        probe = jnp.asarray(rng.integers(0, sched.M0, args.probe), jnp.int32)
        loop._publish()             # measure serving at the final state
        recall_loop = recall_at(loop.svc, loop.svc.params, probe,
                                SERVE.topn)
        fault_free = dict(
            slices=n_slices, seconds=float(free_s),
            rmse_over_time=free_curve,
            rmse_first=free_curve[0]["rmse"],
            rmse_last=free_curve[-1]["rmse"],
            staleness_p99_s=float(stale.get("p99", 0.0)),
            staleness_max_s=float(stale.get("max", 0.0)),
            publishes=int(loop.obs.counter("loop.publishes")),
            ckpts=int(loop.obs.counter("loop.ckpts")),
            micro_epochs=int(loop.obs.counter("online.micro_epochs")),
            drift_rebuilds=int(loop.obs.counter("loop.drift_rebuilds")),
            users=st["users"], qps=st["qps"], degraded=st["degraded"],
            dropped=st["dropped"])
        emit("online.fault_free.staleness_p99", fault_free["staleness_p99_s"],
             f"publishes={fault_free['publishes']};"
             f"rmse={fault_free['rmse_first']:.3f}"
             f"->{fault_free['rmse_last']:.3f}")

        # fault arms — one kill + recover per installed loop site
        fault_runs = [fault_arm(sched, site, call, free_curve, free_snaps,
                                workdir) for site, call in sites]

        # oracle arm
        recall_orc = oracle_recall(sched, loop.state, probe)
        delta = max(0.0, float(recall_orc) - float(recall_loop))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    doc = dict(
        benchmark="bench_online",
        backend=jax.default_backend(),
        jax_version=jax.__version__,
        protocol=dict(
            items=items, slices=n_slices, batch=args.batch,
            loop=dataclasses.asdict(LOOP),
            timing="per-slice held-out RMSE over a rolling "
                   f"{HOLDOUT_WINDOW}-slice window of the drifting "
                   "stream; staleness p99 from the loop registry "
                   "histogram (observed each serve phase); recover = "
                   "checkpoint restore + WAL replay + service rebuild + "
                   "warmup, wall clock",
            floors=dict(staleness_p99_s=CHECK_STALENESS_P99_S,
                        rejoin_slices=CHECK_REJOIN_SLICES,
                        oracle_recall_delta=CHECK_ORACLE_RECALL_DELTA)),
        fault_free=fault_free,
        fault=dict(sites=fault_runs),
        recall_under_drift=float(recall_loop),
        recall_oracle=float(recall_orc),
        recall_delta=delta,
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    if args.trace:
        obs.write_trace(args.trace)

    print(f"# online N={items} slices={n_slices}: rmse "
          f"{fault_free['rmse_first']:.3f} → {fault_free['rmse_last']:.3f} "
          f"| staleness p99 {fault_free['staleness_p99_s'] * 1e3:.1f} ms "
          f"| {fault_free['publishes']} publishes, "
          f"{fault_free['micro_epochs']} micro-epochs")
    for fa in fault_runs:
        print(f"# kill@{fa['site']}: recover "
              f"{fa['recover_seconds']:.2f}s ({fa.get('wal_replayed', 0)} "
              f"replayed) | bit-identical {fa['state_bit_identical']} | "
              f"rejoin {fa['rejoin_slices']} slice(s)")
    print(f"# recall under drift {recall_loop:.3f} vs oracle "
          f"{recall_orc:.3f} (Δ{delta:.3f})")

    if args.check:
        fails = check(doc)
        for f_ in fails:
            print(f"CHECK FAIL: {f_}", file=sys.stderr)
        if fails:
            sys.exit(1)
        print(f"# check passed: recovery bit-identical at "
              f"{len(fault_runs)} site(s), staleness p99 ≤ "
              f"{CHECK_STALENESS_P99_S}s, recall within "
              f"{CHECK_ORACLE_RECALL_DELTA} of the oracle")
    return doc


if __name__ == "__main__":
    compile_cache.enable()
    main()
