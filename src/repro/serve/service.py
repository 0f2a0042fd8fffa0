"""Request-batching serving loop: retrieval → candidate scoring → top-N.

A `RecsysService` owns the trained parameters (packed once into the
`model.ServePlanes` scoring layout), the persistent `LSHIndex`, and two
serving pipelines:

  * ``candidate`` — one fused, jitted program (`recommend_candidates`):
    `retrieve.retrieve_for_users` (ANN candidates, single-sort dedup)
    feeding `kernels/candidate_score` with in-kernel plane gather — O(C)
    work per user, no host hop between retrieval and scoring.
  * ``full``      — exact `μ + b_i + b̂ + U V^T` top-N: O(N) work per
    user, kept as the exactness baseline (and for recall measurement).

Requests are micro-batched: `submit` accumulates user ids (a `deque` —
PR 1's ``list.pop(0)`` was O(n) per flush) and flushes a fixed-shape
batch whenever ``micro_batch`` are pending (padding keeps every flush
the same shape, so the jit cache stays warm after the first call).

Flushes are **dispatch-ahead** (double-buffered): `_flush_one` enqueues
flush k+1 onto the device before syncing flush k, so the host-side batch
assembly and result copy-out of one flush overlap the device compute of
the next.  The ``serve.flush`` span runs from dispatch to the host's
sync, which under steady traffic comes when the next flush is
dispatched — one fill later, not when the answer is ready; the
per-flush spans ``serve.flush.fill`` / ``.dispatch`` / ``.sync`` split
that wait, and `repro.obs.trace_clock` lays them over a device trace.
QPS divides by non-overlapping busy wall-time, never double-counting the
overlap.

Online ingestion (paper Alg. 4): `ingest_online_update` re-signs the
accumulator cache from `core.online.online_update` and *inserts* the new
columns into the index tail — no rebuild, no cold jit caches — falling back
to a rebuild only when the tail overflows.

Resilience (ISSUE 7, see docs/ARCHITECTURE.md §8): tail-overflow rebuilds
run on a background thread behind a validate-then-swap gate
(`resil.rebuild`) while index v keeps serving; the admission queue is
bounded (``max_pending``) with deadline-aware load shedding
(``deadline_s``) into a host-side popularity answer; hot-path failures
fall back to the exact `full_topn` baseline; and poison ingest batches
are quarantined (`resil.validate`) before any state is touched.  All of
it is observable — shed/degraded/fallback/quarantine counters live in
the service registry and surface through `stats()`.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import model, simlsh
from repro.core.model import Params
from repro.core.topk import SENTINEL
from repro.data.sparse import SparseMatrix
from repro.kernels.candidate_score.kernel import NEG
from repro.kernels.candidate_score.ops import score_candidates
from repro.kernels.lsh_retrieve.kernel import lsh_retrieve_topc
from repro.launch.mesh import make_shard_mesh, serve_shard_count
from repro.resil import faults
from repro.resil.rebuild import IndexRebuilder
from repro.resil.validate import (PoisonBatchError, check_accumulators,
                                  check_ingest_batch)
from repro.serve import index as lsh_index
from repro.serve.retrieve import (candidate_pool, enumerate_windows,
                                  finalize_candidates, retrieve_for_users,
                                  seed_items, shard_seed_sigs,
                                  shard_walk_local, tail_hits,
                                  translate_local_ids, walk_candidates,
                                  window_descriptors)


class ShardedIngestUnsupported(NotImplementedError):
    """Online ingestion was attempted on a sharded service.  Sharded
    serving is deliberately read-only — the per-shard index/col-plane
    partitions are built once from a complete catalog.  Either run the
    ingest on a single-device service (``dataclasses.replace(cfg,
    shards=0)``) whose tail + rebuild path absorbs it and construct a
    fresh sharded service from the grown state, or hand the full
    signature set to `RecsysService.request_rebuild` on that
    single-device service and re-shard from the swapped index.
    Rejections are counted in ``serve.ingest_rejected`` (see `stats`)."""


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    mode: str = "candidate"   # candidate | full
    topn: int = 10
    micro_batch: int = 256
    # retrieval knobs
    C: int = 512              # candidate slots per user
    n_seeds: int = 8          # seed items per user
    cap: int = 8              # bucket-mates taken per band per seed
    n_popular: int = 64       # global popularity shortlist size (0 = off)
    seed_window: int = 64
    use_jk: bool = True       # include seeds' training Top-K lists
    fold_mates: bool = True   # fold per-(seed, band) bucket runs pairwise
                              # (halves the dedup sort width; see
                              # retrieve._fold_prefix_runs)
    pool_width: int = 0       # generic pre-dedup pool compaction width
                              # (0 = off — a wash on CPU, see
                              # retrieve.compact_pool; knob for TPU)
    band_budget: int = 512    # > 0 = the window-walk retrieval path (the
                              # default pipeline): merged per-band bucket
                              # intervals enumerated under this shared
                              # per-user slot budget
                              # (retrieve.walk_candidates) — no host-side
                              # dedup sort; duplicates are folded at top-n
                              # selection (CPU) or in the lsh_retrieve
                              # kernel's VMEM (accelerators).  0 = legacy
                              # pool+dedup retrieval (kept as the exact
                              # oracle).  Size it near the p90
                              # merged-interval mass (~q·n_seeds·3 at
                              # cap=8 on zipf catalogs) — budget
                              # truncation drops whole trailing windows,
                              # which costs recall fast
    shards: int | str = 0     # sharded serving data path (million-item
                              # catalogs): 0 = off — the single-device
                              # oracle path, unchanged; "auto" = the
                              # largest power of two ≤ the local device
                              # count; an int = exactly that many shards
                              # (power of two).  The col plane and LSH
                              # index partition into nnz-balanced item
                              # ranges; each flush runs the walk + score
                              # per shard under shard_map and tree-merges
                              # the per-shard top-N partials (log₂D
                              # ppermute rounds, no candidate gather).
                              # Walk path only (requires band_budget > 0)
                              # and read-only: online ingest goes through
                              # the single-device tail + rebuild path
    shard_budget: int = 0     # per-shard walk slot budget (0 = auto:
                              # 1.5×band_budget/D rounded up to 32, ≥64 —
                              # per-shard window mass is ≈1/D of the
                              # global one on nnz-balanced cuts, and the
                              # 1.5× slack absorbs shard skew before
                              # truncation starts costing recall)
    route_full_below: int = 0 # candidate-mode routing escape hatch: serve
                              # via exact full_topn when the catalog has at
                              # most this many items (candidate retrieval
                              # has a fixed per-user cost that exceeds the
                              # O(N) scan on small catalogs — measured
                              # crossover ≈ 48·C items on CPU).  -1 = that
                              # auto threshold; 0 = off (the default: tiny-
                              # catalog tests rely on candidate mode
                              # answering strictly from retrieved
                              # candidates)
    # resilience knobs (ISSUE 7)
    max_pending: int = 0      # admission bound on queued users (0 = off);
                              # overflow sheds the *oldest* chunks into the
                              # degraded popularity path.  Keep it ≥ a few
                              # micro_batches or steady traffic sheds too
    deadline_s: float = 0.0   # queue-wait deadline (0 = off): chunks older
                              # than this at dispatch time are shed instead
                              # of scored — bounded staleness over stalls
    background_rebuild: bool = True  # overflow rebuilds run on a worker
                              # thread behind a validate-then-swap gate
                              # (resil.rebuild); False = legacy synchronous
                              # rebuild on the ingest path
    rebuild_retries: int = 3  # failed/invalid background builds are retried
                              # this many times before giving up (the old
                              # index keeps serving either way)
    # kernel knobs
    tile_b: int = 8
    walk_tile_b: int = 16     # scan tile for the walk path's pool scoring
                              # (pure XLA gather+einsum; distinct from the
                              # Pallas kernel's tile_b).  16 won a paired
                              # interleaved A/B against 32 at B=256, W≈600
                              # on CPU — non-interleaved runs flip the
                              # verdict inside the ±25% container noise.
                              # Batches are padded up to a multiple, so
                              # any B works
    interpret: bool | None = None  # None = auto (interpret only on CPU);
                                   # never leave True on TPU — it would run
                                   # the hot path in the Pallas interpreter
    impl: str = "auto"        # auto | pallas | ref — 'auto' picks the pure-
                              # XLA ref on CPU (Pallas only interprets there)
                              # and the fused kernel elsewhere

    def scorer_impl(self) -> str:
        if self.impl != "auto":
            return self.impl
        return "ref" if jax.default_backend() == "cpu" else "pallas"

    def interpret_mode(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() == "cpu"

    def resolved_pool_width(self) -> int:
        return self.pool_width

    def resolved_shard_budget(self, shards: int) -> int:
        # 2× the per-shard share of the single-device walk budget: a
        # shard's bucket-head windows don't center on the seed, so parity
        # needs more enumeration slack than budget/D — at 1.5× the
        # planted-catalog recall sits ~0.02 below the single-device walk,
        # at 2× it is back within ±0.001 (multidev_checks::sharded_serve)
        if self.shard_budget:
            return self.shard_budget
        per = -(-2 * self.band_budget // max(shards, 1))
        return max(64, -(-per // 32) * 32)


@partial(jax.jit, static_argnames=("topn",))
def full_topn(params: Params, user_ids: jax.Array, *, topn: int):
    """Exact dense scoring — every item, every user.  The O(N) baseline."""
    scores = (params.mu + params.b[user_ids][:, None] + params.bh[None, :]
              + params.U[user_ids] @ params.V.T)
    return jax.lax.top_k(scores, topn)


@partial(jax.jit,
         static_argnames=("n_seeds", "cap", "C", "window", "pool_width",
                          "fold_mates", "tail_scan", "topn", "tile_b",
                          "interpret", "impl"))
def recommend_candidates(planes: model.ServePlanes, index, sp, user_ids,
                         JK, popular, *, n_seeds: int, cap: int, C: int,
                         window: int, pool_width: int, fold_mates: bool,
                         tail_scan: bool, topn: int,
                         tile_b: int, interpret: bool, impl: str):
    """The whole candidate hot path as ONE jitted program — retrieval and
    scoring fuse into a single dispatch with no host round-trip between
    them, and every intermediate (pools, sort keys, the candidate table)
    is program-local, so XLA reuses those buffers across the
    retrieval/scoring boundary instead of holding two jit outputs live
    (the PR 1 layout donated nothing and kept `cand` alive between two
    dispatches)."""
    # named_scope: the stage names below group the fused program's ops in
    # XLA device profiles (the in-jit mirror of the host-side obs spans)
    with jax.named_scope("serve.flush.retrieve"):
        cand = retrieve_for_users(index, sp, user_ids, n_seeds=n_seeds,
                                  cap=cap, C=C, JK=JK, popular=popular,
                                  window=window, pool_width=pool_width,
                                  fold_mates=fold_mates, tail_scan=tail_scan)
    with jax.named_scope("serve.flush.score"):
        return score_candidates(planes, user_ids, cand, topn=topn,
                                tile_b=tile_b, interpret=interpret, impl=impl)


def _pool_scores(urow, plane, cand, *, tile_b: int):
    """Scores of a [B, W] id pool with duplicates intact — tiled
    gather+einsum `lax.scan` (the candidate_score ref idiom: per-tile rows
    stay cache-resident, no [B, W, F] cube).  SENTINEL slots score NEG.
    ``plane`` may carry zero lanes past b̂ (`pack_serve_planes(lanes=)`)."""
    B, W = cand.shape
    F = urow.shape[1] - 1

    def tile(carry, args):
        u, c = args
        rows = plane[jnp.clip(c, 0, plane.shape[0] - 1)]
        s = (jnp.einsum("bf,bcf->bc", u[:, :F], rows[..., :F])
             + rows[..., F] + u[:, F][:, None])
        return carry, jnp.where(c == SENTINEL, NEG, s)

    _, s = jax.lax.scan(
        tile, 0, (urow.reshape(B // tile_b, tile_b, F + 1),
                  cand.reshape(B // tile_b, tile_b, W)))
    return s.reshape(B, W)


def _score_pool(planes: model.ServePlanes, user_ids, cand, popular, *,
                tile_b: int):
    """Walked pool + popularity shortlist → (scores [B, W(+P)],
    cand [B, W(+P)]).  The shortlist is batch-constant, so its scores are
    ONE [B, F]·[F, P] matmul — never a per-user gather."""
    B = cand.shape[0]
    F = planes.F
    pad = (-B) % tile_b
    urow = planes.row[user_ids].at[:, F].add(planes.mu)
    if pad:
        urow = jnp.pad(urow, ((0, pad), (0, 0)))
        cand = jnp.pad(cand, ((0, pad), (0, 0)),
                       constant_values=int(SENTINEL))
    s = _pool_scores(urow, planes.col, cand, tile_b=tile_b)[:B]
    cand = cand[:B]
    urow = urow[:B]
    if popular is None:
        return s, cand
    prow = planes.col[popular]                                   # [P, F+1]
    ps = (urow[:, :F] @ prow[:, :F].T
          + prow[None, :, F] + urow[:, F][:, None])
    cand = jnp.concatenate(
        [cand, jnp.broadcast_to(popular[None, :], (B, popular.shape[0]))],
        axis=1)
    return jnp.concatenate([s, ps], axis=1), cand


def _select_topn_masked(s, cand, *, topn: int):
    """Duplicate-masked top-n over a pool that was never deduplicated.

    n rounds of full-width argmax; each round masks every slot holding
    the picked *id*, so cross-band duplicates (and the popular∩walk
    overlap) collapse here, at O(n·W) elementwise cost, instead of in a
    [B, W] sort.  Full width is deliberate: a `top_k` slack only helps
    when the slack holds n distinct ids, and on zipf catalogs it usually
    does not — one id can occupy a slot in *every* band, and the measured
    rank of the 10th distinct id is p50 ≈ 4·topn, max ≈ 8·topn (N=100k,
    q=10), so a slack path degrades into an always-firing full-width
    fallback that costs strictly more than starting there.  Ties pick the
    lowest slot (`argmax`'s first-index rule), so the returned id *set*
    matches dedup-then-score exactly; only the order among equal-scored
    distinct ids can differ from a hashed-dedup pipeline."""
    bi = jnp.arange(s.shape[0])
    outs, outi = [], []
    for _ in range(topn):
        i = jnp.argmax(s, axis=1)
        sv = s[bi, i]
        picked = cand[bi, i]
        outs.append(sv)
        # an exhausted row (sv ≤ NEG) emits SENTINEL; masking `picked`
        # below is then harmless — every remaining score is already NEG
        outi.append(jnp.where(sv > NEG, picked, SENTINEL))
        s = jnp.where(cand == picked[:, None], NEG, s)
    return jnp.stack(outs, 1), jnp.stack(outi, 1)


def merge_topn(sa, ia, sb, ib, *, topn: int):
    """Merge two top-n partial lists into the top-n of their union.

    (scores, ids) pairs [B, n] → [B, topn].  The total order is (score
    descending, id ascending) — one two-key `lax.sort` over the [B, 2n]
    concatenation — which makes the merge associative and commutative, so
    the butterfly tree reduce below is shard-split-invariant (the
    property suite checks exactly this against a numpy lexsort oracle).
    Rows with fewer than n real candidates carry (NEG, SENTINEL) padding,
    which sinks below every real score; the two sides' real ids must be
    disjoint (shards partition the catalog), otherwise a duplicate id
    could occupy two output slots.

    Tie semantics vs the single-device path: `_select_topn_masked` breaks
    equal scores by pool position, this merge by id — the returned id
    *set* can differ only when distinct items tie exactly at the n-th
    score, where both answers are equally exact.
    """
    s = jnp.concatenate([sa, sb], axis=1)
    i = jnp.concatenate([ia, ib], axis=1)
    ns, ii = jax.lax.sort((-s, i), dimension=1, num_keys=2)
    return -ns[:, :topn], ii[:, :topn]


def _build_sharded_recommend(mesh, *, D: int, F: int, topn: int,
                             n_seeds: int, cap: int, budget: int,
                             window: int, tile_b: int, has_popular: bool):
    """The sharded flush as ONE jitted shard_map program.

    Per device: owner-compute + psum-share the seeds' band signatures
    (each seed lives in exactly one shard; the exchange is a [q, B, S]
    int32 psum — the only all-to-all in the program), walk the shard's
    local buckets by signature, score the local pool against the shard's
    col-plane slice, select a per-shard top-N in global ids, then merge
    partials with a log₂(D) XOR-partner butterfly of `ppermute`s — at
    round k partners' coverage sets are disjoint by construction, so no
    candidate is ever counted twice and no [B, pool] candidate set ever
    leaves its device.  After the butterfly every device holds the global
    answer; the host takes shard 0's copy.
    """
    spec_shard = jax.sharding.PartitionSpec("shard")
    spec_rep = jax.sharding.PartitionSpec()

    def body(urow, seeds, col, ssig, sids, slot, n_local, bounds, popular):
        # sharded operands arrive with a leading [1] shard slice
        col, ssig, sids, slot = col[0], ssig[0], sids[0], slot[0]
        n_loc = n_local[0]
        lo = bounds[jax.lax.axis_index("shard")]
        contrib = shard_seed_sigs(ssig, slot, seeds, lo, n_loc)
        qsigs = jax.lax.psum(contrib, "shard")
        qsigs = jnp.where((seeds != SENTINEL)[None], qsigs,
                          lsh_index._EMPTY_SIG)
        local = shard_walk_local(ssig, sids, qsigs, n_loc, cap=cap,
                                 budget=budget)
        B = urow.shape[0]
        if has_popular:
            # the shard scores only the shortlist items it owns; the
            # union over shards restores the full reserved shortlist
            plocal = popular - lo
            plocal = jnp.where((plocal >= 0) & (plocal < n_loc), plocal,
                               SENTINEL)
            local = jnp.concatenate(
                [local,
                 jnp.broadcast_to(plocal[None], (B, plocal.shape[0]))],
                axis=1)
        pad = (-B) % tile_b
        u = jnp.pad(urow, ((0, pad), (0, 0))) if pad else urow
        c = (jnp.pad(local, ((0, pad), (0, 0)),
                     constant_values=int(SENTINEL)) if pad else local)
        s = _pool_scores(u, col, c, tile_b=tile_b)[:B]
        ps, pi = _select_topn_masked(s, translate_local_ids(local, lo),
                                     topn=topn)
        k = 1
        while k < D:
            perm = [(i, i ^ k) for i in range(D)]
            qs = jax.lax.ppermute(ps, "shard", perm)
            qi = jax.lax.ppermute(pi, "shard", perm)
            ps, pi = merge_topn(ps, pi, qs, qi, topn=topn)
            k *= 2
        return ps[None], pi[None]

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_rep, spec_rep, spec_shard, spec_shard, spec_shard,
                  spec_shard, spec_shard, spec_rep, spec_rep),
        out_specs=(spec_shard, spec_shard),
        check_vma=False)

    @jax.jit
    def run(row, mu, col_stack, ssig, sids, slot, n_local, bounds, sp,
            user_ids, popular):
        with jax.named_scope("serve.flush.sharded"):
            seeds = seed_items(sp, user_ids, n_seeds=n_seeds, window=window)
            urow = row[user_ids].at[:, F].add(mu)
            ps, pi = smapped(urow, seeds, col_stack, ssig, sids, slot,
                             n_local, bounds, popular)
        return ps[0], pi[0]

    return run


@partial(jax.jit,
         static_argnames=("n_seeds", "cap", "budget", "window", "tail_k",
                          "topn", "tile_b"))
def recommend_walked(planes: model.ServePlanes, index, sp, user_ids,
                     popular, *, n_seeds: int, cap: int, budget: int,
                     window: int, tail_k: int, topn: int, tile_b: int):
    """The walk-path hot path as ONE jitted program (CPU/XLA flavour of
    the `lsh_retrieve` fusion): window descriptors → budgeted slot
    enumeration → pool scoring with duplicates intact → duplicate-masked
    top-n.  No [B, pool] dedup sort anywhere — the only sorts left are
    the static bitonic network over each band's S intervals and the
    argmax tournament inside selection.  ``tail_k`` is the static tail
    scan width (`RecsysService._tail_k`); 0 skips the tail entirely."""
    with jax.named_scope("serve.flush.retrieve"):
        ids, seeds = walk_candidates(index, sp, user_ids, n_seeds=n_seeds,
                                     cap=cap, budget=budget, window=window)
        if tail_k:
            ids = jnp.concatenate(
                [ids, tail_hits(index, seeds, k=tail_k)], axis=1)
    with jax.named_scope("serve.flush.score"):
        s, cand = _score_pool(planes, user_ids, ids, popular, tile_b=tile_b)
    with jax.named_scope("serve.flush.select"):
        return _select_topn_masked(s, cand, topn=topn)


@partial(jax.jit,
         static_argnames=("n_seeds", "cap", "C", "window", "tail_scan",
                          "topn", "tile_b", "interpret", "impl"))
def recommend_walked_kernel(planes: model.ServePlanes, index, sp, user_ids,
                            popular, ids_flat, *, n_seeds: int, cap: int,
                            C: int, window: int, tail_scan: bool, topn: int,
                            tile_b: int, interpret: bool, impl: str):
    """Accelerator flavour of the walk path: the `lsh_retrieve` kernel
    walks + dedups bucket windows in VMEM and hands its [B, C] ids
    straight to the `candidate_score` kernel's scalar-prefetch operand —
    two chained kernels in one jitted program, no [B, pool] intermediate
    and no host-side dedup.  ``ids_flat`` is the service-cached
    `padded_flat_ids` plane."""
    # deferred: ops.py imports repro.serve.index, so a module-level import
    # here would close an import cycle for anyone importing ops first
    from repro.kernels.lsh_retrieve.ops import retrieve_candidates
    with jax.named_scope("serve.flush.retrieve"):
        cand = retrieve_candidates(index, sp, user_ids, n_seeds=n_seeds,
                                   cap=cap, C=C, popular=popular,
                                   window=window, tail_scan=tail_scan,
                                   interpret=interpret, impl=impl,
                                   ids_flat=ids_flat)
    with jax.named_scope("serve.flush.score"):
        return score_candidates(planes, user_ids, cand, topn=topn,
                                tile_b=tile_b, interpret=interpret, impl=impl)


def popular_shortlist(params: Params, n: int) -> jax.Array:
    """Items with the highest baseline offset b̂_j — the candidates the bias
    part of Eq. (1) can rank high regardless of the user's neighbourhood."""
    _, ids = jax.lax.top_k(params.bh, n)
    return ids.astype(jnp.int32)


# staged (un-fused) flavours of the walk-path stages, for profile_flush —
# the fused programs above inline the same functions
@jax.jit
def _walk_gather(index, pos):
    flat = index.sorted_ids.reshape(-1)
    return jnp.where(pos >= 0, flat[jnp.maximum(pos, 0)], SENTINEL)


_score_pool_staged = partial(jax.jit, static_argnames=("tile_b",))(_score_pool)
_select_staged = partial(jax.jit, static_argnames=("topn",))(
    _select_topn_masked)


class RecsysService:
    def __init__(self, params: Params, index: lsh_index.LSHIndex,
                 sp: SparseMatrix, cfg: ServeConfig,
                 JK: jax.Array | None = None,
                 registry: obs.Registry | None = None):
        self.params = params
        self.cfg = cfg
        self.planes = self._pack(params)                # built once
        self.index = index
        self.sp = sp
        self.JK = JK if cfg.use_jk else None
        self.popular = (popular_shortlist(params, cfg.n_popular)
                        if cfg.n_popular else None)
        # all serving metrics live here (ISSUE 6: the registry is the
        # single source of timing truth — stats() only reads it).  Always
        # a PRIVATE registry: two services reading the same metric names
        # ("serve.users", "serve.busy_seconds", the flush spans stats()
        # turns into percentiles) must never blend — sharing the process
        # registry made a full-mode service's traffic deflate a candidate
        # service's reported QPS under --trace.  Completed spans still
        # reach the process-wide timeline via the span mirror whenever
        # the default registry is enabled.
        self.obs = registry if registry is not None else obs.Registry(
            enabled=True, mirror=obs.get())
        # pending request chunks: (user_ids, t_submitted perf_counter_ns)
        self._pending: collections.deque = collections.deque()
        self._n_pending = 0
        # dispatched-but-unsynced flushes:
        # (user_ids, n_real, t0_ns, outputs, degraded)
        self._inflight: collections.deque = collections.deque()
        self._results: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self._last_ready_ns = 0
        # when the serving params were adopted (swap on online ingest) —
        # `stats()["model_age_s"]` is the serve-behind-train staleness the
        # always-on loop bounds (ISSUE 10)
        self._params_adopted = time.perf_counter()
        # resilience state (ISSUE 7): background rebuild slot + host-side
        # bias mirror for the degraded popularity path (invalidated on
        # parameter swap)
        self._rebuilder: IndexRebuilder | None = None
        self._rebuild_sigs = None        # full sigs of the build in flight
        self._rebuild_attempts = 0
        self._rebuild_t0 = 0.0
        self._host_bias = None           # (mu, b, bh) numpy mirror
        # walk-kernel path: cached SENTINEL-apron id plane (invalidated
        # whenever self.index is replaced — keyed by index identity)
        self._ids_flat = None
        self._ids_flat_for = None
        # sharded serving tier (ServeConfig.shards): built once from the
        # same (params, index, sp) the single-device path serves, so the
        # two stay answer-comparable
        self._shard_state = None
        self._sharded_fn = None
        shards = serve_shard_count(cfg.shards) if cfg.mode != "full" else 1
        if shards > 1:
            self._init_shards(shards)

    def _pack(self, params: Params) -> model.ServePlanes:
        """Serving planes; lane-padded where the `candidate_score` kernel
        DMAs their rows (single-device kernel path)."""
        kernel = (self.cfg.scorer_impl() == "pallas"
                  and serve_shard_count(self.cfg.shards) == 1)
        lanes = 128 if kernel else 1
        return model.pack_serve_planes(params, lanes=lanes)

    def _init_shards(self, shards: int) -> None:
        """Cut the item space into nnz-balanced shards and build the
        per-shard serving state: the block-padded col-plane stack, the
        sharded index (local bucket CSR per shard), and the jitted
        shard_map program over `make_shard_mesh`."""
        cfg = self.cfg
        if not cfg.band_budget:
            raise ValueError("sharded serving requires the walk path "
                             "(band_budget > 0); the legacy pool+dedup "
                             "pipeline is single-device only")
        if self.index.tail_fill:
            raise ValueError("sharded serving requires an empty index tail "
                             "— rebuild before sharding (online ingest is "
                             "single-device only)")
        counts = np.bincount(np.asarray(self.sp.cols),
                             minlength=self.planes.n_items)
        bounds = lsh_index.shard_bounds(counts, shards)
        sidx = lsh_index.build_sharded_index(
            lsh_index.signatures_of(self.index), shards=shards,
            bounds=bounds)
        col_stack = model.shard_col_plane(self.planes.col, bounds)
        mesh = make_shard_mesh(shards)
        self._shard_state = (sidx, col_stack, mesh, shards)
        self._sharded_fn = _build_sharded_recommend(
            mesh, D=shards, F=self.planes.F, topn=cfg.topn,
            n_seeds=cfg.n_seeds, cap=cfg.cap,
            budget=cfg.resolved_shard_budget(shards),
            window=cfg.seed_window, tile_b=cfg.walk_tile_b,
            has_popular=self.popular is not None)

    # ---- core pipelines (fixed [micro_batch] shapes → warm jit caches) ----

    def route_decision(self) -> dict:
        """The small-catalog routing verdict, exposed for `stats()` and
        the bench: candidate retrieval costs a fixed ~C-proportional
        amount per user, so below a catalog-size crossover the exact O(N)
        scan is simply faster *and* exact.  ``decision`` reports what the
        heuristic would pick even when routing is disabled
        (``enabled=False``) — the bench records the verdict without
        turning it on."""
        cfg = self.cfg
        thr = cfg.route_full_below if cfg.route_full_below > 0 else 48 * cfg.C
        n = self.planes.n_items
        decision = ("full" if cfg.mode == "candidate" and n <= thr
                    else cfg.mode)
        return dict(enabled=cfg.route_full_below != 0, threshold=int(thr),
                    n_items=int(n), decision=decision)

    def _flat_ids(self) -> jax.Array:
        if self._ids_flat_for is not self.index:
            self._ids_flat = lsh_index.padded_flat_ids(self.index,
                                                       cap=self.cfg.cap)
            self._ids_flat_for = self.index
        return self._ids_flat

    def _tail_k(self) -> int:
        """Static tail-scan width for the walk path: the resident tail
        prefix (slots fill strictly in insertion order) rounded up to 16,
        so a burst of inserts retraces at most once per 16 — and the
        steady state between ingests (empty tail) skips the scan and its
        dead SENTINEL score columns entirely."""
        n = self.index.tail_fill
        return 0 if not n else min(self.index.tail_cap, -(-n // 16) * 16)

    def _flush_program(self, user_ids: jax.Array):
        """(jitted program, args, kwargs) that serve one flush of
        ``user_ids`` on this service's configured path."""
        cfg = self.cfg
        if cfg.mode == "full" or (
                cfg.route_full_below
                and self.route_decision()["decision"] == "full"):
            return full_topn, (self.params, user_ids), dict(topn=cfg.topn)
        if self._shard_state is not None:
            sidx, col_stack, _, _ = self._shard_state
            popular = (self.popular if self.popular is not None else
                       jnp.zeros((1,), jnp.int32))
            return self._sharded_fn, (
                self.planes.row, self.planes.mu, col_stack,
                sidx.sorted_sigs, sidx.sorted_ids, sidx.slot_of,
                sidx.n_local, sidx.bounds, self.sp, user_ids, popular), {}
        if cfg.band_budget:
            if cfg.scorer_impl() == "ref":       # CPU: pure-XLA walk path
                return recommend_walked, (
                    self.planes, self.index, self.sp, user_ids,
                    self.popular), dict(
                    n_seeds=cfg.n_seeds, cap=cfg.cap, budget=cfg.band_budget,
                    window=cfg.seed_window, tail_k=self._tail_k(),
                    topn=cfg.topn, tile_b=cfg.walk_tile_b)
            return recommend_walked_kernel, (
                self.planes, self.index, self.sp, user_ids, self.popular,
                self._flat_ids()), dict(
                n_seeds=cfg.n_seeds, cap=cfg.cap, C=cfg.C,
                window=cfg.seed_window,
                tail_scan=self.index.tail_fill > 0, topn=cfg.topn,
                tile_b=cfg.tile_b, interpret=cfg.interpret_mode(),
                impl=cfg.scorer_impl())
        return recommend_candidates, (
            self.planes, self.index, self.sp, user_ids, self.JK,
            self.popular), dict(
            n_seeds=cfg.n_seeds, cap=cfg.cap, C=cfg.C,
            window=cfg.seed_window, pool_width=cfg.resolved_pool_width(),
            fold_mates=cfg.fold_mates,
            # host-side tail mirror: an empty tail (the steady state
            # between ingests) skips the all-miss tail scan; the first
            # insert flips the static flag → one retrace, which the
            # ingestion path absorbs
            tail_scan=self.index.tail_fill > 0,
            topn=cfg.topn, tile_b=cfg.tile_b,
            interpret=cfg.interpret_mode(), impl=cfg.scorer_impl())

    def _recommend(self, user_ids: jax.Array):
        fn, args, kw = self._flush_program(user_ids)
        return fn(*args, **kw)

    def flush_hlo(self) -> str:
        """Optimized HLO of the micro-batch flush program (the one
        `warmup` compiles) — shows which kernels the flush runs."""
        ids = jnp.zeros((self.cfg.micro_batch,), jnp.int32)
        fn, args, kw = self._flush_program(ids)
        return fn.lower(*args, **kw).compile().as_text()

    def warmup(self):
        """Trace + compile both shapes before the timed traffic."""
        ids = jnp.zeros((self.cfg.micro_batch,), jnp.int32)
        jax.block_until_ready(self._recommend(ids))
        return self

    # ---- request plane ----

    def submit(self, user_ids) -> None:
        """Queue a request (any shape); flushes whole micro-batches.

        Admission control (``cfg.max_pending``): when the queue exceeds
        the bound, the *oldest* queued users are shed into the degraded
        popularity path — under overload the service answers with bounded
        staleness instead of letting queue wait grow without limit."""
        self._poll_rebuild()
        arr = np.atleast_1d(np.asarray(user_ids, np.int32))
        self._pending.append((arr, time.perf_counter_ns()))
        self._n_pending += arr.shape[0]
        if self.cfg.max_pending and self._n_pending > self.cfg.max_pending:
            self._shed_over_bound()
        self.obs.gauge_set("serve.queue_depth", self._n_pending)
        while self._n_pending >= self.cfg.micro_batch:
            self._flush_one()

    def flush(self) -> None:
        """Drain everything pending (final partial batch is padded) and
        sync every dispatched flush."""
        self._poll_rebuild()
        while self._n_pending:
            self._flush_one()
        while self._inflight:
            self._sync_oldest()

    def flush_some(self, max_flushes: int) -> int:
        """Slice-aware flush (ISSUE 10): dispatch at most ``max_flushes``
        micro-batches, then sync everything in flight so the device is
        idle when the caller's next phase (a training micro-epoch) starts
        — the cooperative yield of the shared device budget.  Work beyond
        the budget stays queued for the next slice; returns the number of
        flushes dispatched."""
        self._poll_rebuild()
        n = 0
        while self._n_pending and n < max_flushes:
            self._flush_one()
            n += 1
        while self._inflight:
            self._sync_oldest()
        return n

    # ---- load shedding / degraded serving (ISSUE 7) ----

    def _host_degraded(self, users: np.ndarray):
        """Host-side popularity answer: items = the global shortlist,
        scores = the bias part of Eq. (1) (μ + b_u + b̂_j) — no retrieval,
        no device dispatch.  None when ``n_popular`` is off (callers then
        drop instead of degrading)."""
        if self.popular is None:
            return None
        if self._host_bias is None:
            p = self.params
            self._host_bias = (float(p.mu), np.asarray(p.b), np.asarray(p.bh))
        mu, b, bh = self._host_bias
        topn = self.cfg.topn
        pop = np.asarray(self.popular)[:topn]
        n, w = users.shape[0], pop.shape[0]
        safe_u = np.clip(users, 0, b.shape[0] - 1)
        items = np.full((n, topn), SENTINEL, np.int32)
        items[:, :w] = pop[None, :]
        scores = np.full((n, topn), -np.inf, np.float32)
        scores[:, :w] = mu + b[safe_u][:, None] + bh[pop][None, :]
        return scores, items

    def _shed_chunks(self, chunks: list) -> None:
        """Turn shed request chunks into one degraded pseudo-flush so
        `take_results` keeps submission order (shed chunks are always a
        FIFO prefix of the queue, so enqueueing the entry now — before
        the next real dispatch — preserves ordering)."""
        users = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
        reg = self.obs
        reg.counter_add("serve.shed_users", users.shape[0])
        res = self._host_degraded(users)
        if res is None:          # no popularity shortlist → drop, loudly
            reg.counter_add("serve.dropped_users", users.shape[0])
            return
        scores, items = res
        reg.counter_add("serve.degraded_users", users.shape[0])
        self._inflight.append((users, users.shape[0],
                               time.perf_counter_ns(), (scores, items), True))

    def _shed_over_bound(self) -> None:
        bound = self.cfg.max_pending
        shed: list = []
        while self._pending and self._n_pending > bound:
            a, t_sub = self._pending.popleft()
            excess = self._n_pending - bound
            if a.shape[0] > excess:      # split: shed only the overflow
                self._pending.appendleft((a[excess:], t_sub))
                a = a[:excess]
            shed.append(a)
            self._n_pending -= a.shape[0]
        if shed:
            self._shed_chunks(shed)

    def _shed_expired(self, now_ns: int) -> None:
        """Deadline shedding: queue-wait is monotone along the FIFO, so
        expired chunks are exactly the queue prefix."""
        dl = self.cfg.deadline_s * 1e9
        shed: list = []
        while self._pending and now_ns - self._pending[0][1] > dl:
            a, _ = self._pending.popleft()
            self._n_pending -= a.shape[0]
            shed.append(a)
        if shed:
            self._shed_chunks(shed)

    def _flush_one(self) -> None:
        """Dispatch one micro-batch; sync the *previous* flush only after
        this one is enqueued (double-buffered dispatch-ahead).

        Spans, one each per real flush: ``serve.flush.fill`` (submit of
        the batch's oldest request → start of its dispatch),
        ``serve.flush.dispatch`` with its children ``.take`` (pop, join
        and pad the queue) and ``.launch`` (host → device copy and
        program launch), then ``serve.flush.sync`` and ``serve.flush`` in
        `_sync_oldest`.

        Resilience: expired chunks are shed *before* filling the batch
        (deadline shedding), and a hot-path failure — injected or real —
        falls back to the exact O(N) `full_topn` baseline instead of
        failing the flush (counter ``serve.fallback_full``)."""
        mb = self.cfg.micro_batch
        reg = self.obs
        if self.cfg.deadline_s:
            self._shed_expired(time.perf_counter_ns())
        if not self._pending:        # everything this flush would have
            return                   # taken was shed past its deadline
        t_oldest = self._pending[0][1]
        reg.record_span("serve.flush.fill", t_oldest,
                        time.perf_counter_ns() - t_oldest)
        with reg.span("serve.flush.dispatch"):
            with reg.span("serve.flush.dispatch.take"):
                # consume only as many queued arrays as one micro-batch
                # needs — a huge submit is sliced by view, not
                # re-concatenated per flush
                chunks, n = [], 0
                while self._pending and n < mb:
                    a, t_last = self._pending.popleft()
                    chunks.append(a)
                    n += a.shape[0]
                flat = (chunks[0] if len(chunks) == 1
                        else np.concatenate(chunks))
                take = flat[:mb]
                if flat.size > mb:
                    # overflow comes entirely from the last chunk popped
                    self._pending.appendleft((flat[mb:], t_last))
                n_real = take.size
                self._n_pending -= n_real
                reg.gauge_set("serve.queue_depth", self._n_pending)
                if n_real < mb:
                    # pad the final partial batch to the jitted shape
                    take = np.concatenate(
                        [take, np.zeros(mb - n_real, np.int32)])

            try:
                faults.fire("serve.flush")    # before the timer: injected
                # stalls read as queue wait, not scoring latency
                with reg.span("serve.flush.dispatch.launch"):
                    t0_ns = time.perf_counter_ns()
                    out = self._recommend(jnp.asarray(take))  # async
            except Exception:  # noqa: BLE001 — degrade, never stall
                reg.counter_add("serve.fallback_full")
                with reg.span("serve.flush.dispatch.launch"):
                    t0_ns = time.perf_counter_ns()
                    out = full_topn(self.params, jnp.asarray(take),
                                    topn=self.cfg.topn)
        self._inflight.append((take, n_real, t0_ns, out, False))
        reg.counter_add("serve.flushes")
        while len(self._inflight) > 1:
            self._sync_oldest()

    def _sync_oldest(self) -> None:
        take, n_real, t0_ns, (scores, items), degraded = \
            self._inflight.popleft()
        reg = self.obs
        if degraded:
            # shed pseudo-flush: results were computed host-side at shed
            # time; it never touched the device, so it contributes no
            # flush latency / busy time (keeping p50/p95/p99 about the
            # real pipeline)
            reg.counter_add("serve.users", n_real)
            self._results.append((take[:n_real], scores[:n_real],
                                  items[:n_real]))
            return
        with reg.span("serve.flush.sync"):
            try:
                jax.block_until_ready(items)
            except Exception:  # noqa: BLE001 — deferred device failure:
                # recompute through the exact baseline rather than lose a
                # dispatched batch
                reg.counter_add("serve.fallback_full")
                scores, items = full_topn(self.params, jnp.asarray(take),
                                          topn=self.cfg.topn)
                jax.block_until_ready(items)
        now_ns = time.perf_counter_ns()
        # dispatch → the host's sync, which under dispatch-ahead comes
        # when the next flush is dispatched (one fill later under steady
        # traffic), not when the answer is ready; busy wall: overlap
        # counted once
        reg.record_span("serve.flush", t0_ns, now_ns - t0_ns)
        reg.counter_add("serve.busy_seconds",
                        (now_ns - max(self._last_ready_ns, t0_ns)) * 1e-9)
        self._last_ready_ns = now_ns
        reg.counter_add("serve.users", n_real)
        self._results.append((take[:n_real],
                              np.asarray(scores)[:n_real],
                              np.asarray(items)[:n_real]))

    def take_results(self):
        """[(user_ids, scores, items)] for every flush since the last take.

        Results are appended at sync time in dispatch order, so the k-th
        tuple is the k-th flushed micro-batch and its rows line up with
        the user ids that were submitted (padding already stripped).
        Shed chunks appear as degraded pseudo-flushes in the same
        submission order (they are always a queue prefix, enqueued before
        the next real dispatch); only fully *dropped* requests
        (``n_popular == 0`` under shedding) produce no rows."""
        out, self._results = self._results, []
        return out

    def stats(self) -> dict:
        """Serving stats, read *entirely* from the obs registry (ISSUE 6:
        one source of timing truth).  Keys `mode/batches/users/qps/
        p50_ms/p95_ms` keep their pre-obs semantics; `p99_ms`, `queue`
        and `ingest_to_servable_s` (0.0 until the first ingest) are new."""
        reg = self.obs
        flush_s = reg.span_durations("serve.flush")
        secs = np.asarray(flush_s) if flush_s else np.zeros((1,))
        busy = reg.counter("serve.busy_seconds")
        users = int(reg.counter("serve.users"))
        return dict(
            mode=self.cfg.mode,
            batches=int(reg.counter("serve.flushes")),
            users=users,
            qps=users / busy if busy else 0.0,
            p50_ms=float(np.percentile(secs, 50) * 1e3),
            p95_ms=float(np.percentile(secs, 95) * 1e3),
            p99_ms=float(np.percentile(secs, 99) * 1e3),
            queue=self._n_pending,
            ingest_to_servable_s=reg.gauge("serve.ingest_to_servable_s", 0.0),
            # resilience counters (ISSUE 7): shed = admission/deadline
            # victims, degraded = shed users answered via the popularity
            # path, dropped = shed with no fallback, fallbacks = flushes
            # rescued by exact full scoring, quarantined = poison ingest
            # batches rejected, index_stale = overflow awaiting a
            # background rebuild swap
            shed=int(reg.counter("serve.shed_users")),
            degraded=int(reg.counter("serve.degraded_users")),
            dropped=int(reg.counter("serve.dropped_users")),
            fallbacks=int(reg.counter("serve.fallback_full")),
            quarantined=int(reg.counter("serve.quarantined")),
            ingest_rejected=int(reg.counter("serve.ingest_rejected")),
            index_stale=bool(reg.gauge("serve.index_stale", 0.0)),
            # staleness (ISSUE 10): wall-clock age of the serving params —
            # what the always-on loop's publish cadence bounds
            model_age_s=time.perf_counter() - self._params_adopted,
            # small-catalog routing (PR 8): the verdict is always
            # reported; `enabled` says whether _recommend acts on it
            route=self.route_decision(),
            # sharded tier (PR 9): 1 = the single-device oracle path
            shards=(self._shard_state[3] if self._shard_state is not None
                    else 1),
        )

    def profile_flush(self, user_ids=None) -> dict:
        """One *staged* flush with nested host spans — the observability
        view of the hot path.

        The production pipeline fuses retrieval and scoring into a single
        jitted dispatch (host spans cannot subdivide it; only the
        `jax.named_scope` stage names inside the program show up, and only
        in XLA device profiles).  This path runs the same stages as
        separate dispatches with a readiness barrier after each, so the
        span tree — serve.flush → retrieve(.desc → .walk) → score →
        select on the walk path, retrieve(.pool → .dedup) → score on the
        legacy pool path — carries real wall times into the Chrome trace
        export.  Slower than the
        fused path by the un-fused dispatch overhead — a profiling tool,
        not a serving mode.  Returns {span name: seconds} for this run.
        """
        cfg = self.cfg
        reg = self.obs
        if user_ids is None:
            user_ids = np.arange(cfg.micro_batch, dtype=np.int32)
        ids = jnp.asarray(np.atleast_1d(np.asarray(user_ids, np.int32)))
        names = ["serve.flush"]
        with reg.span("serve.flush"):
            if cfg.mode == "full":
                with reg.span("serve.flush.score"):
                    jax.block_until_ready(
                        full_topn(self.params, ids, topn=cfg.topn))
                names += ["serve.flush.score"]
            elif self._shard_state is not None:
                # the sharded flush is one shard_map dispatch — host
                # spans cannot subdivide its collectives; time it whole
                with reg.span("serve.flush.sharded"):
                    jax.block_until_ready(self._recommend(ids))
                names += ["serve.flush.sharded"]
            elif cfg.band_budget and cfg.scorer_impl() == "ref":
                # CPU walk path: desc → walk → score → select (dedup
                # happens inside select; there is no dedup stage to time)
                tail_k = self._tail_k()
                with reg.span("serve.flush.retrieve"):
                    with reg.span("serve.flush.retrieve.desc"):
                        seeds = seed_items(self.sp, ids, n_seeds=cfg.n_seeds,
                                           window=cfg.seed_window)
                        starts, counts = window_descriptors(
                            self.index, seeds, cap=cfg.cap)
                        jax.block_until_ready(counts)
                    with reg.span("serve.flush.retrieve.walk"):
                        pos = enumerate_windows(starts, counts,
                                                budget=cfg.band_budget)
                        walked = _walk_gather(self.index, pos)
                        if tail_k:
                            walked = jnp.concatenate(
                                [walked, tail_hits(self.index, seeds,
                                                   k=tail_k)], axis=1)
                        jax.block_until_ready(walked)
                with reg.span("serve.flush.score"):
                    s, cand = _score_pool_staged(self.planes, ids, walked,
                                                 self.popular,
                                                 tile_b=cfg.walk_tile_b)
                    jax.block_until_ready(s)
                with reg.span("serve.flush.select"):
                    jax.block_until_ready(
                        _select_staged(s, cand, topn=cfg.topn))
                names += ["serve.flush.retrieve",
                          "serve.flush.retrieve.desc",
                          "serve.flush.retrieve.walk",
                          "serve.flush.score", "serve.flush.select"]
            elif cfg.band_budget:
                # accelerator walk path: the lsh_retrieve kernel IS the
                # walk+dedup stage
                tail = self.index.tail_fill > 0 and self.index.tail_cap > 0
                with reg.span("serve.flush.retrieve"):
                    with reg.span("serve.flush.retrieve.desc"):
                        seeds = seed_items(self.sp, ids, n_seeds=cfg.n_seeds,
                                           window=cfg.seed_window)
                        starts, lens = lsh_index.window_slices(
                            self.index, seeds, cap=cfg.cap)
                        extra = (tail_hits(self.index, seeds) if tail else
                                 jnp.full((ids.shape[0], 1), SENTINEL,
                                          jnp.int32))
                        jax.block_until_ready(lens)
                    with reg.span("serve.flush.retrieve.walk"):
                        if self.popular is not None:
                            exclude, core_C = self.popular, \
                                cfg.C - self.popular.shape[0]
                        else:
                            exclude = jnp.full((1,), SENTINEL, jnp.int32)
                            core_C = cfg.C
                        cand = lsh_retrieve_topc(
                            starts, lens, extra, self._flat_ids(), exclude,
                            C=core_C, cap=cfg.cap,
                            interpret=cfg.interpret_mode())
                        if self.popular is not None:
                            cand = jnp.concatenate(
                                [cand, jnp.broadcast_to(
                                    self.popular[None, :],
                                    (ids.shape[0],
                                     self.popular.shape[0]))], axis=1)
                        jax.block_until_ready(cand)
                with reg.span("serve.flush.score"):
                    jax.block_until_ready(score_candidates(
                        self.planes, ids, cand, topn=cfg.topn,
                        tile_b=cfg.tile_b, interpret=cfg.interpret_mode(),
                        impl=cfg.scorer_impl()))
                names += ["serve.flush.retrieve",
                          "serve.flush.retrieve.desc",
                          "serve.flush.retrieve.walk", "serve.flush.score"]
            else:
                with reg.span("serve.flush.retrieve"):
                    with reg.span("serve.flush.retrieve.pool"):
                        pool = candidate_pool(
                            self.index, self.sp, ids, n_seeds=cfg.n_seeds,
                            cap=cfg.cap, JK=self.JK, window=cfg.seed_window,
                            fold_mates=cfg.fold_mates,
                            tail_scan=self.index.tail_fill > 0)
                        jax.block_until_ready(pool)
                    with reg.span("serve.flush.retrieve.dedup"):
                        cand = finalize_candidates(
                            pool, C=cfg.C, popular=self.popular,
                            pool_width=cfg.resolved_pool_width())
                        jax.block_until_ready(cand)
                with reg.span("serve.flush.score"):
                    jax.block_until_ready(score_candidates(
                        self.planes, ids, cand, topn=cfg.topn,
                        tile_b=cfg.tile_b, interpret=cfg.interpret_mode(),
                        impl=cfg.scorer_impl()))
                names += ["serve.flush.retrieve",
                          "serve.flush.retrieve.pool",
                          "serve.flush.retrieve.dedup", "serve.flush.score"]
        return {n: reg.span_durations(n)[-1] for n in names}

    # ---- ingestion plane (paper Alg. 4) ----

    # ---- background rebuild (ISSUE 7: double-buffered validate-then-swap) --

    def _start_rebuild(self, full_sigs) -> None:
        if self._rebuilder is None:
            self._rebuilder = IndexRebuilder(self.obs)
        self._rebuild_sigs = full_sigs       # kept for bounded auto-retry
        self._rebuild_attempts = 0
        self._rebuild_t0 = time.perf_counter()
        # stale: the tail overflowed, so items past base+tail are not yet
        # retrievable — cleared when the validated v+1 swaps in
        self.obs.gauge_set("serve.index_stale", 1.0)
        self._rebuilder.submit(full_sigs, tail_cap=self.index.tail_cap)

    def _poll_rebuild(self) -> None:
        """Called at the serving-loop edges (submit/flush/ingest): swap in
        a validated rebuild, or retry/roll back a failed one.  Serving
        index v continues uninterrupted in every branch — in-flight
        flushes captured v (jax arrays are immutable), and a failed or
        invalid build is simply never taken."""
        if self._rebuilder is None:
            return
        status, idx, err = self._rebuilder.take()
        if status == "ready":
            self.index = idx
            self._rebuild_sigs = None
            with self.obs.span("serve.rebuild.swap"):
                self.warmup()        # n_base changed → one retrace, absorbed
            self.obs.counter_add("serve.rebuild.swaps")
            self.obs.gauge_set("serve.index_stale", 0.0)
            self.obs.gauge_set("serve.ingest_to_servable_s",
                               time.perf_counter() - self._rebuild_t0)
        elif status == "failed":
            self._rebuild_attempts += 1
            if (self._rebuild_sigs is not None
                    and self._rebuild_attempts < self.cfg.rebuild_retries):
                self.obs.counter_add("serve.rebuild.retries")
                self._rebuilder.submit(self._rebuild_sigs,
                                       tail_cap=self.index.tail_cap)
            else:
                # rollback is the default: keep serving v; the index stays
                # stale (missing post-overflow items) and says so loudly
                self.obs.counter_add("serve.rebuild.gave_up")
                self._rebuild_sigs = None

    def request_rebuild(self, full_sigs) -> None:
        """Supervisor-triggered rebuild (ISSUE 10 drift detection): hand
        the full [q, N] signature set to the background rebuilder;
        serving continues on index v and the validated v+1 swaps in at a
        later flush boundary (`_poll_rebuild`).  Single-device only —
        the sharded tier is rebuilt by constructing a new service."""
        if self._shard_state is not None:
            self.obs.counter_add("serve.ingest_rejected")
            raise ShardedIngestUnsupported(
                "sharded serving is read-only: request the rebuild on a "
                "single-device service and construct a new sharded "
                "service from the swapped index")
        self._poll_rebuild()
        self._start_rebuild(full_sigs)

    # ---- ingestion entry points ----

    def ingest(self, new_sigs: jax.Array, new_ids: jax.Array,
               full_sigs: jax.Array | None = None) -> None:
        """Insert new items into the index tail; rebuild on overflow
        (rebuild requires ``full_sigs`` [q, N_total]).

        With ``cfg.background_rebuild`` (default) an overflow hands
        ``full_sigs`` — which already contain the new items — to the
        background rebuilder and returns immediately: the service keeps
        serving index v (marked stale) and swaps in the validated v+1 at
        a later flush boundary.  Poison batches (wrong dtype, NaN rows,
        negative/duplicate ids) raise `PoisonBatchError` before any state
        is touched.

        Crossing the empty-tail boundary (first insert, or a rebuild
        folding the tail away) flips the static tail fast path in
        `_recommend`, so re-warm here — the retrace lands in ingestion
        time, not in the next request's latency window."""
        if self._shard_state is not None:
            self.obs.counter_add("serve.ingest_rejected")
            raise ShardedIngestUnsupported(
                "sharded serving is read-only: apply this ingest on a "
                "single-device service (tail insert + rebuild on "
                "overflow) and construct a new sharded service from the "
                "rebuilt index, or hand full_sigs to request_rebuild() "
                "on that single-device service")
        t0_ns = time.perf_counter_ns()
        try:
            check_ingest_batch(new_sigs, new_ids, q=self.index.q)
        except PoisonBatchError:
            self.obs.counter_add("serve.quarantined")
            raise
        faults.fire("serve.ingest")
        self._poll_rebuild()
        with self.obs.span("serve.ingest"):
            had_tail = self.index.tail_fill > 0
            rebuilt = lsh_index.needs_rebuild(self.index,
                                              int(new_ids.shape[0]))
            if rebuilt:     # a rebuild also grows n_base → new trace shapes
                if full_sigs is None:
                    raise ValueError(
                        "tail overflow and no full_sigs to rebuild")
                if self.cfg.background_rebuild:
                    self._start_rebuild(full_sigs)
                else:
                    with self.obs.span("serve.ingest.rebuild"):
                        self.index = lsh_index.rebuild(self.index, full_sigs)
            else:
                with self.obs.span("serve.ingest.insert"):
                    self.index = lsh_index.insert(self.index, new_sigs,
                                                  new_ids)
            sync_done = not (rebuilt and self.cfg.background_rebuild)
            if sync_done and (rebuilt
                              or (self.index.tail_fill > 0) != had_tail):
                with self.obs.span("serve.ingest.warmup"):
                    self.warmup()
        self.obs.counter_add("serve.ingests")
        self.obs.counter_add("serve.ingested_items", int(new_ids.shape[0]))
        # ingest→servable: new items are retrievable the moment ingest
        # returns (and any forced retrace has already been re-warmed); on
        # the background-rebuild path _poll_rebuild overwrites this with
        # the overflow→swap latency once v+1 lands
        if sync_done:
            self.obs.gauge_set("serve.ingest_to_servable_s",
                               (time.perf_counter_ns() - t0_ns) * 1e-9)

    def ingest_online_update(self, state, N_old: int) -> None:
        """Adopt a `core.online.online_update` result: swap in the grown
        params/interactions and add only the *new* columns to the index,
        re-signing from the updated accumulator cache (Alg. 4 lines 1–6).
        Old columns keep their buckets (the paper's "remains unchanged").

        The index is never rebuilt, but the grown parameter shapes force
        one retrace of the serving pipelines — re-warm here so the compile
        lands in ingestion time, not in a request's latency window."""
        if self._shard_state is not None:
            self.obs.counter_add("serve.ingest_rejected")
            raise ShardedIngestUnsupported(
                "sharded serving is read-only: run the online-update "
                "handoff on a single-device service (shards=0) and "
                "construct a new sharded service from the grown state — "
                "or route the full re-signed signature set through "
                "request_rebuild() there")
        t0_ns = time.perf_counter_ns()
        # quarantine before touching anything: NaN-poisoned accumulator
        # slabs would re-sign new columns into valid-looking garbage
        # signatures (silent mis-bucketing, not a crash)
        try:
            check_accumulators(state.S, N_old)
        except PoisonBatchError:
            self.obs.counter_add("serve.quarantined")
            raise
        with self.obs.span("serve.ingest_online"):
            self.flush()    # drain in-flight work against the old planes
            with self.obs.span("serve.ingest_online.resign"):
                sigs = simlsh.pack_bits(state.S >= 0)         # [q, N_new]
            # swap the grown state in *before* the index ingest: ingest()'s
            # own tail-boundary warmup must compile against the new plane
            # shapes, not trace a pipeline the swap immediately invalidates
            assert state.N <= 1 << 30, \
                "item ids must stay below 2^30 (the dedup hash mask)"
            with self.obs.span("serve.ingest_online.swap"):
                self.params = state.params
                self._params_adopted = time.perf_counter()
                self.planes = self._pack(state.params)
                self._host_bias = None     # degraded-path mirror is stale
                self.sp = state.sp
                if self.JK is not None:
                    self.JK = state.JK
                if self.cfg.n_popular:
                    self.popular = popular_shortlist(state.params,
                                                     self.cfg.n_popular)
            if state.N > N_old:
                self.ingest(sigs[:, N_old:],
                            jnp.arange(N_old, state.N, dtype=jnp.int32),
                            full_sigs=sigs)
            with self.obs.span("serve.ingest_online.warmup"):
                self.warmup()
        # the full online handoff (drain → re-sign → swap → index →
        # re-warm) is this path's ingest→servable latency; overwrites the
        # inner ingest()'s narrower reading
        self.obs.gauge_set("serve.ingest_to_servable_s",
                           (time.perf_counter_ns() - t0_ns) * 1e-9)
