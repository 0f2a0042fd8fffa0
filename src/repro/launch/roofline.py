"""Roofline extraction from compiled dry-run artifacts.

Hardware constants (brief): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

Methodology (DESIGN.md §6): XLA's `cost_analysis()` is post-SPMD per-device
but does NOT multiply `scan`/`while` body cost by trip count.  Cost terms
are therefore extracted from *unrolled marginal* compiles:

    C(L) = fixed + L·layer   ⇒   layer = C(L2) − C(L1),  fixed = C(L1) − layer

with unrolled layers, single-block attention and one microbatch, then
composed:  total = µ · (fixed_fwd + L·layer) + opt  (train)
           total = fixed + L·layer                  (prefill/decode).

Collective bytes are parsed from `compiled.as_text()` of the same unrolled
modules (no while loops ⇒ counts are exact).
"""
from __future__ import annotations

import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch import specs as SPECS
from repro.models import lm, sharding, steps

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-kind output bytes of collective ops (per device, post-SPMD)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_str)
    return out


def collective_schedule(hlo_text: str, limit: int = 2000) -> list:
    """(kind, bytes) in program order — the dry-run's collective schedule."""
    sched = []
    for m in _COLL_RE.finditer(hlo_text):
        sched.append((m.group(2), _shape_bytes(m.group(1))))
        if len(sched) >= limit:
            break
    return sched


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: dict = dataclasses.field(default_factory=dict)

    def __add__(self, o):
        coll = dict(self.coll)
        for k, v in o.coll.items():
            coll[k] = coll.get(k, 0) + v
        return Cost(self.flops + o.flops, self.bytes + o.bytes, coll)

    def __sub__(self, o):
        coll = dict(self.coll)
        for k, v in o.coll.items():
            coll[k] = coll.get(k, 0) - v
        return Cost(self.flops - o.flops, self.bytes - o.bytes, coll)

    def __mul__(self, s):
        return Cost(self.flops * s, self.bytes * s,
                    {k: v * s for k, v in self.coll.items()})

    @property
    def coll_bytes(self):
        return sum(self.coll.values())


def _compile_cost(fn, in_shardings, args, mesh) -> Cost:
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_shardings).lower(*args)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    return Cost(float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)),
                collective_bytes(txt))


MAX_COST_QC = 2048   # keep chunk tensors < 2^31 elements (XLA int32 paths)


def _cost_cfg(cfg: ArchConfig, L: int, enc: int | None = None,
              shape_seq: int = 0) -> ArchConfig:
    qc = min(max(cfg.query_chunk, shape_seq or 1), MAX_COST_QC)
    return dataclasses.replace(
        cfg, L=L,
        enc_layers=enc if enc is not None else cfg.enc_layers,
        unroll_layers=True, microbatches=1,
        query_chunk=qc,
    )


def _attn_chunk_correction(cfg: ArchConfig, shape: ShapeSpec, axes) -> float:
    """FLOPs per layer of the attention chunks NOT counted by
    cost_analysis (the lax.map body runs nchunks times but is costed once).
    Analytic: per chunk ≈ B_loc·H_loc·qc·T·(4·hd + 8)."""
    S = shape.seq_len
    qc = min(max(cfg.query_chunk, S), MAX_COST_QC)
    if shape.kind == "decode" or S <= qc or not cfg.n_heads:
        return 0.0
    nchunks = -(-S // qc)
    B_loc = max(1, shape.global_batch // axes["ndp"])
    H_loc = max(1, cfg.n_heads // axes["ntp"])
    per_chunk = B_loc * H_loc * qc * S * (4.0 * cfg.hd + 8.0)
    n_attn = 3 if cfg.family == "encdec" else 1
    fwd = (nchunks - 1) * per_chunk * n_attn
    # train backward recomputes (remat) + differentiates: ≈ 3.5× fwd total
    return fwd * (3.5 if shape.kind == "train" else 1.0)


def _mk_args(cfg, shape, mesh, axes, kind):
    """(fn, in_shardings, args) for one cost compile."""
    params = jax.eval_shape(
        partial(lm.init_params, cfg, model_shards=axes["ntp"]),
        jax.random.PRNGKey(0))
    psp = sharding.to_named(sharding.param_specs(cfg, params, axes), mesh)
    if kind == "train":
        b = SPECS.batch_specs_for(cfg, shape)
        bsp = sharding.to_named(sharding.batch_specs(cfg, b, axes), mesh)

        def fwdbwd(p, batch):
            return jax.grad(lambda pp: steps.lm_loss(cfg, pp, batch, mesh, axes))(p)

        return fwdbwd, (psp, bsp), (params, b)
    if kind == "prefill":
        b = SPECS.prefill_specs_for(cfg, shape)
        bsp = sharding.to_named(sharding.batch_specs(cfg, b, axes), mesh)
        fn = steps.make_prefill(cfg, mesh, axes)
        return fn, (psp, bsp), (params, b)
    cache, tokens = SPECS.decode_specs_for(cfg, shape)
    csp = sharding.to_named(sharding.cache_specs(cfg, cache, axes), mesh)
    tsp = sharding.to_named(
        sharding.batch_specs(cfg, {"tokens": tokens}, axes), mesh)["tokens"]
    fn = steps.make_decode_step(cfg, mesh, axes)
    return fn, (psp, csp, tsp), (params, cache, tokens)


def _opt_cost(cfg, mesh, axes) -> Cost:
    params = jax.eval_shape(
        partial(lm.init_params, cfg, model_shards=axes["ntp"]),
        jax.random.PRNGKey(0))
    psp = sharding.to_named(sharding.param_specs(cfg, params, axes), mesh)
    opt = jax.eval_shape(partial(steps.init_opt, cfg), params)
    osp = dict(m=psp, v=psp,
               count=sharding.to_named(jax.sharding.PartitionSpec(), mesh))

    def upd(p, g, o):
        p2, o2, _ = steps.adam_update(cfg, p, g, o)
        return p2, o2

    return _compile_cost(upd, (psp, psp, osp), (params, params, opt), mesh)


def _layer_counts(cfg: ArchConfig):
    """(L1, L2, extra) probe sizes per family."""
    if cfg.family == "hybrid":
        k = cfg.attn_every
        return k, 2 * k, cfg.L % k or None     # group marginals (+ partial)
    return 1, 2, None


def micro_shape(shape: ShapeSpec, cfg: ArchConfig) -> ShapeSpec:
    µ = max(1, cfg.microbatches) if shape.kind == "train" else 1
    return dataclasses.replace(shape, global_batch=max(1, shape.global_batch // µ))


def extract_cost(cfg: ArchConfig, shape: ShapeSpec, mesh, axes) -> dict:
    """Composed per-device cost for the full (arch × shape) cell."""
    kind = shape.kind
    mshape = micro_shape(shape, cfg)
    µ = max(1, cfg.microbatches) if kind == "train" else 1
    L1, L2, Lpart = _layer_counts(cfg)

    def cost_at(L):
        c = _cost_cfg(cfg, L, enc=(L if cfg.family == "encdec" else None),
                      shape_seq=mshape.seq_len)
        return _compile_cost(*_mk_args(c, mshape, mesh, axes, kind), mesh=mesh)

    C1, C2 = cost_at(L1), cost_at(L2)
    layer = C2 - C1
    fixed = C1 - layer
    # analytic add-back of attention chunks hidden inside lax.map (per layer)
    layer = layer + Cost(_attn_chunk_correction(cfg, mshape, axes), 0.0, {})
    if cfg.family == "hybrid":
        ngroups_full = cfg.L // cfg.attn_every
        total_layers = ngroups_full
        body = fixed + layer * ngroups_full
        if Lpart:
            Cp = cost_at(Lpart)
            body = body + (Cp - fixed)
        total = body
    elif cfg.family == "encdec":
        # enc and dec scale together in the probes (enc=dec=L)
        total = fixed + layer * cfg.L
    else:
        total = fixed + layer * cfg.L
    total = total * µ
    if kind == "train":
        total = total + _opt_cost(cfg, mesh, axes)
    corr = bf16_coll_correction(cfg)
    return dict(flops=total.flops,
                bytes=analytic_hbm_bytes(cfg, shape, axes),
                bytes_xla_upper=total.bytes,
                coll=total.coll,
                coll_bytes=total.coll_bytes * corr,
                coll_bytes_raw=total.coll_bytes,
                per_layer_flops=layer.flops, fixed_flops=fixed.flops)


# --------------------------------------------------------------------------
# analytic HBM-traffic model
# --------------------------------------------------------------------------
#
# XLA-CPU's "bytes accessed" counts every op's operands as HBM traffic (no
# fusion model) and stores many bf16 tensors as f32 (CPU emulation), so it
# over-states TPU HBM traffic by ~one order of magnitude.  The *primary*
# memory term is therefore an analytic estimate of per-chip HBM traffic —
# the quantities a TPU actually moves; the XLA number is kept in the record
# as `bytes_xla_upper`.


def _dtype_bytes(name: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}.get(name, 4)


def analytic_hbm_bytes(cfg: ArchConfig, shape: ShapeSpec, axes) -> float:
    """Per-chip HBM bytes for one step (documented formulas)."""
    nchips = axes["ndp"] * axes["ntp"]
    total, active = param_counts(cfg, axes["ntp"])
    pb = _dtype_bytes(cfg.param_dtype)
    mb = _dtype_bytes(cfg.moment_dtype)
    gb = _dtype_bytes(cfg.grad_dtype)
    µ = max(1, cfg.microbatches) if shape.kind == "train" else 1
    B, S = shape.global_batch, shape.seq_len
    tokens_local = B * S / axes["ndp"]
    D = cfg.d_model
    act_b = _dtype_bytes(cfg.dtype)
    Lh = cfg.L if cfg.family != "encdec" else cfg.L + cfg.enc_layers

    if shape.kind == "train":
        # params: fwd read + bwd read per µbatch (sharded slice per chip;
        # FSDP gathers count as collective, but the local read still happens)
        p_shard = total * pb / nchips
        t = 2 * µ * p_shard
        # grads: write+read accumulator per µbatch + final read
        t += (2 * µ + 1) * total * gb / nchips
        # optimizer: read m,v + write m,v + read/write params
        t += total * (2 * mb * 2 + 2 * pb) / nchips
        # activations: remat stores carry per layer (SP-sharded if enabled)
        sp_div = axes["ntp"] if cfg.seq_shard_acts else 1
        t += 3 * Lh * tokens_local * D * act_b / sp_div   # write + 2 reads
        # logits: write + read f32, vocab-sharded
        t += 2 * tokens_local * cfg.vocab_padded(axes["ntp"]) / axes["ntp"] * 4
        return t
    if shape.kind == "prefill":
        p_shard = total * pb / nchips
        t = p_shard                                         # one param sweep
        t += 2 * Lh * tokens_local * D * act_b              # acts write+read
        if cfg.n_heads:                                     # KV cache write
            t += 2 * Lh * tokens_local * cfg.n_kv * cfg.hd * 2 / axes["ntp"]
        t += tokens_local / S * cfg.vocab_padded(axes["ntp"]) / axes["ntp"] * 4
        return t
    # decode: param sweep + full KV/state read + tiny activations
    p_shard = active * pb / nchips
    t = p_shard
    B_loc = max(1, B // axes["ndp"])
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        kv = cfg.L * B_loc * S * cfg.n_kv * cfg.hd * 2 * 2  # k+v bf16
        kv_div = axes["ntp"] if (cfg.n_kv % axes["ntp"] == 0 or True) else 1
        t += kv / axes["ntp"]                               # T- or H-sharded
        if cfg.family == "encdec":
            t *= 1.0
    if cfg.family in ("ssm", "hybrid"):
        H = max(1, SSM_n_heads(cfg))
        t += cfg.L * B_loc * H * cfg.ssm_headdim * cfg.ssm_state * 4 \
            / min(axes["ntp"], H)
        if cfg.family == "hybrid":
            napp = -(-cfg.L // cfg.attn_every)
            Tw = min(S, 8192 if S >= 100_000 else S)
            t += napp * B_loc * Tw * cfg.n_kv * cfg.hd * 2 * 2 \
                / min(axes["ntp"], cfg.n_kv)
    t += B_loc * D * Lh * 2 * 4                             # per-layer io
    return t


def SSM_n_heads(cfg):
    from repro.models import ssm as SSM
    return SSM.n_heads(cfg) if cfg.ssm_state else 0


# bf16 collectives are modelled at f32 width by the CPU backend; correct by
# the compute-dtype ratio (documented in EXPERIMENTS.md §Roofline).
def bf16_coll_correction(cfg: ArchConfig) -> float:
    return 0.5 if cfg.dtype == "bfloat16" else 1.0


# --------------------------------------------------------------------------
# analytic MODEL_FLOPS + roofline terms
# --------------------------------------------------------------------------


def param_counts(cfg: ArchConfig, model_shards: int = 16):
    params = jax.eval_shape(
        partial(lm.init_params, cfg, model_shards=model_shards),
        jax.random.PRNGKey(0))
    total = sum(x.size for x in jax.tree.leaves(params))
    inactive = 0
    if cfg.family == "moe" and cfg.n_experts:
        expert = sum(params["layers"][k].size for k in ("w1", "w2", "w3"))
        inactive = int(expert * (1 - cfg.moe_top_k / cfg.n_experts))
    return total, total - inactive


def model_flops(cfg: ArchConfig, shape: ShapeSpec, model_shards: int = 16):
    """Analytic 'useful' FLOPs (global): 6·N_active·tokens for train,
    2·N_active·tokens (+ attention against the KV/state) for serve."""
    total, active = param_counts(cfg, model_shards)
    B, S = shape.global_batch, shape.seq_len
    hd = cfg.hd if cfg.n_heads else 0
    if shape.kind == "train":
        flops = 6.0 * active * B * S
        if cfg.n_heads:
            flops += 3.0 * 4.0 * cfg.L * B * S * S * cfg.n_heads * hd * 0.5
        return flops
    if shape.kind == "prefill":
        flops = 2.0 * active * B * S
        if cfg.n_heads:
            flops += 4.0 * cfg.L * B * S * S * cfg.n_heads * hd * 0.5
        return flops
    # decode: one token against T of context
    flops = 2.0 * active * B
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        flops += 4.0 * cfg.L * B * S * cfg.n_heads * hd
    if cfg.family == "hybrid":
        napp = -(-cfg.L // cfg.attn_every)
        T_eff = min(S, 8192 if S >= 100_000 else S)
        flops += 4.0 * napp * B * T_eff * cfg.n_heads * hd
    return flops


def roofline(cost: dict, nchips: int) -> dict:
    t_comp = cost["flops"] / PEAK_FLOPS
    t_mem = cost["bytes"] / HBM_BW
    t_coll = cost["coll_bytes"] / ICI_BW
    dom = max(("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
              key=lambda kv: kv[1])
    return dict(t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
                bound=dom[0], t_step=max(t_comp, t_mem, t_coll))
