"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

Production pattern (DESIGN.md §5): experts are sharded over the "model"
axis (EP).  Two dispatch paths:

* ``shard_seq=True`` (train/prefill): tokens are sharded over data axes AND
  split along "model" (sequence split) for routing, then exchanged with two
  `all_to_all`s:   route → a2a(dispatch) → grouped expert FFN (local
  experts) → a2a(return) → weighted combine.
* ``shard_seq=False`` (decode, S=1): tokens are replicated over "model";
  each device computes only its own experts' contributions and a `psum`
  over "model" combines — the standard small-batch decode path (no a2a).

Fixed capacities keep every shape static: per-destination-device send slots
``C_send`` and per-local-expert slots ``C_exp``; overflow tokens are dropped
(capacity-factor semantics, gradient-safe).

Router logits/top-k run at pjit level (replicated math, so router-weight
gradients are correct without manual psums); the shard_map region only
touches expert weights (sharded on "model", per-shard local grads, with
`check_vma` inserting the data-axis psum on the backward pass).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig


def router(p, x, cfg: ArchConfig):
    """x [B,S,D] → (eid [B,S,k] int32, gate [B,S,k] f32). pjit-level."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    top, eid = jax.lax.top_k(logits, cfg.moe_top_k)
    gate = jax.nn.softmax(top, axis=-1)
    return eid.astype(jnp.int32), gate


def _expert_ffn(w1, w3, w2, xb):
    """xb [E_loc, C, D] through the local experts."""
    g = jnp.einsum("ecd,edf->ecf", xb, w1.astype(xb.dtype))
    u = jnp.einsum("ecd,edf->ecf", xb, w3.astype(xb.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xb.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(xb.dtype))


def _group_and_ffn(recv_x, recv_e, E_loc, C_exp, w1, w3, w2):
    """Group slots by local expert id (−1 = invalid), run the FFN, return
    outputs aligned with the incoming slot order (zeros for dropped)."""
    R, D = recv_x.shape
    order = jnp.argsort(recv_e)                    # −1s first
    se = recv_e[order]
    first = jnp.searchsorted(se, jnp.arange(E_loc, dtype=se.dtype))
    rank = jnp.arange(R) - first[jnp.clip(se, 0, E_loc - 1)]
    ok = (se >= 0) & (rank < C_exp)
    addr = jnp.where(ok, se * C_exp + rank, E_loc * C_exp)

    buf = jnp.zeros((E_loc * C_exp + 1, D), recv_x.dtype)
    buf = buf.at[addr].set(recv_x[order])[: E_loc * C_exp]
    yb = _expert_ffn(w1, w3, w2, buf.reshape(E_loc, C_exp, D))
    yb = yb.reshape(E_loc * C_exp, D)

    back = jnp.zeros((R, D), recv_x.dtype)
    got = jnp.where(ok, addr, 0)
    back = back.at[order].set(jnp.where(ok[:, None], yb[got], 0.0))
    return back


def moe_dense_ref(p, x, eid, gate, cfg: ArchConfig):
    """Reference semantics (single device / tests): every token through its
    top-k experts via gather — exact, no capacity drops."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    eidf = eid.reshape(-1, cfg.moe_top_k)
    gatef = gate.reshape(-1, cfg.moe_top_k).astype(x.dtype)

    def per_slot(kk):
        w1 = p["w1"][eidf[:, kk]].astype(x.dtype)     # [T, D, ff]
        w3 = p["w3"][eidf[:, kk]].astype(x.dtype)
        w2 = p["w2"][eidf[:, kk]].astype(x.dtype)
        g = jnp.einsum("td,tdf->tf", xt, w1)
        u = jnp.einsum("td,tdf->tf", xt, w3)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return jnp.einsum("tf,tfd->td", h, w2) * gatef[:, kk][:, None]

    out = sum(per_slot(kk) for kk in range(cfg.moe_top_k))
    return out.reshape(B, S, D)


def moe_ffn(p, x, eid, gate, cfg: ArchConfig, mesh, mesh_axes,
            capacity_factor: float = 2.0, shard_seq: bool = True):
    """1D EP: experts sharded over tp; FSDP (if on) gathers weights."""
    tp = mesh_axes["tp"]
    dp = mesh_axes["dp"]
    ntp = mesh.shape[tp]
    E = cfg.n_experts
    assert E % ntp == 0, "experts must divide the model axis"
    E_loc = E // ntp
    k = cfg.moe_top_k

    def _flat(x, eid, gate):
        b, s_loc, D = x.shape
        T = b * s_loc
        xt = x.reshape(T, D)
        slot_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        slot_eid = eid.reshape(T * k)
        slot_gate = gate.reshape(T * k).astype(x.dtype)
        return xt, slot_tok, slot_eid, slot_gate, T, D

    def local_a2a(x, eid, gate, w1, w3, w2):
        xt, slot_tok, slot_eid, slot_gate, T, D = _flat(x, eid, gate)
        S = T * k
        dst = slot_eid // E_loc
        C_send = max(1, int(round(S / ntp * capacity_factor)))

        order = jnp.argsort(dst)
        sdst = dst[order]
        first = jnp.searchsorted(sdst, jnp.arange(ntp, dtype=sdst.dtype))
        rank = jnp.arange(S) - first[sdst]
        keep = rank < C_send
        addr = jnp.where(keep, sdst * C_send + rank, ntp * C_send)

        send_x = jnp.zeros((ntp * C_send + 1, D), x.dtype)
        send_e = jnp.full((ntp * C_send + 1,), -1, jnp.int32)
        send_src = jnp.zeros((ntp * C_send + 1,), jnp.int32)
        send_x = send_x.at[addr].set(xt[slot_tok[order]])[: ntp * C_send]
        send_e = send_e.at[addr].set(slot_eid[order] % E_loc)[: ntp * C_send]
        send_src = send_src.at[addr].set(order)[: ntp * C_send]

        recv_x = jax.lax.all_to_all(send_x.reshape(ntp, C_send, D), tp, 0, 0
                                    ).reshape(ntp * C_send, D)
        recv_e = jax.lax.all_to_all(send_e.reshape(ntp, C_send), tp, 0, 0
                                    ).reshape(ntp * C_send)

        R = ntp * C_send
        C_exp = max(1, int(round(R / max(E_loc, 1) * capacity_factor)))
        back = _group_and_ffn(recv_x, recv_e, E_loc, C_exp, w1, w3, w2)

        ret = jax.lax.all_to_all(back.reshape(ntp, C_send, D), tp, 0, 0
                                 ).reshape(ntp * C_send, D)

        # ret[a] is the processed token for the slot placed at address a
        out = jnp.zeros((T, D), x.dtype)
        valid = (send_e >= 0).astype(x.dtype)
        contrib = ret * (slot_gate[send_src] * valid)[:, None]
        out = out.at[slot_tok[send_src]].add(contrib)
        return out.reshape(x.shape)

    def local_rep(x, eid, gate, w1, w3, w2):
        # tokens replicated over tp: compute only my experts, psum combine
        xt, slot_tok, slot_eid, slot_gate, T, D = _flat(x, eid, gate)
        my = jax.lax.axis_index(tp)
        e_loc = slot_eid - my * E_loc
        mine = (e_loc >= 0) & (e_loc < E_loc)
        recv_e = jnp.where(mine, e_loc, -1)
        C_exp = max(1, int(round(T * k / max(E_loc, 1) * capacity_factor)))
        back = _group_and_ffn(xt[slot_tok], recv_e, E_loc, C_exp, w1, w3, w2)
        out = jnp.zeros((T, D), x.dtype)
        out = out.at[slot_tok].add(back * slot_gate[:, None])
        return jax.lax.psum(out.reshape(x.shape), tp)

    seq_axis = tp if shard_seq else None
    spec_x = P(dp, seq_axis, None)
    spec_w = P(tp, None, None)
    fn = jax.shard_map(
        local_a2a if shard_seq else local_rep, mesh=mesh,
        in_specs=(spec_x, spec_x, spec_x, spec_w, spec_w, spec_w),
        out_specs=spec_x)
    return fn(x, eid, gate, p["w1"], p["w3"], p["w2"])


def moe_ffn_ep2d(p, x, eid, gate, cfg: ArchConfig, mesh, mesh_axes,
                 capacity_factor: float = 2.0):
    """EP-over-data (beyond-paper optimization, §Perf): experts sharded over
    the *data* axes, replicated over tp.

    The FSDP weight all-gathers that dominate 1D-EP prefill (measured 73% of
    collective bytes at arctic-480b) disappear entirely: per-chip expert
    weights are E/|dp| experts (arctic: 8 → 1.6 GiB bf16, resident), and the
    only MoE collective is a token all-to-all over the data axes whose
    payload is activations (hundreds of MB), not weights (tens of GB).
    Tokens on mesh cell (d, m) route to expert-owner row r = e // E_per_row
    at cell (r, m); the gate-weighted combine returns over the same path.
    """
    tp = mesh_axes["tp"]
    dp = mesh_axes["dp"]
    ndp = mesh_axes["ndp"]
    E = cfg.n_experts
    assert E % ndp == 0, "experts must divide the data axes for 2D EP"
    E_loc = E // ndp
    k = cfg.moe_top_k

    def local(x, eid, gate, w1, w3, w2):
        b, s_loc, D = x.shape
        T = b * s_loc
        xt = x.reshape(T, D)
        slot_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        slot_eid = eid.reshape(T * k)
        slot_gate = gate.reshape(T * k).astype(x.dtype)
        S = T * k
        dst = slot_eid // E_loc                        # destination dp row
        C_send = max(1, int(round(S / ndp * capacity_factor)))

        order = jnp.argsort(dst)
        sdst = dst[order]
        first = jnp.searchsorted(sdst, jnp.arange(ndp, dtype=sdst.dtype))
        rank = jnp.arange(S) - first[sdst]
        keep = rank < C_send
        addr = jnp.where(keep, sdst * C_send + rank, ndp * C_send)

        send_x = jnp.zeros((ndp * C_send + 1, D), x.dtype)
        send_e = jnp.full((ndp * C_send + 1,), -1, jnp.int32)
        send_src = jnp.zeros((ndp * C_send + 1,), jnp.int32)
        send_x = send_x.at[addr].set(xt[slot_tok[order]])[: ndp * C_send]
        send_e = send_e.at[addr].set(slot_eid[order] % E_loc)[: ndp * C_send]
        send_src = send_src.at[addr].set(order)[: ndp * C_send]

        recv_x = jax.lax.all_to_all(send_x.reshape(ndp, C_send, D), dp, 0, 0
                                    ).reshape(ndp * C_send, D)
        recv_e = jax.lax.all_to_all(send_e.reshape(ndp, C_send), dp, 0, 0
                                    ).reshape(ndp * C_send)

        R = ndp * C_send
        C_exp = max(1, int(round(R / max(E_loc, 1) * capacity_factor)))
        back = _group_and_ffn(recv_x, recv_e, E_loc, C_exp, w1, w3, w2)

        ret = jax.lax.all_to_all(back.reshape(ndp, C_send, D), dp, 0, 0
                                 ).reshape(ndp * C_send, D)
        out = jnp.zeros((T, D), x.dtype)
        valid = (send_e >= 0).astype(x.dtype)
        contrib = ret * (slot_gate[send_src] * valid)[:, None]
        out = out.at[slot_tok[send_src]].add(contrib)
        return out.reshape(x.shape)

    spec_x = P(dp, tp, None)
    spec_w = P(dp, None, None)   # experts over dp, replicated over tp
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_x, spec_x, spec_x, spec_w, spec_w, spec_w),
        out_specs=spec_x)
    return fn(x, eid, gate, p["w1"], p["w3"], p["w2"])
