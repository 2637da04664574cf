"""Mixture-of-Experts FFN — the paper's machinery as a first-class layer.

MoE dispatch *is* the MapReduce shuffle (DESIGN.md §5): tokens are items
keyed by expert id; experts are reducers with bounded I/O (capacity = the
paper's M); routing = the Shuffle step; combine = a Sum-semigroup funnel.

Two dispatch implementations:

  'einsum'  — GSPMD path.  Tokens are processed in groups (the paper's
     "nodes"); within a group each token's position-in-expert comes from an
     exclusive prefix-sum over the group (Lemma 2.2, here a cumsum over the
     group axis); dispatch/combine are one-hot einsum contractions.  Expert
     capacity enforces the I/O bound; over-capacity tokens fall through the
     residual (bounded-admission discipline of Thm 4.2 — they are *delayed*,
     i.e. handled by later layers, not crashed on).  XLA turns the
     group->expert contractions into all-to-all/all-gather collectives on
     the 'model' (EP) axis.

  'shuffle' — paper-faithful explicit path (shard_map).  Flattened
     (token, choice) pairs are routed with repro.core.distributed.
     shuffle_alltoall to the shard owning the expert; the receiving shard
     sorts arrivals by local expert (the §4.3 sample-sort step), runs the
     grouped FFN (the reducer f), and the inverse shuffle + weighted sum
     implements the funnel combine.  Used on real meshes and as the
     §Perf comparison point.

Router: softmax + top-k with renormalization, plus the standard
load-balancing auxiliary loss.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from . import sharding
from .layers import Params, cdtype, pdtype, _dense_init, residual_shard


class MoEOut(NamedTuple):
    y: jnp.ndarray
    aux_loss: jnp.ndarray
    dropped_frac: jnp.ndarray


def init_moe(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = cfg.n_experts
    ks = jax.random.split(key, 6)
    p = {
        "router": _dense_init(ks[0], (d, e), jnp.float32, scale=0.02),
        "w_gate": _dense_init(ks[1], (e, d, f), pdtype(cfg)),
        "w_up": _dense_init(ks[2], (e, d, f), pdtype(cfg)),
        "w_down": _dense_init(ks[3], (e, f, d), pdtype(cfg)),
    }
    if cfg.shared_expert:
        p["shared"] = {
            "w_gate": _dense_init(ks[4], (d, f), pdtype(cfg)),
            "w_up": _dense_init(ks[5], (d, f), pdtype(cfg)),
            "w_down": _dense_init(jax.random.fold_in(key, 7), (f, d),
                                  pdtype(cfg)),
        }
    return p


def _router(p: Params, cfg: ArchConfig, x: jnp.ndarray):
    """x: (..., d) -> (top-k ids, renormalized weights, aux loss)."""
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # load-balancing loss: E * sum_e f_e * p_e   (Switch/GShard)
    e = cfg.n_experts
    f_e = jnp.mean(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=tuple(
        range(ids.ndim - 1)))                    # (k, e) mean over tokens
    f_e = jnp.sum(f_e, axis=0)
    p_e = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = e * jnp.sum(f_e * p_e) / cfg.top_k
    return ids, w.astype(cdtype(cfg)), aux


def _expert_ffn(p: Params, cfg: ArchConfig, xe: jnp.ndarray) -> jnp.ndarray:
    """xe: (..., e, c, d) grouped per expert -> same shape output."""
    dt = cdtype(cfg)
    gate = jnp.einsum("...ecd,edf->...ecf", xe, p["w_gate"].astype(dt))
    up = jnp.einsum("...ecd,edf->...ecf", xe, p["w_up"].astype(dt))
    h = jax.nn.silu(gate) * up
    return jnp.einsum("...ecf,efd->...ecd", h, p["w_down"].astype(dt))


# ----------------------------------------------------------- einsum path
def _moe_einsum(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                group: int = 512) -> MoEOut:
    """x: (b, s, d).  Tokens processed in groups of ``group``; capacity per
    (group, expert) = ceil(group * k / E * cf)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tokens = x.reshape(-1, d)
    t_total = tokens.shape[0]
    group = min(group, t_total)
    if t_total % group != 0:
        pad = group - t_total % group
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
        t_total += pad
    g = t_total // group
    xg = tokens.reshape(g, group, d)
    xg = sharding.shard(xg, "batch", None, None)

    ids, w, aux = _router(p, cfg, xg)            # (g, t, k)
    cap = max(1, math.ceil(group * k / e * cfg.capacity_factor))

    # one-hot over experts per choice: (g, t, k, e)
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.int32)
    onehot = sharding.shard(onehot, "batch", None, None, "model")
    # position of each (token, choice) within its expert, per group:
    # exclusive prefix-sum over the flattened (t, k) axis — Lemma 2.2.
    flat = onehot.reshape(g, group * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat        # (g, t*k, e)
    pos = jnp.sum(pos * flat, axis=-1).reshape(g, group, k)
    keep = pos < cap
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))

    # dispatch mask (g, t, k, e, cap) contracted immediately (never stored):
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap,
                            dtype=cdtype(cfg))          # (g, t, k, cap)
    disp = jnp.einsum("gtke,gtkc->gtec", onehot.astype(cdtype(cfg)), pos_oh)
    disp = sharding.shard(disp, "batch", None, "model", None)
    xe = jnp.einsum("gtd,gtec->gecd", xg.astype(cdtype(cfg)), disp)
    xe = sharding.shard(xe, "batch", "model", None, None)

    ye = _expert_ffn(p, cfg, xe)                         # (g, e, cap, d)
    ye = sharding.shard(ye, "batch", "model", None, None)

    # weight each choice then combine back to tokens (Sum-semigroup funnel).
    # Contract k FIRST: (g,t,k,e) x (g,t,k,c) -> (g,t,e,c) is one dot_general
    # with batch dims (g,t) — the 5-D (g,t,k,e,c) tensor never materializes.
    oh_w = onehot.astype(cdtype(cfg)) * jnp.where(keep, w, 0).astype(
        cdtype(cfg))[..., None]
    comb = jnp.einsum("gtke,gtkc->gtec", oh_w, pos_oh)
    comb = sharding.shard(comb, "batch", None, "model", None)
    y = jnp.einsum("gecd,gtec->gtd", ye, comb)
    y = y.reshape(-1, d)[:b * s].reshape(b, s, d)
    y = residual_shard(cfg, y)

    if cfg.shared_expert:
        dt = cdtype(cfg)
        sp = p["shared"]
        h = jax.nn.silu(x @ sp["w_gate"].astype(dt)) * (x @ sp["w_up"].astype(dt))
        y = y + h @ sp["w_down"].astype(dt)
    return MoEOut(y=y, aux_loss=aux, dropped_frac=dropped)


# ---------------------------------------------------------- shuffle path
def _moe_shuffle(p: Params, cfg: ArchConfig, x: jnp.ndarray) -> MoEOut:
    """Paper-faithful dispatch: explicit all_to_all shuffle over the 'model'
    (EP) axis inside shard_map.  See module docstring."""
    mesh = sharding.get_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return _moe_einsum(p, cfg, x)
    from jax.sharding import PartitionSpec as P
    from ..core.distributed import shuffle_alltoall

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_ep = mesh.shape["model"]
    e_loc = e // n_ep
    batch_axes = sharding.batch_axes()

    ids, w, aux = _router(p, cfg, x)             # (b, s, k) on global view

    dt = cdtype(cfg)
    x_c = x.astype(dt)

    def local_moe(x_l, ids_l, w_l, wg, wu, wd):
        # shapes per shard: x_l (b_l, s, d); wg (e_loc, d_l, f)
        wg = lax.all_gather(wg, "data", axis=1, tiled=True)
        wu = lax.all_gather(wu, "data", axis=1, tiled=True)
        wd = lax.all_gather(wd, "data", axis=2, tiled=True)
        b_l = x_l.shape[0]
        t_l = b_l * s
        xt = x_l.reshape(t_l, d)
        idf = ids_l.reshape(t_l * k)
        wf = w_l.reshape(t_l * k)
        src_token = jnp.repeat(jnp.arange(t_l, dtype=jnp.int32), k)
        dest_shard = idf // e_loc
        cap = max(1, math.ceil(t_l * k / n_ep * cfg.capacity_factor))
        payload = {"x": xt[src_token], "eloc": idf % e_loc,
                   "slot": jnp.arange(t_l * k, dtype=jnp.int32)}
        out = shuffle_alltoall(dest_shard.astype(jnp.int32), payload,
                               "model", capacity=cap)
        recv_x = out.payload["x"].reshape(n_ep * cap, d)
        recv_e = jnp.where(out.valid.reshape(-1),
                           out.payload["eloc"].reshape(-1), e_loc)
        # group arrivals by local expert (the §4.3 sort step):
        c_loc = max(1, math.ceil(n_ep * cap / max(e_loc, 1)
                                 * cfg.capacity_factor))
        order = jnp.argsort(recv_e, stable=True)
        sorted_e = recv_e[order]
        first = jnp.searchsorted(sorted_e, sorted_e, side="left")
        rank_sorted = (jnp.arange(sorted_e.shape[0], dtype=jnp.int32)
                       - first.astype(jnp.int32))
        rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
        ok = (recv_e < e_loc) & (rank < c_loc)
        buf = jnp.zeros((e_loc, c_loc, d), dt).at[
            jnp.where(ok, recv_e, e_loc), jnp.where(ok, rank, 0)
        ].set(recv_x, mode="drop")
        # reducer f: grouped FFN
        gate = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
        up = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, wd.astype(dt))
        # back to arrival slots, then the inverse shuffle:
        y_rows = jnp.where(ok[:, None],
                           yb[jnp.where(ok, recv_e, 0),
                              jnp.where(ok, rank, 0)],
                           jnp.zeros((1, d), dt))
        y_send = (y_rows * ok[:, None]).reshape(n_ep, cap, d)
        back = lax.all_to_all(y_send, "model", split_axis=0, concat_axis=0,
                              tiled=True)                     # (n_ep, cap, d)
        back_slot = lax.all_to_all(
            out.payload["slot"].reshape(n_ep, cap), "model",
            split_axis=0, concat_axis=0, tiled=True).reshape(-1)
        back_ok = lax.all_to_all(
            (out.valid & ok.reshape(n_ep, cap)).astype(jnp.int32),
            "model", split_axis=0, concat_axis=0, tiled=True).reshape(-1)
        # funnel combine: weighted scatter-add back onto source tokens
        contrib = back.reshape(-1, d) * wf[back_slot][:, None].astype(dt)
        contrib = contrib * back_ok[:, None].astype(dt)
        y_tok = jnp.zeros((t_l, d), dt).at[src_token[back_slot]].add(contrib)
        drop = 1.0 - (lax.psum(jnp.sum(back_ok), "model")
                      / lax.psum(jnp.asarray(t_l * k, jnp.float32), "model"))
        return y_tok.reshape(b_l, s, d), drop

    bspec = P(batch_axes, None, None)
    y, dropped = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(bspec, bspec, bspec,
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x_c, ids, w, p["w_gate"], p["w_up"], p["w_down"])

    if cfg.shared_expert:
        sp = p["shared"]
        h = jax.nn.silu(x @ sp["w_gate"].astype(dt)) * (x @ sp["w_up"].astype(dt))
        y = y + h @ sp["w_down"].astype(dt)
    return MoEOut(y=y, aux_loss=aux, dropped_frac=dropped)


def apply_moe(p: Params, cfg: ArchConfig, x: jnp.ndarray) -> MoEOut:
    if cfg.moe_dispatch == "shuffle":
        return _moe_shuffle(p, cfg, x)
    return _moe_einsum(p, cfg, x)
