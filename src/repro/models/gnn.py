"""GNN model zoo: PNA, EGNN, MeshGraphNet, SchNet.

All four are message-passing networks built on the same primitive the query
engine uses: gather-by-src → edge compute → segment-reduce-by-dst
(`jax.ops.segment_sum`).  JAX has no
sparse message-passing op — this scatter substrate IS part of the system.

Graphs are structure-of-arrays ``GraphBatch``; batched small graphs
(molecule shape) are flattened into one disjoint graph with a node→graph map.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .layers import layer_norm, mlp_apply, mlp_params


@dataclasses.dataclass
class GraphBatch:
    node_feat: jnp.ndarray          # [N, F]
    edge_src: jnp.ndarray           # [E]
    edge_dst: jnp.ndarray           # [E]
    coords: Optional[jnp.ndarray] = None     # [N, 3] (EGNN / SchNet / MGN)
    edge_feat: Optional[jnp.ndarray] = None  # [E, Fe]
    graph_of: Optional[jnp.ndarray] = None   # [N] graph id (batched-small)
    n_graphs: int = 1
    targets: Optional[jnp.ndarray] = None


def _agg(values, dst, n, op="sum"):
    if op == "sum":
        return jax.ops.segment_sum(values, dst, num_segments=n)
    if op == "mean":
        s = jax.ops.segment_sum(values, dst, num_segments=n)
        c = jax.ops.segment_sum(jnp.ones((values.shape[0], 1), values.dtype), dst,
                                num_segments=n)
        return s / jnp.maximum(c, 1.0)
    if op == "max":
        out = jax.ops.segment_max(values, dst, num_segments=n)
        return jnp.where(jnp.isfinite(out), out, 0.0)   # empty segments → 0
    if op == "min":
        out = jax.ops.segment_min(values, dst, num_segments=n)
        return jnp.where(jnp.isfinite(out), out, 0.0)
    raise ValueError(op)


# ====================================================================== PNA
@dataclasses.dataclass(frozen=True)
class PNACfg:
    name: str = "pna"
    n_layers: int = 4
    d_hidden: int = 75
    aggregators: Sequence[str] = ("mean", "max", "min", "std")
    scalers: Sequence[str] = ("identity", "amplification", "attenuation")
    out_dim: int = 1


def pna_init(cfg: PNACfg, key, in_dim: int) -> Dict:
    ks = jax.random.split(key, cfg.n_layers + 2)
    n_in = len(cfg.aggregators) * len(cfg.scalers) * cfg.d_hidden + cfg.d_hidden
    return dict(
        encoder=mlp_params(ks[0], [in_dim, cfg.d_hidden]),
        layers=[
            dict(
                pre=mlp_params(ks[i + 1], [2 * cfg.d_hidden, cfg.d_hidden]),
                post=mlp_params(ks[i + 1], [n_in, cfg.d_hidden, cfg.d_hidden]),
            )
            for i in range(cfg.n_layers)
        ],
        decoder=mlp_params(ks[-1], [cfg.d_hidden, cfg.d_hidden, cfg.out_dim]),
    )


def pna_apply(cfg: PNACfg, params, g: GraphBatch) -> jnp.ndarray:
    n = g.node_feat.shape[0]
    h = mlp_apply(params["encoder"], g.node_feat, final_act=True)
    deg = jax.ops.segment_sum(jnp.ones_like(g.edge_dst, dtype=jnp.float32),
                              g.edge_dst, num_segments=n)
    log_deg = jnp.log1p(deg)[:, None]
    mean_log_deg = jnp.maximum(log_deg.mean(), 1e-6)
    for lp in params["layers"]:
        msg_in = jnp.concatenate([h[g.edge_src], h[g.edge_dst]], axis=-1)
        msg = mlp_apply(lp["pre"], msg_in, final_act=True)
        aggs = []
        mean = _agg(msg, g.edge_dst, n, "mean")
        for a in cfg.aggregators:
            if a == "std":
                sq = _agg(msg * msg, g.edge_dst, n, "mean")
                aggs.append(jnp.sqrt(jnp.maximum(sq - mean * mean, 1e-8)))
            elif a == "mean":
                aggs.append(mean)
            else:
                aggs.append(_agg(msg, g.edge_dst, n, a))
        scaled = []
        for s in cfg.scalers:
            for a in aggs:
                if s == "identity":
                    scaled.append(a)
                elif s == "amplification":
                    scaled.append(a * (log_deg / mean_log_deg))
                else:  # attenuation (degree-0 nodes get factor 1)
                    att = jnp.where(deg[:, None] > 0,
                                    mean_log_deg / jnp.maximum(log_deg, 1e-6), 1.0)
                    scaled.append(a * att)
        h = h + mlp_apply(lp["post"], jnp.concatenate(scaled + [h], axis=-1),
                          final_act=True)
    return mlp_apply(params["decoder"], h)


# ===================================================================== EGNN
@dataclasses.dataclass(frozen=True)
class EGNNCfg:
    name: str = "egnn"
    n_layers: int = 4
    d_hidden: int = 64
    out_dim: int = 1


def egnn_init(cfg: EGNNCfg, key, in_dim: int) -> Dict:
    ks = jax.random.split(key, cfg.n_layers * 3 + 2)
    d = cfg.d_hidden
    return dict(
        encoder=mlp_params(ks[0], [in_dim, d]),
        layers=[
            dict(
                phi_e=mlp_params(ks[3 * i + 1], [2 * d + 1, d, d]),
                phi_x=mlp_params(ks[3 * i + 2], [d, d, 1]),
                phi_h=mlp_params(ks[3 * i + 3], [2 * d, d, d]),
            )
            for i in range(cfg.n_layers)
        ],
        decoder=mlp_params(ks[-1], [d, d, cfg.out_dim]),
    )


def egnn_apply(cfg: EGNNCfg, params, g: GraphBatch):
    """E(n)-equivariant layers: scalar messages from invariant distances,
    coordinate updates along relative vectors."""
    n = g.node_feat.shape[0]
    h = mlp_apply(params["encoder"], g.node_feat, final_act=True)
    x = g.coords
    src, dst = g.edge_src, g.edge_dst
    for lp in params["layers"]:
        rel = x[src] - x[dst]
        d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
        m = mlp_apply(lp["phi_e"], jnp.concatenate([h[src], h[dst], d2], -1),
                      final_act=True)
        coef = jnp.tanh(mlp_apply(lp["phi_x"], m))          # bounded for stability
        dx = _agg(rel * coef, dst, n, "mean")
        x = x + dx
        magg = _agg(m, dst, n, "sum")
        h = h + mlp_apply(lp["phi_h"], jnp.concatenate([h, magg], -1), final_act=True)
    return mlp_apply(params["decoder"], h), x


# ============================================================ MeshGraphNet
@dataclasses.dataclass(frozen=True)
class MGNCfg:
    name: str = "meshgraphnet"
    n_layers: int = 15
    d_hidden: int = 128
    mlp_layers: int = 2
    out_dim: int = 3


def _mgn_mlp(key, sizes):
    return mlp_params(key, sizes)


def mgn_init(cfg: MGNCfg, key, in_dim: int, edge_in: int = 4) -> Dict:
    d = cfg.d_hidden
    hidden = [d] * cfg.mlp_layers
    ks = jax.random.split(key, 2 * cfg.n_layers + 3)
    return dict(
        node_enc=_mgn_mlp(ks[0], [in_dim] + hidden),
        edge_enc=_mgn_mlp(ks[1], [edge_in] + hidden),
        layers=[
            dict(
                edge_mlp=_mgn_mlp(ks[2 + 2 * i], [3 * d] + hidden),
                node_mlp=_mgn_mlp(ks[3 + 2 * i], [2 * d] + hidden),
                ln_e=dict(w=jnp.ones(d), b=jnp.zeros(d)),
                ln_n=dict(w=jnp.ones(d), b=jnp.zeros(d)),
            )
            for i in range(cfg.n_layers)
        ],
        decoder=_mgn_mlp(ks[-1], hidden + [cfg.out_dim]),
    )


def mgn_apply(cfg: MGNCfg, params, g: GraphBatch):
    n = g.node_feat.shape[0]
    src, dst = g.edge_src, g.edge_dst
    h = mlp_apply(params["node_enc"], g.node_feat, final_act=True)
    if g.edge_feat is not None:
        e = mlp_apply(params["edge_enc"], g.edge_feat, final_act=True)
    else:
        rel = g.coords[src] - g.coords[dst]
        ef = jnp.concatenate([rel, jnp.linalg.norm(rel, axis=-1, keepdims=True)], -1)
        e = mlp_apply(params["edge_enc"], ef, final_act=True)
    for lp in params["layers"]:
        e_new = mlp_apply(lp["edge_mlp"], jnp.concatenate([e, h[src], h[dst]], -1),
                          final_act=True)
        e = e + layer_norm(e_new, lp["ln_e"]["w"], lp["ln_e"]["b"])
        agg = _agg(e, dst, n, "sum")
        h_new = mlp_apply(lp["node_mlp"], jnp.concatenate([h, agg], -1),
                          final_act=True)
        h = h + layer_norm(h_new, lp["ln_n"]["w"], lp["ln_n"]["b"])
    return mlp_apply(params["decoder"], h)


# ==================================================================== SchNet
@dataclasses.dataclass(frozen=True)
class SchNetCfg:
    name: str = "schnet"
    n_interactions: int = 3
    d_hidden: int = 64
    n_rbf: int = 300
    cutoff: float = 10.0
    out_dim: int = 1


def schnet_init(cfg: SchNetCfg, key, in_dim: int) -> Dict:
    d = cfg.d_hidden
    ks = jax.random.split(key, cfg.n_interactions * 3 + 2)
    return dict(
        encoder=mlp_params(ks[0], [in_dim, d]),
        interactions=[
            dict(
                filter_net=mlp_params(ks[3 * i + 1], [cfg.n_rbf, d, d]),
                in_proj=mlp_params(ks[3 * i + 2], [d, d]),
                out_proj=mlp_params(ks[3 * i + 3], [d, d, d]),
            )
            for i in range(cfg.n_interactions)
        ],
        decoder=mlp_params(ks[-1], [d, d, cfg.out_dim]),
    )


def _rbf_expand(dist, n_rbf, cutoff):
    centers = jnp.linspace(0.0, cutoff, n_rbf)
    gamma = n_rbf / cutoff
    return jnp.exp(-gamma * (dist[:, None] - centers[None, :]) ** 2)


def _cosine_cutoff(dist, cutoff):
    c = 0.5 * (jnp.cos(jnp.pi * dist / cutoff) + 1.0)
    return jnp.where(dist < cutoff, c, 0.0)


def schnet_apply(cfg: SchNetCfg, params, g: GraphBatch):
    """Continuous-filter convolutions: W(r_ij) ⊙ h_j summed over neighbors."""
    n = g.node_feat.shape[0]
    src, dst = g.edge_src, g.edge_dst
    h = mlp_apply(params["encoder"], g.node_feat)
    dist = jnp.linalg.norm(g.coords[src] - g.coords[dst] + 1e-9, axis=-1)
    rbf = _rbf_expand(dist, cfg.n_rbf, cfg.cutoff)
    cut = _cosine_cutoff(dist, cfg.cutoff)[:, None]
    for lp in params["interactions"]:
        W = mlp_apply(lp["filter_net"], rbf, act=jax.nn.softplus, final_act=True) * cut
        hj = mlp_apply(lp["in_proj"], h)[src]
        msg = _agg(hj * W, dst, n, "sum")
        h = h + mlp_apply(lp["out_proj"], msg, act=jax.nn.softplus)
    out = mlp_apply(params["decoder"], h)
    if g.graph_of is not None:
        return jax.ops.segment_sum(out, g.graph_of, num_segments=g.n_graphs)
    return out


# ------------------------------------------------------------- loss wrappers
def gnn_loss(arch: str, cfg, params, g: GraphBatch) -> jnp.ndarray:
    if arch == "pna":
        pred = pna_apply(cfg, params, g)
    elif arch == "egnn":
        pred, _ = egnn_apply(cfg, params, g)
    elif arch == "meshgraphnet":
        pred = mgn_apply(cfg, params, g)
    elif arch == "schnet":
        pred = schnet_apply(cfg, params, g)
    else:
        raise ValueError(arch)
    tgt = g.targets
    if tgt is None or tgt.shape[0] != pred.shape[0]:
        tgt = jnp.zeros_like(pred)   # graph-level heads w/ node targets: MSE to 0
    elif tgt.shape != pred.shape:
        tgt = jnp.broadcast_to(tgt.reshape(tgt.shape[0], -1)[:, : pred.shape[-1]],
                               pred.shape)
    return jnp.mean((pred.astype(jnp.float32) - tgt.astype(jnp.float32)) ** 2)


INIT = {"pna": pna_init, "egnn": egnn_init, "meshgraphnet": mgn_init,
        "schnet": schnet_init}
