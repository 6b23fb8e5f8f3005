"""Unified architecture API: one bundle per (arch x shape) cell.

``build(arch, shape_name, smoke=...)`` returns an ``ArchBundle`` exposing:

* ``init(key)``            -> state pytrees (params [+ opt state] or graph)
* ``input_specs()``        -> dict[name, ShapeDtypeStruct] for the step inputs
* ``step``                 -> the function to jit (train_step / serve_step)
* ``state_specs()/in_specs()/out_specs()`` -> PartitionSpecs for pjit
* ``model_flops()``        -> MODEL_FLOPS (6ND / 6 N_active D or family analogue)

This is the single surface consumed by launch/dryrun.py, launch/train.py,
launch/serve.py, the smoke tests and the benchmarks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (
    GNNConfig,
    ProbeSimConfig,
    RecsysConfig,
    ShapeSpec,
    TransformerConfig,
    get_config,
    shapes_for,
)
from repro.graph.sampler import block_shapes
from repro.models.common import resolve_axis
from repro.training.optimizer import AdamW, warmup_cosine_schedule

Array = jax.Array
SDS = jax.ShapeDtypeStruct


@dataclass
class ArchBundle:
    arch: str
    cfg: Any
    shape: ShapeSpec
    step: Callable  # fn(*state, **inputs) per family convention
    init: Callable  # fn(key) -> state tuple
    input_specs: Callable  # fn() -> dict[str, SDS]
    state_specs: Callable  # fn(state) -> specs pytree (same struct as state)
    input_shardings: Callable  # fn() -> dict[str, PartitionSpec]
    model_flops: Callable  # fn() -> float
    notes: str = ""


def _dp():
    return resolve_axis("dp")


def _tp():
    return resolve_axis("tp")


def _all_axes():
    axes = tuple(a for a in (_dp() if isinstance(_dp(), tuple) else (_dp(),))
                 if a) + ((_tp(),) if _tp() else ())
    flat = []
    for a in axes:
        if isinstance(a, tuple):
            flat.extend(a)
        elif a:
            flat.append(a)
    return tuple(flat) or None


def _extent(axes) -> int:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or axes is None:
        return 1
    out = 1
    for a in axes if isinstance(axes, tuple) else (axes,):
        out *= mesh.shape[a]
    return out


def _best_axes(dim: int, candidates=None):
    """Largest sharding (by extent) from a candidate list that divides dim.

    jit argument shardings REQUIRE even divisibility; this picks the widest
    legal layout and falls back to replication."""
    if candidates is None:
        candidates = [_all_axes(), _dp(), _tp(), None]
    best, best_e = None, 1
    for c in candidates:
        e = _extent(c)
        if c is not None and dim % e == 0 and e > best_e:
            best, best_e = c, e
    return best


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _make_optimizer(cfg) -> AdamW:
    state_dtype = jnp.bfloat16 if getattr(cfg, "param_dtype", "") == "bfloat16" else jnp.float32
    return AdamW(
        schedule=warmup_cosine_schedule(3e-4, 100, 10_000),
        state_dtype=state_dtype,
    )


def _opt_specs(param_specs):
    return dict(mu=param_specs, nu=param_specs, count=P())


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------


def _lm_bundle(arch: str, cfg: TransformerConfig, shape: ShapeSpec,
               use_kernel: bool = False) -> ArchBundle:
    from repro.models.transformer import model as M

    B = shape.dims["global_batch"]
    S = shape.dims["seq_len"]
    opt = _make_optimizer(cfg)

    def flops():
        if shape.kind == "train":
            return 6.0 * cfg.params_active * B * S
        if shape.kind == "prefill":
            return 2.0 * cfg.params_active * B * S
        # decode: one token per sequence + attention over the cache
        attn = 4.0 * B * S * cfg.n_heads * cfg.d_head
        return 2.0 * cfg.params_active * B + attn

    if shape.kind == "train":

        def step(params, opt_state, batch):
            from repro.training.step import make_train_step

            loss_fn = partial(M.lm_loss, cfg=cfg, use_kernel=use_kernel)
            ts = make_train_step(lambda p, b: loss_fn(p, b), opt,
                                 microbatches=getattr(cfg, "microbatches", 1))
            return ts(params, opt_state, batch)

        def init(key):
            params = M.init_lm(key, cfg)
            return (params, opt.init(params))

        def input_specs():
            return dict(
                batch=dict(
                    tokens=SDS((B, S), jnp.int32),
                    targets=SDS((B, S), jnp.int32),
                )
            )

        def input_shardings():
            ba = _best_axes(B, [_dp(), None])
            return dict(batch=dict(tokens=P(ba, None), targets=P(ba, None)))

        def state_specs(state):
            ps = M.param_specs(state[0], cfg)
            return (ps, _opt_specs(ps))

    elif shape.kind == "prefill":

        def step(params, batch):
            logits, _ = M.lm_forward(
                params, batch["tokens"], cfg, use_kernel=use_kernel,
                seq_shard=True, last_only=True,
            )
            return logits[:, 0]

        def init(key):
            return (M.init_lm(key, cfg),)

        def input_specs():
            return dict(batch=dict(tokens=SDS((B, S), jnp.int32)))

        def input_shardings():
            return dict(batch=dict(tokens=P(_best_axes(B, [_dp(), None]), None)))

        def state_specs(state):
            return (M.param_specs(state[0], cfg),)

    else:  # decode

        def step(params, caches, batch):
            caches, logits = M.lm_decode_step(
                params, caches, batch["tokens"], batch["positions"], cfg
            )
            return caches, logits

        def init(key):
            params = M.init_lm(key, cfg)
            caches = M.init_cache(cfg, B, S)
            return (params, caches)

        def input_specs():
            return dict(
                batch=dict(
                    tokens=SDS((B,), jnp.int32),
                    positions=SDS((B,), jnp.int32),
                )
            )

        def input_shardings():
            ba = _best_axes(B, [_dp(), None])
            return dict(batch=dict(tokens=P(ba), positions=P(ba)))

        def state_specs(state):
            return (
                M.param_specs(state[0], cfg),
                M.cache_specs(state[1], cfg),
            )

    return ArchBundle(
        arch=arch, cfg=cfg, shape=shape, step=step, init=init,
        input_specs=input_specs, state_specs=state_specs,
        input_shardings=input_shardings, model_flops=flops,
    )


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------


def _gnn_batch_shapes(cfg: GNNConfig, shape: ShapeSpec) -> dict:
    d = shape.dims
    if shape.kind == "full_graph":
        N, E, df = d["n_nodes"], d["n_edges"], d["d_feat"]
        G = 1
    elif shape.kind == "minibatch":
        bs = block_shapes(d["batch_nodes"], tuple(d["fanout"]))
        N, E, df = bs["table"], sum(bs["edges"]), d["d_feat"]
        G = 1
    else:  # batched_graphs (molecule)
        N = d["n_nodes"] * d["batch"]
        E = d["n_edges"] * d["batch"]
        df = d["d_feat"]
        G = d["batch"]
    # pad to 8192 so jit argument shardings divide every mesh extent
    # (padding rows/edges are sentinel-masked by the layers)
    if N > 8192:
        N = _pad_to(N, 8192)
    if E > 8192:
        E = _pad_to(E, 8192)
    return dict(N=N, E=E, df=df, G=G)


def _gnn_bundle(arch: str, cfg: GNNConfig, shape: ShapeSpec) -> ArchBundle:
    from repro.models.gnn.model import gnn_loss, init_gnn
    from repro.training.step import make_train_step

    s = _gnn_batch_shapes(cfg, shape)
    N, E, df, G = s["N"], s["E"], s["df"], s["G"]
    opt = _make_optimizer(cfg)
    is_nequip = cfg.conv == "nequip"
    batched = shape.kind == "batched_graphs"

    def loss_fn(params, batch):
        return gnn_loss(params, batch, cfg, n_graphs=G)

    def step(params, opt_state, batch):
        ts = make_train_step(loss_fn, opt)
        return ts(params, opt_state, batch)

    def init(key):
        params = init_gnn(key, cfg, df)
        return (params, opt.init(params))

    def input_specs():
        b = dict(
            feats=SDS((N, df), jnp.float32),
            src=SDS((E,), jnp.int32),
            dst=SDS((E,), jnp.int32),
            mask=SDS((E,), jnp.bool_),
        )
        if is_nequip:
            b["pos"] = SDS((N, 3), jnp.float32)
            b["energy"] = SDS((G,), jnp.float32)
            if batched:
                b["graph_ids"] = SDS((N,), jnp.int32)
        else:
            if batched:
                b["graph_ids"] = SDS((N,), jnp.int32)
                b["labels"] = SDS((G,), jnp.int32)
                b["label_mask"] = SDS((G,), jnp.float32)
            else:
                b["labels"] = SDS((N,), jnp.int32)
                b["label_mask"] = SDS((N,), jnp.float32)
        return dict(batch=b)

    def input_shardings():
        if getattr(cfg, "node_shard", "all") == "model":
            na = _best_axes(N, [_tp(), None])
            ea = _best_axes(E, [_tp(), None])
        else:
            na = _best_axes(N)
            ea = _best_axes(E)
        ga = _best_axes(G, [_dp(), None])
        b = dict(
            feats=P(na, None),
            src=P(ea),
            dst=P(ea),
            mask=P(ea),
        )
        if is_nequip:
            b["pos"] = P(na, None)
            b["energy"] = P(ga)
            if batched:
                b["graph_ids"] = P(na)
        else:
            if batched:
                b["graph_ids"] = P(na)
                b["labels"] = P(ga)
                b["label_mask"] = P(ga)
            else:
                b["labels"] = P(na)
                b["label_mask"] = P(na)
        return dict(batch=b)

    def state_specs(state):
        ps = jax.tree_util.tree_map(lambda p: P(*([None] * p.ndim)), state[0])
        return (ps, _opt_specs(ps))

    def flops():
        d = cfg.d_hidden
        # messages ~ 2 E d, transforms ~ 2 N d^2 per layer (x3 for train)
        per_layer = 2.0 * E * d + 2.0 * N * d * d
        if is_nequip:
            per_layer = 16 * 2.0 * E * d * 9 + 2.0 * N * d * d * 9
        return 3.0 * cfg.n_layers * per_layer

    return ArchBundle(
        arch=arch, cfg=cfg, shape=shape, step=step, init=init,
        input_specs=input_specs, state_specs=state_specs,
        input_shardings=input_shardings, model_flops=flops,
    )


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------


def _recsys_bundle(arch: str, cfg: RecsysConfig, shape: ShapeSpec) -> ArchBundle:
    from repro.models.recsys.widedeep import (
        init_widedeep,
        retrieval_scores,
        widedeep_forward,
        widedeep_loss,
    )
    from repro.training.step import make_train_step

    d = shape.dims
    B = d.get("batch", 1)
    opt = _make_optimizer(cfg)

    def param_sharding(params):
        def spec(path, leaf):
            key = getattr(path[-1], "key", None)
            if key == "embed":  # [F, V, D] -> vocab rows over model
                return P(None, _tp(), None)
            if key == "wide":  # [F, V]
                return P(None, _tp())
            if key == "w" and leaf.ndim == 2:
                return P(None, _tp()) if leaf.shape[1] >= 256 else P(None, None)
            return P(*([None] * leaf.ndim))

        return jax.tree_util.tree_map_with_path(spec, params)

    if shape.kind == "train":

        def step(params, opt_state, batch):
            ts = make_train_step(lambda p, b: widedeep_loss(p, b, cfg), opt)
            return ts(params, opt_state, batch)

        def init(key):
            params = init_widedeep(key, cfg)
            return (params, opt.init(params))

        def input_specs():
            return dict(
                batch=dict(
                    sparse_ids=SDS((B, cfg.n_sparse), jnp.int32),
                    dense=SDS((B, cfg.n_dense), jnp.float32),
                    labels=SDS((B,), jnp.int32),
                )
            )

        def input_shardings():
            ba = _best_axes(B, [_dp(), None])
            return dict(batch=dict(
                sparse_ids=P(ba, None), dense=P(ba, None), labels=P(ba),
            ))

        def state_specs(state):
            ps = param_sharding(state[0])
            return (ps, _opt_specs(ps))

    elif shape.kind == "serve":

        def step(params, batch):
            return widedeep_forward(params, batch, cfg)

        def init(key):
            return (init_widedeep(key, cfg),)

        def input_specs():
            return dict(
                batch=dict(
                    sparse_ids=SDS((B, cfg.n_sparse), jnp.int32),
                    dense=SDS((B, cfg.n_dense), jnp.float32),
                )
            )

        def input_shardings():
            ba = _best_axes(B, [_dp(), None])
            return dict(batch=dict(sparse_ids=P(ba, None), dense=P(ba, None)))

        def state_specs(state):
            return (param_sharding(state[0]),)

    else:  # retrieval

        nc = _pad_to(d["n_candidates"], 8192) if d["n_candidates"] > 8192 else d["n_candidates"]

        def step(params, batch):
            scores = retrieval_scores(params, batch, cfg)
            return jax.lax.top_k(scores, 100)

        def init(key):
            return (init_widedeep(key, cfg),)

        def input_specs():
            return dict(
                batch=dict(
                    sparse_ids=SDS((B, cfg.n_sparse), jnp.int32),
                    dense=SDS((B, cfg.n_dense), jnp.float32),
                    cand_ids=SDS((nc,), jnp.int32),
                )
            )

        def input_shardings():
            return dict(batch=dict(
                sparse_ids=P(None, None), dense=P(None, None),
                cand_ids=P(_best_axes(nc)),
            ))

        def state_specs(state):
            return (param_sharding(state[0]),)

    def flops():
        mlp_flops = 0
        d_in = cfg.n_sparse * cfg.embed_dim + cfg.n_dense
        for w in cfg.mlp:
            mlp_flops += 2 * d_in * w
            d_in = w
        mult = 3.0 if shape.kind == "train" else 1.0
        per_ex = mlp_flops + 2 * cfg.n_sparse * cfg.embed_dim
        total = mult * B * per_ex
        if shape.kind == "retrieval":
            total += 2.0 * d["n_candidates"] * cfg.embed_dim
        return total

    return ArchBundle(
        arch=arch, cfg=cfg, shape=shape, step=step, init=init,
        input_specs=input_specs, state_specs=state_specs,
        input_shardings=input_shardings, model_flops=flops,
    )


# ---------------------------------------------------------------------------
# ProbeSim family (the paper)
# ---------------------------------------------------------------------------


def _probesim_bundle(arch: str, cfg: ProbeSimConfig, shape: ShapeSpec) -> ArchBundle:
    from repro.core.distributed import (
        ShardedGraph,
        build_sharded_graph,
        graph_specs,
        make_serve_step,
    )
    from repro.core.params import make_params
    from repro.core.ring import (
        build_ring_graph,
        make_ring_serve_step,
        ring_graph_abstract,
        ring_graph_specs,
    )

    d = shape.dims
    Q = d["queries"]
    Bw = d["walk_chunk"]
    params = make_params(cfg.n, c=cfg.c, eps_a=cfg.eps_a, delta=cfg.delta)
    L = params.max_len
    n_pad_mult = 16 * 8
    m_pad_mult = 512 * 8  # divisible by all device counts x edge chunks
    ring = cfg.push_mode == "ring"
    fdt = jnp.bfloat16 if cfg.frontier_dtype == "bfloat16" else jnp.float32

    if ring:
        serve = make_ring_serve_step(cfg, queries=Q, walk_chunk=Bw,
                                     max_len=L, frontier_dtype=fdt)
    else:
        serve = make_serve_step(cfg, queries=Q, walk_chunk=Bw, max_len=L,
                                edge_chunks=8)

    def step(graph, batch):
        return serve(graph, batch["queries"], batch["key"])

    def init(key):
        # dry-run scale: build abstract graph (ShapeDtypeStructs); smoke
        # configs are small enough to build a real synthetic graph.
        shards = max(_extent(_tp()), 1)
        if cfg.n <= 100_000:
            from repro.graph.generators import powerlaw_graph

            src, dst, n = powerlaw_graph(cfg.n, cfg.m, seed=0)
            if ring:
                return (build_ring_graph(src, dst, n, shards=shards),)
            return (build_sharded_graph(src, dst, n, pad_nodes=n_pad_mult,
                                        pad_edges=m_pad_mult),)
        if ring:
            # bucket padding: expected m/S^2 per bucket, 1.5x skew slack
            # (production rebalances hub destinations across buckets)
            e_max = -(-cfg.m * 3 // (2 * shards * shards) // 8) * 8
            return (ring_graph_abstract(cfg.n, cfg.m, shards, e_max),)
        n_pad = -(-cfg.n // n_pad_mult) * n_pad_mult
        m_pad = -(-cfg.m // m_pad_mult) * m_pad_mult
        return (ShardedGraph(
            indptr=SDS((n_pad,), jnp.int32),
            in_deg=SDS((n_pad,), jnp.int32),
            indices=SDS((m_pad,), jnp.int32),
            src=SDS((m_pad,), jnp.int32),
            dst=SDS((m_pad,), jnp.int32),
            n=cfg.n, n_pad=n_pad, m=cfg.m, m_pad=m_pad,
        ),)

    def input_specs():
        return dict(batch=dict(
            queries=SDS((Q,), jnp.int32),
            key=SDS((2,), jnp.uint32),
        ))

    def input_shardings():
        return dict(batch=dict(queries=P(), key=P()))

    def state_specs(state):
        if ring:
            return (ring_graph_specs(state[0]),)
        return (graph_specs(state[0]),)

    def flops():
        # telescoped probe: (L-1) pushes x 2 flops/edge/column
        return 2.0 * cfg.m * Q * Bw * (L - 1)

    return ArchBundle(
        arch=arch, cfg=cfg, shape=shape, step=step, init=init,
        input_specs=input_specs, state_specs=state_specs,
        input_shardings=input_shardings, model_flops=flops,
        notes=f"n_r={params.n_r} walks/query; this step covers {Bw} of them",
    )


# ---------------------------------------------------------------------------


def build(arch: str, shape_name: str, *, smoke: bool = False,
          use_kernel: bool = False) -> ArchBundle:
    cfg = get_config(arch, smoke=smoke)
    shape = next(s for s in shapes_for(arch) if s.name == shape_name)
    if smoke:
        shape = _shrink_shape(cfg, shape)
    return build_with_cfg(arch, cfg, shape, use_kernel=use_kernel)


def build_with_cfg(arch: str, cfg, shape: ShapeSpec, *,
                   use_kernel: bool = False) -> ArchBundle:
    """Build a bundle for an explicit config (depth-extrapolation dry-runs)."""
    if cfg.family == "lm":
        return _lm_bundle(arch, cfg, shape, use_kernel=use_kernel)
    if cfg.family == "gnn":
        return _gnn_bundle(arch, cfg, shape)
    if cfg.family == "recsys":
        return _recsys_bundle(arch, cfg, shape)
    if cfg.family == "probesim":
        return _probesim_bundle(arch, cfg, shape)
    raise ValueError(cfg.family)


def _shrink_shape(cfg, shape: ShapeSpec) -> ShapeSpec:
    d = dict(shape.dims)
    if cfg.family == "lm":
        d.update(seq_len=min(d["seq_len"], 64), global_batch=min(d["global_batch"], 2))
    elif cfg.family == "gnn":
        if shape.kind == "full_graph":
            d.update(n_nodes=128, n_edges=512, d_feat=24)
        elif shape.kind == "minibatch":
            d.update(n_nodes=256, n_edges=2048, batch_nodes=8, fanout=(3, 2), d_feat=24)
        else:
            d.update(batch=4, n_nodes=10, n_edges=20, d_feat=8)
    elif cfg.family == "recsys":
        d.update(batch=min(d.get("batch", 1), 32))
        if "n_candidates" in d:
            d["n_candidates"] = 512
    elif cfg.family == "probesim":
        d.update(queries=2, walk_chunk=16)
    return ShapeSpec(shape.name, shape.kind, d)


def is_applicable(arch: str, shape_name: str) -> tuple[bool, str]:
    """Cell applicability (DESIGN.md §Arch-applicability / long_500k rule)."""
    cfg = get_config(arch)
    if cfg.family == "lm" and shape_name == "long_500k":
        return (
            False,
            "pure full-attention arch: long_500k skipped per assignment "
            "(decode itself is O(seq); reported as bonus cell)",
        )
    return True, ""
