"""Cell builders: every (architecture x input-shape) pair becomes a
(step_fn, abstract-args, in_shardings) triple the dry-run lowers and
compiles on the production mesh.  Nothing here allocates device memory —
all inputs are ShapeDtypeStructs (jax.eval_shape for params).

Cell kinds:
  LM      : train_step (loss+grad+AdamW), prefill, decode (KV cache)
  GNN     : train_step (full-graph / sampled / batched)
  recsys  : train_step, serve, retrieval scoring
  BFS     : whole direction-optimizing search + single-level steps
            (the level steps feed the roofline; the whole search proves
            the multi-pod schedule compiles)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (BFSConfig, BFSShape, GNNConfig, GNNShape,
                                LMConfig, LMShape, RecsysConfig, RecsysShape,
                                get_config)
from repro.core import steps as bfs_steps
from repro.core.engine import plan_for_part
from repro.core.local_ops import get_local_ops
from repro.core.partition import make_partition
from repro.graph.dist_build import BuildSpec, build_relabel
from repro.graph.sampler import khop_sample
from repro.models import autoint as ai
from repro.models import gnn as gnn_mod
from repro.models import mace as mace_mod
from repro.models import transformer as tf
from repro.models.common import ShardCtx
from repro.optim.adamw import AdamW, AdamWState


class Cell(NamedTuple):
    fn: Callable
    args: Tuple[Any, ...]          # ShapeDtypeStructs / spec pytrees
    in_shardings: Any
    label: str
    meta: Dict[str, Any]           # model-flops accounting inputs


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def _round_up(x, q):
    return ((x + q - 1) // q) * q


def _dp(mesh):
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def _flat(mesh):
    return tuple(mesh.axis_names)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_param_shardings(cfg, mesh, ctx):
    specs = tf.param_specs(cfg, ctx)
    shapes = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return shapes, {k: NamedSharding(mesh, specs[k]) for k in shapes}


def _cache_spec(cfg, mesh, batch):
    dp = _dp(mesh)
    dp_ok = batch % int(np.prod([mesh.shape[a] for a in dp])) == 0 if dp else False
    bspec = dp if dp_ok else None
    tpn = mesh.shape.get("model", 1)
    if cfg.n_kv_heads % tpn == 0:
        return P(None, bspec, None, "model", None)
    return P(None, bspec, "model", None, None)


def build_lm_cell(cfg: LMConfig, shape: LMShape, mesh) -> Cell:
    if shape.kind != "train" and getattr(cfg, "fsdp", False):
        # FSDP is a training-memory optimization (optimizer moments);
        # serving keeps plain TP weights (no per-layer weight gathers)
        cfg = dataclasses.replace(cfg, fsdp=False)
    ctx = ShardCtx(mesh=mesh)
    dp = _dp(mesh)
    B, S = shape.global_batch, shape.seq_len
    dp_total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    tok_b = dp if (dp and B % dp_total == 0) else None
    params, p_sh = _lm_param_shardings(cfg, mesh, ctx)
    label = f"{cfg.arch}/{shape.name}"
    meta = {"family": "lm", "n_params": cfg.n_params(),
            "n_active_params": cfg.n_active_params(),
            "tokens": B * S, "kind": shape.kind,
            "n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "scan_layers": True, "global_batch": B, "seq_len": S}

    if shape.kind == "train":
        opt = AdamW(state_dtype=getattr(cfg, "opt_state_dtype", "float32"))
        opt_state = jax.eval_shape(opt.init, params)
        opt_sh = AdamWState(step=_ns(mesh), mu=p_sh, nu=p_sh)
        toks = _sds((B, S), jnp.int32)
        tok_sh = _ns(mesh, tok_b, None)

        def train_step(p, ost, tokens, labels):
            loss, g = jax.value_and_grad(
                lambda p_: tf.lm_loss(p_, tokens, labels, cfg, ctx))(p)
            p2, ost2 = opt.update(g, ost, p)
            return p2, ost2, loss

        return Cell(train_step, (params, opt_state, toks, toks),
                    (p_sh, opt_sh, tok_sh, tok_sh), label, meta)

    cache_len = S
    if shape.kind == "decode" and cfg.swa_window:
        cache_len = min(S, cfg.swa_window)       # SWA ring window cache
    cache = jax.eval_shape(
        lambda: tf.init_kv_cache(cfg, B, cache_len))
    cspec = _cache_spec(cfg, mesh, B)
    cache_sh = {k: NamedSharding(mesh, cspec) for k in cache}

    if shape.kind == "prefill":
        toks = _sds((B, S), jnp.int32)

        def prefill_step(p, tokens, c):
            return tf.prefill(p, tokens, c, cfg, ctx)

        return Cell(prefill_step, (params, toks, cache),
                    (p_sh, _ns(mesh, tok_b, None), cache_sh), label,
                    {**meta, "tokens": B * S})

    tok = _sds((B, 1), jnp.int32)
    pos = _sds((), jnp.int32)

    def dec_step(p, c, t, pos):
        return tf.decode_step(p, c, t, pos, cfg, ctx)

    return Cell(dec_step, (params, cache, tok, pos),
                (p_sh, cache_sh, _ns(mesh, tok_b, None), _ns(mesh)),
                label, {**meta, "tokens": B, "kv_len": cache_len})


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------


def _gnn_loss(cfg: GNNConfig, shape: GNNShape, ctx: ShardCtx, n: int,
              n_graphs: int, d_in: int):
    """Returns (init_shapes, loss_fn(params, batch))."""
    if cfg.model == "mace":
        def loss_fn(p, b):
            e = mace_mod.mace_energy(p, cfg, b["species"], b["pos"],
                                     b["senders"], b["receivers"],
                                     b["edge_mask"], b["graph_ids"],
                                     n_graphs)
            return jnp.mean((e - b["targets_g"]) ** 2)
        init = lambda k: mace_mod.init_mace(cfg, k)
        return init, loss_fn
    init, apply = gnn_mod.build_gnn_apply(cfg, d_in, cfg.n_classes)

    def loss_fn(p, b):
        out = apply(p, b)
        if cfg.model == "meshgraphnet":
            return jnp.mean((out[:, :3] - b["targets"]) ** 2)
        if shape.kind == "batched":
            return gnn_mod.graph_readout_xent(out, b["graph_ids"],
                                              b["labels"], n_graphs)
        return gnn_mod.node_xent(out, b["labels"], b["node_mask"])
    return init, loss_fn


def build_gnn_cell(cfg: GNNConfig, shape: GNNShape, mesh) -> Cell:
    ctx = ShardCtx(mesh=mesh)
    flat = _flat(mesh)
    n_dev = int(np.prod(list(mesh.shape.values())))
    label = f"{cfg.arch}/{shape.name}"

    if shape.kind == "sampled":
        return _gnn_sampled_cell(cfg, shape, mesh, label)

    if shape.kind == "batched":
        n_graphs = shape.batch_graphs
        N = _round_up(n_graphs * shape.n_nodes, n_dev)
        E = _round_up(n_graphs * shape.n_edges, n_dev)
        d_feat = 16
    else:
        n_graphs = 1
        N = _round_up(shape.n_nodes, n_dev)     # padded isolated vertices
        E = _round_up(shape.n_edges, n_dev)
        d_feat = shape.d_feat or 16

    espec = P(flat)
    big = N > 500_000
    nspec = P(flat) if big else P(None)
    batch = {
        "senders": _sds((E,), jnp.int32),
        "receivers": _sds((E,), jnp.int32),
        "edge_mask": _sds((E,), jnp.float32),
        "graph_ids": _sds((N,), jnp.int32),
        "labels": _sds((n_graphs if shape.kind == "batched" else N,),
                       jnp.int32),
        "node_mask": _sds((N,), jnp.float32),
    }
    b_sh = {"senders": _ns(mesh, *espec), "receivers": _ns(mesh, *espec),
            "edge_mask": _ns(mesh, *espec),
            "graph_ids": NamedSharding(mesh, nspec),
            "labels": NamedSharding(mesh, nspec if n_graphs == 1 else P(None)),
            "node_mask": NamedSharding(mesh, nspec)}
    if cfg.model == "mace":
        batch.update({"species": _sds((N,), jnp.int32),
                      "pos": _sds((N, 3), jnp.float32),
                      "targets_g": _sds((n_graphs,), jnp.float32)})
        b_sh.update({"species": NamedSharding(mesh, nspec),
                     "pos": NamedSharding(mesh, nspec),
                     "targets_g": _ns(mesh, None)})
    elif cfg.model == "meshgraphnet":
        batch.update({"x": _sds((N, d_feat), jnp.float32),
                      "e_feat": _sds((E, 4), jnp.float32),
                      "targets": _sds((N, 3), jnp.float32)})
        b_sh.update({"x": NamedSharding(mesh, nspec),
                     "e_feat": _ns(mesh, *espec),
                     "targets": NamedSharding(mesh, nspec)})
    else:
        batch["x"] = _sds((N, d_feat), jnp.float32)
        b_sh["x"] = NamedSharding(mesh, nspec)

    init, loss_fn = _gnn_loss(cfg, shape, ctx, N, n_graphs, d_feat)
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda _: _ns(mesh), params)
    opt = AdamW()
    opt_state = jax.eval_shape(opt.init, params)
    opt_sh = AdamWState(step=_ns(mesh), mu=p_sh, nu=p_sh)

    def train_step(p, ost, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        p2, ost2 = opt.update(g, ost, p)
        return p2, ost2, loss

    meta = {"family": "gnn", "model": cfg.model, "n_nodes": N, "n_edges": E,
            "d_hidden": cfg.d_hidden, "n_layers": cfg.n_layers,
            "d_feat": d_feat}
    return Cell(train_step, (params, opt_state, batch),
                (p_sh, opt_sh, b_sh), label, meta)


def _gnn_sampled_cell(cfg: GNNConfig, shape: GNNShape, mesh, label) -> Cell:
    """minibatch_lg: neighbor-sample + train, fused into one step."""
    ctx = ShardCtx(mesh=mesh)
    N, M = shape.n_nodes, shape.n_edges
    Bs = shape.batch_nodes
    fan = shape.fanout
    d_feat = 128
    n_sub = Bs * (1 + fan[0] + fan[0] * fan[1])
    E_sub = Bs * (fan[0] + fan[0] * fan[1])

    init, _ = _gnn_loss(cfg, GNNShape("sub", n_sub, E_sub, d_feat),
                        ctx, n_sub, Bs, d_feat)
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda _: _ns(mesh), params)
    opt = AdamW()
    opt_state = jax.eval_shape(opt.init, params)
    opt_sh = AdamWState(step=_ns(mesh), mu=p_sh, nu=p_sh)

    args = (params, opt_state,
            _sds((N + 1,), jnp.int32),            # row_ptr
            _sds((M,), jnp.int32),                # col_idx
            _sds((N, d_feat), jnp.float32),       # features
            _sds((N,), jnp.int32),                # labels (full)
            _sds((Bs,), jnp.int32),               # seeds
            _sds((2,), jnp.uint32))               # rng key
    shard = (p_sh, opt_sh, _ns(mesh, None), _ns(mesh, None),
             _ns(mesh, None), _ns(mesh, None), _ns(mesh, None), _ns(mesh, None))

    def train_step(p, ost, row_ptr, col_idx, feats, labels, seeds, key):
        sub = khop_sample(jax.random.wrap_key_data(key, impl="threefry2x32"),
                          row_ptr, col_idx, seeds, fan)
        b = {
            "senders": sub["senders"], "receivers": sub["receivers"],
            "edge_mask": sub["edge_mask"],
            "x": feats[sub["node_ids"]],
            "graph_ids": jnp.zeros((n_sub,), jnp.int32),
            "labels": labels[sub["node_ids"]],
            "node_mask": (jnp.arange(n_sub) < Bs).astype(jnp.float32),
            "species": sub["node_ids"] % 8,
            "pos": feats[sub["node_ids"]][:, :3],
            "targets": feats[sub["node_ids"]][:, :3] * 0.5,
            "targets_g": jnp.zeros((1,), jnp.float32),
            "e_feat": jnp.concatenate(
                [feats[sub["node_ids"]][sub["senders"], :3]
                 - feats[sub["node_ids"]][sub["receivers"], :3],
                 jnp.ones((E_sub, 1))], axis=1),
        }
        _, loss_fn = _gnn_loss(cfg, GNNShape("sub", n_sub, E_sub, d_feat),
                               ctx, n_sub, 1, d_feat)
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        p2, ost2 = opt.update(g, ost, p)
        return p2, ost2, loss

    meta = {"family": "gnn", "model": cfg.model, "n_nodes": n_sub,
            "n_edges": E_sub, "d_hidden": cfg.d_hidden,
            "n_layers": cfg.n_layers, "d_feat": d_feat, "sampled": True}
    return Cell(train_step, args, shard, label, meta)


# ---------------------------------------------------------------------------
# Recsys cells
# ---------------------------------------------------------------------------


def build_recsys_cell(cfg: RecsysConfig, shape: RecsysShape, mesh) -> Cell:
    ctx = ShardCtx(mesh=mesh)
    dp = _dp(mesh)
    label = f"{cfg.arch}/{shape.name}"
    params = jax.eval_shape(lambda k: ai.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    p_sh = {k: (_ns(mesh, "model", None) if k == "table" else _ns(mesh))
            for k in params}
    B = shape.batch
    dp_total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bspec = dp if B % max(dp_total, 1) == 0 and B >= dp_total else None
    meta = {"family": "recsys", "batch": B, "n_fields": cfg.n_sparse,
            "embed_dim": cfg.embed_dim, "kind": shape.kind}

    if shape.kind == "train":
        opt = AdamW()
        opt_state = jax.eval_shape(opt.init, params)
        opt_sh = AdamWState(step=_ns(mesh), mu=p_sh, nu=p_sh)
        idx = _sds((B, cfg.n_sparse), jnp.int32)
        lab = _sds((B,), jnp.float32)

        def train_step(p, ost, idx, labels):
            loss, g = jax.value_and_grad(
                lambda p_: ai.bce_loss(p_, cfg, idx, labels, ctx))(p)
            p2, ost2 = opt.update(g, ost, p)
            return p2, ost2, loss

        return Cell(train_step, (params, opt_state, idx, lab),
                    (p_sh, opt_sh, _ns(mesh, bspec, None), _ns(mesh, bspec)),
                    label, meta)

    if shape.kind == "serve":
        idx = _sds((B, cfg.n_sparse), jnp.int32)

        def serve_step(p, idx):
            return jax.nn.sigmoid(ai.forward(p, cfg, idx, ctx))

        return Cell(serve_step, (params, idx),
                    (p_sh, _ns(mesh, bspec, None)), label, meta)

    # retrieval: 1 query vs n_candidates
    NC = shape.n_candidates
    d_user = cfg.n_heads * cfg.d_attn
    idx = _sds((B, cfg.n_sparse), jnp.int32)
    cand = _sds((NC, d_user), jnp.float32)

    def retrieval_step(p, idx, cand):
        u = ai.user_tower(p, cfg, idx, ctx)
        return ai.retrieval_scores(u, cand, ctx)

    return Cell(retrieval_step, (params, idx, cand),
                (p_sh, _ns(mesh, None, None), _ns(mesh, "model", None)),
                label, {**meta, "n_candidates": NC})


# ---------------------------------------------------------------------------
# BFS cells (the paper's workload)
# ---------------------------------------------------------------------------


def _bfs_graph_specs(part, cap, cap_seg, keys):
    nr, nc, chunk, pr, pc = part.nr, part.nc, part.chunk, part.pr, part.pc
    full = {
        "edge_src": (cap,), "row_idx": (cap,), "nnz": (),
        "deg_A": (chunk,), "col_idx": (cap + cap_seg,),
        "edge_dst": (cap + cap_seg,),
        "row_ptr": (nr + 1,), "seg_ptr": (pc + 1,),
        "col_ptr": (nc + 1,), "jc": (cap,), "cp": (cap + 1,), "nzc": (),
    }
    return {k: _sds((pr, pc) + full[k], jnp.int32) for k in keys}


def build_bfs_cell(cfg: BFSConfig, shape: BFSShape, mesh,
                   level_only: bool = False) -> Cell:
    pr = mesh.shape["data"]
    pc = mesh.shape["model"]
    n = 1 << shape.scale
    part = make_partition(n, pr, pc, align=128)
    p = part.p
    # capacity model: symmetrized+deduped R-MAT keeps ~0.94 of 2*ef*n edges;
    # R-MAT block skew needs ~1.4x headroom at this grid size
    m_est = int(2 * shape.degree * n * 0.94)
    cap = _round_up(int(m_est / p * 1.4), 128)
    cap_seg = _round_up(int(cap / pc * 2.0), 128)
    label = f"{cfg.arch}/{shape.name}" + ("/level" if level_only else "")
    meta = {"family": "bfs", "n": part.n, "m": m_est, "pr": pr, "pc": pc,
            "scale": shape.scale, "storage": cfg.storage}

    if level_only:
        ops = get_local_ops("2d", "dense", cfg.storage)
        args_l = bfs_steps.LevelArgs(
            part=part, row_axis="data", col_axis="model",
            fold_mode=cfg.fold_mode, perm=tuple(part.transpose_perm()),
            cap_seg=cap_seg, storage=cfg.storage,
            compact_updates=cfg.compact_updates, ops=ops)
        keys = ops.keys

        def level_fn(g, pi, front):
            g = {k: v[0, 0] for k, v in g.items()}
            pi1, f1, c1 = bfs_steps.topdown_level(g, pi[0, 0], front[0, 0],
                                                  args_l)
            pi2, f2, c2 = bfs_steps.bottomup_level(g, pi1, f1, args_l)
            return pi2[None, None], f2[None, None]

        spec = P("data", "model")
        mapped = jax.shard_map(
            level_fn, mesh=mesh,
            in_specs=({k: spec for k in keys}, spec, spec),
            out_specs=(spec, spec), check_vma=False)
        g_specs = _bfs_graph_specs(part, cap, cap_seg, keys)
        pi = _sds((pr, pc, part.chunk), jnp.int32)
        fr = _sds((pr, pc, part.chunk), jnp.bool_)
        sh = NamedSharding(mesh, spec)
        return Cell(mapped, (g_specs, pi, fr),
                    ({k: sh for k in g_specs}, sh, sh), label, meta)

    # the engine's plan layer owns dispatch/validation; cells only need
    # the abstract program, so they build a graph-less plan, relabeled
    # as dist_build relabels a graph over these p shards
    relabel = build_relabel(
        BuildSpec(scale=shape.scale, edge_factor=shape.degree), p)
    plan = plan_for_part(part, cfg, mesh, cap_seg=cap_seg, maxdeg=1024,
                         n_real_edges=float(m_est), relabel=relabel)
    if "pod" in mesh.axis_names and kwargs_get_multiroot(cfg):
        pods = mesh.shape["pod"]
        fn = plan.build_batch_fn("pod")
        g_specs = _bfs_graph_specs(part, cap, cap_seg, plan.keys)
        sh = NamedSharding(mesh, P("data", "model"))
        return Cell(fn, (g_specs, _sds((pods,), jnp.int32)),
                    ({k: sh for k in g_specs}, _ns(mesh, "pod")),
                    label + "/multiroot", {**meta, "n_roots": pods})
    fn = plan.build_fn()
    g_specs = _bfs_graph_specs(part, cap, cap_seg, plan.keys)
    sh = NamedSharding(mesh, P("data", "model"))
    return Cell(fn, (g_specs, _sds((), jnp.int32)),
                ({k: sh for k in g_specs}, _ns(mesh)), label, meta)


def kwargs_get_multiroot(cfg) -> bool:
    return getattr(cfg, "arch", "").endswith("multiroot")


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

SKIPPED_CELLS = {
    # long_500k needs sub-quadratic attention; these are pure full-attention
    # archs (DESIGN.md §Arch-applicability) — mixtral (SWA) runs it.
    ("stablelm-3b", "long_500k"), ("smollm-135m", "long_500k"),
    ("starcoder2-7b", "long_500k"), ("qwen3-moe-30b-a3b", "long_500k"),
}


def build_cell(arch: str, shape_name: str, mesh, **kw) -> Optional[Cell]:
    if arch == "gin-tu-2d":
        from repro.launch.optimized import build_gin2d_cell
        return build_gin2d_cell(shape_name, mesh)
    if arch == "mace-2d":
        from repro.launch.optimized import build_mace2d_cell
        return build_mace2d_cell(shape_name, mesh)
    cfg = get_config(arch)
    if (arch, shape_name) in SKIPPED_CELLS:
        return None
    shape = next(s for s in cfg.shapes if s.name == shape_name)
    if cfg.kind == "lm":
        return build_lm_cell(cfg, shape, mesh)
    if cfg.kind == "gnn":
        return build_gnn_cell(cfg, shape, mesh)
    if cfg.kind == "recsys":
        return build_recsys_cell(cfg, shape, mesh)
    if cfg.kind == "bfs":
        return build_bfs_cell(cfg, shape, mesh, **kw)
    raise ValueError(arch)


def all_cells():
    """(arch, shape) ids for the full matrix (incl. skips -> None)."""
    out = []
    for arch in ("stablelm-3b", "smollm-135m", "starcoder2-7b",
                 "qwen3-moe-30b-a3b", "mixtral-8x22b"):
        for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            out.append((arch, s))
    for arch in ("mace", "gin-tu", "gat-cora", "meshgraphnet"):
        for s in ("full_graph_sm", "minibatch_lg", "ogb_products",
                  "molecule"):
            out.append((arch, s))
    for s in ("train_batch", "serve_p99", "serve_bulk", "retrieval_cand"):
        out.append(("autoint", s))
    return out


def bfs_cells():
    return [("bfs-rmat", s) for s in ("scale22", "scale26", "scale30")]
