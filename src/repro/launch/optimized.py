"""§Perf hillclimb cells (beyond-baseline variants).

gin-tu-2d/ogb_products: full-graph GIN training with aggregation routed
through the paper's 2D expand/fold partition (core/spmm.py schedule)
instead of GSPMD gather/scatter.  Napkin math (EXPERIMENTS.md §Perf):
baseline moves ~2*N*d*4B per device per layer in all-reduce traffic;
2D moves (N/pc + N/pr)*d*4B in allgather + reduce-scatter — a ~pc/2 x
reduction at pr=pc=16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.core.partition import make_partition
from repro.launch.cells import Cell, _ns, _round_up, _sds
from repro.optim.adamw import AdamW, AdamWState


def build_mace2d_cell(shape_name: str, mesh) -> Cell:
    """MACE with the 2D expand/fold aggregation — the most
    collective-bound baseline cell (mace/ogb_products, 1.8s collective).
    Positions + scalar channels expand along the column; the (nr, C, 9)
    first-order features fold via psum_scatter; Gaunt products stay
    chunk-local."""
    import numpy as _np
    from repro.models import mace as mace_mod
    cfg = get_config("mace")
    shape = next(s for s in cfg.shapes if s.name == shape_name)
    pr, pc = mesh.shape["data"], mesh.shape["model"]
    part = make_partition(shape.n_nodes, pr, pc, align=128)
    chunk, nr, nc = part.chunk, part.nr, part.nc
    cap = _round_up(int(shape.n_edges / part.p * 1.4), 128)
    C, L = cfg.d_hidden, cfg.n_layers
    perm = tuple(part.transpose_perm())
    spec = P("data", "model")
    G = mace_mod.gaunt_table().astype(_np.float32)
    lmap = mace_mod._LM_L

    def loss_body(p, esrc, ridx, nnz, species, pos, target):
        esrc, ridx, nnz = esrc[0, 0], ridx[0, 0], nnz[0, 0]
        species, pos = species[0, 0], pos[0, 0]      # (chunk,), (chunk, 3)
        Gj = jnp.asarray(G)
        e_mask = (jnp.arange(cap) < nnz)[:, None].astype(jnp.float32)

        def expand(x):     # layout A chunk -> C_j slice (nc, ...)
            xb = lax.ppermute(x, ("data", "model"), perm)
            return lax.all_gather(xb, "data", tiled=True)

        def gather_rows(x):  # layout A chunk -> R_i strip (nr, ...)
            return lax.all_gather(x, "model", tiled=True)

        pos_c = expand(pos)                           # (nc, 3)
        pos_r = gather_rows(pos)                      # (nr, 3)
        h = jnp.zeros((chunk, C, mace_mod.N_LM), jnp.float32)
        h = h.at[:, :, 0].set(p["embed"][species])
        rvec = pos_r[ridx] - pos_c[esrc]
        d = jnp.linalg.norm(rvec + 1e-12, axis=-1)
        u = rvec / jnp.maximum(d, 1e-9)[:, None]
        Y = mace_mod.real_sph_harm(u)                 # (cap, 9)
        for l in range(L):
            rb = mace_mod.bessel_basis(d, cfg.n_rbf, 3.0)
            R = jax.nn.silu(rb @ p[f"rad_w0_{l}"]) @ p[f"rad_w1_{l}"]
            R = R.reshape(-1, C, 3)[:, :, lmap]       # (cap, C, 9)
            hs_c = expand(h[:, :, 0])                 # (nc, C)
            msg = R * Y[:, None, :] * hs_c[esrc][:, :, None] * e_mask[..., None]
            partial = jax.ops.segment_sum(msg, ridx, num_segments=nr)
            A = lax.psum_scatter(partial, "model", scatter_dimension=0,
                                 tiled=True)          # (chunk, C, 9)
            B2 = mace_mod._gaunt_contract(A, A, Gj)
            B3 = mace_mod._gaunt_contract(B2, A, Gj)
            m = jnp.zeros_like(A)
            for o, feat in enumerate((A, B2, B3)):
                for li in range(3):
                    sel = lmap == li
                    m = m.at[:, :, sel].add(jnp.einsum(
                        "ncm,cd->ndm", feat[:, :, sel], p[f"mix_{l}"][o, li]))
            h = h + m
            h = h.at[:, :, 0].add(h[:, :, 0] @ p[f"upd_{l}"])
        e_node = jax.nn.silu(h[:, :, 0] @ p["out_w0"]) @ p["out_w1"]
        e_tot = lax.psum(jnp.sum(e_node), ("data", "model"))
        return (e_tot - target[0]) ** 2

    params = jax.eval_shape(lambda k: mace_mod.init_mace(cfg, k),
                            jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda _: _ns(mesh), params)
    opt = AdamW()
    opt_state = jax.eval_shape(opt.init, params)
    opt_sh = AdamWState(step=_ns(mesh), mu=p_sh, nu=p_sh)
    mapped = jax.shard_map(
        loss_body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params), spec, spec, spec,
                  spec, spec, P()),
        out_specs=P(), check_vma=False)

    def train_step(p, ost, esrc, ridx, nnz, species, pos, target):
        loss, g = jax.value_and_grad(
            lambda p_: mapped(p_, esrc, ridx, nnz, species, pos, target))(p)
        p2, ost2 = opt.update(g, ost, p)
        return p2, ost2, loss

    blk = (pr, pc)
    args = (params, opt_state,
            _sds(blk + (cap,), jnp.int32), _sds(blk + (cap,), jnp.int32),
            _sds(blk, jnp.int32), _sds(blk + (chunk,), jnp.int32),
            _sds(blk + (chunk, 3), jnp.float32), _sds((1,), jnp.float32))
    sh = NamedSharding(mesh, spec)
    meta = {"family": "gnn", "model": "mace", "n_nodes": part.n,
            "n_edges": cap * part.p, "d_hidden": C, "n_layers": L,
            "d_feat": 3, "variant": "2d-fold"}
    return Cell(train_step, args, (p_sh, opt_sh, sh, sh, sh, sh, sh,
                                   _ns(mesh)),
                f"mace-2d/{shape_name}", meta)


def build_gin2d_cell(shape_name: str, mesh) -> Cell:
    cfg = get_config("gin-tu")
    shape = next(s for s in cfg.shapes if s.name == shape_name)
    pr = mesh.shape["data"]
    pc = mesh.shape["model"]
    part = make_partition(shape.n_nodes, pr, pc, align=128)
    chunk, nr, nc = part.chunk, part.nr, part.nc
    cap = _round_up(int(shape.n_edges / part.p * 1.4), 128)
    d_feat = shape.d_feat or 16
    dh, L, n_cls = cfg.d_hidden, cfg.n_layers, cfg.n_classes
    perm = tuple(part.transpose_perm())
    spec = P("data", "model")

    def loss_body(p, esrc, ridx, nnz, x, y, mask):
        esrc, ridx, nnz = esrc[0, 0], ridx[0, 0], nnz[0, 0]
        h, y, mask = x[0, 0], y[0, 0], mask[0, 0]
        e_mask = (jnp.arange(cap) < nnz)[:, None].astype(h.dtype)

        def agg2d(h):
            hb = lax.ppermute(h, ("data", "model"), perm)
            h_cj = lax.all_gather(hb, "data", tiled=True)     # (nc, d)
            partial = jax.ops.segment_sum(h_cj[esrc] * e_mask, ridx,
                                          num_segments=nr)
            return lax.psum_scatter(partial, "model",
                                    scatter_dimension=0, tiled=True)

        for l in range(L):
            z = (1.0 + p[f"eps{l}"]) * h + agg2d(h)
            z = jax.nn.relu(z @ p[f"l{l}_w0"] + p[f"l{l}_b0"])
            h = jax.nn.relu(z @ p[f"l{l}_w1"] + p[f"l{l}_b1"])
        logits = h @ p["head_w0"] + p["head_b0"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
        num = lax.psum(jnp.sum(nll * mask), ("data", "model"))
        den = lax.psum(jnp.sum(mask), ("data", "model"))
        return num / jnp.maximum(den, 1.0)

    from repro.models.gnn import init_gin
    params = jax.eval_shape(
        lambda k: init_gin(cfg, k, d_feat, n_cls), jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda _: _ns(mesh), params)
    opt = AdamW()
    opt_state = jax.eval_shape(opt.init, params)
    opt_sh = AdamWState(step=_ns(mesh), mu=p_sh, nu=p_sh)

    mapped = jax.shard_map(
        loss_body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params), spec, spec, spec,
                  spec, spec, spec),
        out_specs=P(), check_vma=False)

    def train_step(p, ost, esrc, ridx, nnz, x, y, mask):
        loss, g = jax.value_and_grad(
            lambda p_: mapped(p_, esrc, ridx, nnz, x, y, mask))(p)
        p2, ost2 = opt.update(g, ost, p)
        return p2, ost2, loss

    blk = (pr, pc)
    args = (params, opt_state,
            _sds(blk + (cap,), jnp.int32), _sds(blk + (cap,), jnp.int32),
            _sds(blk, jnp.int32),
            _sds(blk + (chunk, d_feat), jnp.float32),
            _sds(blk + (chunk,), jnp.int32),
            _sds(blk + (chunk,), jnp.float32))
    sh = NamedSharding(mesh, spec)
    meta = {"family": "gnn", "model": "gin", "n_nodes": part.n,
            "n_edges": cap * part.p, "d_hidden": dh, "n_layers": L,
            "d_feat": d_feat, "variant": "2d-fold"}
    return Cell(train_step, args, (p_sh, opt_sh, sh, sh, sh, sh, sh, sh),
                f"gin-tu-2d/{shape_name}", meta)
