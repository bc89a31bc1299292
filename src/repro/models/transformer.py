"""Decoder-only LM family: dense + MoE, GQA, RoPE, optional SWA.

Distribution:
  * DP over ("pod","data"), TP over "model" (Megatron col/row splits via
    PartitionSpecs; XLA inserts the psum on row-parallel matmuls).
  * MoE uses *explicit expert parallelism*: a shard_map token exchange
    with lax.all_to_all along "model" — structurally the paper's *fold*
    step (owner-computes exchange with static capacity), see DESIGN.md
    §Arch-applicability.  When E < tp, each expert is co-owned by a
    tp-subgroup that splits d_ff (duplicated dispatch + partial-sum
    return).  A replicated-token EP-psum path serves decode (tiny token
    counts).
  * FSDP-style extra sharding of big weights over the dp axes for the
    MoE archs (specs produced here; XLA materializes the allgathers).

Layers are stacked (leading L dim) and scanned; remat is configurable.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import LMConfig
from repro.models.common import ShardCtx, chunked_attention, rms_norm, rope

# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _split(key, n):
    return jax.random.split(key, n)


def init_params(cfg: LMConfig, key: jax.Array, dtype=None) -> Dict[str, Any]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    D, L = cfg.d_model, cfg.n_layers
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = _split(key, 12)

    def nrm(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    p = {
        "embed": nrm(ks[0], (cfg.vocab, D), D),
        "final_ln": jnp.ones((D,), jnp.float32),
        "wq": nrm(ks[1], (L, D, Hq * dh), D),
        "wk": nrm(ks[2], (L, D, Hkv * dh), D),
        "wv": nrm(ks[3], (L, D, Hkv * dh), D),
        "wo": nrm(ks[4], (L, Hq * dh, D), Hq * dh),
        "ln1": jnp.ones((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
    }
    if cfg.moe is None:
        F = cfg.d_ff
        p["wg"] = nrm(ks[5], (L, D, F), D)
        p["wu"] = nrm(ks[6], (L, D, F), D)
        p["wd"] = nrm(ks[7], (L, F, D), F)
    else:
        E, Fe = cfg.moe.n_experts, cfg.moe.d_ff_expert
        p["router"] = nrm(ks[8], (L, D, E), D)
        p["wg_e"] = nrm(ks[9], (L, E, D, Fe), D)
        p["wu_e"] = nrm(ks[10], (L, E, D, Fe), D)
        p["wd_e"] = nrm(ks[11], (L, E, Fe, D), Fe)
    return p


def param_specs(cfg: LMConfig, ctx: ShardCtx) -> Dict[str, P]:
    """PartitionSpecs per parameter (see module docstring)."""
    tp = ctx.tp
    dp = ctx.dp
    tpn = ctx.tp_size
    head_tp = tp if (tp and cfg.n_heads % tpn == 0) else None
    kv_tp = tp if (tp and cfg.n_kv_heads % tpn == 0) else None
    # heads not divisible by tp (e.g. starcoder's 36): shard the d_model
    # contraction dim instead of replicating — replication would also
    # replicate the f32 optimizer moments (~8 bytes/param) and blow the
    # per-device HBM budget at 7B scale.
    d_tp = None if head_tp else tp
    dkv_tp = None if kv_tp else tp
    # FSDP: additionally shard the free d_model dim of the big matrices
    # over dp (params + optimizer moments scale down n_dev-way; XLA
    # inserts the per-layer allgather)
    fs = (dp if (getattr(cfg, "fsdp", False) and dp) else None)
    specs = {
        "embed": P(tp, None),
        "final_ln": P(None),
        "wq": P(None, d_tp, head_tp if head_tp else fs),
        "wk": P(None, dkv_tp, kv_tp if kv_tp else fs),
        "wv": P(None, dkv_tp, kv_tp if kv_tp else fs),
        "wo": P(None, head_tp if head_tp else tp, fs),
        "ln1": P(None, None),
        "ln2": P(None, None),
    }
    if cfg.moe is None:
        specs.update({"wg": P(None, fs, tp), "wu": P(None, fs, tp),
                      "wd": P(None, tp, fs)})
    else:
        E = cfg.moe.n_experts
        dpa = dp if dp else None
        if tp and E % tpn == 0:
            # EP over model, FSDP over dp on the D dim
            specs.update({
                "router": P(None, None, None),
                "wg_e": P(None, tp, dpa, None),
                "wu_e": P(None, tp, dpa, None),
                "wd_e": P(None, tp, None, dpa),
            })
        else:
            # E < tp: d_ff split over model, FSDP over dp on the D dim
            specs.update({
                "router": P(None, None, None),
                "wg_e": P(None, None, dpa, tp),
                "wu_e": P(None, None, dpa, tp),
                "wd_e": P(None, None, tp, dpa),
            })
    return specs


# ---------------------------------------------------------------------------
# MoE: explicit expert-parallel dispatch (the "fold" exchange)
# ---------------------------------------------------------------------------


def _moe_local_math(xs, wg, wu, wd):
    """xs: (E_loc, C, D) grouped tokens -> SwiGLU expert FFN."""
    g = jnp.einsum("ecd,edf->ecf", xs, wg)
    u = jnp.einsum("ecd,edf->ecf", xs, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_ep_shardmap(x, router_w, wg, wu, wd, cfg: LMConfig, ctx: ShardCtx,
                    capacity_mult: float = 1.0):
    """Token-exchange expert parallelism along the "model" axis.

    x: (T, D) tokens already sharded P((dp..., "model"), None) — i.e. the
    token batch is split across every device.  Returns same shape/sharding.
    """
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    tpn = ctx.tp_size
    if ctx.mesh is None or tpn == 1:
        return _moe_reference(x, router_w, wg, wu, wd, cfg)
    E_loc = max(E // tpn, 1)            # experts owned per device
    tp_sub = max(tpn // E, 1)           # devices co-owning one expert
    cf = cfg.moe.capacity_factor * capacity_mult

    def body(xl, rw, wgl, wul, wdl):
        # xl: (T_loc, D); wgl: (E_loc, D, Fl).  For tp_sub > 1 the caller
        # pre-reshaped weights to (E*tp_sub, D, F/tp_sub) so sharding dim 0
        # over "model" hands device r = e*tp_sub + sub exactly expert e's
        # sub-th F-chunk (a plain F-shard would strand half of each
        # expert's FFN on devices that never compute it).
        T_loc, D = xl.shape
        cap = int(max(8, np.ceil(T_loc * k * tp_sub * cf / tpn)))
        logits = xl.astype(jnp.float32) @ rw.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, choice = lax.top_k(probs, k)            # (T_loc, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        flat_e = choice.reshape(-1)                   # (T_loc*k,)
        # destination device group + local expert slot
        dest0 = (flat_e // E_loc) * tp_sub if tp_sub == 1 else flat_e * tp_sub
        e_loc = flat_e % E_loc
        # position of each (token,choice) within its (dest, e_loc) queue
        key = (dest0 * E_loc + e_loc).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)
        sorted_key = key[order]
        # rank of each (token, choice) within its (dest, expert) group
        pos = jnp.zeros_like(key).at[order].set(
            jnp.arange(key.size, dtype=jnp.int32)
            - jnp.searchsorted(sorted_key, sorted_key, side="left").astype(
                jnp.int32))
        keep = pos < cap
        tok = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), k)

        outs = []
        for sub in range(tp_sub):
            dest = dest0 + sub
            # dropped (over-capacity) slots are routed out of bounds: JAX
            # scatter drops OOB updates, gather returns fill (masked below)
            slot = jnp.where(keep, e_loc * cap + pos, E_loc * cap)
            buf = jnp.zeros((tpn, E_loc * cap, D), xl.dtype)
            buf = buf.at[dest, slot].set(xl[tok])
            recv = lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
            xs = recv.reshape(tpn, E_loc, cap, D).transpose(1, 0, 2, 3)
            xs = xs.reshape(E_loc, tpn * cap, D)
            ys = _moe_local_math(xs, wgl, wul, wdl)
            ys = ys.reshape(E_loc, tpn, cap, D).transpose(1, 0, 2, 3)
            ys = ys.reshape(tpn, E_loc * cap, D)
            back = lax.all_to_all(ys, "model", split_axis=0, concat_axis=0)
            outs.append(back[dest, slot] * keep[:, None])
        contrib = sum(outs)                            # (T_loc*k, D)
        contrib = contrib.astype(jnp.float32) * gate.reshape(-1)[:, None]
        out = jnp.zeros((T_loc, D), jnp.float32).at[tok].add(contrib)
        return out.astype(xl.dtype)

    dpa = ctx.dp
    tok_spec = P((*dpa, "model"), None)
    if tp_sub > 1:
        # (E, D, F) -> (E*tp_sub, D, F/tp_sub): expert-major co-owner split
        Eg, D, F = wg.shape
        Fs = F // tp_sub
        wg = wg.reshape(Eg, D, tp_sub, Fs).transpose(0, 2, 1, 3).reshape(
            Eg * tp_sub, D, Fs)
        wu = wu.reshape(Eg, D, tp_sub, Fs).transpose(0, 2, 1, 3).reshape(
            Eg * tp_sub, D, Fs)
        wd = wd.reshape(Eg, tp_sub, Fs, D).reshape(Eg * tp_sub, Fs, D)
    wspec = P("model", None, None)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(tok_spec, P(None, None), wspec, wspec, wspec),
        out_specs=tok_spec, check_vma=False,
    )(x, router_w, wg, wu, wd)


def _moe_reference(x, router_w, wg, wu, wd, cfg: LMConfig):
    """Dense reference MoE (single device / smoke tests): exact top-k, no
    capacity drops."""
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, choice = lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(choice, E, dtype=x.dtype)   # (T, k, E)
    w = jnp.einsum("tk,tke->te", gate.astype(x.dtype), onehot)
    g = jnp.einsum("td,edf->tef", x, wg)
    u = jnp.einsum("td,edf->tef", x, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("tef,efd->ted", h, wd)
    return jnp.einsum("ted,te->td", y, w)


def moe_decode_psum(x, router_w, wg, wu, wd, cfg: LMConfig, ctx: ShardCtx):
    """Decode-path MoE: tokens replicated over "model"; each device applies
    its expert shard and a psum combines — no all_to_all for tiny T."""
    if ctx.mesh is None or ctx.tp_size == 1 or cfg.moe.n_experts < ctx.tp_size:
        return _moe_reference(x, router_w, wg, wu, wd, cfg)
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    tpn = ctx.tp_size
    E_loc = E // tpn

    def body(xl, rw, wgl, wul, wdl):
        T, D = xl.shape
        r = lax.axis_index("model")
        logits = xl.astype(jnp.float32) @ rw.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, choice = lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        mine = (choice >= r * E_loc) & (choice < (r + 1) * E_loc)
        out = jnp.zeros((T, D), jnp.float32)
        for e in range(E_loc):
            sel = (jnp.where(mine, choice - r * E_loc, -1) == e)
            wsum = jnp.sum(jnp.where(sel, gate, 0.0), axis=-1)  # (T,)
            g = xl @ wgl[e]
            u = xl @ wul[e]
            h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
            y = (h @ wdl[e]).astype(jnp.float32)
            out = out + y * wsum[:, None]
        return lax.psum(out, "model").astype(xl.dtype)

    dpa = ctx.dp
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(dpa if dpa else None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(dpa if dpa else None, None), check_vma=False,
    )(x, router_w, wg, wu, wd)


# ---------------------------------------------------------------------------
# Blocks + model passes
# ---------------------------------------------------------------------------


def _attn(h, lp, cfg: LMConfig, ctx: ShardCtx, q_offset, kv_cache=None,
          cache_pos=None, kv_chunk=1024):
    B, S, D = h.shape
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
    q = (hn @ lp["wq"]).reshape(B, S, Hq, dh)
    k = (hn @ lp["wk"]).reshape(B, S, Hkv, dh)
    v = (hn @ lp["wv"]).reshape(B, S, Hkv, dh)
    pos = q_offset + jnp.arange(S)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                             cache_pos, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                             cache_pos, axis=1)
        out = chunked_attention(q, ck, cv, q_offset=q_offset,
                                causal=True, window=cfg.swa_window,
                                kv_chunk=kv_chunk,
                                kv_valid_len=cache_pos + S)
        new_cache = (ck, cv)
    else:
        out = chunked_attention(q, k, v, q_offset=q_offset, causal=True,
                                window=cfg.swa_window, kv_chunk=kv_chunk)
        new_cache = None
    out = out.reshape(B, S, Hq * dh) @ lp["wo"]
    return h + out, new_cache


def _ffn(h, lp, cfg: LMConfig, ctx: ShardCtx, decode: bool = False):
    B, S, D = h.shape
    hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
    if cfg.moe is None:
        g = hn @ lp["wg"]
        u = hn @ lp["wu"]
        y = (jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u) @ lp["wd"]
        return h + y
    x = hn.reshape(B * S, D)
    if decode:
        y = moe_decode_psum(x, lp["router"], lp["wg_e"], lp["wu_e"],
                            lp["wd_e"], cfg, ctx)
    else:
        y = moe_ep_shardmap(x, lp["router"], lp["wg_e"], lp["wu_e"],
                            lp["wd_e"], cfg, ctx)
    return h + y.reshape(B, S, D)


def _stack_layers(params):
    keys = [k for k in params if k not in ("embed", "final_ln")]
    return {k: params[k] for k in keys}


def forward(params, tokens, cfg: LMConfig, ctx: ShardCtx, *, remat=True,
            kv_chunk=1024):
    """Full causal pass -> final hidden states (B, S, D)."""
    B, S = tokens.shape
    # sequence-parallel activation sharding (Megatron-SP): the remat-saved
    # per-layer h is S-sharded over "model", cutting saved-activation HBM
    # by tp at the cost of per-layer gathers inside attention.
    sp = ctx.tp if (ctx.tp and S % ctx.tp_size == 0 and S > 1) else None
    bspec = ctx.dp if ctx.dp else None
    h = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    h = ctx.cons(h, bspec, sp, None)
    layers = _stack_layers(params)

    def block(h, lp):
        h, _ = _attn(h, lp, cfg, ctx, q_offset=0, kv_chunk=kv_chunk)
        h = _ffn(h, lp, cfg, ctx)
        h = ctx.cons(h, bspec, sp, None)
        return h, None

    policy = getattr(cfg, "remat_policy", "full")
    if not remat or policy == "none":
        blk = block
    elif policy == "dots":
        blk = jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    else:
        blk = jax.checkpoint(block)
    h, _ = lax.scan(blk, h, layers)
    return rms_norm(h, params["final_ln"], cfg.norm_eps)


def lm_loss(params, tokens, labels, cfg: LMConfig, ctx: ShardCtx,
            seq_chunk: int = 2048, remat: bool = True):
    """Causal-LM cross entropy with sequence-chunked logits (never
    materializes (B, S, V) at once)."""
    h = forward(params, tokens, cfg, ctx, remat=remat)
    B, S, D = h.shape
    emb = params["embed"]
    n_chunks = max(S // min(seq_chunk, S), 1)
    hs = h.reshape(B, n_chunks, S // n_chunks, D)
    ls = labels.reshape(B, n_chunks, S // n_chunks)

    def chunk_loss(carry, inp):
        hc, lc = inp
        if getattr(cfg, "loss_bf16", False):
            # bf16 operands, f32 accumulation: halves logits-path traffic
            logits = jnp.einsum("bsd,vd->bsv", hc, emb,
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsd,vd->bsv", hc.astype(jnp.float32),
                                emb.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = lax.scan(chunk_loss, jnp.float32(0),
                        (jnp.moveaxis(hs, 1, 0), jnp.moveaxis(ls, 1, 0)))
    return total / (B * S)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: LMConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, tokens, cache, cfg: LMConfig, ctx: ShardCtx,
            kv_chunk: int = 1024):
    """Full-prompt pass that fills the KV cache; returns (cache, logits of
    the last position)."""
    B, S = tokens.shape
    h = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    layers = _stack_layers(params)

    def block(h, lp_cache):
        lp, (ck, cv) = lp_cache
        h, new_kv = _attn(h, lp, cfg, ctx, q_offset=0,
                          kv_cache=(ck, cv), cache_pos=0, kv_chunk=kv_chunk)
        h = _ffn(h, lp, cfg, ctx)
        return h, new_kv

    h, (k_all, v_all) = lax.scan(block, h, (layers, (cache["k"], cache["v"])))
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    logits = jnp.einsum("bd,vd->bv", h[:, -1].astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    return {"k": k_all, "v": v_all}, logits


def decode_step(params, cache, token, pos, cfg: LMConfig, ctx: ShardCtx,
                kv_chunk: int = 2048):
    """One decode step: token (B, 1), pos scalar int32 (current length).
    Returns (cache, logits (B, V))."""
    h = params["embed"][token].astype(jnp.dtype(cfg.dtype))
    layers = _stack_layers(params)

    def block(h, lp_cache):
        lp, (ck, cv) = lp_cache
        h, new_kv = _attn(h, lp, cfg, ctx, q_offset=pos,
                          kv_cache=(ck, cv), cache_pos=pos,
                          kv_chunk=kv_chunk)
        h = _ffn(h, lp, cfg, ctx, decode=True)
        return h, new_kv

    h, (k_all, v_all) = lax.scan(block, h, (layers, (cache["k"], cache["v"])))
    h = rms_norm(h, params["final_ln"], cfg.norm_eps)
    logits = jnp.einsum("bd,vd->bv", h[:, -1].astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    return {"k": k_all, "v": v_all}, logits
