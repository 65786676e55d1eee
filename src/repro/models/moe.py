"""Mixture-of-Experts LM (phi3.5-moe 16e top-2, olmoe 64e top-8).

Top-k routing with per-expert capacity. Two equivalent execution paths:

* local (no mesh): all experts on-device — the semantic reference.
* expert-parallel (ambient mesh): shard_map over the full mesh; experts are
  sharded over the ``model`` axis, tokens are gathered from sequence-parallel
  shards, each shard computes only its local experts, and the combine is a
  reduce-scatter (psum_scatter) back to sequence-parallel layout. The
  baseline combine is psum_scatter; an all-to-all dispatch variant is the
  §Perf hillclimb (see EXPERIMENTS.md).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common as C
from repro.models import dense
from repro.models.params import PDef, stack
from repro.sharding import ctx
from repro.sharding.ctx import constrain

BF16 = jnp.bfloat16
F32 = jnp.float32


def layer_defs(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    defs = dense.layer_defs(cfg)
    for name in ("w_gate", "w_up", "w_down"):
        defs.pop(name, None)
    defs["router"] = PDef((d, e), (None, None), scale=0.02)
    defs["e_gate"] = PDef((e, d, f), ("expert", "fsdp", None))
    defs["e_up"] = PDef((e, d, f), ("expert", "fsdp", None))
    defs["e_down"] = PDef((e, f, d), ("expert", None, "fsdp"))
    return defs


def model_defs(cfg) -> dict:
    defs = dense.model_defs(cfg)
    defs["layers"] = stack(layer_defs(cfg), cfg.n_layers)
    return defs


def _capacity(n_tokens: int, cfg) -> int:
    cap = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(cap, 1)


def _route(router_w, xf, cfg):
    """xf: (T, D) f32 -> (weights (T, k), experts (T, k), probs (T, E))."""
    logits = (xf @ router_w.astype(F32)).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return top_w, top_e, probs


def _expert_compute(e_gate, e_up, e_down, xt):
    """xt: (E_loc, C, D) -> (E_loc, C, D) through each expert's SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", xt, e_gate.astype(BF16))
    u = jnp.einsum("ecd,edf->ecf", xt, e_up.astype(BF16))
    h = jax.nn.silu(g.astype(F32)).astype(BF16) * u
    return jnp.einsum("ecf,efd->ecd", h, e_down.astype(BF16))


def _moe_local(p, x_tokens, cfg, e_start: int, e_count: int):
    """Token-choice MoE over experts [e_start, e_start+e_count).

    x_tokens: (T, D). Returns (out (T, D) f32 partial sum, aux-loss terms).
    """
    t = x_tokens.shape[0]
    cap = _capacity(t, cfg)
    xf = x_tokens.astype(F32)
    top_w, top_e, probs = _route(p["router"], xf, cfg)

    # per-expert token scores: router weight if assigned else -inf
    eids = e_start + jnp.arange(e_count)  # (E_loc,)
    assign = top_e[None] == eids[:, None, None]  # (E_loc, T, k)
    w_e = jnp.where(assign, top_w[None], 0.0).sum(-1)  # (E_loc, T)
    score = jnp.where(w_e > 0.0, w_e, -jnp.inf)
    top_scores, top_pos = jax.lax.top_k(score, min(cap, t))  # (E_loc, C)
    valid = jnp.isfinite(top_scores)

    gathered = jnp.take(x_tokens.astype(BF16), top_pos, axis=0)  # (E_loc, C, D)
    gathered = jnp.where(valid[..., None], gathered, 0)
    out_e = _expert_compute(p["e_gate"], p["e_up"], p["e_down"], gathered)
    out_e = out_e.astype(F32) * jnp.where(valid, top_scores, 0.0)[..., None]

    out = jnp.zeros((t, x_tokens.shape[1]), F32)
    out = out.at[top_pos.reshape(-1)].add(out_e.reshape(-1, out_e.shape[-1]))

    # load-balancing stats (global across experts; computed from full probs)
    load = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts, dtype=F32), axis=1), axis=0
    )  # (E,) fraction routed
    imp = jnp.mean(probs, axis=0)  # (E,)
    aux = cfg.n_experts * jnp.sum(load * imp) / cfg.top_k
    return out, aux


def _moe_a2a_body(pp, xx, cfg, axis, ep, e_loc):
    """All-to-all dispatch (perf iteration B2, EXPERIMENTS.md §Perf).

    Each shard sends only the token copies routed to remote experts
    (T_loc*k/ep per peer, capacity-padded) instead of gathering all T
    tokens everywhere: wire bytes drop from ~2*T*D to ~2*T*k*D/ep.
    xx: (B_loc, S/ep, D) sequence-parallel shard.
    """
    b_loc, s_loc, d = xx.shape
    t_loc = b_loc * s_loc
    xt = xx.reshape(t_loc, d)
    top_w, top_e, probs = _route(pp["router"], xt.astype(F32), cfg)

    # flat token copies and their destination shards
    flat_w = top_w.reshape(-1)  # (T_loc*k,)
    flat_e = top_e.reshape(-1)
    flat_pos = jnp.repeat(jnp.arange(t_loc), cfg.top_k)
    dest = flat_e // e_loc  # (T_loc*k,)
    cap = max(
        1, int(math.ceil(t_loc * cfg.top_k / ep * cfg.capacity_factor))
    )

    # per-destination top-CAP selection (by router weight)
    score = jnp.where(
        dest[None, :] == jnp.arange(ep)[:, None], flat_w[None, :], -jnp.inf
    )  # (ep, T_loc*k)
    sel_w, sel_i = jax.lax.top_k(score, min(cap, score.shape[1]))  # (ep, CAP)
    valid = jnp.isfinite(sel_w)
    send_x = jnp.take(xt.astype(BF16), flat_pos[sel_i], axis=0)  # (ep, CAP, D)
    send_x = jnp.where(valid[..., None], send_x, 0)
    send_e = jnp.where(valid, flat_e[sel_i], 0)
    send_w = jnp.where(valid, sel_w, 0.0)
    send_pos = jnp.where(valid, flat_pos[sel_i], -1)

    # exchange: recv[j] = what shard j sent to me
    recv_x = jax.lax.all_to_all(send_x, axis, split_axis=0, concat_axis=0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e, axis, split_axis=0, concat_axis=0, tiled=True)
    recv_w = jax.lax.all_to_all(send_w, axis, split_axis=0, concat_axis=0, tiled=True)
    recv_x = recv_x.reshape(ep, -1, d)
    recv_e = recv_e.reshape(ep, -1)
    recv_w = recv_w.reshape(ep, -1)

    # local expert compute over the received copies
    me = jax.lax.axis_index(axis)
    eids = me * e_loc + jnp.arange(e_loc)
    tokens = recv_x.reshape(-1, d)  # (ep*CAP, D)
    te = recv_e.reshape(-1)
    tw = recv_w.reshape(-1)
    onehot = te[None, :] == eids[:, None]  # (e_loc, ep*CAP)
    escore = jnp.where(onehot & (tw[None, :] > 0), tw[None, :], -jnp.inf)
    c_in = max(1, int(math.ceil(ep * cap * cfg.capacity_factor / e_loc)))
    g_w, g_i = jax.lax.top_k(escore, min(c_in, escore.shape[1]))  # (e_loc, C)
    g_valid = jnp.isfinite(g_w)
    gathered = jnp.take(tokens, jnp.maximum(g_i, 0), axis=0)
    gathered = jnp.where(g_valid[..., None], gathered, 0)
    out_e = _expert_compute(pp["e_gate"], pp["e_up"], pp["e_down"], gathered)
    out_e = out_e.astype(F32) * jnp.where(g_valid, g_w, 0.0)[..., None]
    out_tokens = jnp.zeros((tokens.shape[0], d), F32)
    out_tokens = out_tokens.at[g_i.reshape(-1)].add(out_e.reshape(-1, d))

    # send results home + scatter into the local activations
    back = jax.lax.all_to_all(
        out_tokens.reshape(ep, -1, d).astype(BF16), axis,
        split_axis=0, concat_axis=0, tiled=True,
    ).reshape(ep, -1, d)
    pos = send_pos  # (ep, CAP) original positions of MY tokens per peer
    out = jnp.zeros((t_loc, d), F32)
    out = out.at[jnp.maximum(pos.reshape(-1), 0)].add(
        jnp.where((pos.reshape(-1) >= 0)[:, None], back.reshape(-1, d).astype(F32), 0)
    )

    load = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts, dtype=F32), axis=1), axis=0
    )
    imp = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(load * imp) / cfg.top_k
    aux = jax.lax.pmean(aux, axis)
    return out.reshape(b_loc, s_loc, d).astype(BF16), aux


def moe_apply(p, x, cfg):
    """x: (B, S, D) sequence-parallel -> (out, aux_loss)."""
    b, s, d = x.shape
    mesh = ctx.get_mesh()
    ep_axes = tuple(a for a in ctx.get_rules().expert if mesh and a in mesh.shape)
    ep = ctx.mesh_axis_size(*ep_axes) if ep_axes else 1

    if mesh is None or ep == 1 or cfg.n_experts % ep != 0 or s % ep != 0:
        out, aux = _moe_local(p, x.reshape(b * s, d), cfg, 0, cfg.n_experts)
        return out.reshape(b, s, d).astype(x.dtype), aux

    axis = ep_axes[0]
    if cfg.moe_impl == "a2a":
        e_loc = cfg.n_experts // ep
        batch_axes = tuple(a for a in ctx.get_rules().batch if a in mesh.shape)
        in_p = jax.tree.map(lambda _: P(), p)
        in_p["e_gate"] = P(axis, None, None)
        in_p["e_up"] = P(axis, None, None)
        in_p["e_down"] = P(axis, None, None)
        out, aux = jax.shard_map(
            lambda pp, xx: _moe_a2a_body(pp, xx, cfg, axis, ep, e_loc),
            mesh=mesh,
            in_specs=(in_p, P(batch_axes if batch_axes else None, axis, None)),
            out_specs=(P(batch_axes if batch_axes else None, axis, None), P()),
            check_vma=False,
        )(p, x.astype(BF16))
        return out.astype(x.dtype), aux
    e_loc = cfg.n_experts // ep
    batch_axes = tuple(a for a in ctx.get_rules().batch if a in mesh.shape)

    def body(pp, xx):
        # xx: (B_loc, S/ep, D) sequence-parallel -> gather full local batch.
        # bf16 at the collective boundary: halves EP comm vs f32 (perf
        # iteration B1, EXPERIMENTS.md §Perf)
        xg = jax.lax.all_gather(xx, axis, axis=1, tiled=True)  # (B_loc, S, D)
        t = xg.shape[0] * xg.shape[1]
        me = jax.lax.axis_index(axis)
        out, aux = _moe_local(pp, xg.reshape(t, d), cfg, me * e_loc, e_loc)
        out = out.reshape(xg.shape).astype(BF16)
        # combine partial expert outputs + return to sequence-parallel
        out = jax.lax.psum_scatter(out, axis, scatter_dimension=1, tiled=True)
        aux = jax.lax.psum(aux, axis) / ep  # each shard computed full stats
        return out, aux

    in_p = jax.tree.map(lambda _: P(), p)
    in_p["e_gate"] = P(axis, None, None)
    in_p["e_up"] = P(axis, None, None)
    in_p["e_down"] = P(axis, None, None)
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(in_p, P(batch_axes if batch_axes else None, axis, None)),
        out_specs=(P(batch_axes if batch_axes else None, axis, None), P()),
        check_vma=False,
    )(p, x.astype(BF16))
    return out.astype(x.dtype), aux


# ------------------------------------------------------------- blocks
def block_train(cfg, p, x, positions):
    h = C.rms_norm(x, p["ln1"])
    q, k, v = dense._qkv(cfg, p, h)
    q = C.apply_rope(q, positions, cfg.rope_theta)
    k = C.apply_rope(k, positions, cfg.rope_theta)
    attn = C.chunked_attention(
        q, k, v, causal=cfg.causal, window=cfg.window, q_chunk=cfg.q_chunk
    ).reshape(x.shape[0], x.shape[1], -1)
    x = x + (attn.astype(BF16) @ p["wo"].astype(BF16)).astype(x.dtype)
    x = constrain(x, "batch", "seq", None)
    h2 = C.rms_norm(x, p["ln2"])
    mo, aux = moe_apply(p, h2, cfg)
    x = x + mo.astype(x.dtype)
    return constrain(x, "batch", "seq", None), aux


def loss_fn(cfg, params, batch, remat_policy: str = "dots"):
    x, mask = dense._embed_inputs(cfg, params, batch)
    s = x.shape[1]
    positions = jnp.arange(s)

    def body(carry, lp):
        x, aux_sum = carry
        x, aux = block_train(cfg, lp, x, positions)
        return (x, aux_sum + aux), None

    body_fn = body
    if remat_policy == "full":
        body_fn = jax.checkpoint(body, prevent_cse=False)
    elif remat_policy == "dots":
        body_fn = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False,
        )
    (x, aux_sum), _ = jax.lax.scan(body_fn, (x, jnp.float32(0)), params["layers"])
    x = C.rms_norm(x, params["final_norm"])
    labels = jnp.concatenate([batch["tokens"][:, 1:], batch["tokens"][:, :1]], 1)
    mask = mask & (jnp.arange(s) < s - 1)[None, :]
    ce = C.chunked_softmax_xent(x, dense._lm_head(cfg, params), labels, mask, cfg.loss_chunk)
    return ce + cfg.aux_loss_coef * aux_sum / cfg.n_layers


init_cache = dense.init_cache
cache_logical_axes = dense.cache_logical_axes


def prefill(cfg, params, batch, max_len: int):
    x, _ = dense._embed_inputs(cfg, params, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)

    def body(carry, lp):
        h = C.rms_norm(carry, lp["ln1"])
        q, k, v = dense._qkv(cfg, lp, h)
        q = C.apply_rope(q, positions, cfg.rope_theta)
        k = C.apply_rope(k, positions, cfg.rope_theta)
        attn = C.chunked_attention(
            q, k, v, causal=cfg.causal, window=cfg.window, q_chunk=cfg.q_chunk
        ).reshape(b, s, -1)
        x2 = carry + (attn.astype(BF16) @ lp["wo"].astype(BF16)).astype(carry.dtype)
        h2 = C.rms_norm(x2, lp["ln2"])
        mo, _ = moe_apply(lp, h2, cfg)
        x2 = constrain(x2 + mo.astype(x2.dtype), "batch", "seq", None)
        return x2, (k.astype(BF16), v.astype(BF16))

    x, (k_all, v_all) = jax.lax.scan(body, x, params["layers"])
    x = C.rms_norm(x, params["final_norm"])
    logits = (x[:, -1].astype(BF16) @ dense._lm_head(cfg, params).astype(BF16)).astype(F32)
    pad = max_len - s
    cache = {
        "k": jnp.pad(k_all, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(v_all, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "len": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


def decode_step(cfg, params, cache, tokens):
    x = C.embed_tokens(params["embed"], tokens)
    cur = cache["len"]

    def body(carry, xs):
        lp, kc, vc = xs
        b = carry.shape[0]
        h = C.rms_norm(carry, lp["ln1"])
        q, k, v = dense._qkv(cfg, lp, h)
        pos = cur[:, None]
        q = C.apply_rope(q, pos, cfg.rope_theta)
        k = C.apply_rope(k, pos, cfg.rope_theta)
        kc = kc.at[jnp.arange(b), cur].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[jnp.arange(b), cur].set(v[:, 0].astype(vc.dtype))
        attn = C.decode_attention_cp(q, kc, vc, cur + 1).reshape(b, 1, -1)
        x2 = carry + (attn.astype(BF16) @ lp["wo"].astype(BF16)).astype(carry.dtype)
        h2 = C.rms_norm(x2, lp["ln2"])
        mo, _ = moe_apply(lp, h2, cfg)
        return x2 + mo.astype(x2.dtype), (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = C.rms_norm(x, params["final_norm"])
    logits = (x[:, 0].astype(BF16) @ dense._lm_head(cfg, params).astype(BF16)).astype(F32)
    return logits, {"k": k_new, "v": v_new, "len": cur + 1}
