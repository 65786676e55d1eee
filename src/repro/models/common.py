"""Shared transformer building blocks (pure functions over param dicts).

Everything computes in bf16 with f32 accumulations/norms, and applies
logical sharding constraints (batch/seq/tensor) that resolve against the
ambient mesh (no-ops on a single device).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding.ctx import constrain, mesh_axis_size

COMPUTE_DTYPE = jnp.bfloat16


# ------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(x.dtype)


# -------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    if positions.ndim == 1:
        ang = positions[None, :, None].astype(jnp.float32) * freqs[None, None, :]
        ang = ang[:, :, None, :]  # (1, S, 1, dh/2)
    else:
        ang = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- attention
def chunked_attention(
    q: jax.Array,  # (B, Sq, Hq, dh)
    k: jax.Array,  # (B, Skv, Hkv, dh)
    v: jax.Array,  # (B, Skv, Hkv, dh)
    *,
    causal: bool = True,
    window: int | None = None,
    sink: int = 0,  # first ``sink`` kv positions always visible (meta tokens)
    q_offset: int = 0,
    kv_len: jax.Array | None = None,  # dynamic valid kv length (decode)
    q_chunk: int = 512,
) -> jax.Array:
    """Online-softmax attention, lax.scan over query chunks ("flash in XLA").

    Peak memory is O(q_chunk * Skv) per head instead of O(Sq * Skv); the
    Pallas flash kernel (kernels/flash_attention) is the TPU-runtime twin.
    """
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = 1.0 / (dh**0.5)
    q_chunk = min(q_chunk, sq)
    q_pad = (-sq) % q_chunk
    if q_pad:  # ragged tail: pad queries, slice the outputs back
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
        out = chunked_attention(
            q, k, v, causal=causal, window=window, sink=sink, q_offset=q_offset,
            kv_len=kv_len, q_chunk=q_chunk,
        )
        return out[:, :sq]
    n_chunks = sq // q_chunk

    qf = (q.astype(jnp.float32) * scale).reshape(b, n_chunks, q_chunk, hkv, group, dh)
    qf = jnp.moveaxis(qf, 1, 0)  # (n_chunks, B, qc, hkv, g, dh)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = jnp.arange(skv)

    def one_chunk(ci, qc):  # qc: (B, qc, hkv, g, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kf)  # (B, hkv, g, qc, skv)
        q_pos = q_offset + ci * q_chunk + jnp.arange(q_chunk)
        ok = jnp.ones((q_chunk, skv), bool)
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            in_win = k_pos[None, :] > q_pos[:, None] - window
            if sink:
                in_win |= (k_pos < sink)[None, :]
            ok &= in_win
        if kv_len is not None:
            ok &= k_pos[None, :] < kv_len
        s = jnp.where(ok[None, None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.maximum(m, -1e30)  # fully-masked rows
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p, vf) / jnp.maximum(l, 1e-30)
        return jnp.moveaxis(o, 3, 1).reshape(b, q_chunk, hkv * group, dh)

    if n_chunks == 1:
        out = one_chunk(0, qf[0])
    else:
        # checkpoint the chunk body: without it, AD stacks per-chunk score
        # residuals across the whole sequence (GiBs at 32k context)
        body = jax.checkpoint(lambda args: one_chunk(*args), prevent_cse=False)
        out = jax.lax.map(body, (jnp.arange(n_chunks), qf))
        out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, dh)
        return out.astype(q.dtype)
    return out.astype(q.dtype)


def _partial_attn_local(qf, kf, vf, pos_offset, cl, hkv, dh, scale):
    """Masked partial-softmax attention over a local KV slice.

    qf: (B, Hq, dh); kf/vf: (B, s_loc, Hkv, dh); cl: (B,) valid lengths.
    Returns (m, l, acc) online-softmax statistics.
    """
    b = qf.shape[0]
    s_loc = kf.shape[1]
    group = qf.shape[1] // hkv
    qq = qf.reshape(b, hkv, group, dh).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bkhd->bhgk", qq, kf.astype(jnp.float32))
    pos = pos_offset + jnp.arange(s_loc)  # (s_loc,)
    ok = pos[None, :] < cl[:, None]  # (B, s_loc)
    s = jnp.where(ok[:, None, None, :], s, -jnp.inf)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e30)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, vf.astype(jnp.float32))
    return m, l, acc


def decode_attention_cp(
    q: jax.Array,  # (B, 1, Hq, dh)
    k_cache: jax.Array,  # (B, S_max, Hkv, dh) — seq dim may be mesh-sharded
    v_cache: jax.Array,
    cur_len: jax.Array,  # () or (B,) int32 — number of valid cache positions
) -> jax.Array:
    """Context-parallel decode attention (partial softmax + tiny psum).

    When the cache's seq dim is sharded over the ``model`` axis, each shard
    reads only its local KV slice — the memory-optimal decode pattern — and
    merges (m, l, acc) with O(B*H*dh) collectives. Falls back to plain
    masked attention when no mesh is ambient.
    """
    from repro.sharding import ctx as _ctx

    mesh = _ctx.get_mesh()
    tp = tuple(a for a in _ctx.get_rules().seq if mesh and a in mesh.shape)
    b, _, hq, dh = q.shape
    s_max, hkv = k_cache.shape[1], k_cache.shape[2]
    scale = 1.0 / (dh**0.5)

    if mesh is None or not tp or s_max % _ctx.mesh_axis_size(*tp) != 0:
        cl = jnp.broadcast_to(cur_len, (b,))
        m, l, acc = _partial_attn_local(q[:, 0], k_cache, v_cache, 0, cl, hkv, dh, scale)
        out = acc / jnp.maximum(l, 1e-30)
        return out.reshape(b, 1, hq, dh).astype(q.dtype)

    axis = tp[0]
    from jax.sharding import PartitionSpec as P

    # preserve batch sharding through the shard_map (replicating the cache
    # over the batch axes would blow per-device memory by the DP degree)
    batch_axes = tuple(
        a for a in _ctx.get_rules().batch if a in mesh.shape and mesh.shape[a] > 1
    )
    bspec = batch_axes if batch_axes else None
    if batch_axes:
        import math

        bsz = math.prod(mesh.shape[a] for a in batch_axes)
        if b % bsz != 0:
            bspec = None  # undivisible batch (e.g. B=1 long-context)

    def body(qf, kf, vf, cl):
        b_loc = qf.shape[0]
        s_loc = kf.shape[1]
        idx = jax.lax.axis_index(axis)
        m, l, acc = _partial_attn_local(
            qf[:, 0], kf, vf, idx * s_loc, cl, hkv, dh, scale
        )
        g_m = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - g_m)
        g_l = jax.lax.psum(l * corr, axis)
        g_acc = jax.lax.psum(acc * corr, axis)
        out = g_acc / jnp.maximum(g_l, 1e-30)
        return out.reshape(b_loc, 1, hq, dh).astype(q.dtype)

    cur_b = jnp.broadcast_to(cur_len, (b,))
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),
            P(bspec, axis, None, None),
            P(bspec, axis, None, None),
            P(bspec),
        ),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, cur_b)


# ----------------------------------------------------------------- MLPs
def mlp_apply(params: dict, x: jax.Array, kind: str) -> jax.Array:
    xc = x.astype(COMPUTE_DTYPE)
    if kind == "swiglu":
        g = xc @ params["w_gate"].astype(COMPUTE_DTYPE)
        u = xc @ params["w_up"].astype(COMPUTE_DTYPE)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * u
    elif kind == "relu2":  # nemotron squared-ReLU
        h = xc @ params["w_up"].astype(COMPUTE_DTYPE)
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(COMPUTE_DTYPE)
    elif kind == "gelu":
        h = xc @ params["w_up"].astype(COMPUTE_DTYPE)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(COMPUTE_DTYPE)
    else:
        raise ValueError(kind)
    h = constrain(h, "batch", None, "tensor")
    return (h @ params["w_down"].astype(COMPUTE_DTYPE)).astype(x.dtype)


# --------------------------------------------------------- embeddings / CE
def embed_tokens(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    out = jnp.take(embed, tokens, axis=0).astype(COMPUTE_DTYPE)
    return constrain(out, "batch", "seq", None)


def chunked_softmax_xent(
    x: jax.Array,  # (B, S, D) final hidden
    lm_head: jax.Array,  # (D, V) — vocab dim tensor-sharded
    labels: jax.Array,  # (B, S) int32
    mask: jax.Array,  # (B, S) bool
    seq_chunk: int = 1024,
) -> jax.Array:
    """Cross entropy without materializing (B, S, V) logits."""
    b, s, d = x.shape
    seq_chunk = min(seq_chunk, s)
    assert s % seq_chunk == 0
    n = s // seq_chunk
    xc = jnp.moveaxis(x.reshape(b, n, seq_chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, seq_chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(b, n, seq_chunk), 1, 0)

    def one(args):
        xi, li, mi = args
        logits = (xi.astype(COMPUTE_DTYPE) @ lm_head.astype(COMPUTE_DTYPE)).astype(
            jnp.float32
        )
        logits = constrain(logits, "batch", None, "tensor")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        nll = jnp.where(mi, lse - gold, 0.0)
        return jnp.sum(nll), jnp.sum(mi.astype(jnp.float32))

    if n == 1:
        tot, cnt = one((xc[0], lc[0], mc[0]))
    else:
        # checkpoint: logits chunks must be recomputed in the backward pass,
        # never stacked ((n, B, chunk, V) would defeat the chunking)
        tots, cnts = jax.lax.map(jax.checkpoint(one, prevent_cse=False), (xc, lc, mc))
        tot, cnt = jnp.sum(tots), jnp.sum(cnts)
    return tot / jnp.maximum(cnt, 1.0)
