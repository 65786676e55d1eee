"""LSH hash families (paper §2).

Two (r, cr, p1, p2)-sensitive families:

* Bit-sampling for the l1 norm (Gionis et al. VLDB'99): the classic unary-code
  bit-sampling family. Sampling bit j of the unary encoding of coordinate i is
  equivalent to the predicate ``x[i] > t_j`` for a threshold drawn uniformly
  over the coordinate range — we implement it that way (no unary expansion).
* Sign random projection for cosine similarity (Charikar STOC'02):
  ``bit_j = (x . r_j) >= 0`` with gaussian ``r_j``.

A table's m-bit signature is packed into ``ceil(m/32)`` uint32 words and mixed
into a single uint32 bucket key (FNV-1a over words, salted by table id).
Equal signatures map to equal keys, so LSH collision semantics are preserved;
key aliasing across distinct signatures (~n/2^32) only adds the occasional
spurious candidate, which is harmless for correctness (see DESIGN.md §8.3).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_FNV_PRIME = jnp.uint32(16777619)
_FNV_BASIS = jnp.uint32(2166136261)


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack boolean bits (..., m) into (..., ceil(m/32)) uint32 words."""
    m = bits.shape[-1]
    n_words = (m + 31) // 32
    pad = n_words * 32 - m
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), bits.dtype)], axis=-1
        )
    b = bits.reshape(bits.shape[:-1] + (n_words, 32)).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def mix32(words: jax.Array, salt: jax.Array) -> jax.Array:
    """FNV-1a mix of uint32 words (..., W) + per-table salt -> (...,) uint32."""
    h = _FNV_BASIS ^ salt.astype(jnp.uint32)
    for w in range(words.shape[-1]):
        word = words[..., w]
        for shift in (0, 8, 16, 24):
            byte = (word >> jnp.uint32(shift)) & jnp.uint32(0xFF)
            h = (h ^ byte) * _FNV_PRIME
    return h


class BitSampleParams(NamedTuple):
    """l1 bit-sampling family: L tables x m bits, bit = x[dim] > thr."""

    dims: jax.Array  # (L, m) int32 in [0, d)
    thrs: jax.Array  # (L, m) float32
    salts: jax.Array  # (L,) uint32


class SignRPParams(NamedTuple):
    """Cosine sign-random-projection family: L tables x m projections."""

    proj: jax.Array  # (L, d, m) float32
    salts: jax.Array  # (L,) uint32


HashParams = BitSampleParams | SignRPParams


def make_bitsample(
    key: jax.Array, L: int, m: int, d: int, lo: float, hi: float
) -> BitSampleParams:
    """Sample an l1 bit-sampling family: L tables, m bits over value range
    [lo, hi] (bit j of table t is the predicate ``x[dims[t,j]] > thrs[t,j]``)."""
    kd, kt, ks = jax.random.split(key, 3)
    dims = jax.random.randint(kd, (L, m), 0, d, dtype=jnp.int32)
    thrs = jax.random.uniform(kt, (L, m), jnp.float32, lo, hi)
    salts = jax.random.randint(ks, (L,), 0, 2**31 - 1, dtype=jnp.int32).astype(
        jnp.uint32
    )
    return BitSampleParams(dims, thrs, salts)


def make_signrp(key: jax.Array, L: int, m: int, d: int) -> SignRPParams:
    """Sample a cosine sign-random-projection family: L tables, m gaussian
    projections each (``bit_j = (x . proj[:, j]) >= 0``)."""
    kp, ks = jax.random.split(key)
    proj = jax.random.normal(kp, (L, d, m), jnp.float32)
    salts = jax.random.randint(ks, (L,), 0, 2**31 - 1, dtype=jnp.int32).astype(
        jnp.uint32
    )
    return SignRPParams(proj, salts)


def signature_bits(params: HashParams, x: jax.Array) -> jax.Array:
    """x: (n, d) -> bits (n, L, m) bool."""
    if isinstance(params, BitSampleParams):
        gathered = x[:, params.dims]  # (n, L, m)
        return gathered > params.thrs[None]
    # full f32 precision: TPU matmuls default to bf16 passes, which would
    # flip bits near zero and make keys depend on the platform
    proj = jnp.einsum(
        "nd,ldm->nlm", x, params.proj, precision=jax.lax.Precision.HIGHEST
    )
    return proj >= 0.0


def hash_points(params: HashParams, x: jax.Array) -> jax.Array:
    """x: (n, d) -> bucket keys (L, n) uint32."""
    bits = signature_bits(params, x)  # (n, L, m)
    words = pack_bits(bits)  # (n, L, W)
    keys = mix32(words, params.salts[None, :])  # (n, L)
    return keys.T


def probe_keys_from_margins(
    params: BitSampleParams,
    words: jax.Array,
    margins: jax.Array,
    n_probes: int,
) -> jax.Array:
    """Batched multiprobe keys from signature words + quantizer margins.

    ``words`` (n, L, W) and ``margins`` (n, L, m) — both emitted by one
    fused hash launch on the pallas backend (``hash_pack`` margins kernels,
    DESIGN.md §4) — yield (n, L, 1 + n_probes) uint32 keys: the base bucket
    key first, then the keys obtained by flipping the ``n_probes``
    lowest-margin bits (margin = |x[dim] - thr|, the distance to the
    quantizer boundary) — the classic multiprobe-LSH heuristic adapted to
    the bit-sampling family.
    """
    base = mix32(words, params.salts[None, :])  # (n, L)
    if n_probes == 0:
        return base[..., None]
    _, flip_idx = jax.lax.top_k(-margins, n_probes)  # (n, L, n_probes)
    w_idx = flip_idx // 32
    b_idx = (flip_idx % 32).astype(jnp.uint32)
    n_words = words.shape[-1]
    onehot = (
        jax.nn.one_hot(w_idx, n_words, dtype=jnp.uint32)
        * (jnp.uint32(1) << b_idx)[..., None]
    )  # (n, L, n_probes, W)
    probed = words[:, :, None, :] ^ onehot
    keys = mix32(probed, params.salts[None, :, None])  # (n, L, n_probes)
    return jnp.concatenate([base[..., None], keys], axis=-1)


def probe_keys_from_words(
    params: BitSampleParams, x: jax.Array, words: jax.Array, n_probes: int
) -> jax.Array:
    """Batched multiprobe keys from precomputed signature words.

    The reference formulation: recompute the quantizer margins from ``x``
    (n, d) and delegate to :func:`probe_keys_from_margins`. The pallas
    backend skips the recomputation — its fused hash launch emits the
    margins alongside the words (``kernels/hash_pack``).
    """
    if n_probes == 0:
        return probe_keys_from_margins(params, words, words[..., :0], 0)
    gathered = x[:, params.dims]  # (n, L, m)
    margins = jnp.abs(gathered - params.thrs[None])  # (n, L, m)
    return probe_keys_from_margins(params, words, margins, n_probes)


def probe_keys_bitsample(
    params: BitSampleParams, x: jax.Array, n_probes: int
) -> jax.Array:
    """Multiprobe keys for one query x (d,) -> (L, 1 + n_probes) uint32."""
    words = pack_bits(signature_bits(params, x[None, :]))  # (1, L, W)
    return probe_keys_from_words(params, x[None, :], words, n_probes)[0]


def hash_points_chunked(
    params: HashParams, x: jax.Array, chunk: int = 4096
) -> jax.Array:
    """Memory-bounded hashing: scan over point chunks. x (n, d) -> (L, n)."""
    n = x.shape[0]
    n_chunks = (n + chunk - 1) // chunk
    pad = n_chunks * chunk - n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xc = xp.reshape(n_chunks, chunk, -1)
    keys = jax.lax.map(lambda c: hash_points(params, c), xc)  # (n_chunks, L, chunk)
    keys = jnp.moveaxis(keys, 1, 0).reshape(params.salts.shape[0], -1)
    return keys[:, :n]
