"""The slab probe of the inner layer equals the element takes, bit for bit.

``kernels/inner_probe`` reads each (table, query) pair's heavy bucket as
one ``(L_in, P)`` slab and picks the windows with vector ops; the staged
gather's element takes (``pipeline._gather_one_table``) are the oracle.
Each case builds a small synthetic index whose inner tables and heavy
registry force one situation, checks with numpy that the situation is
there, and compares the whole candidate tensor of ``_stage_gather`` with
and without the kernel (interpret mode).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline, slsh, tables
from repro.kernels.inner_probe import ops as ip_ops
from repro.kernels.inner_probe import ref as ip_ref

jax.config.update("jax_platform_name", "cpu")

L, H, L_IN, Q, N_OUT = 3, 4, 3, 6, 48
PAD = int(tables.PAD_KEY)


def _cfg(p_max, c_in):
    return slsh.SLSHConfig.compose(
        m_out=12, L_out=L, m_in=8, L_in=L_IN, alpha=0.02, k=5, val_lo=0.0,
        val_hi=1.0, c_max=8, c_in=c_in, h_max=H, p_max=p_max,
    )


def _index(rng, *, p, alphabet, pop, reg_keys, reg_valid):
    """A synthetic index: sorted inner rows over ``alphabet`` keys with a
    ``PAD_KEY`` tail past ``pop`` (L, H), and registry ``reg_keys`` /
    ``reg_valid`` (L, H); the outer tables hold every registry key."""
    keys = rng.integers(0, alphabet, size=(L, H, L_IN, p)).astype(np.uint32)
    live = np.arange(p)[None, None, None, :] < pop[:, :, None, None]
    keys = np.sort(np.where(live, keys, np.uint32(PAD)), axis=-1)
    idx = np.where(
        keys != PAD, rng.integers(0, 10_000, size=keys.shape), -1
    ).astype(np.int32)
    outer = np.sort(
        np.concatenate(
            [np.repeat(reg_keys, 4, axis=1),
             rng.integers(0, 1000, size=(L, N_OUT - 4 * H))], axis=1
        ).astype(np.uint32), axis=1,
    )
    heavy = tables.HeavyBuckets(
        jnp.asarray(reg_keys, jnp.uint32),
        jnp.zeros((L, H), jnp.int32),
        jnp.asarray(pop, jnp.int32),
        jnp.asarray(reg_valid),
        jnp.zeros((L,), jnp.int32),
    )
    return pipeline.SLSHIndex(
        None, None,
        tables.TableSet(jnp.asarray(outer), jnp.asarray(
            rng.integers(0, 10_000, size=(L, N_OUT)).astype(np.int32))),
        heavy, jnp.asarray(keys), jnp.asarray(idx), jnp.int32(10_000),
    )


def _registry(dup=False):
    reg = (1000 + np.arange(L * H)).reshape(L, H).astype(np.uint32)
    valid = np.ones((L, H), bool)
    if dup:  # the same key in two valid slots, and behind an invalid one
        reg[:, 2] = reg[:, 1]
        reg[:, 3] = reg[:, 0]
        valid[:, 0] = False
    return reg, valid


def _queries(rng, reg, alphabet, *, hit=1.0):
    """Base probe keys (Q, L, 1) hitting the registry with share ``hit``,
    and inner keys (Q, L_in) in ``alphabet``."""
    pick = rng.integers(0, H, size=(Q, L))
    base = reg[np.arange(L)[None, :], pick]
    miss = rng.random((Q, L)) >= hit
    base = np.where(miss, 5000 + rng.integers(0, 100, size=(Q, L)), base)
    inner = rng.integers(0, alphabet, size=(Q, L_IN))
    return base.astype(np.uint32)[:, :, None], inner.astype(np.uint32)


def _runs(index, base, inner):
    """numpy: found, the first valid slot h, and per inner row (lo, n) of
    every (query, table)."""
    reg, valid = np.asarray(index.heavy.keys), np.asarray(index.heavy.valid)
    keys = np.asarray(index.inner_keys)
    match = (reg[None] == base[:, :, :1]) & valid[None]  # (Q, L, H)
    found, h = match.any(-1), match.argmax(-1)
    lo = np.zeros((Q, L, L_IN), int)
    n = np.zeros((Q, L, L_IN), int)
    for q in range(Q):
        for t in range(L):
            for i in range(L_IN):
                row = keys[t, h[q, t], i]
                lo[q, t, i] = np.searchsorted(row, inner[q, i], "left")
                n[q, t, i] = np.searchsorted(row, inner[q, i], "right") - lo[q, t, i]
    # the key sits in more than one slot of the registry, valid or not
    dup = found & ((reg[None] == base[:, :, :1]).sum(-1) > 1)
    return found, h, lo, n, dup


def _case(name, rng):
    """-> (p, c_in, index, base keys, inner keys, witness(found, lo, n, dup))."""
    p, c_in, alphabet = 64, 8, 12
    pop = rng.integers(p // 2, p + 1, size=(L, H))
    reg, valid = _registry(dup=name == "duplicate_heavy_keys")
    kw = {}
    if name == "empty_buckets":
        def witness(f, lo, n, d):
            return f.any() and (n[f] == 0).all()
        kw["inner_shift"] = alphabet + 3  # keys the rows do not hold
    elif name == "runs_longer_than_c_in":
        alphabet = 2

        def witness(f, lo, n, d):
            return (n[f] > c_in).any()
    elif name == "window_past_slab_end":
        pop = np.full((L, H), p)  # no PAD tail: the last run ends at P

        def witness(f, lo, n, d):
            return ((n > 0) & (lo + c_in > p))[f].any()
    elif name == "pad_key_tails":
        pop = rng.integers(1, p // 2, size=(L, H))

        def witness(f, lo, n, d):
            return f.any() and (lo + n == p)[f].any() and (n > 0)[f].any()
        kw["pad_rows"] = True
    elif name == "not_found":
        kw["hit"] = 0.4

        def witness(f, lo, n, d):
            return f.any() and not f.all()
    elif name == "duplicate_heavy_keys":
        def witness(f, lo, n, d):
            return d.any()
    elif name == "c_in_wider_than_slab":
        p, c_in, alphabet = 8, 16, 2
        pop = rng.integers(1, p + 1, size=(L, H))

        def witness(f, lo, n, d):
            return f.any() and (n[f] > 0).any()
    else:
        raise AssertionError(name)
    index = _index(rng, p=p, alphabet=alphabet, pop=pop, reg_keys=reg, reg_valid=valid)
    base, inner = _queries(rng, reg, alphabet, hit=kw.get("hit", 1.0))
    inner = inner + kw.get("inner_shift", 0)
    if kw.get("pad_rows"):
        inner[: Q // 2, 0] = PAD  # the query's key is the PAD key itself
    return p, c_in, index, base, inner.astype(np.uint32), witness


CASES = [
    "empty_buckets",
    "runs_longer_than_c_in",
    "window_past_slab_end",
    "pad_key_tails",
    "not_found",
    "duplicate_heavy_keys",
    "c_in_wider_than_slab",
]


@pytest.mark.parametrize("case", CASES)
def test_slab_probe_matches_element_takes(case):
    rng = np.random.default_rng(CASES.index(case))
    p, c_in, index, base, inner, witness = _case(case, rng)
    found_np, h_np, lo, n, dup = _runs(index, base, inner)
    assert witness(found_np, lo, n, dup), f"{case}: situation not built"
    cfg = _cfg(p, c_in)
    probe = functools.partial(ip_ops.inner_probe, interpret=True)
    pk, ik = jnp.asarray(base), jnp.asarray(inner)
    want, want_sz = pipeline._stage_gather(index, cfg, pk, ik)
    got, got_sz = pipeline._stage_gather(index, cfg, pk, ik, None, probe)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_sz), np.asarray(want_sz))
    # the registry match's tie rule, and the kernel alone against its
    # oracle over every (table, query) pair
    found, h = pipeline._heavy_match(index, pk[:, :, 0])
    np.testing.assert_array_equal(np.asarray(found), found_np)
    np.testing.assert_array_equal(np.asarray(h), h_np)
    args = (index.inner_keys, index.inner_idx, h.T, ik)
    np.testing.assert_array_equal(
        np.asarray(probe(*args, c_in=c_in)),
        np.asarray(ip_ref.inner_probe_ref(*args, c_in=c_in)),
    )
