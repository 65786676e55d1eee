"""Hypothesis sweep: chunked sorted-run build ≡ monolithic build (§13).

The chunked builder (``build_mode="chunked"``) must reproduce the
monolithic full-sort oracle bit-for-bit on every index component — the
ladder merges ascending-index runs with left-wins ties, which is exactly
one stable sort. Deterministic always-run cases live in
tests/test_out_of_core.py; this module needs hypothesis
(requirements-dev.txt).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import pipeline, slsh

jax.config.update("jax_platform_name", "cpu")


def _cfg(chunk, backend, l_out, mode="chunked"):
    return pipeline.SLSHConfig.compose(
        m_out=10, L_out=l_out, m_in=6, L_in=2, alpha=0.02, k=3,
        val_lo=20.0, val_hi=180.0, c_max=16, c_in=8, h_max=4, p_max=32,
        c_comp=64, build_chunk=chunk, backend=backend, build_mode=mode,
    )


@given(
    n=st.integers(0, 220),
    l_out=st.sampled_from([2, 4, 6]),
    chunk=st.integers(1, 256),  # covers chunk=1, non-dividing, chunk >= n
    backend=st.sampled_from(["reference", "pallas"]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=15, deadline=None)
def test_chunked_build_property(n, l_out, chunk, backend, seed):
    data = (
        jax.random.normal(jax.random.PRNGKey(seed), (n, 7)) * 20 + 80
    )
    cfg = _cfg(chunk, backend, l_out)
    if n == 0:  # an index over no points is a configuration error
        for mode in ("monolithic", "chunked"):
            with pytest.raises(pipeline.ConfigError, match="zero points"):
                slsh.build_index(
                    jax.random.PRNGKey(seed + 1), data,
                    cfg.replace(build_mode=mode),
                )
        return
    mono = slsh.build_index(
        jax.random.PRNGKey(seed + 1), data, cfg.replace(build_mode="monolithic")
    )
    chnk = slsh.build_index(jax.random.PRNGKey(seed + 1), data, cfg)
    for x, y in zip(jax.tree.leaves(mono), jax.tree.leaves(chnk)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(
    n=st.integers(1, 160),
    chunk=st.integers(1, 64),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=8, deadline=None)
def test_chunked_build_traced_property(n, chunk, seed):
    """Under an outer jit (simulate_build's vmapped cell programs) the
    in-trace chunked build stays bit-exact with the eager monolithic
    oracle."""
    data = jax.random.normal(jax.random.PRNGKey(seed), (n, 5)) * 20 + 80
    cfg = _cfg(chunk, "reference", 4)
    mono = slsh.build_index(
        jax.random.PRNGKey(seed + 1), data, cfg.replace(build_mode="monolithic")
    )
    traced = jax.jit(
        lambda d: pipeline.build_from_params(
            d, mono.outer_params, mono.inner_params, cfg
        )
    )(data)
    for x, y in zip(jax.tree.leaves(mono), jax.tree.leaves(traced)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
