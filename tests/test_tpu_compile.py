"""Compile the main-path Pallas kernels for a described TPU v5e.

Every other test runs the kernels in interpret mode, which accepts shapes
and ops the TPU compiler refuses. These tests lower the compiled (Mosaic)
formulation at the paper deployment's widths (d=30, m_out=32, L_out=16,
k=10, c_comp=256, Q=64) — the inner probe at the benchmark cell's (L_out=63,
h_max=8, L_in=20, p_max=512, c_in=32) — for one chip of a ``v5e:2x2``
topology described without a chip, check that a Mosaic kernel
(``tpu_custom_call``) is in the executable, and record its
``memory_analysis()``.

The topology is described only inside the module fixture: describing it
loads the TPU compiler library, which one process at a time may hold.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hashing, pipeline, slsh
from repro.kernels.hash_pack import ops as hp_ops
from repro.kernels.inner_probe import ops as ip_ops
from repro.kernels.l1_topk import ops as l1_ops
from repro.kernels.query_fused import ops as qf_ops

N, D, M_OUT, L_OUT, M_IN, L_IN, K, C_COMP, Q = (
    1_370_000, 30, 32, 16, 12, 4, 10, 256, 64
)
C = L_OUT * 512  # gather width: L_out * slot, slot = max(2 * c_max, L_in * c_in)
RUN = 16  # gcd(c_max, slot, c_in)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(record_property, fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    record_property("memory_analysis", str(mem))
    return mem


def _bitsample(sharding):
    return (
        jax.ShapeDtypeStruct((L_OUT, M_OUT), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((L_OUT, M_OUT), jnp.float32, sharding=sharding),
        jax.ShapeDtypeStruct((L_OUT,), jnp.uint32, sharding=sharding),
    )


@pytest.mark.parametrize("family", ["bitsample", "signrp"])
def test_hash_pack_signature_words_compile(one_chip, record_property, family):
    x = jax.ShapeDtypeStruct((Q, D), jnp.float32, sharding=one_chip)
    if family == "bitsample":
        def fn(dims, thrs, salts, x):
            params = hashing.BitSampleParams(dims, thrs, salts)
            return hp_ops.signature_words_kernel(params, x, interpret=False)

        _compile(record_property, fn, *_bitsample(one_chip), x)
    else:
        def fn(proj, salts, x):
            params = hashing.SignRPParams(proj, salts)
            return hp_ops.signature_words_kernel(params, x, interpret=False)

        proj = jax.ShapeDtypeStruct((L_IN, D, M_IN), jnp.float32, sharding=one_chip)
        salts = jax.ShapeDtypeStruct((L_IN,), jnp.uint32, sharding=one_chip)
        _compile(record_property, fn, proj, salts, x)


def test_hash_pack_probe_margins_compile(one_chip, record_property):
    def fn(dims, thrs, salts, x):
        params = hashing.BitSampleParams(dims, thrs, salts)
        return hp_ops.probe_words_kernel(params, x, interpret=False)

    x = jax.ShapeDtypeStruct((Q, D), jnp.float32, sharding=one_chip)
    _compile(record_property, fn, *_bitsample(one_chip), x)


def test_l1_topk_compile(one_chip, record_property):
    def fn(q, cands, mask):
        return l1_ops.l1_topk(q, cands, mask, k=K, interpret=False)

    _compile(
        record_property, fn,
        jax.ShapeDtypeStruct((Q, D), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((Q, C_COMP, D), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((Q, C_COMP), jnp.bool_, sharding=one_chip),
    )


def test_query_tail_f32_compile(one_chip, record_property):
    """The f32 query tail as it runs compiled: Mosaic refuses the fused
    body (``query_fused.XLA_STAGES``), so the pallas backend has no
    ``query_tail`` there and stages 3-5 run staged, stage 5 in the
    ``l1_topk`` kernel."""
    cfg = slsh.SLSHConfig.compose(
        m_out=M_OUT, L_out=L_OUT, m_in=M_IN, L_in=L_IN, alpha=0.005, k=K,
        val_lo=20.0, val_hi=180.0, c_max=256, c_in=16, h_max=16, p_max=512,
        c_comp=C_COMP, backend="pallas", interpret=False,
    )
    backend = pipeline.get_backend("pallas", cfg)
    assert backend.query_tail is None

    def fn(data, queries, cand):
        srt, uniq, comparisons = pipeline._stage_dedup(cand)
        comp, valid, overflow = pipeline._stage_compact(
            srt, uniq, comparisons, C_COMP
        )
        kd, ki = pipeline._stage_topk(data, queries, comp, valid, cfg, backend)
        return kd, ki, comparisons, overflow

    _compile(
        record_property, fn,
        jax.ShapeDtypeStruct((N, D), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((Q, D), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((Q, C), jnp.int32, sharding=one_chip),
    )


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.int8])
def test_query_fused_payload_compile(one_chip, record_property, dtype):
    def fn(data, qdata, meta, queries, cand):
        return qf_ops.query_tail_payload(
            data, qdata, meta, queries, cand,
            run=RUN, c_comp=C_COMP, c_rerank=128, k=K, interpret=False,
        )

    _compile(
        record_property, fn,
        jax.ShapeDtypeStruct((N, D), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((N, D), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((N, 2), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((Q, D), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((Q, C), jnp.int32, sharding=one_chip),
    )


@pytest.mark.parametrize("q", [8, 64])
def test_inner_probe_compile(one_chip, record_property, q):
    """The slab probe at the benchmark cell's widths, for both rungs'
    chunk sizes (8 rows, and the 64-row query chunk)."""
    l_out, h_max, l_in, p_max, c_in = 63, 8, 20, 512, 32

    def fn(inner_keys, inner_idx, h, keys):
        return ip_ops.inner_probe(
            inner_keys, inner_idx, h, keys, c_in=c_in, interpret=False
        )

    slab = (l_out, h_max, l_in, p_max)
    _compile(
        record_property, fn,
        jax.ShapeDtypeStruct(slab, jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct(slab, jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((l_out, q), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((q, l_in), jnp.uint32, sharding=one_chip),
    )
