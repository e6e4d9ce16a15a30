"""The served path's kernels compile for a TPU v5e, at the served shapes.

Nothing runs: each test lowers and compiles for one chip of a *described*
v5e:2x2 topology (the TPU compiler is installed; no chip is attached), so
the TPU compiler's refusals — unaligned blocks, primitives without a Mosaic
lowering, too much scoped VMEM, a program that does not fit HBM — surface
here instead of on the chip.  Served shapes: RlweParams() (N=4096, three
primes), 768-dim embeddings, k'=154 (k=5 at radius 0.03 over 10^5 docs),
batches of 8 and of 3.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.crypto import rlwe
from repro.kernels.ntt import fused, ntt
from repro.kernels.ntt import ops as ntt_ops
from repro.kernels.scoretopk import ops as sops
from repro.kernels.scoretopk import scoretopk

PARAMS = rlwe.RlweParams()
N = PARAMS.n_poly
DIM, KPRIME, NUM_DOCS = 768, 154, 100_000
CPT = PARAMS.cands_per_ct(DIM)                       # 4 slots per ciphertext
NUM_CT = -(-KPRIME // CPT)                           # 39 result ciphertexts
HBM_BYTES = 16 * 2**30                               # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    # only an absent TPU compiler skips; a broken one fails every test here
    pytest.importorskip("libtpu", reason="the TPU compiler (libtpu, pinned "
                                         "in requirements.txt) is absent")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep any cache out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    """(compiled, names of the Pallas kernels in the lowered program)."""
    lowered = jax.jit(fn).lower(*args)
    return lowered.compile(), re.findall(r'kernel_name = "([^"]+)"',
                                         lowered.as_text())


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batch", [8, 3])
@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
def test_ntt_compiles(one_chip, inverse, batch):
    ctx = PARAMS.ctxs[0]
    _, kernels = _compile(
        lambda x: ntt.ntt_pallas(x, ctx, inverse=inverse, interpret=False),
        _sds(one_chip, (batch, N)))
    assert kernels == ["ntt_inv" if inverse else "ntt_fwd"]


@pytest.mark.parametrize("batch", [8, 3])
def test_pointwise_mul_compiles(one_chip, batch):
    ctx = PARAMS.ctxs[0]
    _, kernels = _compile(
        lambda a, b: ntt.pointwise_mul_pallas(a, b, ctx, interpret=False),
        _sds(one_chip, (batch, N)), _sds(one_chip, (batch, N)))
    assert kernels == ["ntt_pointwise_mul"]


@pytest.mark.parametrize("batch", [8, 3])
def test_fused_rerank_intt_compiles(one_chip, batch):
    ctx = PARAMS.ctxs[0]
    _, kernels = _compile(
        lambda p, tw, f0, f1: fused.fused_rerank_intt_pallas(
            p, tw, f0, f1, ctx, interpret=False),
        _sds(one_chip, (batch, NUM_CT, CPT, N)), _sds(one_chip, (CPT, N)),
        _sds(one_chip, (batch, 1, N)), _sds(one_chip, (batch, 1, N)))
    assert kernels == ["rerank_fused_intt"]


def test_score_topk_compiles(one_chip):
    _, kernels = _compile(
        lambda q, e: scoretopk.score_topk_pallas(q, e, kk=KPRIME,
                                                 interpret=False),
        _sds(one_chip, (8, DIM), jnp.float32),
        _sds(one_chip, (NUM_DOCS, DIM), jnp.float32))
    assert kernels == ["score_topk"]
    # the width-derived tile keeps the double-buffered corpus tile well
    # inside a v5e core's 16 MiB of scoped VMEM
    assert 2 * scoretopk.corpus_tile(DIM) * DIM * 4 <= 8 * 2**20


@pytest.mark.parametrize("num_docs", [NUM_DOCS, 1_000_000])
def test_reference_scan_fits_chip(one_chip, num_docs):
    """The XLA reference top-k' scan (the `use_pallas=False` engine) keeps
    its scratch O(B * N): at 10^6 x 768 a (B, N, n) intermediate alone
    would be 24.6 GB, more than the chip holds."""
    compiled, kernels = _compile(
        lambda q, e: sops.topk_scores(q, e, KPRIME, use_pallas=False),
        _sds(one_chip, (8, DIM), jnp.float32),
        _sds(one_chip, (num_docs, DIM), jnp.float32))
    assert kernels == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 8 * num_docs


def test_cached_scores_step_compiles(one_chip, monkeypatch):
    """The whole dense-cache scoring step (gather -> per prime: 2 query
    NTTs + fused rotate/Hadamard/iNTT) over a 10^5-doc pool, as served:
    nine kernels, the program fits one chip's HBM, and the gather reads the
    pool's rows in place: no instruction but the parameter has a result
    with one row per document (a relayout of the pool would)."""
    # the step picks interpret mode from the platform, which is the CPU
    # here; steer it to the compiled kernels this compile is for
    monkeypatch.setattr(ntt_ops, "_interpret", lambda: False)
    chunks, nprimes = PARAMS.num_chunks(DIM), PARAMS.num_primes
    pad = NUM_CT * CPT - KPRIME
    compiled, kernels = _compile(
        lambda c0, c1, polys, ids, tw: rlwe._cached_scores(
            c0, c1, polys, ids, tw, PARAMS.ctxs, CPT, pad, True),
        _sds(one_chip, (8, chunks, nprimes, N)),
        _sds(one_chip, (8, chunks, nprimes, N)),
        _sds(one_chip, (NUM_DOCS, chunks * nprimes * N)),  # the pool rows
        _sds(one_chip, (8, KPRIME)),
        _sds(one_chip, (nprimes, CPT, N)))
    # the lowered program names each kernel once per prime; the compiled
    # one runs all nine launches (two query NTTs + one re-rank per prime)
    assert sorted(set(kernels)) == ["ntt_fwd", "rerank_fused_intt"]
    assert compiled.as_text().count("tpu_custom_call") == 3 * nprimes
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert np.int64(NUM_DOCS) * chunks * nprimes * N * 4 <= used < HBM_BYTES
    pool_sized = re.findall(
        rf"^\s*(?:ROOT )?%\S+ = \w+\[{NUM_DOCS},.*?\s(\S+)\(",
        compiled.as_text(), re.M)
    assert pool_sized and set(pool_sized) == {"parameter"}
