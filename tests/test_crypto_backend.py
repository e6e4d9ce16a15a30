"""The crypto-backend seam (repro.crypto.backend) and the vectorized
Paillier fallback boundary (repro.crypto.paillier_vec): typed unknown-
backend errors, wire parity against the object path under deterministic
seeds, bit-exact batched decryption, and the oversized-key object
fallback."""

import jax
import numpy as np
import pytest

from repro.core import protocol
from repro.crypto import backend as backends
from repro.crypto import paillier as pai
from repro.crypto import paillier_vec as pvec
from repro.crypto import rlwe

DIM, KPRIME = 48, 12


def _keys(n, bits=256):
    return [pai.keygen(bits, rng=np.random.default_rng(100 + i))
            for i in range(n)]


def _unit(rng, *shape):
    x = rng.normal(size=shape)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


# -- registry / typed errors (satellite: UnknownBackend) --------------------


def test_get_backend_registry():
    assert backends.available() == ("paillier", "rlwe")
    assert backends.get_backend("rlwe").name == "rlwe"
    assert backends.get_backend("paillier").name == "paillier"


def test_unknown_backend_is_typed_valueerror():
    with pytest.raises(backends.UnknownBackend) as ei:
        backends.get_backend("ecc")
    assert isinstance(ei.value, ValueError)
    assert ei.value.backend == "ecc"
    assert ei.value.known == ("paillier", "rlwe")
    assert "ecc" in str(ei.value) and "rlwe" in str(ei.value)


def test_unknown_backend_raises_from_user_ctor():
    with pytest.raises(backends.UnknownBackend):
        protocol.RemoteRagUser(n=DIM, N=512, k=3, radius=0.05,
                               backend="bgv")


def test_scores_backend_structural_dispatch():
    params = rlwe.RlweParams(n_poly=1024, chunk=512)
    sk = rlwe.keygen(params, np.random.default_rng(0))
    ct = rlwe.encrypt_query(sk, _unit(np.random.default_rng(1), DIM),
                            np.random.default_rng(2))
    rows = _unit(np.random.default_rng(3), KPRIME, DIM)
    packed = rlwe.pack_candidates(params, rows)
    scores = rlwe.encrypted_scores(params, ct, packed, use_pallas=False)
    assert backends.scores_backend(scores).name == "rlwe"
    assert backends.scores_backend([1, 2, 3]).name == "paillier"


# -- satellite 4: wire parity at the fallback boundary ----------------------


def test_encrypt_vector_wire_parity():
    """Same seed -> the vectorized encryptor must emit the *identical*
    ciphertext integers as the object path (not just equal plaintexts):
    identical randomness consumption, identical wire bytes."""
    sk = _keys(1)[0]
    e = _unit(np.random.default_rng(5), DIM)
    want = pai.encrypt_vector(sk.pub, e, rng=np.random.default_rng(42))
    got = pvec.encrypt_vector(sk.pub, e, rng=np.random.default_rng(42))
    assert got == want


def test_encrypted_scores_wire_parity():
    """Per-lane seeded blinding: the batched RNS score path must produce
    bit-identical score ciphertexts to per-lane object calls."""
    keys = _keys(3)
    rng = np.random.default_rng(6)
    queries = _unit(rng, 3, DIM)
    cands = [_unit(rng, KPRIME, DIM) for _ in keys]
    enc = [pai.encrypt_vector(k.pub, q, rng=np.random.default_rng(7 + i))
           for i, (k, q) in enumerate(zip(keys, queries))]
    want = [pai.encrypted_scores(k.pub, e, c,
                                 rng=np.random.default_rng(50 + i))
            for i, (k, e, c) in enumerate(zip(keys, enc, cands))]
    got = pvec.encrypted_scores_batch(
        [k.pub for k in keys], enc, cands,
        rngs=[np.random.default_rng(50 + i) for i in range(3)])
    assert got == want


@pytest.mark.parametrize("batch", [1, 3, 8])
def test_decrypt_bit_exact_across_batch_sizes(batch):
    """Vectorized score + vectorized decrypt == object score + object
    decrypt, element-exact, at batch 1 / 3 / 8."""
    keys = _keys(batch)
    rng = np.random.default_rng(batch)
    queries = _unit(rng, batch, DIM)
    cands = [_unit(rng, KPRIME, DIM) for _ in keys]
    enc = [pvec.encrypt_vector(k.pub, q, rng=np.random.default_rng(9))
           for k, q in zip(keys, queries)]
    cts = pvec.encrypted_scores_batch([k.pub for k in keys], enc, cands)
    got = pvec.decrypt_scores_batch(keys, cts)
    for k, e, c, g in zip(keys, enc, cands, got):
        obj = pai.decrypt_scores(k, pai.encrypted_scores(k.pub, e, c))
        assert np.array_equal(g, obj)
        assert g.shape == (KPRIME,)


def test_oversized_key_selects_object_path():
    """A 1024-bit key needs 90 RNS channels — over the MAX_CHANNELS=64
    vectorization budget — so every stage must fall back to the object
    path per lane, counted, while a 256-bit lane in the same batch stays
    vectorized.  Results remain exact either way."""
    from repro.kernels.bignum import ref

    big = pai.keygen(1024, rng=np.random.default_rng(0))
    small = pai.keygen(256, rng=np.random.default_rng(1))
    assert not ref.fits(big.pub.n_sq) and ref.fits(small.pub.n_sq)
    assert not pvec.fits(big.pub) and pvec.fits(small.pub)

    rng = np.random.default_rng(2)
    queries = _unit(rng, 2, DIM)
    cands = [_unit(rng, KPRIME, DIM) for _ in range(2)]

    pvec.reset_counters()
    enc = [pvec.encrypt_vector(k.pub, q, rng=np.random.default_rng(3))
           for k, q in zip((big, small), queries)]
    assert pvec.counters == {"vectorized": 1, "object": 1}

    cts = pvec.encrypted_scores_batch([big.pub, small.pub], enc, cands)
    assert pvec.counters == {"vectorized": 2, "object": 2}

    got = pvec.decrypt_scores_batch([big, small], cts)
    assert pvec.counters == {"vectorized": 3, "object": 3}

    for k, e, c, g in zip((big, small), enc, cands, got):
        obj = pai.decrypt_scores(k, pai.encrypted_scores(k.pub, e, c))
        assert np.array_equal(g, obj)


def test_fallback_wire_parity_under_seeds():
    """The fallback lane consumes its rng exactly as a direct object call
    would: same seeds -> same ciphertext integers on both sides of the
    fits() boundary."""
    big = pai.keygen(1024, rng=np.random.default_rng(0))
    e = _unit(np.random.default_rng(4), DIM)
    assert (pvec.encrypt_vector(big.pub, e, rng=np.random.default_rng(8))
            == pai.encrypt_vector(big.pub, e, rng=np.random.default_rng(8)))
    enc = pai.encrypt_vector(big.pub, e, rng=np.random.default_rng(8))
    cands = [_unit(np.random.default_rng(5), KPRIME, DIM)]
    assert (pvec.encrypted_scores_batch(
                [big.pub], [enc], cands,
                rngs=[np.random.default_rng(11)])[0]
            == pai.encrypted_scores(big.pub, enc, cands[0],
                                    rng=np.random.default_rng(11)))


# -- exactness of the float64 channels on the device ------------------------


def test_float64_rule_admits_cpu_only(monkeypatch):
    """The vectorized tier runs where doubles are native (the CPU) and
    nowhere else: a TPU emulates float64."""
    from repro import kernels
    assert kernels.exact_float64()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not kernels.exact_float64()


def test_inexact_device_refuses_vectorized_tier(monkeypatch):
    """On an inexact device every vectorized entry point raises the typed
    error before touching the lane's rng — never wrong integers — while
    lanes on the object path (oversized keys) still serve exactly."""
    monkeypatch.setattr(pvec, "exact_float64", lambda: False)
    small = _keys(1)[0]
    big = pai.keygen(1024, rng=np.random.default_rng(0))
    rng = np.random.default_rng(3)
    e = _unit(rng, DIM)
    cands = [_unit(rng, KPRIME, DIM)]
    enc = pai.encrypt_vector(small.pub, e, rng=np.random.default_rng(4))
    cts = [pai.encrypted_scores(small.pub, enc, cands[0])]

    draws = np.random.default_rng(9)
    with pytest.raises(pvec.InexactDevice):
        pvec.encrypt_vector(small.pub, e, rng=draws)
    assert draws.bit_generator.state == \
        np.random.default_rng(9).bit_generator.state
    with pytest.raises(pvec.InexactDevice):
        pvec.encrypted_scores_batch([small.pub], [enc], cands)
    with pytest.raises(pvec.InexactDevice):
        pvec.decrypt_scores_batch([small], cts)
    assert (pvec.encrypt_vector(big.pub, e, rng=np.random.default_rng(8))
            == pai.encrypt_vector(big.pub, e, rng=np.random.default_rng(8)))


# -- backend objects drive the protocol symmetrically -----------------------


@pytest.mark.parametrize("backend", ["rlwe", "paillier"])
def test_backend_roundtrip_through_protocol(backend):
    """Both registered backends run the whole sequential protocol through
    the same seam methods — no scheme-specific branches left in the
    driver."""
    import jax

    from repro.data import synth
    from repro.retrieval.index import FlatIndex

    rng = np.random.default_rng(0)
    emb = synth.uniform_corpus(rng, 256, DIM)
    index = FlatIndex.build(
        emb, documents=[f"d{i}".encode() for i in range(256)])
    kw = ({"rlwe_params": rlwe.RlweParams(n_poly=1024, chunk=512)}
          if backend == "rlwe" else {"paillier_bits": 256})
    user = protocol.RemoteRagUser(n=DIM, N=256, k=3, radius=0.05,
                                  backend=backend,
                                  rng=np.random.default_rng(1), **kw)
    assert user.impl is backends.get_backend(backend)
    cloud = protocol.RemoteRagCloud(index, **(
        {"rlwe_params": kw["rlwe_params"]} if backend == "rlwe" else {}))
    q = synth.queries_near_corpus(np.random.default_rng(2), emb, 1)[0]
    docs, ids, tr = protocol.run_remoterag(user, cloud, q,
                                           jax.random.PRNGKey(0))
    assert len(docs) == 3 and ids.shape == (3,)
    assert tr.request_bytes > 0 and tr.reply_bytes > 0
    oracle = np.argsort(-(emb @ q), kind="stable")[:3]
    assert set(ids.tolist()) == set(oracle.tolist())
