"""The cells' data is the same draw for draw as before topics, bursts and
hot shifts existed: digests of the corpus, the documents and the three
committed mixes' schedules at a fixed seed and a small size, recorded on
the tree before those keys were added."""

import hashlib

import numpy as np
import pytest

from chipbench import corpus, loadgen, spec

SEED = 2**40 + 99


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        if a is None:
            h.update(b"none")
            continue
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def rng(stream):
    return np.random.default_rng(corpus.seeds(SEED)[stream])


def test_unit_rows_unchanged():
    # 70000 rows: past the first block of BLOCK_ROWS
    emb = corpus.unit_rows(rng("corpus"), 70000, 8)
    assert digest(emb) == "7fac9f993f6a99f0"
    emb2, topics = corpus.embeddings(rng("corpus"),
                                     {"n_docs": 70000, "dim": 8})
    assert topics is None and np.array_equal(emb, emb2)


def test_payloads_unchanged():
    docs = corpus.payloads(rng("docs"), 500, 64)
    assert hashlib.sha256(b"|".join(docs)).hexdigest()[:16] \
        == "d358ee03dd7d7c82"


@pytest.mark.parametrize("mix, rows, want", [
    ("poisson_dense", 100000, "8d955195ccd21baa"),
    ("poisson_cold", 1000000, "dacc276485a8863c"),
    ("closed32", 100000, "821ee74f9eb9fc28"),
])
def test_schedules_unchanged(mix, rows, want):
    s = loadgen.make_schedule(spec.load_traffic(mix), 40.0, rows,
                              rng("traffic"))
    assert digest(s.due_s, s.tenant, s.row) == want
