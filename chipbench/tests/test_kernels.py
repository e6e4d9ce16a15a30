import pytest

from chipbench import kernels


def test_score_topk_bytes_hand_worked():
    # 10^5 x 768 corpus, batch 8, k' = 160: tile 1024 rows (1024 * 768 * 4
    # B = 3 MiB <= 4 MiB), 98 tiles; corpus 307,200,000 B + queries 24,576 B
    # + 98 * 8 * 160 * (4 + 4) = 1,003,520 B of per-tile candidates
    assert kernels.corpus_tile(768) == 1024
    assert kernels.score_topk_bytes(8, 100_000, 768, 160) == (
        307_200_000 + 24_576 + 1_003_520)


def test_rerank_fused_intt_bytes_hand_worked():
    # dim 768, k' = 160, N = 4096, chunk 1024: stride 1024, 4 candidates per
    # result ciphertext, 1 chunk, 40 result ciphertexts.  Per prime at
    # batch 8 (int32 words): polys 8*40*4*1*4096 = 5,242,880; slot twiddles
    # 4*4096 = 16,384; query NTTs 2*8*1*4096 = 65,536; inverse stage
    # twiddles 12*4096 = 49,152; both outputs 2*8*40*4096 = 2,621,440
    g = kernels.rerank_geometry(768, 160, 4096, 1024)
    assert g == {"cands_per_ct": 4, "chunks": 1, "num_ct": 40}
    words = 5_242_880 + 16_384 + 65_536 + 49_152 + 2_621_440
    assert kernels.rerank_fused_intt_bytes(8, 40, 4, 1, 4096) == 4 * words


def test_corpus_tile_matches_the_kernel():
    from repro.kernels.scoretopk import scoretopk

    for dim in (64, 128, 384, 768, 1024, 3072):
        assert kernels.corpus_tile(dim) == scoretopk.corpus_tile(dim)


def test_peaks_known_and_unknown_device():
    v5e = kernels.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(kernels.UnknownDevice):
        kernels.peaks("TPU v9 imaginary")
