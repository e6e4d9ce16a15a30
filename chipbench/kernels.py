"""Bytes each served kernel must move, from the shapes of its call, and the
chip's published peaks (``peaks.json``, keyed by JAX's ``device_kind``).

A kernel's roofline share here is the least time its HBM traffic could take
at the chip's peak bandwidth over the time the trace gives it.  The bound is
bytes only: the scan is an f32 dot at about 4 flop/byte at batch 8, far
under the v5e ridge (197e12 / 819e9 = 240 flop/byte), and the NTT kernels
are int32 vector work, for which v5e publishes no peak.  Each operand is
counted once, as the least traffic the call needs.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
I32 = F32 = 4


class UnknownDevice(KeyError):
    """The device kind has no row in peaks.json."""


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def corpus_tile(dim: int) -> int:
    """Rows per score-top-k' corpus tile at width ``dim``: the largest power
    of two <= 2048 (and >= 8) whose f32 tile fits 4 MiB."""
    tile = 2048
    while tile > 8 and tile * dim * F32 > (4 << 20):
        tile //= 2
    return tile


def score_topk_bytes(batch: int, n_rows: int, dim: int, kprime: int) -> int:
    """One fused score + per-tile top-k' call: the corpus and the queries
    read once, (tiles, batch, kk) f32 values and int32 ids written."""
    tile = min(corpus_tile(dim), n_rows)
    kk = min(kprime, tile, n_rows)
    tiles = -(-n_rows // tile)
    return (n_rows * dim * F32 + batch * dim * F32
            + tiles * batch * kk * (F32 + I32))


def rerank_fused_intt_bytes(batch: int, num_ct: int, cands_per_ct: int,
                            chunks: int, n_poly: int) -> int:
    """One per-prime rotate -> Hadamard -> accumulate -> inverse-NTT call:
    the gathered candidate plaintexts, the slot twiddles, both query NTTs
    and the inverse-NTT stage twiddles read, both result components
    written, all int32."""
    stages = n_poly.bit_length() - 1
    reads = (batch * num_ct * cands_per_ct * chunks * n_poly
             + cands_per_ct * n_poly
             + 2 * batch * chunks * n_poly
             + stages * n_poly)
    writes = 2 * batch * num_ct * n_poly
    return (reads + writes) * I32


def rerank_geometry(dim: int, kprime: int, n_poly: int, chunk: int) -> dict:
    """The re-rank's ciphertext layout: candidates per result ciphertext,
    chunks per embedding and result ciphertexts per request."""
    stride = chunk if dim <= chunk else 2 * chunk
    cpt = n_poly // stride
    return {"cands_per_ct": cpt, "chunks": -(-dim // chunk),
            "num_ct": -(-kprime // cpt)}
