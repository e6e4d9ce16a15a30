"""Pallas kernels of the served path (NTT, fused re-rank, score-top-k), the
float64 bignum channels, and the platform rules they share: Pallas kernels
compile on a TPU, interpret on the CPU (the test platform) and are refused
anywhere else; the float64 channels run only where doubles are native — a
kernel never silently runs somewhere that hides the device."""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (Mosaic)."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; platform "
        f"{platform!r} has neither path (pass use_pallas=False)")


def resolve_use_pallas(use_pallas) -> bool:
    """``None`` -> Pallas on a TPU, the XLA references elsewhere."""
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def exact_float64() -> bool:
    """Whether the default device's float64 arithmetic is IEEE-exact, as
    the bignum channels need: true on the CPU; a TPU emulates float64
    (`chip_smoke.py --paillier` shows its channel products come out
    wrong)."""
    return jax.default_backend() == "cpu"


__all__ = ["interpret_mode", "resolve_use_pallas", "exact_float64"]
