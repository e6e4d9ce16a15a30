"""RNS Montgomery bignum primitives as jitted lax ops.

The batched counterpart of ``ref.py`` — identical formulas, expressed over
``jax.numpy`` so a whole ``[batch, k', channels]`` ciphertext block moves
through one fused XLA computation.  The two base extensions are ``@``
contractions against the fixed [s, s+1] matrices from `ref.RnsSystem`, which
XLA CPU lowers to Eigen GEMMs; everything else is elementwise and fuses.

All functions assume float64 inputs and MUST run (trace + execute) under
``jax.enable_x64(True)`` — the caller owns that context.  Constants
travel in a plain dict pytree (see `make_consts`): system matrices are
shared across lanes, per-modulus vectors (`c1`, `NMinv_t`, `one`) are
stacked/broadcast by the caller to match the value batch shape, which is
what lets one compiled kernel serve a multi-tenant batch whose lanes hold
*different* keys of one channel count.

Exactness contract (proved in ref.py, differential-tested in
tests/test_bignum.py): channels < 2^23, products < 2^46, GEMM sums
< s·2^46 <= 2^53 for s <= 128 — every double is an exact integer.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bignum import ref


def make_consts(system: ref.RnsSystem,
                moduli: Sequence[ref.RnsModulus],
                batch_ndim: int) -> dict:
    """Build the constants pytree for a stack of per-lane moduli.

    ``batch_ndim`` is the number of batch axes on the values the kernel
    will see (e.g. 2 for ``[lanes, k', channels]``): per-lane vectors are
    shaped ``[lanes, 1, ..., s]`` so they broadcast against any trailing
    batch axes, while the shared system matrices stay rank-2.
    """
    if any(m.system is not system for m in moduli):
        raise ValueError("all moduli must share one RnsSystem")
    lane_shape = (len(moduli),) + (1,) * (batch_ndim - 1)

    def stack(rows):
        arr = np.stack(rows).astype(np.float64)
        return arr.reshape(lane_shape + (arr.shape[-1],))

    return {
        "E1": jnp.asarray(system.E1), "E2": jnp.asarray(system.E2),
        "Minv_t": jnp.asarray(system.Minv_t), "c4": jnp.asarray(system.c4),
        "Mp_mod_m": jnp.asarray(system.Mp_mod_m),
        "Mpinv_r": jnp.float64(system.Mpinv_r),
        "mv": jnp.asarray(system.mv), "mpv": jnp.asarray(system.mpv),
        "tgt": jnp.asarray(system.tgt), "allm": jnp.asarray(system.allm),
        "c1": jnp.asarray(stack([m.c1 for m in moduli])),
        "NMinv_t": jnp.asarray(stack([m.NMinv_t for m in moduli])),
        "one": jnp.asarray(stack([m.one for m in moduli])),
        "plain_one": jnp.asarray(stack([m.plain_one for m in moduli])),
    }


def _mod(t: jnp.ndarray, m) -> jnp.ndarray:
    q = jnp.floor(t * (1.0 / m))
    r = t - q * m
    r = r + m * (r < 0)
    return r - m * (r >= m)


def mont_mul(a: jnp.ndarray, b: jnp.ndarray, C: dict) -> jnp.ndarray:
    """Batched RNS Montgomery multiply over channel-last arrays."""
    s = C["mv"].shape[0]
    x = _mod(a * b, C["allm"])
    xi = _mod(x[..., :s] * C["c1"], C["mv"])
    u = _mod(xi @ C["E1"], C["tgt"])
    wt = _mod(x[..., s:] * C["Minv_t"] + u * C["NMinv_t"], C["tgt"])
    xip = _mod(wt[..., :s] * C["c4"], C["mpv"])
    g2 = xip @ C["E2"]
    alpha = _mod((_mod(g2[..., s:], float(ref.RADIX)) - wt[..., s:])
                 * C["Mpinv_r"], float(ref.RADIX))
    wm = _mod(g2[..., :s] - alpha * C["Mp_mod_m"], C["mv"])
    return jnp.concatenate([wm, wt], axis=-1)


def pow_table(base: jnp.ndarray, C: dict, window: int) -> jnp.ndarray:
    """``[2^window, *base.shape]`` table of base^0 .. base^(2^w - 1)."""
    rows = [jnp.broadcast_to(C["one"], base.shape), base]
    for _ in range(2, 1 << window):
        rows.append(mont_mul(rows[-1], base, C))
    return jnp.stack(rows)


def mont_exp_digits(table: jnp.ndarray, digits: jnp.ndarray, C: dict,
                    window: int) -> jnp.ndarray:
    """Left-to-right windowed exponentiation from a precomputed table.

    ``digits`` is ``[*batch, positions]`` int32, most-significant window
    first, with ``*batch`` equal to the table's value batch shape (callers
    broadcast per-lane exponents across candidates on the host — the
    digits are tiny).  Runs as one `lax.scan` whose body is ``window``
    squarings plus one gathered multiply.
    """
    base_shape = table.shape[1:]
    acc0 = jnp.broadcast_to(C["one"], base_shape)

    def body(acc, dig):
        for _ in range(window):
            acc = mont_mul(acc, acc, C)
        t = jnp.take_along_axis(
            table, dig[None, ..., None].astype(jnp.int32), axis=0)[0]
        return mont_mul(acc, t, C), None

    acc, _ = jax.lax.scan(body, acc0, jnp.moveaxis(digits, -1, 0))
    return acc


def square_n(x: jnp.ndarray, C: dict, n: int) -> jnp.ndarray:
    for _ in range(n):
        x = mont_mul(x, x, C)
    return x


def product_reduce(x: jnp.ndarray, C: dict) -> jnp.ndarray:
    """Tree-reduce a ``[..., n, channels]`` stack to ``[..., channels]``
    with Montgomery multiplies (log2(n) levels, odd tails carried)."""
    while x.shape[-2] > 1:
        half = x.shape[-2] // 2
        y = mont_mul(x[..., :half, :], x[..., half:2 * half, :], C)
        if x.shape[-2] % 2:
            y = jnp.concatenate([y, x[..., 2 * half:, :]], axis=-2)
        x = y
    return x[..., 0, :]


def to_digits(exponents: Sequence[int], window: int,
              positions: int | None = None) -> np.ndarray:
    """Fixed-width base-2^window digit planes, most-significant first,
    ``[len(exponents), positions]`` int32 (leading zeros pad short ones)."""
    if positions is None:
        bits = max(int(e).bit_length() for e in exponents)
        positions = max(1, -(-bits // window))
    mask = (1 << window) - 1
    out = np.zeros((len(exponents), positions), np.int32)
    for i, e in enumerate(exponents):
        e = int(e)
        for p in range(positions - 1, -1, -1):
            out[i, p] = e & mask
            e >>= window
        if e:
            raise ValueError("exponent wider than digit plan")
    return out


__all__ = ["make_consts", "mont_mul", "pow_table", "mont_exp_digits",
           "square_n", "product_reduce", "to_digits"]
