"""The numerics of the references and of their controls.

``fp8`` rounds a matmul or convolution operand to float8 e4m3 with one
scale per tensor (amax / 448), the rest of the computation in float32.
The rounding is on the forward values; a gradient passes it unchanged
(straight through); the LM reference's control.  ``Numerics`` says how
the vision reference computes: the reference itself, and each control one
step below it."""

from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp


def identity(a):
    return a


def fp8(a):
    a = a.astype(jnp.float32)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0)
    r = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a + jax.lax.stop_gradient(r - a)


def _split(a):
    """``a`` as the sum of two bfloat16 values (held in float32)."""
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class Numerics:
    """Matmul and convolution products in float32 (``passes`` 6, the TPU's
    ``highest``) or, with ``passes`` 3, in the TPU's ``high``: three
    bfloat16 passes; the scan's streams and carry rounded to ``scan``
    (values stay float32)."""
    passes: int = 6
    scan: str = "float32"

    def product(self, op, a, b):
        """``op(a, b)`` for a bilinear ``op`` that multiplies at highest
        precision.  With ``passes`` 3 the value is a_hi b_hi + a_hi b_lo +
        a_lo b_hi, each product exact in float32 (as the TPU computes
        ``high``, on any backend); the gradient is the float32 product's."""
        y = op(a, b)
        if self.passes == 3:
            (ah, al), (bh, bl) = _split(a), _split(b)
            y3 = op(ah, bh) + (op(ah, bl) + op(al, bh))
            y = y + jax.lax.stop_gradient(y3 - y)
        return y

    def round_scan(self, a):
        if self.scan == "float32":
            return a
        return a.astype(self.scan).astype(jnp.float32)


REFERENCE = Numerics()
# float32 at ``highest`` (six bfloat16 passes on TPU) -> ``high`` (three)
HIGH = Numerics(passes=3)
# the scan's streams (taps, input, gate) and its carry in bfloat16
BF16_SCAN = Numerics(scan="bfloat16")
