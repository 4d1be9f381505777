"""Strict-precision conv as a bf16x3 decomposition.

The north-star bar (BASELINE.json north_star; utils/equivalence.py) wants
float32-strict math on both backends. `jax.default_matmul_precision(
'float32')` makes XLA compile convs at HIGHEST precision; whether that
compile is practical on the attached chip is not measured. This module
spells the same arithmetic out: split each f32 conv operand into EXACT
bf16 high/low parts (x = hi + lo with hi = bf16(x); both parts round-trip
bf16 losslessly) and take three DEFAULT-precision convs:

    conv(x, w) ~= conv(hi_x, hi_w) + conv(hi_x, lo_w) + conv(lo_x, hi_w)

Each pass multiplies exactly-representable bf16 values on the MXU with
f32 accumulation, so the only dropped term is lo*lo ~ 2^-16 * 2^-16
relative — f32-class accuracy through the DEFAULT conv compile path. This
is the same decomposition XLA's own HIGHEST conv uses. Applied on BOTH
equivalence legs so the curves compare backend numerics (accumulation
order), not decomposition error.
"""

from __future__ import annotations

import contextlib
from functools import partial

from deeplearning4j_tpu.ops import env as envknob

import jax.numpy as jnp
from jax import lax

_STRICT_CONV = 0


@contextlib.contextmanager
def strict_conv_3pass():
    """Scope (trace-time) in which conv layers run the bf16x3 strict
    decomposition instead of one default-precision conv. Mirrors
    ops/pallas_kernels.pallas_disabled's override pattern."""
    global _STRICT_CONV
    _STRICT_CONV += 1
    try:
        yield
    finally:
        _STRICT_CONV -= 1


def strict_conv_active() -> bool:
    return _STRICT_CONV > 0 or (
        envknob.raw("DL4J_TPU_STRICT_CONV") == "3pass")


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def conv_f32_3pass(x, w, **conv_kwargs):
    """f32-class-accurate conv via three DEFAULT-precision passes (module
    docstring). The explicit precision argument overrides any ambient
    `jax.default_matmul_precision('float32')`, keeping the conv on the
    fast compile path even inside a globally-strict region."""
    conv = partial(lax.conv_general_dilated,
                   precision=lax.Precision.DEFAULT, **conv_kwargs)
    xh, xl = _split_bf16(jnp.asarray(x, jnp.float32))
    wh, wl = _split_bf16(jnp.asarray(w, jnp.float32))
    return conv(xh, wh) + conv(xh, wl) + conv(xl, wh)
