"""Mamba-1's selective scan over one sequence, for the admission prefill:
``S_t = exp(delta_t A) * S_{t-1} + B_t (delta_t x_t)``, ``y_t = C_t . S_t``
a channel, with the state ``S [N, inner]`` float32 (the state entries on the
sublanes, the channels on the lanes).

Mamba-1 decays each (state entry, channel) pair at its own rate, so it has
no matrix-product form inside a chunk (Mamba-2's scalar decay a head is
what gives it one): a chunk's closed form would weigh every (t, s) pair of
the chunk by a decay of its own a channel and entry, ``[Q, Q, N, inner]``.
The scan is therefore sequential in time, and what is chosen is where the
state lives while it walks:

  * on the chip, a Pallas kernel (``selective_scan``) whose grid is (channel
    tile of 128, time chunk): each step's state ``[N, 128]`` stays in
    vector registers through a time chunk's ``chunk`` steps and in VMEM
    between chunks; the inputs stream a chunk at a time, and nothing of size
    ``[T, N, inner]`` is ever written (at 16,384 positions and 5,120 channels
    that would be 5.4 GB a layer in float32). B and C come in broadcast over
    the 128 lanes of a tile, so a step's products are whole vector
    registers;
  * elsewhere (the CPU, and as the kernel's oracle), ``lax.scan`` over the
    time chunks, each a ``lax.scan`` over its steps: the same arithmetic in
    the same order.

Reference anchor: none in the DL4J 0.4 reference; provenance Gu & Dao,
"Mamba: Linear-Time Sequence Modeling with Selective State Spaces" (the
selective scan and its hardware-aware sequential form).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import device

NAME = "selective_scan"
LANES = 128
UNROLL = 8      # steps a pass of the kernel's loop


def fits(inner: int) -> bool:
    """The kernel takes the scan on a TPU, over channels in whole tiles of
    128 lanes."""
    return device.on_tpu() and inner % LANES == 0


def _pad(t: int, chunk: int):
    """Rows of padding that make t a whole number of chunks."""
    return -(-t // chunk) * chunk - t


def scan_xla(delta, du, b, c, a, chunk: int):
    """The scan in plain XLA: delta, du [T, inner] float32 (du = delta x),
    b, c [T, N], a [N, inner] (= -exp(A_log)) -> (y [T, inner], S [N,
    inner] after the last position). Padding rows carry delta = du = 0:
    no decay, no input."""
    t, inner = delta.shape
    n = b.shape[1]
    p = _pad(t, chunk)
    nc = (t + p) // chunk
    ch = lambda m: jnp.pad(m, ((0, p), (0, 0))).reshape(
        (nc, chunk) + m.shape[1:])

    def step(s, xs):
        d, u, b_, c_ = xs
        s = jnp.exp(d[None] * a) * s + b_[:, None] * u[None]
        return s, jnp.sum(s * c_[:, None], axis=0)

    def one_chunk(s, xs):
        return lax.scan(step, s, xs)

    s, y = lax.scan(one_chunk, jnp.zeros((n, inner), jnp.float32),
                    (ch(delta), ch(du), ch(b), ch(c)))
    return y.reshape(nc * chunk, inner)[:t], s


def _kernel(a_ref, d_ref, u_ref, b_ref, c_ref, y_ref, s_ref, state, *,
            chunk):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    a = a_ref[...]                                         # [N, 128]

    def steps(k, s):
        # UNROLL steps a pass, written out: the loop's own unroll is
        # all-or-nothing in Mosaic
        for j in range(UNROLL):
            i = k * UNROLL + j
            d = d_ref[pl.ds(i, 1), :]                      # [1, 128]
            u = u_ref[pl.ds(i, 1), :]
            s = jnp.exp(d * a) * s + b_ref[i] * u          # [N, 128]
            y_ref[pl.ds(i, 1), :] = jnp.sum(s * c_ref[i], axis=0,
                                            keepdims=True)
        return s

    s = lax.fori_loop(0, chunk // UNROLL, steps, state[...])
    state[...] = s

    @pl.when(ti == pl.num_programs(1) - 1)
    def _end():
        s_ref[...] = s


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def scan_kernel(delta, du, b, c, a, *, chunk: int, interpret: bool = False):
    """The scan in one Pallas call: the arguments and results of
    ``scan_xla``."""
    t, inner = delta.shape
    n = b.shape[1]
    p = _pad(t, chunk)
    rows = lambda m: jnp.pad(m, ((0, p), (0, 0)))
    # B and C a step as a whole [N, 128] tile: broadcast over the lanes
    lanes = lambda m: jnp.broadcast_to(rows(m)[:, :, None],
                                       (t + p, n, LANES))
    tiles = (inner // LANES, (t + p) // chunk)
    row_block = pl.BlockSpec((chunk, LANES), lambda ci, ti: (ti, ci))
    bc_block = pl.BlockSpec((chunk, n, LANES), lambda ci, ti: (ti, 0, 0))
    state_block = pl.BlockSpec((n, LANES), lambda ci, ti: (0, ci))
    y, s = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=tiles,
        in_specs=[state_block, row_block, row_block, bc_block, bc_block],
        out_specs=[row_block, state_block],
        out_shape=[jax.ShapeDtypeStruct((t + p, inner), jnp.float32),
                   jax.ShapeDtypeStruct((n, inner), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=6 * (t + p) * n * inner,
            transcendentals=(t + p) * n * inner,
            bytes_accessed=4 * (3 * (t + p) * inner
                                + 2 * (t + p) * n * LANES * tiles[0]
                                + 2 * n * inner)),
        interpret=interpret,
        name=NAME,
    )(a, rows(delta), rows(du), lanes(b), lanes(c))
    return y[:t], s


def selective_scan(delta, du, b, c, a, *, chunk: int):
    """The scan of ``scan_xla``'s arguments: the kernel where it takes them
    (``fits``), else the XLA form."""
    if fits(delta.shape[1]):
        return scan_kernel(delta, du, b, c, a, chunk=chunk)
    return scan_xla(delta, du, b, c, a, chunk)
