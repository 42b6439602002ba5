"""Int8 stochastic-rounding quantizer kernel (Pallas TPU).

Used by the HierTrain tiered gradient sync: "backend" (parameter-heavy)
gradient tiers cross the inter-pod DCN link int8-quantized — the TPU
analogue of JALAD's 8-bit edge-cloud compression, applied to the
paper's insight that bulk parameters should not cross the slow link at
full width.

Per-row absmax scaling::

    scale_i = max_j |x_ij| / 127
    q_ij    = clip(floor(x_ij / scale_i + u_ij), -127, 127)   u ~ U[0,1)

Stochastic rounding keeps the quantizer unbiased (E[q*scale] = x), so
the compressed all-reduce is an unbiased gradient estimator — the
property the tiered-sync equivalence tests check.  The uniform noise is
an explicit kernel input (generated with jax.random outside), keeping
runs reproducible and the kernel portable to interpret mode.

Tiling.  A row can be far wider than VMEM (the wire codec quantizes a
whole ``T x D`` activation sample as one row), so the row is cut into
lane-aligned ``[bm, bn]`` tiles and the grid makes two passes over
them: ``(M/bm, 2, N/bn)``.  Pass 0 folds each tile's row absmax into a
VMEM accumulator; pass 1 quantizes with the finished scale.  The
quantized output and the noise keep block ``(i, 0)`` during pass 0, so
neither is fetched nor written back until pass 1 reaches it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import pick_block

LANES = 128
ROW_ALIGN = 8
TILE_ELEMS = 1 << 18            # 1 MiB of f32 per input tile


def _quant_kernel(x_ref, u_ref, q_ref, scale_ref, amax_scr):
    phase = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((phase == 0) & (j == 0))
    def _init():
        amax_scr[...] = jnp.zeros_like(amax_scr)

    @pl.when(phase == 0)
    def _absmax():
        x = x_ref[...].astype(jnp.float32)             # [bm, bn]
        m = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        amax_scr[...] = jnp.maximum(amax_scr[...], m)

    @pl.when(phase == 1)
    def _quantize():
        x = x_ref[...].astype(jnp.float32)
        scale = jnp.maximum(amax_scr[:, :1], 1e-30) / 127.0   # [bm, 1]
        u = u_ref[...].astype(jnp.float32)
        q = jnp.floor(x / scale + u)
        q = jnp.clip(q, -127.0, 127.0)
        q_ref[...] = q.astype(jnp.int8)
        scale_ref[...] = jnp.broadcast_to(scale, scale_ref.shape)


def quantize_int8(x: jax.Array, noise: jax.Array, *, block_rows: int = 256,
                  interpret: bool = False):
    """x, noise: [M, N] (noise uniform in [0,1)).  Returns
    (q int8 [M, N], scale f32 [M])."""
    M, N = x.shape
    Mp = M if M <= block_rows else -(-M // ROW_ALIGN) * ROW_ALIGN
    bm = pick_block(Mp, block_rows, ROW_ALIGN)
    Np = -(-N // LANES) * LANES
    if (Mp, Np) != (M, N):
        # Zero padding is exact: it never raises a row's absmax, and the
        # padded rows/lanes are cut off below.
        pad = ((0, Mp - M), (0, Np - N))
        x, noise = jnp.pad(x, pad), jnp.pad(noise, pad)
    bn = pick_block(Np, TILE_ELEMS // max(bm, ROW_ALIGN), LANES)

    q, scale = pl.pallas_call(
        _quant_kernel,
        name="int8_quant",
        grid=(Mp // bm, 2, Np // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, p, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, p, j: (i, j * p)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, p, j: (i, j * p)),
            pl.BlockSpec((bm, LANES), lambda i, p, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Np), jnp.int8),
            jax.ShapeDtypeStruct((Mp, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, LANES), jnp.float32)],
        interpret=interpret,
    )(x, noise)
    return q[:M, :N], scale[:M, 0]


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse map (pure jnp — a single multiply needs no kernel)."""
    return q.astype(jnp.float32) * scale[:, None]
