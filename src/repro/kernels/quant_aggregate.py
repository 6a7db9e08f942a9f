"""Pallas TPU fused dequantize + weighted-aggregate kernel.

The FL hot loop the paper benchmarks (its Fig. 8e/9e/12b "network bandwidth"
plots) is client-delta aggregation. Communication-efficient FL sends int8
block-quantized deltas; the naive path dequantizes every client to f32 (4x HBM
traffic) before averaging. This kernel fuses dequant + weighted reduce so each
int8 byte is read exactly once and only the f32 result is written.

Layout: the flat ``(C, N)`` int8 deltas are viewed (a free reshape in XLA) as
``(C, N/qblock, qblock)`` — one quantization block per row — and read in
``(cb, rows, qblock)`` tiles. The scales stay lane-dense ``(C, N/qblock)`` f32
in ``(cb, rows)`` tiles; the kernel transposes each tile once so that client
c's scales form a ``(rows, 1)`` column that broadcasts along its rows' lanes.
The weights ``(C,)`` sit in SMEM as scalars. The grid walks row tiles and,
for cohorts above ``MAX_CLIENTS``, client chunks. The kernel accumulates the
clients in an unrolled loop in the fused jnp path's order,
``out = 0 + (q0*s0)*w0 + (q1*s1)*w1 + ...`` (client chunks run in order on
the resident output tile), so no ``(C, rows, qblock)`` f32 intermediate is
held in VMEM. Whether the result is bitwise the fused path's depends on how
each backend contracts multiply-adds; it is always within rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scale tiles are (cb, rows) f32, so a tile's row count is a multiple of 128
# (or all of the rows, when there are fewer).
ROW_ALIGN = 128
# Clients per grid step; larger cohorts take several steps per tile.
MAX_CLIENTS = 128
# Per-step byte budget (one buffer of deltas, scales and output);
# double-buffered it stays inside v5e's 16 MiB default scoped VMEM.
_TILE_BYTES = 5 << 20


def tile_shape(C: int, qblock: int, nblocks: int) -> tuple[int, int]:
    """(clients, rows) per grid step for C clients over ``nblocks`` rows.

    Fewer than ROW_ALIGN rows make one tile. Otherwise rows are the largest
    power of two from ROW_ALIGN to 512 whose step fits ``_TILE_BYTES``,
    halved while padding ``nblocks`` up to whole tiles would add more than
    an eighth."""
    cb = min(C, MAX_CLIENTS)
    if nblocks < ROW_ALIGN:
        return cb, nblocks
    per_row = cb * (qblock + 4) + qblock * 4
    rows = 512
    while rows > ROW_ALIGN and (rows * per_row > _TILE_BYTES
                                or 8 * (-nblocks % rows) > nblocks):
        rows //= 2
    return cb, rows


def _agg_kernel(w_ref, qd_ref, sc_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    cb = qd_ref.shape[0]
    sc = sc_ref[...].T                                   # (rows, cb)
    acc = out_ref[...]
    for c in range(cb):
        deq = qd_ref[c].astype(jnp.float32) * sc[:, c:c + 1]
        acc = acc + deq * w_ref[j * cb + c]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("cb", "rows", "interpret"))
def quant_aggregate(qdeltas, scales, weights, *, cb: int, rows: int,
                    interpret: bool = False):
    """-> (N,) f32: sum_c weights[c] * dequant(qdeltas[c]).

    ``qdeltas`` (C, N) int8, ``scales`` (C, N/qblock) f32, ``weights`` (C,),
    with C a multiple of ``cb`` and N/qblock a multiple of ``rows``
    (``tile_shape`` picks both; ``ops.quant_aggregate`` pads to them)."""
    C, N = qdeltas.shape
    nblocks = scales.shape[1]
    qblock = N // nblocks
    assert N == nblocks * qblock and nblocks % rows == 0 and C % cb == 0, \
        (C, N, qblock, cb, rows)

    out = pl.pallas_call(
        _agg_kernel,
        grid=(nblocks // rows, C // cb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((cb, rows, qblock), lambda i, j: (j, i, 0)),
            pl.BlockSpec((cb, rows), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((rows, qblock), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, qblock), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="quant_aggregate",
    )(weights.astype(jnp.float32), qdeltas.reshape(C, nblocks, qblock),
      scales)
    return out.reshape(N)
