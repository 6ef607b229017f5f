"""Pallas TPU kernel: gather packed rows by index and decode in VMEM.

The packed ``embed`` path (``PackedTensor.take``): a decode tick gathers a
handful of rows out of a 150k-row vocabulary table. The jnp oracle
gathers the uint32 words with XLA and decodes the gathered rows; this
kernel moves the whole read onto the scalar-prefetch DMA path —

    HBM:  the aligned (8, words) sublane slab holding the wanted row, one
          per grid step, the slab index coming from a scalar-prefetched
          index vector (a TPU block cannot be a single row)
    VMEM: a dynamic-sublane read of the row, the whole-vreg shift/or
          unpack of ``bitpack.unpack_block`` + Value Converter
          (``formats.decode_float`` / ``decode_int``)
    HBM:  the decoded row, written into an (8, n) 32-bit output block
          that stays resident across the 8 grid steps that fill it

so gather traffic stays a few slabs of packed words and the decoded table
never materializes. Index order is arbitrary (out-of-order, duplicated
rows are fine — each grid step DMAs its own slab, and consecutive steps
on the same slab skip the re-DMA).

``interpret=None`` resolves through ``repro.compat.pallas``: compiled on
a real TPU, interpret (Python validation) elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat.pallas import pallas_interpret_default
from repro.core import bitpack
from repro.core.formats import FLOAT_FORMATS, decode_float, decode_int

ROWS = 8                               # sublanes per slab / rows per block


def _take_kernel(idx_ref, p_ref, o_ref, *, bits: int, n: int, lanes: int,
                 kind: str, signed: bool):
    i, j = pl.program_id(0), pl.program_id(1)
    row = p_ref[pl.ds(idx_ref[i * ROWS + j] % ROWS, 1), :]
    codes = bitpack.unpack_block(row, bits, lanes)[:, :n]
    if kind == "float":
        out = decode_float(codes, FLOAT_FORMATS[bits])
    else:
        out = decode_int(codes, bits, signed)
    o_ref[pl.ds(j, 1), :] = out


@functools.partial(
    jax.jit, static_argnames=("bits", "n", "kind", "signed", "out_dtype",
                              "interpret")
)
def take_rows(
    packed: jnp.ndarray,
    indices: jnp.ndarray,
    bits: int,
    n: int,
    kind: str = "float",
    signed: bool = True,
    out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Gather+decode rows: packed (R, packed_group_words(n)) uint32,
    indices (B,) int -> (B, n) decoded values. One grid step per gathered
    row; the row's slab of packed words is DMA'd straight from the
    scalar-prefetched index, so only gathered slabs ever reach VMEM."""
    from jax.experimental.pallas import tpu as pltpu

    interpret = pallas_interpret_default(interpret)
    assert packed.ndim == 2, "flatten leading index dims before calling"
    assert indices.ndim == 1
    b = indices.shape[0]
    b_pad = -(-b // ROWS) * ROWS
    idx = jnp.pad(indices.astype(jnp.int32), (0, b_pad - b))
    words = packed.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b_pad // ROWS, ROWS),
        in_specs=[
            pl.BlockSpec((ROWS, words),
                         lambda i, j, idx_ref:
                         (idx_ref[i * ROWS + j] // ROWS, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, n), lambda i, j, idx_ref: (i, 0)),
    )
    # rows land at a dynamic sublane of the output block, which Mosaic
    # stores only for 32-bit tiles: decode to f32 / int32, cast after
    out = pl.pallas_call(
        functools.partial(_take_kernel, bits=bits, n=n,
                          lanes=bitpack.tile_lanes(n, bits), kind=kind,
                          signed=signed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b_pad, n), jnp.float32 if kind == "float" else jnp.int32),
        interpret=interpret,
    )(idx, packed)
    return out[:b].astype(out_dtype)
