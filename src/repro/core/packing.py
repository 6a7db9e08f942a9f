"""Pytree <-> (N,) flat packing for the quant_aggregate kernel layout.

``kernels/quant_aggregate`` reduces client deltas laid out as a dense
``(C, N) int8`` matrix plus ``(C, N/qblock) f32`` block scales. Model deltas
are pytrees of arbitrarily-shaped leaves, so the compressed path needs a
deterministic flatten: each leaf is raveled and zero-padded up to a whole
number of quantization blocks, then the padded leaves are concatenated in
``jax.tree`` leaf order.

Per-leaf padding (rather than one pad at the end) is load-bearing: it keeps
every quantization block contained within a single leaf, so the packed
quantizer produces bitwise the same (q, scale) stream as quantizing each
leaf on its own — which is exactly what the unpacked reference roundtrip
(``strategies/compressed._roundtrip_int8``) does. Error-feedback residuals
computed against either representation therefore agree bit for bit.

The pack spec (offsets, padded sizes) is a pure function of the tree
*structure*, known at trace time; nothing here inspects runtime values.
Every traced function here runs under the ``fl.pack`` scope, so the device
ops of quantization, block scales and the flat layout carry it in a
profile.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

QBLOCK = 256   # quantization block; matches _roundtrip_int8's default


class PackedDelta(NamedTuple):
    """A block-quantized flat delta: what crosses the simulated network.

    ``q``: (N,) int8 quantized values (N a multiple of the block size);
    ``scale``: (N // qblock,) f32 per-block dequant scales.
    NamedTuple => a pytree, so PackedDelta flows through vmap/scan/cond and
    picks up leading batch dims ((C, N) / (C, N/qblock)) like any leaf.
    """
    q: jax.Array
    scale: jax.Array


def _padded_size(n: int, qblock: int) -> int:
    return n + (-n) % qblock


def packed_size(template, qblock: int = QBLOCK) -> tuple[int, int]:
    """(N, n_blocks) of the packed representation of ``template``'s tree."""
    n = sum(_padded_size(leaf.size, qblock)
            for leaf in jax.tree.leaves(template))
    return n, n // qblock


def packed_nbytes(template, qblock: int = QBLOCK) -> int:
    """Wire bytes of one packed delta: 1 byte per int8 value + 4 bytes per
    f32 block scale — what a ``compression: int8`` client actually sends
    (~dense/4 + 1/qblock scale overhead; the comms plane's int8 payload)."""
    n, n_blocks = packed_size(template, qblock)
    return n + 4 * n_blocks


@jax.named_scope("fl.pack")
def pack_tree(tree, qblock: int = QBLOCK) -> jax.Array:
    """Flatten a pytree to (N,) f32, zero-padding each leaf to whole blocks."""
    pieces = []
    for leaf in jax.tree.leaves(tree):
        flat = leaf.reshape(-1).astype(jnp.float32)
        pad = (-flat.shape[0]) % qblock
        pieces.append(jnp.pad(flat, (0, pad)) if pad else flat)
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


@jax.named_scope("fl.pack")
def quantize_tree(tree, qblock: int = QBLOCK) -> PackedDelta:
    """Block-quantize a delta pytree into the kernel's packed layout."""
    from repro.kernels import ref as kref
    q, sc = kref.quantize_blockwise_ref(pack_tree(tree, qblock), block=qblock)
    return PackedDelta(q=q, scale=sc)


@jax.named_scope("fl.pack")
def dequant_flat(pd: PackedDelta) -> jax.Array:
    """(N,) f32 dequantized values; same arithmetic order as the unpacked
    reference roundtrip (int8 -> f32, then one multiply per block)."""
    n, nblocks = pd.q.shape[-1], pd.scale.shape[-1]
    qblock = n // nblocks
    deq = pd.q.astype(jnp.float32).reshape(*pd.q.shape[:-1], nblocks, qblock)
    return (deq * pd.scale[..., None]).reshape(pd.q.shape)


@jax.named_scope("fl.pack")
def unpack_tree(flat, template, qblock: int = QBLOCK):
    """Invert pack_tree: slice (N,) back into ``template``-shaped f32 leaves
    (padding lanes dropped). Caller casts to the target dtype."""
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for leaf in leaves:
        out.append(flat[off:off + leaf.size].reshape(leaf.shape))
        off += _padded_size(leaf.size, qblock)
    return jax.tree.unflatten(treedef, out)
