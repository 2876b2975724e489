"""One-pass Pallas extraction of minor-dimension halo planes.

The TPU counterpart of the reference's custom pack kernels for its
worst-strided plane (`/root/reference/src/update_halo.jl:439-462`, thread
blocks re-shaped per dimension at `:341-353`): on TPU the worst case is the
sublane/lane (y/z) dimensions, where materializing a squeezed plane makes
XLA emit a separate relayout pass per plane over the source tiles (measured
491 us for the four y/z send planes of a 256^3 f32 block on v5e).  This
kernel streams the block through VMEM once and emits every requested plane
as a dense 2-D array (measured 92 us — the cost of one HBM read of the
block), including the in-kernel lane extraction for z planes.

Used by the halo engine when at least two minor-dim planes must be
materialized for a `ppermute` (z-split or y+z-split meshes); single planes
and untiled-dim (x) planes stay lazy XLA slices.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

# VMEM budget for the double-buffered input block.
_BLOCK_BYTES = 4 * 1024 * 1024


def pack_planes_supported(shape, dtype) -> bool:
    import numpy as np

    if len(shape) != 3:
        return False
    if np.dtype(dtype).itemsize > 4:
        # The in-kernel lane extraction is 32-bit territory in Mosaic;
        # 64-bit planes stay lazy XLA slices.
        return False
    s0, s1, s2 = shape
    return s0 >= 1 and s1 * s2 * 4 <= _BLOCK_BYTES


def _pick_bx(s0: int, s1: int, s2: int, itemsize: int) -> int:
    bx = 1
    while (s0 % (bx * 2) == 0
           and (bx * 2) * s1 * s2 * itemsize <= _BLOCK_BYTES):
        bx *= 2
    return bx


def pack_planes(A, reqs: Sequence[Tuple[int, int]]) -> List:
    """Extract the squeezed planes `[A[:, p, :] or A[:, :, p] for (d, p) in
    reqs]` (d in {1, 2}) in a single pass over `A`.  TPU compiled mode only —
    callers gate on platform and fall back to XLA slices elsewhere."""
    import jax
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s0, s1, s2 = A.shape
    itemsize = np.dtype(A.dtype).itemsize
    bx = _pick_bx(s0, s1, s2, itemsize)
    nb = s0 // bx
    reqs = list(reqs)

    def kernel(a_ref, *outs):
        for (d, p), o_ref in zip(reqs, outs):
            o_ref[:] = a_ref[:, p, :] if d == 1 else a_ref[:, :, p]

    vma = getattr(getattr(A, "aval", None), "vma", None)

    def shp(d):
        dims = (s0, s2) if d == 1 else (s0, s1)
        return (jax.ShapeDtypeStruct(dims, A.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(dims, A.dtype))

    out_specs = [
        pl.BlockSpec((bx, s2 if d == 1 else s1), lambda i: (i, 0))
        for d, _ in reqs
    ]
    return list(pl.pallas_call(
        kernel,
        name="halo_pack_planes",
        grid=(nb,),
        in_specs=[pl.BlockSpec((bx, s1, s2), lambda i: (i, 0, 0))],
        out_specs=out_specs,
        out_shape=[shp(d) for d, _ in reqs],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
    )(A))
