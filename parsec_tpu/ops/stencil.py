"""1-D stencil kernels: the VPU/bandwidth workhorse.

Kernel incarnations for the stencil task bodies
(``tests/apps/stencil/stencil_internal.h`` CORE_stencil_1D role):

- :func:`stencil1d_xla` — the jnp tap loop, and the DEFAULT incarnation:
  XLA fuses the taps into one pass, so the model's traceable uses it (its
  bandwidth is not measured on this machine).
- :func:`stencil1d_pallas` — the hand-tiled alternative: rows move
  HBM→VMEM in (8, lane-tile) blocks and every tap accumulates on-chip as
  a lane rotation of the block and its right neighbour (see
  /opt/skills/guides/pallas_guide.md).  For shapes/epilogues XLA fuses
  poorly.  It compiles through Mosaic unless a test asks for interpret
  mode; no row is too long for it.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# widest lane tile of one block: (8, 2048) f32 is 64 KiB, so the two input
# views, the output and their double buffers sit far inside VMEM whatever
# the row length
_LANE_TILE = 2048


def stencil1d_xla(padded: Any, weights: Any) -> Any:
    """out[i] = sum_j w[j] * padded[i+j] over the interior (tap loop)."""
    w = np.asarray(weights)
    n = padded.shape[-1] - len(w) + 1
    ct = jnp.result_type(padded.dtype, jnp.float32)
    out = jnp.zeros(padded.shape[:-1] + (n,), ct)
    for j in range(len(w)):
        out = out + ct.type(float(w[j])) * padded[..., j:j + n].astype(ct)
    return out.astype(padded.dtype)


def _stencil_block_kernel(cur_ref, nxt_ref, o_ref, *, w: tuple):
    # output lanes [0, tl) of this block read input lanes [0, tl + taps - 1):
    # the block itself plus the head of its right neighbour.  Each tap is a
    # lane rotation of the pair cut back to the first tile, so no load
    # starts off a 128-lane boundary.
    from jax.experimental.pallas import tpu as pltpu
    tl = o_ref.shape[1]
    ct = jnp.result_type(o_ref.dtype, jnp.float32)
    x = jnp.concatenate([cur_ref[...], nxt_ref[...]], axis=1).astype(ct)
    acc = jnp.zeros(o_ref.shape, ct)
    for j in range(len(w)):
        shifted = x if j == 0 else pltpu.roll(x, 2 * tl - j, 1)
        acc = acc + ct.type(w[j]) * shifted[:, :tl]
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("weights", "interpret"))
def _stencil1d_pallas_rows(padded: Any, weights: tuple,
                           interpret: bool) -> Any:
    from jax.experimental import pallas as pl

    taps = len(weights)
    b, npad = padded.shape
    n = npad - taps + 1
    tl = min(_LANE_TILE, -(-n // 128) * 128)     # lanes come in 128s
    if taps - 1 > tl:
        raise ValueError(f"stencil of {taps} taps is wider than the "
                         f"{tl}-lane tile")
    nt = -(-n // tl)
    b8 = -(-b // 8) * 8                          # sublanes come in 8s
    # one tile of slack so the last block has a right neighbour
    padded = jnp.pad(padded, ((0, b8 - b), (0, (nt + 1) * tl - npad)))
    out = pl.pallas_call(
        functools.partial(_stencil_block_kernel, w=weights),
        grid=(b8 // 8, nt),
        in_specs=[pl.BlockSpec((8, tl), lambda i, j: (i, j)),
                  pl.BlockSpec((8, tl), lambda i, j: (i, j + 1))],
        out_specs=pl.BlockSpec((8, tl), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b8, nt * tl), padded.dtype),
        interpret=interpret,
    )(padded, padded)
    return out[:b, :n]


def stencil1d_pallas(padded: Any, weights: Any,
                     interpret: bool = False) -> Any:
    """Blocked stencil over ``padded`` (1-D or batched rows); the last dim
    carries ``len(weights)-1`` halo elements, dropped in the output.
    ``interpret=True`` runs the Pallas interpreter — for tests off-TPU."""
    w = tuple(float(x) for x in np.asarray(weights))
    lead = padded.shape[:-1]            # arbitrary leading dims, like xla
    p2 = padded.reshape((-1, padded.shape[-1]))
    out = _stencil1d_pallas_rows(p2, w, interpret)
    return out.reshape(lead + (out.shape[-1],))
