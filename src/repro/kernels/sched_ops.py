"""Masked segmented argmin/argmax scoring as a Pallas TPU kernel.

Every selection the fleet scheduler makes per tick is the same reduction:
score a masked set of candidates and take the first extremum — stealing a
cloud-queued task (§5.3), picking a peer-offload export victim, choosing
the overloaded source edge and least-loaded destination edge.  On TPU the
whole fleet's selections run as one VPU pass over a ``(batch, N)`` score
tile; each row yields the first-occurrence arg-extremum and its value.

Semantics (shared bit-for-bit with :func:`repro.kernels.ref.
ref_masked_argext`, the jnp oracle):

* masked-out entries count as ``NEG`` (max mode) / ``POS`` (min mode);
* ``idx`` is the *first* index attaining the extremum (ties break low,
  matching ``jnp.argmax``/``jnp.argmin`` on the filled array);
* a row with no enabled entry returns ``idx == -1`` and the fill value.

``masked_argext`` picks its lowering with ``jax.lax.platform_dependent``:
a program compiled for a TPU runs the Mosaic kernel, one compiled for any
other platform traces the jnp reference (identical semantics, no
interpreter in the per-tick hot path).  The choice follows the platform
being compiled for, not the process's default backend, so a compile for
a described TPU contains the kernel.  A program traced under a
multi-device mesh (``jax.set_mesh``, as the fleet's sharded entry points
do) selects with the reference: XLA cannot partition a Mosaic call.
``interpret=True`` forces the kernel body through the Pallas interpreter
for equivalence tests.

Mosaic tiling: both outputs are ``(rows, 1)`` columns and the row block
is either every row (up to ``block_b``) or ``block_b`` rows, a multiple
of 8, so the kernel compiles unbatched at any row count and under the
``vmap`` the fleet tick applies over edges (which prepends a squeezed
batch dimension to every block).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

NEG = -1e30
POS = 1e30

DEFAULT_BLOCK_B = 8
_LANES = 128


def _argext_kernel(s_ref, m_ref, idx_ref, val_ref, *, is_max: bool,
                   n: int):
    """One (block_b, Np) tile → per-row (first arg-extremum, value)."""
    fill = NEG if is_max else POS
    s = s_ref[...]                                           # (bb, Np)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    enabled = (m_ref[...] != 0) & (cols < n)                 # lane padding
    v = jnp.where(enabled, s, fill)
    best = (v.max if is_max else v.min)(axis=-1, keepdims=True)  # (bb, 1)
    first = jnp.where(v == best, cols, n).min(axis=-1, keepdims=True)
    some = enabled.astype(jnp.int32).max(axis=-1, keepdims=True) > 0
    idx_ref[...] = jnp.where(some, first, -1)
    val_ref[...] = best


def _pallas_argext(scores: jax.Array, mask: jax.Array, *, is_max: bool,
                   block_b: int, interpret: bool):
    b, n = scores.shape
    if b > block_b and block_b % 8:
        raise ValueError(f"block_b must be a multiple of 8, got {block_b}")
    block_b = min(block_b, b)
    pad_b = (-b) % block_b
    pad_n = (-n) % _LANES
    s = jnp.pad(scores.astype(jnp.float32), ((0, pad_b), (0, pad_n)))
    m = jnp.pad(mask.astype(jnp.int32), ((0, pad_b), (0, pad_n)))
    rows, np_ = s.shape
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    idx, val = pl.pallas_call(
        functools.partial(_argext_kernel, is_max=is_max, n=n),
        grid=(rows // block_b,),
        in_specs=[pl.BlockSpec((block_b, np_), lambda i: (i, 0)),
                  pl.BlockSpec((block_b, np_), lambda i: (i, 0))],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(s, m)
    return idx[:b, 0], val[:b, 0]


def _kernel_argext(scores: jax.Array, mask: jax.Array, *, is_max: bool,
                   block_b: int, interpret: bool):
    lead = scores.shape[:-1]
    n = scores.shape[-1]
    s2 = scores.reshape(-1, n)
    m2 = jnp.broadcast_to(mask, scores.shape).reshape(-1, n)
    idx, val = _pallas_argext(s2, m2, is_max=is_max, block_b=block_b,
                              interpret=interpret)
    return idx.reshape(lead), val.reshape(lead)


def masked_argext(scores: jax.Array, mask: jax.Array, *, is_max: bool,
                  block_b: int = DEFAULT_BLOCK_B,
                  interpret: Optional[bool] = None):
    """``scores, mask: (..., N)`` → ``(idx (...,), val (...,))``.

    ``interpret=None`` lowers per platform: the Pallas kernel where the
    program is compiled for one TPU, the jnp reference elsewhere and in
    programs traced under a multi-device mesh.
    ``interpret=True`` runs the kernel body through the Pallas
    interpreter on any platform — the kernel-vs-reference test path.
    """
    kernel = functools.partial(_kernel_argext, is_max=is_max,
                               block_b=block_b, interpret=bool(interpret))
    if interpret is not None:
        return kernel(scores, mask)
    if jax.sharding.get_abstract_mesh().size > 1:
        # traced under a multi-device mesh (``jax.set_mesh``): XLA cannot
        # partition a Mosaic call, so select with the reference, which it
        # partitions like the rest of the tick
        return ref.ref_masked_argext(scores, mask, is_max=is_max)
    return jax.lax.platform_dependent(
        scores, mask, tpu=kernel,
        default=functools.partial(ref.ref_masked_argext, is_max=is_max))


def masked_argmax(scores, mask, **kw):
    """First argmax over enabled entries; (-1, NEG) when none enabled."""
    return masked_argext(scores, mask, is_max=True, **kw)


def masked_argmin(scores, mask, **kw):
    """First argmin over enabled entries; (-1, POS) when none enabled."""
    return masked_argext(scores, mask, is_max=False, **kw)
