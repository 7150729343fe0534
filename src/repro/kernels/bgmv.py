"""BGMV — batched-gather LoRA matmul for decode (TPU adaptation of Punica).

One grid step per token block: the per-token adapter id arrives via scalar
prefetch and drives the A/B BlockSpec index maps, so each step DMAs exactly
one adapter's (d, r) shrink and (r, o) expand matrices into VMEM and runs
two MXU matmuls.  CUDA-Punica's warp-gather has no TPU analogue; the
data-dependent index_map is the TPU-native equivalent (the gather happens in
the DMA engine, overlapped with compute by the Pallas pipeline).

Each grid step handles one token, the fully general case (decode batches
are small — this is exactly Punica's BGMV regime).  Mosaic requires the
last two dims of a block to be (8, 128)-aligned or to equal the array's,
so the token axis is a leading dim: x is viewed as (T, 1, d) and y as
(T, 1, o), and each step's (1, 1, d) / (1, 1, o) block spans the last two
dims whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bgmv_kernel(idx_ref, x_ref, a_ref, b_ref, o_ref, *, scale: float):
    i = pl.program_id(0)
    x = x_ref[0]                                      # (1, d)
    a = a_ref[0]                                      # (d, r)
    b = b_ref[0]                                      # (r, o)
    h = jnp.dot(x.astype(jnp.float32), a.astype(jnp.float32),
                preferred_element_type=jnp.float32)   # (1, r)
    y = jnp.dot(h, b.astype(jnp.float32),
                preferred_element_type=jnp.float32)   # (1, o)
    # idx < 0 = base-model token: the index map clamped the DMA to
    # adapter 0; mask its contribution to a zero delta here.
    y = jnp.where(idx_ref[i] >= 0, y * scale, 0.0)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def bgmv(x, a, b, idx, scale: float = 1.0, interpret: bool = False):
    """y[t] = scale * x[t] @ A[idx[t]] @ B[idx[t]].

    x: (T, d); a: (N, d, r); b: (N, r, o); idx: (T,) int32 -> (T, o).
    Tokens with idx < 0 (base model, no adapter) get a zero delta.
    """
    t, d = x.shape
    n, _, r = a.shape
    o = b.shape[-1]
    grid = (t,)

    def _ab_map(i, idx_ref):
        return (jnp.maximum(idx_ref[i], 0), 0, 0)

    out = pl.pallas_call(
        functools.partial(_bgmv_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda i, idx_ref: (i, 0, 0)),
                pl.BlockSpec((1, d, r), _ab_map),
                pl.BlockSpec((1, r, o), _ab_map),
            ],
            out_specs=pl.BlockSpec((1, 1, o), lambda i, idx_ref: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, 1, o), x.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), x.reshape(t, 1, d), a, b)
    return out.reshape(t, o)
