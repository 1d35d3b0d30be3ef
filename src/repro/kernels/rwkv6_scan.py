"""RWKV6 (Finch) WKV recurrence as a chunked Pallas TPU kernel.

The recurrence per head (state S in R^{Dk x Dv}, data-dependent decay w_t):

    out_t = r_t^T (diag(u) k_t v_t^T + S_{t-1})
    S_t   = diag(w_t) S_{t-1} + k_t v_t^T

A naive scan is sequential in T.  The kernel uses the standard chunked
linear-attention reformulation: within a chunk of length L, with cumulative
decays D_t = prod_{s<=t} w_s (D_0 = 1),

    r~_t = r_t * D_{t-1}          k~_s = k_s / D_s
    A[t,s] = (r~_t . k~_s)  for s < t;   A[t,t] = r_t . (u * k_t)
    out = A @ V + r~ @ S_0
    S_L = diag(D_L) (S_0 + sum_s k~_s v_s^T)

so each chunk is three small matmuls (MXU) instead of L rank-1 updates, and
the sequential dependency is only chunk-to-chunk through S (kept in VMEM
scratch across the T grid axis).  Chunk length is bounded (default 32) so the
1/D_s terms stay in f32 range for decays w >= exp(-8) (RWKV6's
exp(-softplus) parameterization keeps w in (0, 1); tests cover the extremes).

Grid: (B, H, T/L) with T innermost ("arbitrary"); per-(b,h) state persists in
scratch across chunk steps.  ref.py's rwkv6_scan_ref is the sequential
oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _kernel(
    r_ref,  # (1, L, 1, Dk)
    k_ref,  # (1, L, 1, Dk)
    v_ref,  # (1, L, 1, Dv)
    w_ref,  # (1, L, 1, Dk)  decays in (0, 1)
    u_ref,  # (1, Dk)        bonus
    out_ref,  # (1, L, 1, Dv)
    state_ref,  # scratch (Dk, Dv) f32
    *,
    nt: int,
):
    t_step = pl.program_id(2)

    @pl.when(t_step == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, :, 0, :].astype(jnp.float32)  # (L, Dk)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)  # (L, Dv)
    w = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0, :].astype(jnp.float32)  # (Dk,)

    logw = jnp.log(w)
    logD = jnp.cumsum(logw, axis=0)  # log D_t, t = 1..L
    d_full = jnp.exp(logD[-1])  # D_L
    r_t = r * jnp.exp(jnp.concatenate([jnp.zeros_like(logD[:1]), logD[:-1]], 0))
    k_t = k * jnp.exp(-logD)

    s0 = state_ref[...]
    ell = r.shape[0]
    # strictly-lower-triangular inter-position matrix + diagonal u term
    a = jnp.dot(r_t, k_t.T, preferred_element_type=jnp.float32)  # (L, L)
    ti = jax.lax.broadcasted_iota(jnp.int32, (ell, ell), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (ell, ell), 1)
    a = jnp.where(si < ti, a, 0.0)
    diag = jnp.sum(r * (u[None, :] * k), axis=1)  # (L,)
    out = jnp.dot(a, v, preferred_element_type=jnp.float32)
    out = out + diag[:, None] * v
    out = out + jnp.dot(r_t, s0, preferred_element_type=jnp.float32)

    state_ref[...] = d_full[:, None] * (
        s0 + jnp.dot(k_t.T, v, preferred_element_type=jnp.float32)
    )
    out_ref[0, :, 0, :] = out.astype(out_ref.dtype)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(
    r: jax.Array,  # (B, T, H, Dk)
    k: jax.Array,
    v: jax.Array,  # (B, T, H, Dv)
    w: jax.Array,  # (B, T, H, Dk) decays in (0, 1)
    u: jax.Array,  # (H, Dk)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """Chunked WKV: returns out (B, T, H, Dv).  T must divide by ``chunk``
    (ops.py pads).  Initial state is zero (prefill semantics); decode-time
    stateful stepping uses the jnp path in models/rwkv_lm.py."""
    b, t, h, dk = r.shape
    dv = v.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nt = t // chunk
    grid = (b, h, nt)

    def tile(d):
        return pl.BlockSpec((1, chunk, 1, d), lambda bi, hi, ti: (bi, ti, hi, 0))

    return pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid=grid,
        in_specs=[
            tile(dk),
            tile(dk),
            tile(dv),
            tile(dk),
            pl.BlockSpec((1, dk), lambda bi, hi, ti: (hi, 0)),
        ],
        out_specs=tile(dv),
        out_shape=jax.ShapeDtypeStruct((b, t, h, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(r, k, v, w, u)
