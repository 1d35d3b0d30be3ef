"""Compute/communication overlap + wire-compressed collectives (shard_map).

  rs_matmul_overlapped   row-parallel matmul with a hand-scheduled ring
                         reduce-scatter + all-gather, chunked so each ring
                         hop's ppermute overlaps the NEXT chunk's dot.
                         Semantically y = x @ W with x, W sharded on the
                         contraction axis; the baseline GSPMD form is
                         dot + all-reduce, which serializes all ICI behind
                         the full matmul.  Here the matmul is emitted as n
                         independent (K/n x N/n) dots interleaved with the
                         ring permutes — the classic latency-hiding
                         collective-matmul decomposition.

  compressed_psum        data-parallel gradient combine that moves int8 on
                         the wire (pairs with optim.grad_compress error
                         feedback): quantize leaf -> all_gather(int8 +
                         f32 scale) -> dequantized mean.  Intended for the
                         cross-pod ("pod") axis where DCN bandwidth, not
                         ICI, is the bottleneck.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def rs_matmul_overlapped(x: jax.Array, w: jax.Array, mesh, axis: str) -> jax.Array:
    """y = x @ W.  x: (..., K) sharded on K over ``axis``; w: (K, N) sharded
    on K.  Returns y replicated over ``axis``.

    Ring schedule per device i (n = ring size, N split into n chunks):
      reduce-scatter phase, n-1 steps: the traveling accumulator for output
      chunk c = (i - s) mod n picks up this device's partial
      x_i @ W_i[:, c] and moves on; the ppermute of step s overlaps the dot
      of step s+1 (no data dependence).
      all-gather phase, n-1 steps: the finished chunks circulate back.
    """
    n = mesh.shape[axis]
    nn = w.shape[1]
    assert nn % n == 0, (nn, n)
    chunk = nn // n

    def shard_fn(xs, ws):
        idx = jax.lax.axis_index(axis)
        fwd = [(i, (i + 1) % n) for i in range(n)]

        def local_part(c):
            wsc = jax.lax.dynamic_slice_in_dim(ws, c * chunk, chunk, axis=1)
            return jax.lax.dot_general(
                xs, wsc, (((xs.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        # reduce-scatter: after n-1 hops, device i holds the summed chunk
        # (i + 1) mod n.
        acc = local_part((idx - 0) % n)
        for s in range(1, n):
            acc = jax.lax.ppermute(acc, axis, fwd)
            acc = acc + local_part((idx - s) % n)
        own = (idx - (n - 1)) % n  # chunk id now resident on this device

        # all-gather the n finished chunks (ring broadcast).
        pieces = [(own, acc)]
        blk = acc
        for _ in range(n - 1):
            blk = jax.lax.ppermute(blk, axis, fwd)
            pieces.append((None, blk))
        # chunk resident after hop h came from device i-h => chunk (own - h).
        out = jnp.zeros(xs.shape[:-1] + (nn,), jnp.float32)
        for h, (_, piece) in enumerate(pieces):
            c = (own - h) % n
            out = jax.lax.dynamic_update_slice_in_dim(
                out, piece, c * chunk, axis=out.ndim - 1
            )
        return out.astype(xs.dtype)

    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(*((None,) * (x.ndim - 1) + (axis,))), P(axis, None)),
        out_specs=P(),
        check_vma=False,
    )(x, w)


# kept under both names: ag_* was the working title used in DESIGN notes
ag_matmul_overlapped = rs_matmul_overlapped


def compressed_psum(grads: Any, mesh, axis: str) -> Any:
    """Data-parallel mean of gradient pytrees with int8 wire format.

    Each leaf: quantize locally (absmax/127) -> all_gather(int8) +
    all_gather(scale f32) -> dequantized mean.  ~4x fewer wire bytes than a
    f32 all-reduce; pair with optim.grad_compress error feedback so the
    quantization bias vanishes across steps.
    """
    n = mesh.shape[axis]

    def leaf_fn(g):
        def shard_fn(gl):
            scale = jnp.maximum(jnp.max(jnp.abs(gl)), 1e-12) / 127.0
            q = jnp.clip(jnp.round(gl / scale), -127, 127).astype(jnp.int8)
            qs = jax.lax.all_gather(q, axis)  # int8 on the wire
            ss = jax.lax.all_gather(scale, axis)
            deq = qs.astype(jnp.float32) * ss.reshape((n,) + (1,) * gl.ndim)
            return jnp.mean(deq, axis=0).astype(gl.dtype)

        return jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=P(*((None,) * g.ndim)),
            out_specs=P(*((None,) * g.ndim)),
            check_vma=False,
        )(g)

    return jax.tree.map(leaf_fn, grads)
