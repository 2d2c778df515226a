"""Gradient compression for the inter-pod (DCI) axis (DESIGN.md §5).

At 512+ chips the gradient all-reduce crosses the data-center
interconnect once per step; int8 quantization with error feedback
(1-bit-Adam-style memory, Seide et al. 2014 / Tang et al. 2021) cuts
those bytes 4× vs f32 / 2× vs bf16 while keeping SGD convergence
(the quantization error is fed back into the next step, so the
compressed stream is unbiased over time).

Usage (shard_map DP training or a custom grad sync):

    state = init_error_feedback(grads)
    def sync(g, state):
        q, scales, state = compress_with_feedback(g, state)
        q = jax.lax.psum(q, "pod")          # int8 wire traffic
        return decompress(q, scales, n_pods), state

Hierarchical reduction helper included: reduce-scatter intra-pod in
bf16, all-reduce inter-pod in int8, all-gather intra-pod.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x):
    """Per-tensor symmetric int8. Returns (q: int8, scale: f32)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_int8`: q·scale, cast to ``dtype``.

    Round-trip error is ≤ scale/2 per element (symmetric rounding).
    """
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_int8_rows(x):
    """Per-ROW symmetric int8 for the quantized serving corpus (§8.4).

    x: f32[M, I] → (q: int8[M, I], scale: f32[M])
    with ``scale[r]`` the POWER OF TWO ≥ ``max(|x[r]|, 1e-30)/127``
    (next-above, or equal when already a power of two) and
    ``q[r] = round(x[r]/scale[r])`` clipped to ±127.

    Power-of-two scales are the load-bearing choice: every scale
    application in the serving kernels (s_q·s_c, s_c², ×acc) is then an
    exact f32 exponent shift, so each blended score involves exactly
    one rounding — which makes the int8 scores invariant to FMA
    contraction (``a·b − c`` fuses to ``fma(a, b, −c)`` or not,
    depending on how XLA/Mosaic lowers each program; with a·b exact
    both round identically).  That is what upgrades the D-tiled int8
    path's kernel-vs-oracle agreement from allclose to bitwise.  The
    cost is ≤ 1 bit of the 8: per-element round-trip error is ≤
    scale[r]/2 ≤ max|x[r]|/127 (vs /254 for a free scale) — pinned by
    tests/test_quantized_serving.py.

    Row-wise scaling also makes the representation corpus-partition
    invariant: a row quantizes to the same (q, scale) on any shard or
    slice, so sharded int8 serving scores are bitwise the single-corpus
    ones, and a streaming-update row refresh re-quantizes exactly the
    touched rows (`streaming.state_store.StateStore.quantized_corpus`).
    """
    xf = x.astype(jnp.float32)
    raw = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-30) / 127.0
    # next power of two ≥ raw: frexp gives raw = m·2^e with m ∈ [0.5, 1);
    # m == 0.5 means raw IS 2^(e−1), else round up to 2^e.  The pow2 is
    # assembled from its IEEE-754 exponent bits — XLA's exp2() is an
    # APPROXIMATION (exp2(15) → 32767.984 on CPU) and would silently
    # void the exactness invariant above.
    mant, exp = jnp.frexp(raw)
    e = jnp.where(mant == 0.5, exp - 1, exp).astype(jnp.int32)
    scale = jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)
    q = jnp.clip(jnp.round(xf / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8_rows(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_int8_rows`: ``q · scale[:, None]``.

    q: int8[..., M, I] with a matching ``scale`` broadcast over the last
    axis (scale: f32[..., M]).  Exact elementwise f32 multiply — the
    serving kernels apply the same product in VMEM, so host-side
    dequantization reproduces the kernel's operand values bitwise.
    """
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_error_feedback(grads):
    """Residual accumulator pytree (same shapes as grads, f32)."""
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compress_with_feedback(grads, err):
    """Quantize (grad + residual); the rounding error is the new residual.

    Over steps the transmitted sum is exact (error feedback).
    Returns (q_tree int8, scale_tree f32 scalars, new_err_tree).
    """
    def one(g, e):
        target = g.astype(jnp.float32) + e
        q, scale = quantize_int8(target)
        decoded = dequantize_int8(q, scale)
        return q, scale, target - decoded

    out = jax.tree.map(one, grads, err)
    q = jax.tree.map(lambda t: t[0], out,
                     is_leaf=lambda x: isinstance(x, tuple))
    scales = jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda t: t[2], out,
                           is_leaf=lambda x: isinstance(x, tuple))
    return q, scales, new_err


def decompress(q, scales, dtype=jnp.float32):
    """Dequantize a compressed gradient pytree leaf-by-leaf."""
    return jax.tree.map(lambda a, s: dequantize_int8(a, s, dtype), q,
                        scales)


def hierarchical_psum_mean(x, intra_axes, inter_axis, err=None):
    """Hierarchical gradient mean for shard_map DP training:

      reduce-scatter intra-pod (bf16 wire) → int8 all-reduce inter-pod
      (with optional error feedback) → all-gather intra-pod.

    Crossing the DCI with int8 moves 4× fewer bytes than f32.  Must run
    inside shard_map with ``intra_axes``/``inter_axis`` mesh axes.
    Returns (mean, new_err).
    """
    n_intra = 1
    for a in (intra_axes if isinstance(intra_axes, (tuple, list))
              else (intra_axes,)):
        n_intra *= jax.lax.axis_size(a)
    n_inter = jax.lax.axis_size(inter_axis)
    # intra-pod reduce-scatter over the flattened leading dim when
    # divisible; otherwise a plain psum (small tensors)
    flat = x.reshape(-1)
    if flat.shape[0] % n_intra == 0:
        part = jax.lax.psum_scatter(flat, intra_axes, scatter_dimension=0,
                                    tiled=True)
    else:
        part = jax.lax.psum(flat, intra_axes)
    if err is not None:
        q, scale, err = compress_with_feedback(part, err)
        q32 = jax.lax.psum(q.astype(jnp.int32), inter_axis)
        part = (q32.astype(jnp.float32) * scale)
    else:
        part = jax.lax.psum(part.astype(jnp.bfloat16), inter_axis)
        part = part.astype(jnp.float32)
    if flat.shape[0] % n_intra == 0:
        full = jax.lax.all_gather(part, intra_axes, axis=0, tiled=True)
    else:
        full = part
    return (full.reshape(x.shape) / (n_intra * n_inter)).astype(x.dtype), \
        err
