"""Gradient compression for cross-pod synchronization.

On the multi-pod mesh the per-step gradient all-reduce crosses the (slow)
inter-pod links.  We compress the pod-crossing reduction:

- bf16 compression: cast grads to bf16 before the cross-pod psum (2x bytes).
- int8 compression: per-tensor absmax scale, symmetric int8 quantize, psum
  in int32, dequantize (4x bytes) with ERROR FEEDBACK: the quantization
  residual is carried and added to the next step's gradient, preserving
  convergence (1-bit-Adam-style analysis applies).

Implemented with shard_map over the 'pod' axis so the quantize/psum/
dequantize appears explicitly in the lowered HLO (visible to the roofline's
collective scan).
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bf16_compress(grads):
    """Simple 2x compression of the gradient tree (no state)."""
    return jax.tree.map(lambda g: g.astype(jnp.bfloat16).astype(g.dtype),
                        grads)


def int8_quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def int8_dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def int8_roundtrip_with_feedback(g: jax.Array, err: jax.Array
                                 ) -> Tuple[jax.Array, jax.Array]:
    """Quantize (g + err), return (dequantized, new_err)."""
    corrected = g.astype(jnp.float32) + err.astype(jnp.float32)
    q, scale = int8_quantize(corrected)
    deq = int8_dequantize(q, scale, jnp.float32)
    new_err = corrected - deq
    return deq.astype(g.dtype), new_err.astype(err.dtype)


def make_error_feedback_state(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compress_tree_int8(grads, err_state):
    """Apply int8 round-trip with error feedback to every leaf."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_state)
    outs = [int8_roundtrip_with_feedback(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_e = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return new_g, new_e


def cross_pod_psum_int8(mesh, grad_specs):
    """Returns fn(grads) that all-reduces over the 'pod' axis with int8
    payload via shard_map (grads assumed pre-divided by pod count)."""
    def psum_one(g):
        q, scale = int8_quantize(g)
        qsum = jax.lax.psum(q.astype(jnp.int32), "pod")
        ssum = jax.lax.pmax(scale, "pod")         # shared conservative scale
        return int8_dequantize(qsum, ssum, g.dtype)

    def fn(grads):
        return jax.tree.map(psum_one, grads)

    return jax.shard_map(fn, mesh=mesh, in_specs=(grad_specs,),
                         out_specs=grad_specs, check_vma=False)
