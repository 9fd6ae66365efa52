"""Error-feedback int8 gradient compression for cross-pod all-reduce.

Distributed-optimization trick for the DCN-bound multi-pod mesh: gradients
are quantized to int8 with a per-tensor scale before the pod-axis all-reduce
(8x fewer bytes over the slow inter-pod links), and the quantization residual
is fed back into the next step (error feedback keeps SGD convergence —
Karimireddy et al., arXiv:1901.09847).

Implemented with shard_map over the 'pod' axis so the collective is explicit
and the quantization happens on the wire-adjacent side. Within a pod the
usual full-precision psum runs over the 'data' axis first.

The quantize/dequantize core lives in ``repro.quant`` (shared with the
inference engines' per-channel int8 weight path); this module keeps the
error-feedback + collective machinery and re-exports the primitives for
backward compatibility.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant import dequantize, quantize  # noqa: F401  (re-export)

from jax.sharding import PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def ef_compress(g, err):
    """Error-feedback compression: returns (codes, scale, new_err)."""
    corrected = g.astype(jnp.float32) + err
    codes, scale = quantize(corrected)
    new_err = corrected - dequantize(codes, scale)
    return codes, scale, new_err


def compressed_psum_pod(grads, err_state, mesh):
    """All-reduce `grads` over the 'pod' axis with int8 wire format.

    grads: pytree already reduced within the pod (data axis). err_state:
    matching pytree of fp32 residuals. Returns (reduced grads, new errs).
    """
    if "pod" not in mesh.axis_names:
        return grads, err_state

    def one(g, e):
        def body(g_loc, e_loc):
            codes, scale, new_err = ef_compress(g_loc, e_loc)
            # int8 codes cross the DCN; scales are scalar and cheap
            summed = jax.lax.psum(codes.astype(jnp.int32), "pod")
            scale_sum = jax.lax.psum(scale, "pod")  # conservative joint scale
            npods = jax.lax.psum(jnp.ones((), jnp.float32), "pod")
            out = summed.astype(jnp.float32) * (scale_sum / npods)
            return out.astype(g_loc.dtype), new_err

        spec = P()  # per-pod replicated view of this tensor shard
        return shard_map(body, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec))(g, e)

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(err_state)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = tdef.unflatten([o[0] for o in outs])
    new_e = tdef.unflatten([o[1] for o in outs])
    return new_g, new_e


def init_error_state(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
