"""Ulysses sequence parallelism: all-to-all context parallelism over the
mesh (first-class here; the reference has NONE — SURVEY §5.7.  Public
technique: DeepSpeed-Ulysses, Jacobs et al. 2023; jax shard_map
collective idioms from the scaling book).

Q/K/V arrive sequence-sharded (each device holds T/N positions of every
head).  One ``lax.all_to_all`` re-partitions to head-sharded (each
device holds ALL positions of H/N heads), local attention runs exactly
and unblocked on the MXU, and a final all-to-all restores sequence
sharding.  Four all-to-alls per attention (Q/K/V in, output out; plus an
all_gather for the optional key mask) — a constant collective count vs
the ring's N ppermute rounds, favorable when H >= N — at the cost of
requiring H % N == 0.
"""
from __future__ import annotations

from functools import partial

from ..base import MXNetError

__all__ = ["ulysses_attention"]


def _ulysses_body(q, k, v, mask=None, *, axis_name, scale, causal,
                  use_flash=False):
    """Per-shard body (runs inside shard_map).

    q/k/v: (B, H, T_local, D) sequence shards; optional ``mask``
    (B, T_local) key-validity shard.  Returns the (B, H, T_local, D)
    attention output shard.  ``use_flash`` runs the post-all-to-all
    full-sequence attention through the Pallas flash kernel (the
    (B, H/n, T, D) gathered shape is exactly the kernel's contract; the
    dispatcher still falls back to XLA for non-tile-aligned T)."""
    from jax import lax
    from .ring import local_flash_attention

    # seq-sharded -> head-sharded: split heads into n groups, gather the
    # full sequence for our group
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)                  # (B, H/n, T, D)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    full_mask = (None if mask is None else
                 lax.all_gather(mask, axis_name, axis=1,
                                tiled=True))         # (B, T)
    if use_flash:
        from ..kernels import flash_attention
        oh = flash_attention(qh, kh, vh, scale=scale, causal=causal,
                             mask=full_mask)
    else:
        oh = local_flash_attention(qh, kh, vh, scale=scale,
                                   causal=causal, key_mask=full_mask)
    # head-sharded -> seq-sharded
    return lax.all_to_all(oh, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_attention(q, k, v, mesh=None, axis_name="seq", scale=None,
                      causal=False, mask=None, use_flash=False):
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``,
    computed with the DeepSpeed-Ulysses all-to-all schedule.

    q/k/v: (batch, heads, T, D), T sharded over the mesh axis; heads
    must be divisible by the axis size.  ``mask``: optional (batch, T)
    key-validity array, sequence-sharded like K/V.  Accepts jax arrays
    or NDArrays; returns the same sharding as the inputs."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from . import mesh as mesh_mod
    from ..ndarray.ndarray import NDArray

    mesh = mesh or mesh_mod.current_mesh()
    if mesh is None:
        raise MXNetError("ulysses_attention needs a mesh")
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh.shape[axis_name]
    wrap = isinstance(q, NDArray)
    if wrap:
        q, k, v = q._data, k._data, v._data
        if mask is not None and isinstance(mask, NDArray):
            mask = mask._data
    if q.shape[1] % n:
        raise MXNetError(
            f"ulysses_attention: heads ({q.shape[1]}) must be divisible "
            f"by the '{axis_name}' axis size ({n}); use ring_attention "
            "for head counts smaller than the sequence axis")
    if scale is None:
        scale = q.shape[-1] ** -0.5

    spec = P(None, None, axis_name, None)
    if mask is not None:
        fn = shard_map(
            partial(_ulysses_body, axis_name=axis_name, scale=scale,
                    causal=causal, use_flash=use_flash),
            mesh=mesh, in_specs=(spec, spec, spec, P(None, axis_name)),
            out_specs=spec, check_vma=False)
        out = fn(q, k, v, mask)
    else:
        fn = shard_map(
            partial(_ulysses_body, axis_name=axis_name, scale=scale,
                    causal=causal, use_flash=use_flash),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        out = fn(q, k, v)
    return NDArray(out) if wrap else out
