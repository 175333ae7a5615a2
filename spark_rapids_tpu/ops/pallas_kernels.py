"""Pallas TPU kernels for shuffle-critical ops.

Where XLA's fusion already covers most of the engine, the shuffle map
side's hash-partition pass is worth a hand kernel: murmur3 is a chain of
int32 bit ops (rotates, xors, multiplies) that map 1:1 onto VPU lanes, and
fusing hash + pmod in VMEM avoids materializing the hash column in HBM.
(reference: the JNI Hash kernels feeding GpuHashPartitioningBase.)

TPU constraints honored: 2D (sublane, 128-lane) tiles, 32-bit ops only,
static partition count. Falls back to interpret mode off-TPU so tests run
on the CPU backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pallas_partition_ids_i32"]

_LANES = 128
_SUBLANES = 8


def _make_kernel(num_partitions: int):
    def kernel(vals_ref, valid_ref, out_ref):
        x = vals_ref[:, :].astype(jnp.uint32)
        seed = jnp.uint32(42)

        def rotl(v, r):
            return (v << r) | (v >> (32 - r))

        k1 = x * jnp.uint32(0xCC9E2D51)
        k1 = rotl(k1, 15)
        k1 = k1 * jnp.uint32(0x1B873593)
        h1 = seed ^ k1
        h1 = rotl(h1, 13)
        h1 = h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)
        # fmix(h1, 4)
        h1 = h1 ^ jnp.uint32(4)
        h1 = h1 ^ (h1 >> 16)
        h1 = h1 * jnp.uint32(0x85EBCA6B)
        h1 = h1 ^ (h1 >> 13)
        h1 = h1 * jnp.uint32(0xC2B2AE35)
        h1 = h1 ^ (h1 >> 16)
        h = h1.astype(jnp.int32)
        # null keys hash to the seed (Spark semantics)
        h = jnp.where(valid_ref[:, :], h, jnp.int32(42))
        n = jnp.int32(num_partitions)
        m = h % n
        out_ref[:, :] = jnp.where(m < 0, m + n, m)
    return kernel


def pallas_partition_ids_i32(vals, validity, num_partitions: int,
                             interpret: bool = False):
    """Spark HashPartitioning pmod(murmur3(int32 key), n) as one VMEM-tiled
    Pallas pass. vals: int32[cap] with cap a multiple of 1024.

    Traced under disable_x64: the engine globally enables x64, but Mosaic
    cannot legalize the i64 index types x64 mode introduces; this kernel is
    pure 32-bit."""
    cap = vals.shape[0]
    tile = _SUBLANES * _LANES
    assert cap % tile == 0, "capacity must be a multiple of 1024"
    rows = cap // _LANES
    v2 = vals.reshape(rows, _LANES)
    m2 = validity.reshape(rows, _LANES)
    grid = (rows // _SUBLANES,)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _make_kernel(num_partitions),
            grid=grid,
            in_specs=[
                pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            interpret=interpret,
        )(v2, m2)
    return out.reshape(cap)
