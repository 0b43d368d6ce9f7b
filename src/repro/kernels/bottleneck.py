"""Pallas batched bottleneck evaluation over rounding samples.

The fused rounding backend (``repro.core.rounding``, DESIGN.md §6) scores
every repaired Gaussian sample with Eq. 2 — per sample: machine loads,
per-task compute times, per-dependency communication delays, max.  The jnp
path vmaps a gather-based evaluator over samples; this kernel evaluates a
whole block of samples per grid step with the samples on the lane axis.

Layout: the wrapper transposes the (S, T) assignments to (T, S), so a
block is a (T, bs) slab — one sublane row per task, one lane per sample —
and every per-sample quantity is a lane-parallel 2-D vector op:

  - machine loads: for each machine k, a masked sublane sum of the task
    workloads ``p`` over the tasks assigned to k;
  - compute time of task t: its machine's load over that machine's speed;
  - communication: one ``fori_loop`` pass over the dependency edges, read
    from scalar memory; each edge loads the (1, bs) assignment rows of its
    two endpoints, looks ``C[a_src, a_dst]`` up by K² selects on scalars
    of ``C``, and max-accumulates it into its source task's row of a VMEM
    scratch slab.

There are no one-hot operands and no matmuls, so nothing depends on the
MXU's matmul precision: the compute times match the gather evaluator
(``repro.kernels.ref.bottleneck_eval_ref``) to f32 rounding of the load
sums, and every delay is the f32 table entry itself.

Inputs: ``assign`` (S, T) int32 machine indices, ``p`` (T,) task
workloads, ``e`` (K,) machine speeds, ``C`` (K, K) inter-machine delays,
``src`` / ``dst`` (E,) int32 endpoint tasks of each dependency edge
(E may be 0).  Output: (S,) f32 bottleneck times (Eq. 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vmem import LANE, lane_block, pad_rows


def _bottleneck_kernel(src_ref, dst_ref, c_ref, e_ref, a_ref, p_ref, t_ref,
                       comm_ref, *, n_machines, n_edges):
    a = a_ref[...]                                   # (T, bs) int32
    p = p_ref[...].astype(jnp.float32)               # (T, 1)
    t_comp = jnp.zeros(a.shape, jnp.float32)
    for k in range(n_machines):
        on_k = a == k
        load = jnp.sum(jnp.where(on_k, p, 0.0), axis=0, keepdims=True)
        t_comp = jnp.where(on_k, load / e_ref[0, k], t_comp)

    comm_ref[...] = jnp.zeros(comm_ref.shape, jnp.float32)

    def edge(i, carry):
        s = src_ref[0, i]
        # (1, bs) machine pair index a_src·K + a_dst of this edge
        pair = (a_ref[pl.ds(s, 1), :] * n_machines
                + a_ref[pl.ds(dst_ref[0, i], 1), :])
        delay = jnp.zeros(pair.shape, jnp.float32)
        for k in range(n_machines):
            for l in range(n_machines):
                delay = jnp.where(pair == k * n_machines + l, c_ref[k, l],
                                  delay)
        comm_ref[pl.ds(s, 1), :] = jnp.maximum(comm_ref[pl.ds(s, 1), :], delay)
        return carry

    if n_edges:
        jax.lax.fori_loop(0, n_edges, edge, 0)
    t_ref[...] = jnp.max(t_comp + comm_ref[...], axis=0, keepdims=True)


def bottleneck_block_samples(n_samples: int, n_tasks: int) -> int:
    """Samples per grid step: the (T, bs) assignment block and the (1, bs)
    output stream, the (T, bs) comm scratch plus three (T, bs) f32
    temporaries of the body (mask, compute times, their sum), and the
    lane-padded (T, 1) workload column."""
    return lane_block(
        n_samples, n_tasks, 1, scratch_rows=4 * pad_rows(n_tasks),
        fixed_bytes=2 * pad_rows(n_tasks) * LANE * 4,
    )


def bottleneck_eval_fwd(
    assign: jnp.ndarray,   # (S, T) int32 machine index per task per sample
    p: jnp.ndarray,        # (T,)
    e: jnp.ndarray,        # (K,)
    C: jnp.ndarray,        # (K, K)
    src: jnp.ndarray,      # (E,) int32 edge sources (E may be 0)
    dst: jnp.ndarray,      # (E,) int32 edge destinations
    *,
    block_samples: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    s, t = assign.shape
    k = e.shape[0]
    assert p.shape == (t,), (p.shape, t)
    assert C.shape == (k, k), C.shape
    n_e = src.shape[0]
    assert src.shape == dst.shape == (n_e,), (src.shape, dst.shape)
    bs = block_samples or bottleneck_block_samples(s, t)
    bs = min(bs, s)
    sp = -(-s // bs) * bs
    aT = jnp.pad(assign.astype(jnp.int32).T, ((0, 0), (0, sp - s)))
    if n_e == 0:
        # scalar memory cannot hold an empty array; the loop never reads it
        src = dst = jnp.zeros((1,), jnp.int32)
    # Scalar-memory operands are 2-D, so that under vmap (batched rounding)
    # their blocks still span the last two dimensions whole.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    times = pl.pallas_call(
        functools.partial(_bottleneck_kernel, n_machines=k, n_edges=n_e),
        grid=(sp // bs,),
        in_specs=[
            smem, smem, smem, smem,
            pl.BlockSpec((t, bs), lambda i: (0, i)),
            pl.BlockSpec((t, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, sp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, bs), jnp.float32)],
        interpret=interpret,
    )(
        src.astype(jnp.int32).reshape(1, -1),
        dst.astype(jnp.int32).reshape(1, -1),
        C.astype(jnp.float32),
        e.astype(jnp.float32).reshape(1, k),
        aT,
        p.astype(jnp.float32).reshape(t, 1),
    )
    return times[0, :s]
