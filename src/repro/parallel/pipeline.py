"""Pipeline parallelism: executable 1F1B-style pipeline over a ``pipe``
mesh axis + the DualPipe schedule model (paper §4.2, T8).

Executable pipeline
-------------------
``pipeline_forward`` runs a stage function over microbatches with
shard_map on a ``pipe`` axis: activations travel stage-to-stage with
``ppermute``; autodiff through ppermute yields the reverse-direction
backward pipeline automatically, so ``jax.grad`` of a pipelined loss is a
correct 1F1B-ish schedule (fwd and bwd ticks interleave under XLA's
scheduler). Equivalence-tested against the unpipelined model.

DualPipe schedule model
-----------------------
The paper's DualPipe feeds microbatches from BOTH ends of the pipeline and
overlaps each microbatch's attention/MoE compute with the other direction's
dispatch/combine. Real DualPipe needs per-device program divergence which
SPMD can't express directly; we reproduce its *schedule mathematics*
(bubble fraction, 1F/1B/1W timing — the quantities in the paper's Table 4)
in ``dualpipe_bubble`` and compare 1F1B vs DualPipe analytically in the
benchmarks.

  1F1B bubble fraction      = (P-1) / (M + P - 1)
  DualPipe bubble fraction  ≈ (P/2 - 1) / (2M/ (1)) ... see fn docstring.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _shift(x: jax.Array, axis: str, n: int) -> jax.Array:
    """Move activations one stage forward along the pipe axis."""
    return jax.lax.ppermute(x, axis, [(i, i + 1) for i in range(n - 1)])


def pipeline_forward(stage_fn: Callable, params_stages, x_mb: jax.Array,
                     mesh: Mesh, axis: str = "pipe"):
    """Run P pipeline stages over M microbatches.

    stage_fn(stage_params, x) -> y, applied by every device to its stage.
    params_stages: pytree with leading dim P (sharded over ``axis``).
    x_mb: (M, mb, ...) microbatches (replicated over ``axis``).
    Returns (M, mb, ...) outputs of the LAST stage.

    Schedule: M + P - 1 ticks; tick t has device s working on microbatch
    t - s (when in range) — the classic pipelined forward. Implemented as a
    scan over ticks inside shard_map; ppermute moves activations.
    """
    Pn = mesh.shape[axis]
    M = x_mb.shape[0]

    def local(params_local, xs_local):
        # params_local: stage params with leading dim 1; xs: (M, mb, ...)
        pstage = jax.tree.map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        mb_shape = xs_local.shape[1:]
        ticks = M + Pn - 1

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 ingests microbatch t (if t < M); others use inflight
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = xs_local[mb_idx]
            x_in = jnp.where(s == 0, fresh, inflight)
            active = (t - s >= 0) & (t - s < M)
            y = stage_fn(pstage, x_in)
            y = jnp.where(active, y, inflight)
            # last stage writes its finished microbatch t - (P-1)
            out_idx = jnp.clip(t - (Pn - 1), 0, M - 1)
            write = active & (s == Pn - 1)
            outputs = jnp.where(write, outputs.at[out_idx].set(y), outputs)
            # shift activations to the next stage
            nxt = _shift(y, axis, Pn)
            return (nxt, outputs), None

        init = (jnp.zeros(mb_shape, xs_local.dtype),
                jnp.zeros((M,) + mb_shape, xs_local.dtype))
        (_, outputs), _ = jax.lax.scan(tick, init,
                                       jnp.arange(M + Pn - 1))
        # outputs live on the last stage; broadcast to all for out_specs
        outputs = jax.lax.all_gather(outputs, axis)[Pn - 1]
        return outputs

    pspec = jax.tree.map(lambda _: P(axis), params_stages)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(pspec, P()), out_specs=P(),
                         check_vma=False)(params_stages, x_mb)


# ---------------------------------------------------------------------------
# Schedule mathematics (paper Table 4 quantities)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScheduleStats:
    name: str
    ticks: float          # total slots in units of one microbatch fwd+bwd
    bubble_frac: float
    comm_overlapped: bool


def onef1b_bubble(P: int, M: int, f: float = 1.0, b: float = 2.0,
                  w: float = 0.0) -> ScheduleStats:
    """Classic 1F1B: bubble = (P-1)(f+b) over M(f+b) + (P-1)(f+b)."""
    total = M * (f + b + w) + (P - 1) * (f + b + w)
    bubble = (P - 1) * (f + b + w)
    return ScheduleStats("1F1B", total, bubble / total, False)


def dualpipe_bubble(P: int, M: int, f: float = 1.0, b: float = 2.0,
                    w: float = 0.0) -> ScheduleStats:
    """DualPipe (paper [29]): bidirectional injection halves the pipeline
    depth seen by each direction and the W (weight-grad) slots fill the
    remaining bubble: bubble ≈ (P/2 - 1)(f + b - 2w) per direction over the
    same span, with dispatch/combine fully overlapped."""
    total = M * (f + b + w) + (P / 2 - 1) * (f + b)
    bubble = max(P / 2 - 1, 0) * max(f + b - 2 * w, 0)
    return ScheduleStats("DualPipe", total, bubble / total, True)
