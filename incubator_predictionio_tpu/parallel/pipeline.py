"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pipe`` axis.

No reference counterpart (the reference is not a neural-net trainer); this is
the pp leg of the parallelism story alongside dp/tp/sp/ep. The transformer's
layer stack is split into S contiguous stages, one per device along the
``pipe`` mesh axis; M microbatches flow through a scan of ``ppermute`` steps
(the classic M + S - 1 schedule). Everything is differentiable — autodiff
reverses the ppermute chain, so one ``jax.grad`` trains the whole pipeline.

Design choices (deliberately simple, compiler-friendly):
- stage weights live STACKED with a leading [S] dim sharded ``P("pipe")`` —
  each device holds only its stage's layers (the memory win);
- activations ride [microbatch, L, D]; embedding/unembedding stay outside
  the shard_map (replicated — they are tied to the item table anyway);
- the bubble (S - 1 idle slots) is accepted, not hidden: per-step work is
  identical on every stage, so XLA compiles ONE program;
- the final hidden states are psum-broadcast so the loss is computed
  replicated — simple, and the logits matmul is tiny next to the stack.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from incubator_predictionio_tpu.parallel.ring import _mark_varying


def stack_layers(layers: list[dict]) -> dict:
    """List-of-layer-pytrees → one pytree with a leading [n_layers] dim
    (the layout both ``lax.scan`` over layers and pipe-sharding want).
    Stacks on HOST so placement controls where the result lives."""
    return jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *layers)


def pipeline_forward(stacked_layers, h0, apply_layer, mesh,
                     n_microbatches: int, axis: str = "pipe",
                     data_axis: str | None = None):
    """Run h0 [B, L, D] through the pipelined layer stack → [B, L, D].

    ``apply_layer(layer_params, h) -> h`` is the single-layer body (closed
    over the static config). ``stacked_layers`` leaves have leading dim
    n_layers, which must be divisible by the pipe axis size; B must be
    divisible by n_microbatches. ``data_axis`` keeps the microbatch dim
    data-sharded through the pipeline (dp × pp composes without an
    allgather of the batch).
    """
    s = mesh.shape[axis]
    n_layers = jax.tree.leaves(stacked_layers)[0].shape[0]
    if n_layers % s:
        raise ValueError(f"n_layers={n_layers} not divisible by pipe axis {s}")
    b = h0.shape[0]
    m = n_microbatches
    if b % m:
        raise ValueError(f"batch {b} not divisible by n_microbatches {m}")
    mb = b // m
    h0 = h0.reshape(m, mb, *h0.shape[1:])

    def stage_fn(my_layers, x):
        def one(h, lp):
            return apply_layer(lp, h), None

        h, _ = jax.lax.scan(one, x, my_layers)
        return h

    @partial(
        jax.shard_map,
        mesh=mesh,
        # stacked layers split over the pipe axis; microbatch rows keep
        # their data sharding (dim 1 after the [m, mb, ...] reshape)
        in_specs=(P(axis), P(None, data_axis)),
        out_specs=P(None, data_axis),
    )
    def run(layers_sharded, h0_rep):
        stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % s) for i in range(s)]

        def step(carry, t):
            received = carry
            # stage 0 ingests microbatch t (clamped — late steps drain)
            x = jnp.where(
                stage == 0,
                h0_rep[jnp.clip(t, 0, m - 1)],
                received,
            )
            y = stage_fn(layers_sharded, x)
            handoff = jax.lax.ppermute(y, axis, perm)
            # only the LAST stage's outputs are the real hidden states
            collected = jnp.where(stage == s - 1, y, jnp.zeros_like(y))
            return handoff, collected

        # the carry becomes device-varying after the first ppermute; mark
        # the zeros init varying over the pipe axis up front (same helper
        # as parallel/ring.py)
        init = _mark_varying(jnp.zeros_like(h0_rep[0]), (axis,))
        _, collected = jax.lax.scan(step, init, jnp.arange(m + s - 1))
        # step t >= s-1 emits microbatch t-(s-1) from the last stage;
        # psum broadcasts them (zeros everywhere but the last stage)
        return jax.lax.psum(collected[s - 1:], axis)

    out = run(stacked_layers, h0)
    return out.reshape(b, *out.shape[2:])
