"""Fused gather→adam→scatter for sparse touched-row updates.

The streaming fold (streaming/trainer.py) updates only the embedding rows a
micro-batch names. The reference path pays three passes per touched-row
batch — a per-key row gather, a per-key adam step, a per-key scatter back
into the working state. This module fuses them:

- :func:`fused_adam_rows` — the host numpy engine: ONE stacked gather, one
  vectorized adam over the ``[R, D]`` stack, one scatter. The math is the
  per-row ``DeltaTrainer._adam`` / ``utils/optim.adam_apply`` fp32 recipe
  reproduced **bit-for-bit**: every op is elementwise IEEE f32 in the same
  order, and the per-row bias corrections are computed with the same scalar
  ``b1 ** t`` double pow (:func:`adam_bias_corrections`), so fused and
  three-pass folds produce identical bytes (tests/test_streaming.py pins
  this).
- :func:`fused_gather_adam_scatter` — the device engine: gather, adam and
  scatter-back compiled into ONE dispatch (a Pallas kernel runs the adam
  core on TPU; plain jnp elsewhere). XLA may contract multiply-add into
  FMA, so the compiled engines are pinned to fp32 roundoff of the host
  pass rather than bytes — pick one engine per stream and replay
  determinism holds.

Per-row step counts ride along unchanged: a row's ``t`` advances only when
the row trains, exactly like the sparse-adam convention the trainer keeps.
"""

from __future__ import annotations

import functools

import numpy as np

ADAM_B1 = 0.9
ADAM_B2 = 0.999
ADAM_EPS = 1e-8

#: Rows per grid step for the Pallas adam kernel (f32 [256, D+1] blocks).
ROW_BLOCK = 256


def adam_bias_corrections(
    t: np.ndarray, b1: float = ADAM_B1, b2: float = ADAM_B2,
) -> tuple[np.ndarray, np.ndarray]:
    """Per-row ``(1 - b1**t, 1 - b2**t)`` as f32, computed with the scalar
    double ``**`` the per-row reference uses — one pow per UNIQUE step count
    (a fold batch holds few distinct ``t`` values), so the fused path cannot
    drift from the reference by a libm-vs-ufunc pow difference."""
    t = np.asarray(t, np.int64)
    bc1 = np.empty(len(t), np.float32)
    bc2 = np.empty(len(t), np.float32)
    for tv in np.unique(t):
        sel = t == tv
        bc1[sel] = np.float32(1.0 - b1 ** int(tv))
        bc2[sel] = np.float32(1.0 - b2 ** int(tv))
    return bc1, bc2


def fused_adam_rows(
    rows: np.ndarray,        # [R, D] f32 current row values (will not mutate)
    m: np.ndarray,           # [R, D] f32 first moments
    v: np.ndarray,           # [R, D] f32 second moments
    g: np.ndarray,           # [R, D] f32 accumulated gradients
    t: np.ndarray,           # [R] int step counts AFTER this step (t >= 1)
    lr: float,
    b1: float = ADAM_B1, b2: float = ADAM_B2, eps: float = ADAM_EPS,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One vectorized adam step over a stacked touched-row batch. Returns
    new ``(rows, m, v)``; op-for-op the ``DeltaTrainer._adam`` fp32 math."""
    bc1, bc2 = adam_bias_corrections(t, b1, b2)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * (g * g)
    rows = rows - lr * (m / bc1[:, None]) / (
        np.sqrt(v / bc2[:, None]) + eps)
    return rows, m, v


# -- device engine -----------------------------------------------------------


def _adam_rows_kernel(rows_ref, m_ref, v_ref, g_ref, bc1_ref, bc2_ref,
                      out_rows, out_m, out_v, *, lr, b1, b2, eps):
    import jax.numpy as jnp

    g = g_ref[:]
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * (g * g)
    out_m[:] = m
    out_v[:] = v
    out_rows[:] = rows_ref[:] - lr * (m / bc1_ref[:]) / (
        jnp.sqrt(v / bc2_ref[:]) + eps)


def _pallas_adam_rows(rows, m, v, g, bc1, bc2, lr, b1, b2, eps, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, d = rows.shape
    if r % ROW_BLOCK:
        pad = (-r) % ROW_BLOCK
        rows, m, v, g = (jnp.pad(a, ((0, pad), (0, 0)))
                         for a in (rows, m, v, g))
        # padded bc rows are 1.0 — the padded lanes divide by one, not zero
        bc1 = jnp.pad(bc1, (0, pad), constant_values=1.0)
        bc2 = jnp.pad(bc2, (0, pad), constant_values=1.0)
    rp = rows.shape[0]
    grid = (rp // ROW_BLOCK,)
    row = lambda j: (j, 0)
    mat = pl.BlockSpec((ROW_BLOCK, d), row, memory_space=pltpu.VMEM)
    col = pl.BlockSpec((ROW_BLOCK, 1), row, memory_space=pltpu.VMEM)
    kernel = functools.partial(
        _adam_rows_kernel, lr=lr, b1=b1, b2=b2, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mat, mat, mat, mat, col, col],
        out_specs=(mat, mat, mat),
        out_shape=tuple(
            jax.ShapeDtypeStruct((rp, d), jnp.float32) for _ in range(3)),
        interpret=interpret,
    )(rows, m, v, g, bc1.reshape(rp, 1), bc2.reshape(rp, 1))
    return tuple(a[:r] for a in out)


#: Lazily-built jitted adam-core executable over padded row stacks — built
#: on first use so importing this module (the host fold does) never
#: imports jax.
_ROWS_JIT = None


def _adam_rows_jit():
    global _ROWS_JIT
    if _ROWS_JIT is not None:
        return _ROWS_JIT
    import jax

    from incubator_predictionio_tpu.parallel.mesh import kernel_backend

    def step(rows, m, v, g, bc1, bc2, *, lr, b1, b2, eps, interpret):
        backend = kernel_backend()
        if backend or interpret:
            return _pallas_adam_rows(
                rows, m, v, g, bc1, bc2, lr, b1, b2, eps,
                interpret or backend == "interpret")
        import jax.numpy as jnp

        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * (g * g)
        rows = rows - lr * (m / bc1[:, None]) / (
            jnp.sqrt(v / bc2[:, None]) + eps)
        return rows, m, v

    _ROWS_JIT = jax.jit(
        step, static_argnames=("lr", "b1", "b2", "eps", "interpret"))
    return _ROWS_JIT


def fused_adam_rows_device(
    rows: np.ndarray, m: np.ndarray, v: np.ndarray, g: np.ndarray,
    t: np.ndarray, lr: float,
    b1: float = ADAM_B1, b2: float = ADAM_B2, eps: float = ADAM_EPS,
    interpret: bool = False,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The one-dispatch device twin of :func:`fused_adam_rows`: the whole
    touched-row micro-batch runs as a single compiled adam step (Pallas
    kernel on TPU). Row counts are padded to :data:`ROW_BLOCK` buckets so a
    stream of varying batch sizes shares a bounded executable set; padded
    rows carry zero gradients and unit bias corrections, and are sliced off
    before return. Bias corrections come from :func:`adam_bias_corrections`
    — the bitwise contract is the same as the host path's."""
    import jax

    r, d = rows.shape
    bc1, bc2 = adam_bias_corrections(t, b1, b2)
    pad = (-r) % ROW_BLOCK
    if pad:
        z = np.zeros((pad, d), np.float32)
        rows, m, v, g = (np.concatenate([a, z]) for a in (rows, m, v, g))
        bc1 = np.concatenate([bc1, np.ones(pad, np.float32)])
        bc2 = np.concatenate([bc2, np.ones(pad, np.float32)])
    out = _adam_rows_jit()(
        rows, m, v, g, bc1, bc2,
        lr=float(lr), b1=float(b1), b2=float(b2), eps=float(eps),
        interpret=interpret)
    rows2, m2, v2 = jax.device_get(out)
    return rows2[:r], m2[:r], v2[:r]


#: The lazily-built jitted gather→adam→scatter executable — built on first
#: use so importing this module (the host fold does) never imports jax.
_FUSED_JIT = None


def _fused_fn():
    global _FUSED_JIT
    if _FUSED_JIT is not None:
        return _FUSED_JIT
    import jax

    from incubator_predictionio_tpu.parallel.mesh import kernel_backend

    def fused(table, m_tab, v_tab, idx, g, bc1, bc2,
              *, lr, b1, b2, eps, interpret):
        rows = table[idx]
        m = m_tab[idx]
        v = v_tab[idx]
        backend = kernel_backend()
        if backend or interpret:
            rows, m, v = _pallas_adam_rows(
                rows, m, v, g, bc1, bc2, lr, b1, b2, eps,
                interpret or backend == "interpret")
        else:
            import jax.numpy as jnp

            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * (g * g)
            rows = rows - lr * (m / bc1[:, None]) / (
                jnp.sqrt(v / bc2[:, None]) + eps)
        return (table.at[idx].set(rows), m_tab.at[idx].set(m),
                v_tab.at[idx].set(v))

    _FUSED_JIT = jax.jit(
        fused, static_argnames=("lr", "b1", "b2", "eps", "interpret"))
    return _FUSED_JIT


def fused_gather_adam_scatter(
    table, m_tab, v_tab, idx, g, bc1, bc2,
    *, lr, b1=ADAM_B1, b2=ADAM_B2, eps=ADAM_EPS, interpret=False,
):
    """ONE dispatch for a touched-row batch against device-resident tables:
    gather ``table/m/v`` rows at ``idx``, run the adam core (Pallas on TPU,
    jnp elsewhere), scatter the results back. Returns new
    ``(table, m_tab, v_tab)`` — functional, the inputs are never mutated.

    ``bc1``/``bc2`` are the per-row bias corrections, precomputed host-side
    by :func:`adam_bias_corrections` so the double-precision ``b1 ** t``
    stays bit-identical to the reference path."""
    return _fused_fn()(
        table, m_tab, v_tab, idx, g, bc1, bc2,
        lr=lr, b1=b1, b2=b2, eps=eps, interpret=interpret)
