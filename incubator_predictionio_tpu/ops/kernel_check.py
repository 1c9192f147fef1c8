"""Compile and run every Pallas kernel of the package at the shapes its
callers use, each against its jnp reference.

``python -m incubator_predictionio_tpu.ops.kernel_check`` is the last phase
of ``chip_smoke.py``: on a TPU every kernel goes through Mosaic (never the
interpreter) and must agree with its reference within the tolerance the
CPU parity tests already pin. ``--interpret`` is the CPU rehearsal of the
same list at cut sizes (the stock flash-attention call has no interpreter
switch and is skipped there).

One JSON line per case on stdout, then one summary line; exit status 1
when any case failed to compile, run, or agree.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Callable, Iterator

import numpy as np


def _case(kernel: str, shape: dict, run: Callable[[], tuple], rtol: float,
          atol: float) -> dict:
    """Run one case: ``run() -> (got, want)`` pytrees of arrays."""
    import jax

    t0 = time.perf_counter()
    out = {"kernel": kernel, **shape}
    try:
        got, want = jax.device_get(run())
    except Exception as e:  # noqa: BLE001 - the failure IS the case's result
        out.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        return out
    err = 0.0
    ok = True
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        finite = np.isfinite(w)
        ok = ok and g.shape == w.shape \
            and bool(np.array_equal(finite, np.isfinite(g))) \
            and bool(np.allclose(g[finite], w[finite], rtol=rtol, atol=atol))
        if finite.any() and g.shape == w.shape:
            err = max(err, float(np.max(np.abs(g[finite] - w[finite]))))
    out.update(ok=ok, max_abs_err=err,
               seconds=round(time.perf_counter() - t0, 3))
    return out


def _coarse_cases(interpret: bool, rng) -> Iterator[dict]:
    """``score_centroids_quantized`` as serving/ann._probe_tpu calls it:
    int8 query buckets from 8 rows up, rank-wide, the centroid table padded
    to the block multiple with -inf bias."""
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.retrieval import (
        pad_centroids,
        score_centroids_quantized,
        score_centroids_reference,
    )

    # 316 = √100k partitions (one block), 1000 = √1M (two blocks)
    grid = [(b, 128, 316) for b in (8, 16, 32, 64, 128, 256)] + [
        (8, 32, 316), (64, 64, 1000)]
    if interpret:
        grid = [(8, 128, 316), (64, 32, 600)]
    for b, d, c in grid:
        cq, cs, cb = pad_centroids(
            rng.integers(-127, 128, (c, d)).astype(np.int8),
            rng.random(c).astype(np.float32) * 0.01,
            rng.normal(size=c).astype(np.float32))
        args = (jnp.asarray(rng.integers(-127, 128, (b, d)).astype(np.int8)),
                jnp.asarray(rng.random(b).astype(np.float32) * 0.01),
                jnp.asarray(cq), jnp.asarray(cs), jnp.asarray(cb))
        # exact int32 accumulation both sides; only the one fp32 rescale
        # may contract differently (tests/test_retrieval_kernel.py)
        yield _case(
            "score_centroids_quantized", {"b": b, "d": d, "c": int(cq.shape[0])},
            lambda args=args: (
                score_centroids_quantized(*args, interpret=interpret),
                score_centroids_reference(*args)),
            rtol=1e-6, atol=1e-6)


def _catalog_cases(interpret: bool, rng) -> Iterator[dict]:
    """``score_catalog_quantized`` ± row mask for every serve bucket, the
    int8 block ``(512, d)`` at d 32/64/128, the 100k catalog at rank 128."""
    import jax.numpy as jnp

    from incubator_predictionio_tpu.serving.plan import (
        ROW_MASK_MAX_ELEMENTS,
        SERVE_BUCKETS,
    )
    from incubator_predictionio_tpu.ops.retrieval import (
        pad_catalog,
        quantize_rows,
        score_catalog_quantized,
        score_catalog_reference,
    )

    grid = [(b, 128, 100_000) for b in SERVE_BUCKETS] + [
        (b, d, 8_192) for d in (32, 64) for b in (1, 8, 256)]
    if interpret:
        grid = [(1, 128, 1_500), (4, 32, 1_024), (64, 64, 1_024)]
    catalogs: dict = {}
    for b, d, n in grid:
        if (d, n) not in catalogs:
            items_q, scales = quantize_rows(
                rng.normal(size=(n, d)).astype(np.float32))
            catalogs[(d, n)] = tuple(jnp.asarray(a) for a in pad_catalog(
                items_q, scales, rng.normal(size=n).astype(np.float32),
                np.zeros(n, np.float32)))
        cat = catalogs[(d, n)]
        n_p = int(cat[0].shape[0])
        q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
        masks = [None]
        if b * n <= ROW_MASK_MAX_ELEMENTS:
            rm = np.zeros((b, n_p), np.float32)
            rm[np.arange(b), rng.integers(0, n, b)] = -np.inf
            masks.append(jnp.asarray(rm))
        for rm in masks:
            yield _case(
                "score_catalog_quantized",
                {"b": b, "d": d, "n": n_p, "row_mask": rm is not None},
                lambda q=q, cat=cat, rm=rm: (
                    score_catalog_quantized(q, *cat, rm, interpret=interpret),
                    score_catalog_reference(q, *cat, rm)),
                rtol=2e-2, atol=2e-2)


def _adam_cases(interpret: bool, rng) -> Iterator[dict]:
    """``_pallas_adam_rows`` through ``fused_gather_adam_scatter`` (the
    streaming fold's device engine): row blocks ``(256, rank + 1)``."""
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.sparse_update import (
        adam_bias_corrections,
        fused_adam_rows,
        fused_gather_adam_scatter,
    )

    grid = [(33, 300, 5_000), (65, 256, 5_000), (129, 1_000, 20_000)]
    if interpret:
        grid = [(33, 40, 500), (129, 300, 1_000)]
    for d, r, rows in grid:
        table, m_tab, g = (rng.normal(size=s).astype(np.float32)
                           for s in ((rows, d), (rows, d), (r, d)))
        v_tab = rng.random((rows, d)).astype(np.float32)
        idx = rng.choice(rows, r, replace=False).astype(np.int32)
        t = rng.integers(1, 50, r)
        bc1, bc2 = adam_bias_corrections(t)

        def run(table=table, m_tab=m_tab, v_tab=v_tab, idx=idx, g=g,
                bc1=bc1, bc2=bc2, t=t):
            got = fused_gather_adam_scatter(
                *(jnp.asarray(a) for a in
                  (table, m_tab, v_tab, idx, g, bc1, bc2)),
                lr=0.01, interpret=interpret)
            want = [table.copy(), m_tab.copy(), v_tab.copy()]
            for dst, new in zip(want, fused_adam_rows(
                    table[idx], m_tab[idx], v_tab[idx], g, t, 0.01)):
                dst[idx] = new
            return got, tuple(want)

        # compiled engines are pinned to fp32 roundoff of the host pass
        # (tests/test_sparse_update.py)
        yield _case("fused_gather_adam_scatter",
                    {"d": d, "touched": r, "rows": rows}, run,
                    rtol=2e-5, atol=1e-6)


def _attention_cases(interpret: bool, rng) -> Iterator[dict]:
    """``causal_mha_small_head`` forward and backward at the sequential
    template's benched shape and at the largest shapes
    ``fits_small_head_kernel`` admits; then the stock flash kernel with the
    block sizes ``parallel/ring.causal_attention`` pins."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.attention import (
        causal_mha_small_head,
        fits_small_head_kernel,
    )
    from incubator_predictionio_tpu.parallel.ring import (
        causal_attention,
        causal_attention_reference,
    )

    grid = [(64, 8, 512, 64), (4, 16, 512, 64), (4, 8, 640, 64),
            (4, 8, 512, 128)]
    if interpret:
        grid = [(2, 2, 128, 64)]

    def ref(q, k, v):  # [B, H, L, D] in and out
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(causal_attention_reference(t(q), t(k), t(v)))

    # eager reference on CPU: XLA:CPU has no bf16 dot thunk for the layout
    # it picks when the transposes fuse into the matmul under jit
    jit = (lambda f: f) if interpret else jax.jit

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w)

    for b, h, l, d in grid:
        assert fits_small_head_kernel(b, l, h, d), (b, h, l, d)
        q, k, v = (jnp.asarray(rng.normal(size=(b, h, l, d)), jnp.bfloat16)
                   for _ in range(3))
        w = jnp.asarray(rng.normal(size=(b, h, l, d)), jnp.float32)
        kern = lambda q, k, v: causal_mha_small_head(q, k, v, interpret)
        shape = {"b": b, "h": h, "l": l, "d": d}
        yield _case("causal_mha_small_head.fwd", shape,
                    lambda: (kern(q, k, v), jit(ref)(q, k, v)),
                    rtol=2e-2, atol=2e-2)
        yield _case(
            "causal_mha_small_head.bwd", shape,
            lambda: (jit(jax.grad(loss(kern), (0, 1, 2)))(q, k, v),
                     jit(jax.grad(loss(ref), (0, 1, 2)))(q, k, v)),
            rtol=5e-2, atol=5e-2)
    if interpret:
        return
    # d 128 is outside the small-head budget at L 1024, so causal_attention
    # itself routes this shape to the stock kernel with the pinned blocks
    b, l, h, d = 4, 1024, 8, 128
    assert not fits_small_head_kernel(b, l, h, d)
    q, k, v = (jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.bfloat16)
               for _ in range(3))
    w = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    shape = {"b": b, "h": h, "l": l, "d": d}
    yield _case("flash_attention.fwd", shape,
                lambda: (jax.jit(causal_attention)(q, k, v),
                         jax.jit(causal_attention_reference)(q, k, v)),
                rtol=2e-2, atol=2e-2)
    yield _case(
        "flash_attention.bwd", shape,
        lambda: (
            jax.jit(jax.grad(loss(causal_attention), (0, 1, 2)))(q, k, v),
            jax.jit(jax.grad(loss(causal_attention_reference),
                             (0, 1, 2)))(q, k, v)),
        rtol=5e-2, atol=5e-2)


#: ``(held, d, f, [(sorted rows, experts touched)])`` of the five sequence
#: cells' routed experts as served: a lone turn's picks, then a long block's
GROUPED_MATMUL_WIDTHS = (
    (64, 2688, 1920, [(96, 12), (768, 64), (12_288, 64)]),    # visitor cell
    (16, 2048, 1792, [(64, 4), (16_384, 16)]),                 # feed cell
    (32, 4096, 2048, [(64, 4), (12_288, 32)]),                 # Mistral cell
    (128, 2048, 768, [(128, 24), (16_384, 128)]),              # lifelong cell
    (16, 2304, 896, [(32, 6), (16_384, 16)]),                  # histories cell
)


def _grouped_matmul_cases(interpret: bool, rng) -> Iterator[dict]:
    """``grouped_matmul`` as models/latent_moe.moe_experts calls it at the
    five sequence cells' widths (the held experts, both matrices, bfloat16):
    a lone turn's sorted picks on a few experts and a long block's over all
    of them, about half of the rows past the groups (picks on experts held
    elsewhere sort behind them)."""
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.grouped_matmul import (
        grouped_matmul,
        grouped_matmul_reference,
    )

    widths = GROUPED_MATMUL_WIDTHS
    if interpret:
        widths = ((8, 256, 384, [(96, 3), (300, 8)]),
                  (8, 256, 512, [(64, 2), (300, 8)]))
    for held, d, f, grid in widths:
        w1, w2 = (jnp.asarray(
            rng.standard_normal(s, np.float32) * s[1] ** -0.5, jnp.bfloat16)
            for s in ((held, d, f), (held, f, d)))
        for m, touched in grid:
            sizes = np.zeros(held, np.int32)
            picks = rng.choice(held, touched, replace=False)
            np.add.at(sizes, rng.choice(picks, m // 2), 1)
            for rhs in (w1, w2):
                k, n = rhs.shape[1:]
                lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
                args = (lhs, rhs, jnp.asarray(sizes))
                total = int(sizes.sum())

                def run(args=args, total=total):
                    got = grouped_matmul(*args, interpret=interpret)
                    want = grouped_matmul_reference(*args)
                    # (past the groups the kernel leaves zeros and XLA's
                    # grouped matmul, on a TPU, whatever it finds)
                    return ((got[:total], got[total:]),
                            (want[:total], jnp.zeros_like(got[total:])))

                # float32 sums of the same bfloat16 products, in another
                # order
                yield _case(
                    "grouped_matmul",
                    {"m": m, "k": k, "n": n, "groups": held,
                     "touched": touched},
                    run, rtol=1e-3, atol=1e-3)


FAMILIES = (_coarse_cases, _catalog_cases, _adam_cases, _attention_cases,
            _grouped_matmul_cases)


def run_all(interpret: bool, seed: int = 0, out=sys.stdout) -> dict:
    """Run every family; prints one JSON line per case and returns the
    summary. Without ``interpret`` the default backend must be a TPU."""
    import jax

    from incubator_predictionio_tpu.parallel.mesh import (
        configure_compilation_cache,
        kernel_backend,
    )

    configure_compilation_cache()
    if not interpret and kernel_backend() != "mosaic":
        raise RuntimeError(
            f"kernel_check needs a TPU (default backend: "
            f"{jax.default_backend()}); --interpret is the CPU rehearsal")
    rng = np.random.default_rng(seed)
    results = []
    for family in FAMILIES:
        for res in family(interpret, rng):
            print(json.dumps(res), file=out, flush=True)
            results.append(res)
    dev = jax.devices()[0]
    failed = [r for r in results if not r["ok"]]
    return {
        "kernel_check": "interpret" if interpret else "mosaic",
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "cases": len(results), "failed": len(failed),
        "ok": not failed,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--interpret", action="store_true",
                   help="CPU rehearsal: Pallas interpreter, cut sizes")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    summary = run_all(args.interpret, args.seed)
    print(json.dumps(summary), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
