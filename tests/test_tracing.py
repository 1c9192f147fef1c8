"""Profiling hooks (obs/profile.py): trace capture + memory report, with
spans from the one primitive (obs/trace.span) inside the capture."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from incubator_predictionio_tpu.obs.profile import (
    device_memory_report,
    profile_trace,
)
from incubator_predictionio_tpu.obs.trace import span


def test_profile_trace_writes_tensorboard_profile(tmp_path):
    log_dir = str(tmp_path / "trace")
    with profile_trace(log_dir):
        with span("test.matmul.block"):
            x = jnp.ones((64, 64))
            for step in range(2):
                with span("test.matmul.step", step=step):
                    (x @ x).block_until_ready()
    # standard layout: <log_dir>/plugins/profile/<run>/<files>
    profile_root = os.path.join(log_dir, "plugins", "profile")
    assert os.path.isdir(profile_root)
    runs = os.listdir(profile_root)
    assert runs and os.listdir(os.path.join(profile_root, runs[0]))


def test_device_memory_report_shape():
    rows = device_memory_report()
    assert len(rows) == jax.device_count()
    assert all({"device", "platform", "bytes_in_use"} <= set(r) for r in rows)
    assert all(r["platform"] == "cpu" for r in rows)


def test_two_tower_trains_under_trace(tmp_path):
    """The fit's spans (train.fit.*, train.epochs.chunk) must not break
    training under a live profiler session."""
    from incubator_predictionio_tpu.models.two_tower import TwoTowerConfig, TwoTowerMF
    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    rng = np.random.default_rng(0)
    n = 128
    ctx = MeshContext.create(axes={"data": 8})
    with profile_trace(str(tmp_path / "t")):
        model = TwoTowerMF(TwoTowerConfig(rank=4, epochs=2, batch_size=64)).fit(
            ctx,
            rng.integers(0, 10, n).astype(np.int32),
            rng.integers(0, 8, n).astype(np.int32),
            rng.random(n).astype(np.float32),
            n_users=10, n_items=8,
        )
    assert np.isfinite(model.final_loss)
