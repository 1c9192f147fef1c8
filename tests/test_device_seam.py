"""The program's device seam (parallel/mesh.py) and chip_smoke.py.

CPU tier-1 can only pin what the seam DECIDES: where the compile cache goes,
that no unknown chip gets a default peak, that a metrics scrape never
creates a backend, that N local processes are refused on a TPU host, that
chip_smoke.py's parent stays off jax, and that drills.py's children are
held to the CPU. What the chip does with it is
``python chip_smoke.py`` through the chip tool (README "Running").
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code: str, cwd: str, **env) -> str:
    """Run ``code`` in a fresh interpreter (the seam is per-process state)."""
    penv = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    penv.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env)
    out = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=penv,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout.strip().splitlines()[-1]


_CACHE_PROBE = """
import json, jax
from incubator_predictionio_tpu.parallel.mesh import MeshContext
MeshContext.create()
print(json.dumps(jax.config.jax_compilation_cache_dir))
"""


def test_compile_cache_unset_env_is_fixed_path_under_checkout(tmp_path):
    """Unset → <checkout>/.jax_cache, identical from two working dirs (the
    path is part of the cache key: it must not move)."""
    a = json.loads(_py(_CACHE_PROBE, cwd=str(tmp_path)))
    b = json.loads(_py(_CACHE_PROBE, cwd=REPO))
    assert a == b == os.path.join(REPO, ".jax_cache")


def test_compile_cache_env_set_means_code_sets_nothing(tmp_path):
    """JAX_COMPILATION_CACHE_DIR placed from outside: JAX reads it itself,
    the seam returns None and sets no directory in code."""
    code = """
import json, jax
from incubator_predictionio_tpu.parallel import mesh
seen = []
orig = jax.config.update
jax.config.update = lambda k, v: (seen.append(k), orig(k, v))
assert mesh.configure_compilation_cache() is None
mesh.MeshContext.create()
print(json.dumps([seen, jax.config.jax_compilation_cache_dir]))
"""
    outside = str(tmp_path / "outside")
    seen, path = json.loads(_py(code, cwd=str(tmp_path),
                                JAX_COMPILATION_CACHE_DIR=outside))
    assert "jax_compilation_cache_dir" not in seen
    assert path == outside


def test_unknown_device_kind_has_no_peak():
    from incubator_predictionio_tpu.obs import profile as prof

    assert prof.peak_flops_for("tpu", "TPU v5 lite") == 197e12
    assert prof.peak_flops_for("tpu", "TPU v99 imaginary") is None
    assert prof.peak_flops_for("cpu", "cpu") is None


DRILLS = ["overload", "fleet", "multi_tenant", "sharded_fleet", "ingestion",
          "ingest_durability", "streaming_freshness", "storage_failover",
          "continuous_training", "disaster_recovery", "distributed_training"]


def _drills():
    sys.path.insert(0, REPO)
    try:
        import drills
    finally:
        sys.path.remove(REPO)
    return drills


def test_drill_registry_is_the_eleven_host_plane_lanes():
    assert _drills().CONFIG_NAMES == DRILLS


@pytest.mark.parametrize("name", DRILLS)
def test_drill_resolves_and_its_child_is_held_to_the_cpu(
        name, monkeypatch, capsys):
    """Every drill exercises the host plane: its child process never claims
    a chip, whatever the operator's shell had set. The one pin is child
    mode's own (``run_one_config``), which a drill started by hand and a
    child of the runner both pass through."""
    drills = _drills()
    assert callable(drills._build_suite(None)[name])
    assert drills._child_argv(name)[1:] == [
        os.path.join(REPO, "drills.py"), "--config", name]
    # child mode with the drill's body stubbed: what the body would see
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(drills, "_build_suite", lambda ctx: {
        name: lambda: {"platform": os.environ["JAX_PLATFORMS"]}})
    monkeypatch.setattr(sys, "argv", ["drills.py", "--config", name])
    assert drills.main() == 0
    out = capsys.readouterr().out.splitlines()
    assert out == ['CONFIG_RESULT={"platform": "cpu"}']


def test_importing_drills_leaves_jax_out(tmp_path):
    code = ("import sys, drills; "
            "print('jax' if 'jax' in sys.modules else 'ok')")
    assert _py(code, cwd=str(tmp_path)) == "ok"


def test_metrics_scrape_never_creates_a_backend(tmp_path):
    """A process that only IMPORTED the jax-using modules (the stream
    updater, the jobs worker) must not claim the chip because something
    scraped /metrics."""
    code = """
import jax
from incubator_predictionio_tpu.models import two_tower  # noqa: F401
from incubator_predictionio_tpu.obs import profile
from incubator_predictionio_tpu.obs.metrics import REGISTRY
from incubator_predictionio_tpu.parallel.mesh import backend_initialized
REGISTRY.expose()
profile.update_device_watermark()
assert profile.detected_peak_flops() is None
assert profile.record_training_step(1e12, 1.0) is None
assert not backend_initialized(), "a scrape created the backend"
jax.devices()
assert backend_initialized()
REGISTRY.expose()
print("ok")
"""
    assert _py(code, cwd=str(tmp_path)) == "ok"


def test_kernel_backend_is_the_one_device_switch(monkeypatch):
    """No TPU → the jnp references; PIO_PALLAS_INTERPRET=1 → the same
    kernels under the interpreter, and serving_info names what ran."""
    import numpy as np

    from incubator_predictionio_tpu.models.two_tower import (
        TwoTowerConfig,
        TwoTowerMF,
        TwoTowerModel,
    )
    from incubator_predictionio_tpu.parallel import mesh

    monkeypatch.delenv(mesh.PALLAS_INTERPRET_ENV, raising=False)
    assert mesh.kernel_backend() is None
    rng = np.random.default_rng(0)
    user_emb = rng.normal(size=(40, 16)).astype(np.float32)
    item_emb = rng.normal(size=(700, 16)).astype(np.float32)

    def model(serve_k):
        m = TwoTowerModel(
            user_emb=user_emb, item_emb=item_emb,
            user_bias=np.zeros(40, np.float32),
            item_bias=np.zeros(700, np.float32),
            config=TwoTowerConfig(rank=16))
        return m.prepare_for_serving(quantize=True, host_max_elements=0,
                                     build_index=False, serve_k=serve_k)

    users = np.arange(5, dtype=np.int32)
    ref = model(16)
    assert ref.serving_info()["path"] == "device-int8-jnp"
    idx_ref, sc_ref = TwoTowerMF.recommend_batch(ref, users, 7)

    monkeypatch.setenv(mesh.PALLAS_INTERPRET_ENV, "1")
    assert mesh.kernel_backend() == "interpret"
    # a distinct serve_k: _topk_quantized's jit cache is keyed on its static
    # top-k, so this model traces afresh and takes the kernel
    kern = model(24)
    assert kern.serving_info()["path"] == "device-int8-pallas-interpret"
    idx, sc = TwoTowerMF.recommend_batch(kern, users, 7)
    np.testing.assert_array_equal(idx, idx_ref)
    np.testing.assert_allclose(sc, sc_ref, rtol=2e-2, atol=2e-2)


def test_local_fanout_refused_on_a_tpu_host(monkeypatch):
    from incubator_predictionio_tpu.parallel import launcher

    monkeypatch.setattr(launcher, "local_tpu_chips",
                        lambda: ["/dev/vfio/0", "/dev/vfio/1"])
    # explicit CPU (tests, rehearsal) and single processes always pass
    launcher.refuse_local_tpu_fanout(4, None, {"JAX_PLATFORMS": "cpu"})
    launcher.refuse_local_tpu_fanout(4, 2, {"JAX_PLATFORMS": ""})
    launcher.refuse_local_tpu_fanout(1, None, {"JAX_PLATFORMS": ""})
    with pytest.raises(RuntimeError, match="ONE process"):
        launcher.refuse_local_tpu_fanout(4, None, {"JAX_PLATFORMS": ""})
    with pytest.raises(RuntimeError, match="ONE process"):
        launcher.launch_local(["train"], 2, env={"JAX_PLATFORMS": "tpu"})
    # no chips on the host: nothing to contend for
    monkeypatch.setattr(launcher, "local_tpu_chips", lambda: [])
    launcher.refuse_local_tpu_fanout(4, None, {"JAX_PLATFORMS": ""})


def test_claim_devices_failure_names_the_rule(monkeypatch):
    import jax

    from incubator_predictionio_tpu.parallel import mesh

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="one process at a time") as e:
        mesh.claim_devices()
    assert "JAX_PLATFORMS=cpu" in str(e.value)
    assert "Unable to initialize backend" in str(e.value)


def test_chip_smoke_parent_never_imports_jax(tmp_path):
    code = """
import sys
sys.path.insert(0, %r)
import chip_smoke
assert "jax" not in sys.modules, "chip_smoke's parent imported jax"
u, i, r = chip_smoke.generate_events(3, 5000, 900, 700)
assert len(set(i.tolist())) == 700 and r.min() >= 1 and r.max() <= 5
u2, i2, r2 = chip_smoke.generate_events(3, 5000, 900, 700)
assert (u == u2).all() and (i == i2).all() and (r == r2).all()
# the last line of a passed chip run: exactly the contract's keys
import json
line = json.loads(chip_smoke.result_line(
    {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}))
assert line == {"ok": True, "device": {
    "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}, line
assert "jax" not in sys.modules
print("ok")
""" % REPO
    assert _py(code, cwd=str(tmp_path)) == "ok"


def test_chip_smoke_fails_in_its_first_phase_without_a_chip():
    """The bare command (what the driver runs) has no CPU path: with no
    accelerator it exits non-zero at `status` and prints no result."""
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "status" in out.stderr and "FAILED" in out.stderr


def test_kernel_check_refuses_cpu_without_interpret():
    from incubator_predictionio_tpu.ops import kernel_check

    with pytest.raises(RuntimeError, match="needs a TPU"):
        kernel_check.run_all(interpret=False)


@pytest.mark.slow
def test_chip_smoke_rehearsal_end_to_end():
    """--rehearse: every phase, tiny, on CPU with interpreted kernels."""
    # conftest's 8-device XLA_FLAGS would rehearse the sharded variant
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearse"],
        capture_output=True, text=True, timeout=1500, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    # a rehearsal prints its summary and never a result line
    (line,) = out.stdout.strip().splitlines()
    summary = json.loads(line)
    assert summary["ok"] and summary["rehearsal"] is True
    assert summary["platform"] == "cpu" and summary["cuts"]
    assert summary["claim"] is None
    assert summary["recall_at_10"] >= 0.9
    assert summary["kernels"]["ok"]
    assert summary["serve_paths"]["default"]["retrieval_mode"] == "two_stage"
    assert summary["serve_paths"]["exact"]["retrieval_mode"] == "exact"
    assert all(p["serve_path"] == "device-int8-pallas-interpret"
               for p in summary["serve_paths"].values())
