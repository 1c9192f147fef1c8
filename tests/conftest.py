"""Test configuration: force an 8-virtual-device CPU mesh before JAX loads.

Mirrors the reference's test strategy of running a real multi-worker context in
unit tests (Spark ``local[4]`` via core/src/test/.../workflow/BaseTest.scala) —
for us that is an 8-device CPU mesh so every sharding/pjit path executes real
collectives without TPU hardware.
"""

import os

# XLA_FLAGS / platform selection are read at first backend initialization,
# so they are set here, before anything imports jax.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

assert jax.devices()[0].platform == "cpu" and len(jax.devices()) >= 8, (
    "tests need the virtual 8-device CPU mesh; a JAX backend was "
    f"initialized before tests/conftest.py ran: {jax.devices()}"
)

import tempfile

import pytest


@pytest.fixture()
def mesh8():
    """A real data×model mesh over the 8 virtual CPU devices — the tier-1-
    safe stand-in for a multi-chip TPU slice (``@pytest.mark.multichip``
    cases run sharded train/serve parity in the NORMAL suite; the XLA_FLAGS
    + JAX_PLATFORMS=cpu forcing above is what makes that safe)."""
    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    return MeshContext.create(axes={"data": 2, "model": 4})


@pytest.fixture()
def shard_env(monkeypatch):
    """Clean PIO_SHARD_* env for sharded-serving cases; returns monkeypatch
    so tests set the knobs they pin."""
    for var in ("PIO_SHARD_SERVE", "PIO_SHARD_SERVE_SHARDS",
                "PIO_SHARD_HBM_BUDGET"):
        monkeypatch.delenv(var, raising=False)
    return monkeypatch


@pytest.fixture()
def tmp_pio_home(monkeypatch):
    """Isolated PIO_FS_BASEDIR + default sqlite storage config per test."""
    with tempfile.TemporaryDirectory() as d:
        monkeypatch.setenv("PIO_FS_BASEDIR", d)
        monkeypatch.setenv("PIO_STORAGE_SOURCES_SQLITE_TYPE", "sqlite")
        monkeypatch.setenv("PIO_STORAGE_SOURCES_SQLITE_PATH", os.path.join(d, "pio.db"))
        for repo in ("METADATA", "EVENTDATA", "MODELDATA"):
            monkeypatch.setenv(f"PIO_STORAGE_REPOSITORIES_{repo}_NAME", f"pio_{repo.lower()}")
            monkeypatch.setenv(f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE", "SQLITE")
        yield d


@pytest.fixture(scope="session")
def tls_cert(tmp_path_factory):
    """Self-signed PEM cert/key pair for TLS round-trip tests (the reference
    ships a JKS keystore for the same purpose; our servers take PEM)."""
    import subprocess

    d = tmp_path_factory.mktemp("tls")
    cert, key = str(d / "cert.pem"), str(d / "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "1",
         "-subj", "/CN=localhost"],
        check=True, capture_output=True)
    return cert, key
