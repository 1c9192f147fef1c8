"""What the two runners share on the program's side: a clean environment,
storage under the run's work directory, the engine variant file and one
``run_train`` call."""

from __future__ import annotations

import datetime as dt
import json
import os
import re

from benchmarks import harness

FACTORY = "benchmarks.engines.seeded.BenchEngine"


def clean_env(work: str, extra: dict) -> dict:
    """No ``PIO_*`` setting leaks in from outside; the configuration's own
    ``env`` is applied; everything the program writes lands under ``work``."""
    for k in [k for k in os.environ if k.startswith("PIO_")]:
        del os.environ[k]
    home = os.path.join(work, "home")
    os.makedirs(home, exist_ok=True)
    env = {
        "PIO_FS_BASEDIR": home,
        "PIO_NATIVE_BUILD_DIR": os.path.join(work, "native"),
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(home, "pio.db"),
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(home, "models"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
    }
    for k, v in extra.items():
        if not k.startswith("PIO_"):
            raise harness.HarnessError(
                f"configuration env may set PIO_* knobs only, not {k!r}")
        env[k] = str(v)
    os.environ.update(env)
    return env


def write_variant(work: str, algorithm: str, params: dict) -> tuple[str, dict]:
    variant = {
        "id": "bench", "version": "1", "engineFactory": FACTORY,
        "datasource": {"params": {"key": "bench"}},
        "algorithms": [{"name": algorithm, "params": params}],
    }
    path = os.path.join(work, "engine.json")
    with open(path, "w") as f:
        json.dump(variant, f)
    return path, variant


def train_once(engine, variant: dict, variant_path: str, storage, ctx) -> str:
    """One whole ``run_train``: DataSource → Preparator → Algorithm.train →
    persist → instance COMPLETED. Returns the instance id."""
    from incubator_predictionio_tpu.core.workflow import run_train
    from incubator_predictionio_tpu.data.storage.base import EngineInstance

    instance = EngineInstance(
        id="", status="INIT", start_time=dt.datetime.now(dt.timezone.utc),
        end_time=None, engine_id=variant["id"],
        engine_version=variant["version"],
        engine_variant=os.path.abspath(variant_path),
        engine_factory=variant["engineFactory"])
    return run_train(engine, engine.engine_params_from_variant(variant),
                     instance, storage=storage, ctx=ctx)


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_metrics(text: str) -> dict:
    """Prometheus text → ``{"name{labels}": value}`` (counters, gauges and
    histogram ``_sum`` / ``_count`` / ``_bucket`` rows)."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SAMPLE.match(line.strip())
        if not m:
            continue
        try:
            out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
        except ValueError:
            continue
    return out


def print_check(name: str, value: float, op: str, limit: float) -> bool:
    """Every number compared, beside its limit, in every run."""
    ok = {"<=": value <= limit, ">=": value >= limit,
          "==": value == limit}[op]
    print(f"check {name} = {value!r}  limit {op} {limit!r}  "
          f"{'ok' if ok else 'NOT OK'}", flush=True)
    return ok


def batch_histogram(before: dict, after: dict) -> dict:
    """Dispatches in the window by batch-size bucket ``{le: count}``, from the
    cumulative ``pio_serving_template_batch_size_bucket`` rows (their ``le``
    edges are the serve bucket ladder)."""
    cum = {}
    for key, value in after.items():
        if not key.startswith("pio_serving_template_batch_size_bucket"):
            continue
        le = re.search(r'le="([^"]+)"', key).group(1)
        if le == "+Inf":
            continue
        cum[float(le)] = cum.get(float(le), 0.0) + value - before.get(key, 0.0)
    out, prev = {}, 0.0
    for le in sorted(cum):
        out[int(le)] = cum[le] - prev
        prev = cum[le]
    return {le: n for le, n in out.items() if n > 0}
