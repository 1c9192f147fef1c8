#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the recommendation template through the entry points a user calls,
each verb its own OS process, on the directly attached TPU:

    pio-tpu status → app new → import → train → deploy (default retrieval
    mode) + HTTP queries → deploy (PIO_RETRIEVAL_MODE=exact) + the same
    queries → every Pallas kernel against its jnp reference

at the production-representative two-tower shape (rank 128, batch 65536,
100k-item catalog, users drawn from 1M ids), events generated from ``--seed``.

One process holds a chip at a time, so this parent NEVER imports jax: every
phase is a child that exits before the next starts, children run with
``JAX_PLATFORMS=tpu`` (a missing or busy chip is an error, not a CPU run),
and platform / device kind / device count are read from what the children
print (``Devices:`` of status, ``mesh:`` of train, ``GET /`` of deploy).

Any failed phase, non-2xx answer, degraded answer or timeout exits non-zero
with no result line; children are killed by process group. On success
stdout carries two JSON lines: the run's summary (versions, shapes, cuts,
per-phase seconds, serve paths, recall, kernels; also written to
``chiprun_out/chip_smoke/summary.json``), and then, as the LAST line, the
result with exactly these keys and the device as the children's JAX reported
it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse`` (never what the driver runs) walks the same phases at cut
sizes on ``JAX_PLATFORMS=cpu`` with the kernels under the Pallas interpreter
and stamps ``"rehearsal": true, "platform": "cpu"`` on the summary. A
rehearsal prints its summary only, never a result line.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib.metadata
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
CLI = [sys.executable, "-m", "incubator_predictionio_tpu.tools.cli"]
APP = "chipsmoke"

#: rank, batch, catalog and user ids of the benchmark's train configuration
#: (``rec-1Mx100k-r128``). Rank, batch and the catalog are never cut — events
#: and user ids are what a time limit may cut, and a cut is printed
FULL = {"rank": 128, "batch": 65536, "iterations": 2, "n_items": 100_000,
        "user_ids": 1_000_000, "n_events": 2_000_000}
#: rehearsal keeps the width (rank) and the events-per-item / events-per-user
#: ratios, and cuts the catalog only as far as still crosses the
#: device-serving threshold (HOST_SERVE_MAX_ELEMENTS)
REHEARSAL = {"rank": 128, "batch": 8192, "iterations": 2, "n_items": 16_384,
             "user_ids": 163_840, "n_events": 327_680}
TASTE_GROUPS = 64
#: L2 weight of the smoke's engine.json. At ~2 events per user (2M events
#: over 1M ids) the user towers stay near their random init, and at the
#: template default (1e-4) two epochs of adam leave the item towers as
#: noise no IVF index can prune; 0.5 keeps them small enough that the
#: learned item quality (bias) and taste structure dominate the geometry,
#: which is what the two-stage/exact recall check needs to be about the
#: serving paths and not about an untrained model
LAMBDA = 0.5
#: the whole run, compile included, must end inside the driver's 1200 s;
#: every phase's own timeout is cut to what is left of this
BUDGET_SEC = 1150.0
N_SERIAL, N_BURST, NUM = 32, 64, 10
#: server counters that say which path answered (docs/observability.md)
COUNTERS = ("pio_retrieval_two_stage_total", "pio_retrieval_fallback_total",
            "pio_retrieval_int8_coarse_total",
            "pio_retrieval_int8_rerank_total",
            "pio_retrieval_device_rerank_total", "pio_shard_batches_total",
            "pio_shard_fallback_total", "pio_shard_full_gather_total")
MIN_RECALL = 0.9

_current_child: subprocess.Popen | None = None
_started = time.monotonic()


def _cap(timeout: float) -> float:
    """``timeout`` cut to what is left of the run's budget."""
    return max(1.0, min(timeout, BUDGET_SEC - (time.monotonic() - _started)))


class SmokeFailure(Exception):
    """A phase failed; the message says which and why."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# -- child processes -----------------------------------------------------------

def _kill_group(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    proc.wait()


def _on_signal(signum, _frame):
    if _current_child is not None:
        _kill_group(_current_child)
    sys.exit(128 + signum)


def run_child(name: str, argv: list[str], env: dict, log_dir: str,
              timeout: float) -> str:
    """Run one phase's child to completion; returns its combined output.
    Output goes to a file, not a pipe (nothing to drain, nothing to block)."""
    global _current_child
    timeout = _cap(timeout)
    path = os.path.join(log_dir, f"{name}.log")
    with open(path, "w") as f:
        proc = subprocess.Popen(argv, env=env, stdout=f,
                                stderr=subprocess.STDOUT, cwd=HERE,
                                start_new_session=True)
        _current_child = proc
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{name}: no exit within {timeout:.0f}s\n{_tail(path)}")
        finally:
            _kill_group(proc)  # the child, and stragglers of its group
            _current_child = None
    with open(path) as f:
        out = f.read()
    if rc != 0:
        raise SmokeFailure(f"{name}: exit code {rc}\n{_tail(path)}")
    return out


def _tail(path: str, n: int = 4000) -> str:
    with open(path, errors="replace") as f:
        return f.read()[-n:]


def cache_entries(cache_dir: str) -> int:
    """Executables in JAX's persistent compile cache (``*-atime`` files are
    its access-time sidecars, not entries)."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if not f.endswith("-atime"))


# -- data ----------------------------------------------------------------------

def generate_events(seed: int, n_events: int, user_ids: int, n_items: int):
    """Rating events from ``seed``: ``(user ids, item ids, ratings)`` int/int/
    float arrays. Users are drawn from ``user_ids`` ids; EVERY item occurs
    (events cycle through the catalog in shuffled order). A rating is item
    quality, plus whether the item is in the user's taste group, plus noise,
    rounded to 1..5 — the structure a trained catalog needs for pruned
    retrieval to mean anything."""
    import numpy as np

    if n_events < n_items:
        raise ValueError("need at least one event per catalog item")

    rng = np.random.default_rng(seed)
    users = rng.integers(0, user_ids, n_events)
    slot = rng.permutation(n_events)
    items = slot % n_items
    quality = rng.normal(size=n_items)
    user_group = users % TASTE_GROUPS
    # 70% of a user's events land in their own taste group: move the drawn
    # item to the slot of its 64-stride that carries the user's group. The
    # first pass over the catalog (slot < n_items) stays put, so every item
    # keeps at least one event
    own = (rng.random(n_events) < 0.7) & (slot >= n_items)
    moved = (items // TASTE_GROUPS) * TASTE_GROUPS + user_group
    items = np.where(own & (moved < n_items), moved, items)
    match = user_group == items % TASTE_GROUPS
    ratings = np.clip(np.round(
        3.0 + 0.8 * quality[items] + np.where(match, 1.0, -1.0)
        + 0.3 * rng.normal(size=n_events)), 1, 5)
    return users, items, ratings


def write_events(path: str, users, items, ratings) -> None:
    line = ('{{"event":"rate","entityType":"user","entityId":"u{}",'
            '"targetEntityType":"item","targetEntityId":"i{}",'
            '"properties":{{"rating":{}}},'
            '"eventTime":"2024-01-01T00:00:00.000Z"}}\n')
    with open(path, "w") as f:
        chunk = 200_000
        for lo in range(0, len(users), chunk):
            f.write("".join(
                line.format(u, i, r) for u, i, r in zip(
                    users[lo:lo + chunk].tolist(),
                    items[lo:lo + chunk].tolist(),
                    ratings[lo:lo + chunk].tolist())))


# -- HTTP ----------------------------------------------------------------------

def http_json(url: str, payload: dict | None = None, timeout: float = 60.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method="GET" if data is None else "POST",
        headers={"Content-Type": "application/json",
                 "Accept": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            if not 200 <= resp.status < 300:
                raise SmokeFailure(f"{url}: HTTP {resp.status}")
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"{url}: HTTP {e.code} {e.read()[:500]!r}") from e


def http_counters(url: str, names: tuple[str, ...]) -> dict[str, float]:
    """Sum of each named counter over its label sets in a Prometheus text
    exposition (absent = 0)."""
    with urllib.request.urlopen(url, timeout=60.0) as resp:
        text = resp.read().decode()
    totals = dict.fromkeys(names, 0.0)
    for line in text.splitlines():
        m = re.match(r"(\w+)(?:\{[^}]*\})? (\S+)$", line)
        if m and m.group(1) in totals:
            totals[m.group(1)] += float(m.group(2))
    return totals


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def check_answer(answer: dict, user: str, n_items: int) -> list[str]:
    """One query's answer by the issue's rules; returns the item ids."""
    if "degraded" in answer:
        raise SmokeFailure(f"user {user}: degraded answer {answer}")
    scores = answer.get("itemScores")
    if not isinstance(scores, list) or len(scores) != NUM:
        raise SmokeFailure(f"user {user}: want {NUM} itemScores, got {answer}")
    ids = []
    for s in scores:
        m = re.fullmatch(r"i(\d+)", str(s.get("item")))
        if m is None or not 0 <= int(m.group(1)) < n_items:
            raise SmokeFailure(f"user {user}: item outside the catalog: {s}")
        if not isinstance(s.get("score"), (int, float)) \
                or not math.isfinite(s["score"]):
            raise SmokeFailure(f"user {user}: non-finite score: {s}")
        ids.append(s["item"])
    if len(set(ids)) != NUM:
        raise SmokeFailure(f"user {user}: duplicate items {ids}")
    return ids


def deploy_and_query(name: str, variant: str, env: dict, log_dir: str,
                     users: list[str], n_items: int, expect_mode: str,
                     expect_path_prefix: str, timeout: float) -> dict:
    """Deploy in a child, query it on a real socket, check health and the
    status page, stop it. Returns the phase's findings."""
    global _current_child
    timeout = _cap(timeout)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    path = os.path.join(log_dir, f"{name}.log")
    t0 = time.monotonic()
    with open(path, "w") as f:
        proc = subprocess.Popen(
            [*CLI, "deploy", "-v", variant, "--ip", "127.0.0.1",
             "--port", str(port)],
            env=env, stdout=f, stderr=subprocess.STDOUT, cwd=HERE,
            start_new_session=True)
        _current_child = proc
        try:
            # the server binds only after load + prepare + warm-up
            while True:
                if proc.poll() is not None:
                    raise SmokeFailure(
                        f"{name}: deploy exited with code {proc.returncode} "
                        f"before serving\n{_tail(path)}")
                if time.monotonic() - t0 > timeout:
                    raise SmokeFailure(
                        f"{name}: not serving within {timeout:.0f}s\n"
                        f"{_tail(path)}")
                try:
                    http_json(f"{base}/", timeout=5.0)
                    break
                except (urllib.error.URLError, ConnectionError, OSError):
                    time.sleep(0.5)
            ready = time.monotonic() - t0

            def ask(user):
                return check_answer(
                    http_json(f"{base}/queries.json",
                              {"user": user, "num": NUM}), user, n_items)

            answers = {u: ask(u) for u in users[:N_SERIAL]}
            with concurrent.futures.ThreadPoolExecutor(N_BURST) as pool:
                burst = users[N_SERIAL:N_SERIAL + N_BURST]
                answers.update(zip(burst, pool.map(ask, burst)))

            health = http_json(f"{base}/health")
            status = http_json(f"{base}/")
            counters = http_counters(f"{base}/metrics", COUNTERS)
            if health["degradedResponses"] != 0:
                raise SmokeFailure(f"{name}: degradedResponses "
                                   f"{health['degradedResponses']}")
            breakers = {"serving": health["servingBreaker"],
                        **health["algorithmBreakers"],
                        **health["backendBreakers"]}
            opened = {k: b["state"] for k, b in breakers.items()
                      if b["state"] != "closed"}
            if opened:
                raise SmokeFailure(f"{name}: breakers not closed: {opened}")
            serving = status["servingPaths"][0]
            if not serving["path"].startswith(expect_path_prefix):
                raise SmokeFailure(
                    f"{name}: serving path {serving['path']!r}, want "
                    f"{expect_path_prefix}*")
            if serving["retrieval_mode"] != expect_mode:
                raise SmokeFailure(
                    f"{name}: retrieval_mode {serving['retrieval_mode']!r}, "
                    f"want {expect_mode!r}")
            if status["requestCount"] != len(answers):
                raise SmokeFailure(
                    f"{name}: requestCount {status['requestCount']} != "
                    f"{len(answers)} queries sent")
            # the path the answers really took, from the server's counters
            sharded = serving["path"].startswith("sharded-")
            took = ("pio_shard_batches_total" if sharded
                    else "pio_retrieval_two_stage_total"
                    if expect_mode == "two_stage" else None)
            if took is not None and counters[took] < 1:
                raise SmokeFailure(f"{name}: {took} is 0: {counters}")
            if expect_mode == "two_stage" and not sharded and \
                    counters["pio_retrieval_int8_coarse_total"] < 1:
                raise SmokeFailure(
                    f"{name}: the int8 centroid scorer never ran: {counters}")
            if expect_mode == "exact" and \
                    counters["pio_retrieval_two_stage_total"]:
                raise SmokeFailure(f"{name}: exact mode pruned: {counters}")
            if sharded and counters["pio_shard_full_gather_total"]:
                raise SmokeFailure(
                    f"{name}: sharded serving gathered a full table "
                    f"({counters}): restore did not keep the layout")
            if status["maxBatchSeen"] <= 1:
                raise SmokeFailure(
                    f"{name}: the burst of {N_BURST} never formed a batch "
                    f"(maxBatchSeen {status['maxBatchSeen']})")
            # orderly stop: the server answers, drains, and exits 0
            http_json(f"{base}/stop", {})
            try:
                rc = proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(f"{name}: /stop did not end the server")
            if rc != 0:
                raise SmokeFailure(f"{name}: server exit code {rc}")
        finally:
            _kill_group(proc)
            _current_child = None
    return {
        "answers": answers,
        "ready_sec": round(ready, 1),
        "serve_path": serving["path"],
        "retrieval_mode": serving["retrieval_mode"],
        "sharding": (None if not serving.get("sharding") else {
            "n_shards": serving["sharding"]["n_shards"],
            "mode": serving["sharding"]["mode"],
            "item_shard_rows": serving["sharding"]["items"]["shard_rows"],
            "user_shard_rows": serving["sharding"]["users"]["shard_rows"]}),
        "counters": {k: v for k, v in counters.items() if v},
        "device": {"platform": status["platform"],
                   "kind": status["deviceKind"],
                   "count": status["deviceCount"]},
        "max_batch_seen": status["maxBatchSeen"],
        "jit_compile_keys": status["jitCompileKeys"],
    }


# -- the run -------------------------------------------------------------------

def child_env(work: str, rehearse: bool, n_items: int) -> dict:
    """Environment of every child: no PIO_* setting leaks in from outside,
    storage and the native build live under the work directory."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PIO_")}
    home = os.path.join(work, "pio_home")
    env.update({
        "PYTHONPATH": HERE + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu" if rehearse else "tpu",
        "PIO_FS_BASEDIR": home,
        # never the prebuilt (untracked) .so next to the sources: the native
        # reader is built from native/src/*.cc into the work directory
        "PIO_NATIVE_BUILD_DIR": os.path.join(work, "native"),
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": os.path.join(home, "pio.db"),
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(home, "eventlog"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
    })
    if rehearse:
        env["PIO_PALLAS_INTERPRET"] = "1"
        # the cut catalog must still take the default (two-stage) mode
        env["PIO_RETRIEVAL_MIN_ITEMS"] = str(n_items)
    return env


def parse_devices(text: str, pattern: str, what: str) -> dict:
    m = re.search(pattern, text)
    if m is None:
        raise SmokeFailure(f"{what}: no device report in output:\n"
                           f"{text[-2000:]}")
    return {"platform": m.group("platform"), "kind": m.group("kind"),
            "count": int(m.group("count"))}


def result_line(device: dict) -> str:
    """The last line of a passed chip run: these keys and no others."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def smoke(args) -> dict:
    import numpy as np

    global _started
    _started = time.monotonic()

    rehearse = args.rehearse
    shape = dict(REHEARSAL if rehearse else FULL)
    want_platform = "cpu" if rehearse else "tpu"
    work = os.path.join(HERE, "chip_smoke_work")
    log_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    for d in (work, log_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    os.makedirs(os.path.join(work, "native"))
    os.makedirs(os.path.join(work, "pio_home"))
    env = child_env(work, rehearse, shape["n_items"])
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")
    phases: dict[str, dict] = {}

    def phase(name, fn):
        before = cache_entries(cache_dir)
        t0 = time.monotonic()
        result = fn()
        after = cache_entries(cache_dir)
        phases[name] = {"seconds": round(time.monotonic() - t0, 1),
                        "cache_entries": [before, after]}
        log(f"phase {name}: {phases[name]['seconds']}s, compile cache "
            f"{before} -> {after} entries ({cache_dir})")
        return result

    # 1. status: the first claim of the chip — fails here when there is none
    out = phase("status", lambda: run_child(
        "status", [*CLI, "status"], env, log_dir, 180))
    device = parse_devices(
        out, r"Devices: (?P<count>\d+) × (?P<platform>\w+) \((?P<kind>[^)]*)\)",
        "status")
    if device["platform"] != want_platform:
        raise SmokeFailure(f"status reports {device}, want {want_platform}")
    # four chips: all of the host in ONE process, tables sharded over "model"
    mesh_axes = ({"data": device["count"] // 2, "model": 2}
                 if device["count"] >= 4 else None)
    log(f"device: {device}")

    # 2. app new + import of seeded events
    out = phase("app_new", lambda: run_child(
        "app_new", [*CLI, "app", "new", APP], env, log_dir, 180))
    app_id = int(re.search(r"ID: (\d+)", out).group(1))

    def gen():
        users, items, ratings = generate_events(
            args.seed, shape["n_events"], shape["user_ids"], shape["n_items"])
        if len(np.unique(items)) != shape["n_items"]:
            raise SmokeFailure("generator left catalog items without events")
        write_events(os.path.join(work, "events.jsonl"), users, items, ratings)
        return users

    users = phase("generate", gen)
    out = phase("import", lambda: run_child(
        "import", [*CLI, "import", "--appid", str(app_id), "--input",
                   os.path.join(work, "events.jsonl")], env, log_dir, 600))
    if f"Imported {shape['n_events']} events" not in out:
        raise SmokeFailure(f"import: unexpected output:\n{out[-2000:]}")

    # 3. train
    variant = os.path.join(work, "engine.json")
    with open(variant, "w") as f:
        json.dump({
            "id": "default", "version": "1",
            "engineFactory": "incubator_predictionio_tpu.templates."
                             "recommendation.RecommendationEngine",
            "datasource": {"params": {"appName": APP}},
            "algorithms": [{"name": "als", "params": {
                "rank": shape["rank"], "batchSize": shape["batch"],
                "numIterations": shape["iterations"], "lambda_": LAMBDA,
                "seed": args.seed}}],
        }, f)
    train_cmd = [*CLI, "train", "-v", variant]
    if mesh_axes:
        train_cmd += ["--mesh-axes", json.dumps(mesh_axes)]
    out = phase("train", lambda: run_child(
        "train", train_cmd, env, log_dir, 900))
    train_dev = parse_devices(
        out, r"mesh: .* over (?P<count>\d+) (?P<platform>\w+) devices "
             r"\((?P<kind>[^)]*)\)", "train")
    if train_dev != device:
        raise SmokeFailure(f"train ran on {train_dev}, status saw {device}")
    m = re.search(r"two-tower fit: final loss (?P<loss>\S+) .* of "
                  r"(?P<steps>\d+) steps/epoch", out)
    if m is None or not math.isfinite(float(m.group("loss"))):
        raise SmokeFailure(f"train: no finite final loss:\n{out[-3000:]}")
    loss, steps = float(m.group("loss")), int(m.group("steps"))
    if not rehearse and steps < 30:
        raise SmokeFailure(f"train: {steps} steps per epoch, want >= 30")
    reader = re.search(r"assemble_triples: .* read by the (.*)", out)
    if reader is None:
        raise SmokeFailure("train: no event-reader report in the log")
    log(f"train: loss {loss}, {steps} steps/epoch; events read by the "
        f"{reader.group(1)}")
    shards = re.search(r"table shards (?P<s>\{.*\})", out)
    table_shards = json.loads(shards.group("s")) if shards else None
    if mesh_axes:
        holders = {d for t in table_shards.values() for d, rows in t.items()
                   if rows > 0}
        if len(holders) != device["count"]:
            raise SmokeFailure(
                f"train: table shards on devices {sorted(holders)}, want all "
                f"{device['count']}: {table_shards}")
    dev_mem = re.findall(r"device memory: (\S+) bytes_in_use=(\S+)", out)

    # 4./5. deploy twice: default retrieval mode, then exact
    known = np.unique(users)
    rng = np.random.default_rng(args.seed + 1)
    query_users = [f"u{u}" for u in rng.choice(
        known, N_SERIAL + N_BURST, replace=False)]
    prefix = "sharded-device" if mesh_axes else "device-"
    default = phase("deploy_default", lambda: deploy_and_query(
        "deploy_default", variant, env, log_dir, query_users,
        shape["n_items"], "two_stage", prefix, 900))
    exact = phase("deploy_exact", lambda: deploy_and_query(
        "deploy_exact", variant, {**env, "PIO_RETRIEVAL_MODE": "exact"},
        log_dir, query_users, shape["n_items"], "exact", prefix, 900))
    for d in (default, exact):
        if d["device"] != device:
            raise SmokeFailure(
                f"deploy served from {d['device']}, status saw {device}")
    recall = sum(
        len(set(default["answers"][u]) & set(exact["answers"][u])) / NUM
        for u in query_users) / len(query_users)
    distinct = len({i for u in query_users for i in exact["answers"][u]})
    log(f"recall@{NUM} of default (two-stage) against exact over "
        f"{len(query_users)} users: {recall:.4f}; {distinct} distinct items")
    if recall < MIN_RECALL:
        raise SmokeFailure(f"recall {recall:.4f} < {MIN_RECALL}")

    # 6. every Pallas kernel against its jnp reference
    kern_cmd = [sys.executable, "-m",
                "incubator_predictionio_tpu.ops.kernel_check",
                "--seed", str(args.seed)]
    if rehearse:
        kern_cmd.append("--interpret")
    out = phase("kernels", lambda: run_child(
        "kernels", kern_cmd, env, log_dir, 900))
    kernels = json.loads(out.strip().splitlines()[-1])
    if not kernels["ok"] or kernels["platform"] != want_platform:
        raise SmokeFailure(f"kernels: {kernels}")

    # distribution metadata only: importing jax here would claim the chip
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    summary = {
        "ok": True,
        "device": device,
        "platform": device["platform"],
        "versions": versions,
        "shapes": shape,
        "lambda": LAMBDA,
        "cuts": ([f"{k}: {FULL[k]} -> {v}" for k, v in shape.items()
                  if FULL[k] != v]),
        "seed": args.seed,
        "mesh_axes": mesh_axes,
        "event_reader": reader.group(1),
        "train": {"final_loss": loss, "steps_per_epoch": steps,
                  "table_shards": table_shards,
                  "device_bytes_in_use": dict(dev_mem)},
        "serve_paths": {
            name: {k: d[k] for k in (
                "serve_path", "retrieval_mode", "ready_sec",
                "max_batch_seen", "jit_compile_keys", "sharding",
                "counters")}
            for name, d in (("default", default), ("exact", exact))},
        "queries_per_deploy": len(query_users),
        "degraded_answers": 0,
        "recall_at_10": round(recall, 4),
        "distinct_items_recommended": distinct,
        "kernels": kernels,
        "phases": phases,
        "compile_cache_dir": cache_dir,
        "total_seconds": round(time.monotonic() - _started, 1),
        "claim": None,
    }
    if rehearse:
        summary["rehearsal"] = True
    with open(os.path.join(log_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    # the model checkpoint and the event file are the bulk of the work
    # directory; nothing reads them after the run
    shutil.rmtree(work, ignore_errors=True)
    return summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the generated events and the model init")
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal at cut sizes, kernels interpreted; "
                        "never a chip result")
    args = p.parse_args(argv)
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)
    try:
        summary = smoke(args)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps(summary), flush=True)
    if not args.rehearse:
        print(result_line(summary["device"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
