"""``pio-tpu`` console — the ``pio`` CLI counterpart.

Parity target: tools/console/Console.scala:134-623 and commands/*. Verbs:

  version, status,
  app {new,list,show,delete,data-delete,channel-new,channel-delete},
  accesskey {new,list,delete},
  template {list,get} (commands/Template.scala — the gallery collapses to
  the in-package template registry; ``get`` scaffolds a ready-to-train
  engine.json),
  train, eval, deploy, undeploy, batchpredict, eventserver, storageserver,
  export, import, metrics (scrape + pretty-print any server's Prometheus
  /metrics page, docs/observability.md),
  wal (inspect/verify/--replay an event-server spill WAL directory,
  docs/resilience.md),
  shell (bin/pio-shell: interactive console with the
  storage/event-store/mesh bootstrap preloaded),
  start-all, stop-all (bin/pio-start-all / pio-stop-all: daemonize the
  serving stack with pidfiles), redeploy (examples/redeploy-script: cron-able
  train-with-retries + hot /reload of the deployed engine)

Differences by design: no ``build``/``unregister`` verbs (Python engines
need no sbt/assembly step or manifest registry — the variant JSON's
``engineFactory`` import path replaces the built jar), ``run``'s
spark-submit plumbing is unnecessary (everything runs in-process on the
mesh; ``launch`` covers multi-process), and ``upgrade`` (0.8-era HBase
data migration) has no legacy stores to migrate.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import sys
from dataclasses import replace as dataclasses_replace
from typing import Optional

import incubator_predictionio_tpu as piotpu
from incubator_predictionio_tpu.data.storage.base import AccessKey, App, Channel
from incubator_predictionio_tpu.data.storage.registry import Storage, get_storage


def _out(msg: str) -> None:
    print(msg)


def _err(msg: str) -> None:
    print(msg, file=sys.stderr)


# ---------------------------------------------------------------------------
# app / accesskey commands (commands/App.scala:31-363, AccessKey.scala)
# ---------------------------------------------------------------------------

def cmd_app_new(args, storage: Storage) -> int:
    apps = storage.get_meta_data_apps()
    if apps.get_by_name(args.name) is not None:
        _err(f"App {args.name} already exists. Aborting.")
        return 1
    app_id = apps.insert(App(args.id or 0, args.name, args.description))
    if app_id is None:
        _err("Unable to create new app.")
        return 1
    storage.get_events().init(app_id)
    key = storage.get_meta_data_access_keys().insert(
        AccessKey(args.access_key or "", app_id, ())
    )
    _out("Initialized Event Store for this app ID: {}.".format(app_id))
    _out(f"Created new app:")
    _out(f"      Name: {args.name}")
    _out(f"        ID: {app_id}")
    _out(f"Access Key: {key}")
    return 0


def cmd_app_list(args, storage: Storage) -> int:
    apps = sorted(storage.get_meta_data_apps().get_all(), key=lambda a: a.name)
    keys = storage.get_meta_data_access_keys()
    _out(f"{'Name':<20} | {'ID':<4} | Access Key | Allowed Event(s)")
    for app in apps:
        for k in keys.get_by_app_id(app.id):
            events = ", ".join(k.events) if k.events else "(all)"
            _out(f"{app.name:<20} | {app.id:<4} | {k.key} | {events}")
    _out(f"Finished listing {len(apps)} app(s).")
    return 0


def cmd_app_show(args, storage: Storage) -> int:
    app = storage.get_meta_data_apps().get_by_name(args.name)
    if app is None:
        _err(f"App {args.name} does not exist. Aborting.")
        return 1
    _out(f"    App Name: {app.name}")
    _out(f"      App ID: {app.id}")
    _out(f" Description: {app.description or ''}")
    for k in storage.get_meta_data_access_keys().get_by_app_id(app.id):
        events = ", ".join(k.events) if k.events else "(all)"
        _out(f"  Access Key: {k.key} | {events}")
    for c in storage.get_meta_data_channels().get_by_app_id(app.id):
        _out(f"     Channel: {c.name} (ID {c.id})")
    return 0


def cmd_app_delete(args, storage: Storage) -> int:
    app = storage.get_meta_data_apps().get_by_name(args.name)
    if app is None:
        _err(f"App {args.name} does not exist. Aborting.")
        return 1
    if not args.force and not _confirm(f"Delete app {args.name}?"):
        return 1
    for c in storage.get_meta_data_channels().get_by_app_id(app.id):
        storage.get_events().remove(app.id, c.id)
        storage.get_meta_data_channels().delete(c.id)
    storage.get_events().remove(app.id)
    for k in storage.get_meta_data_access_keys().get_by_app_id(app.id):
        storage.get_meta_data_access_keys().delete(k.key)
    storage.get_meta_data_apps().delete(app.id)
    _out(f"Deleted app {args.name}.")
    return 0


def cmd_app_data_delete(args, storage: Storage) -> int:
    app = storage.get_meta_data_apps().get_by_name(args.name)
    if app is None:
        _err(f"App {args.name} does not exist. Aborting.")
        return 1
    if not args.force and not _confirm(f"Delete data of app {args.name}?"):
        return 1
    if args.channel:
        channels = storage.get_meta_data_channels().get_by_app_id(app.id)
        channel = next((c for c in channels if c.name == args.channel), None)
        if channel is None:
            _err(f"Channel {args.channel} does not exist.")
            return 1
        storage.get_events().remove(app.id, channel.id)
        storage.get_events().init(app.id, channel.id)
    else:
        storage.get_events().remove(app.id)
        storage.get_events().init(app.id)
    _out("Done.")
    return 0


def cmd_channel_new(args, storage: Storage) -> int:
    app = storage.get_meta_data_apps().get_by_name(args.app_name)
    if app is None:
        _err(f"App {args.app_name} does not exist. Aborting.")
        return 1
    if not Channel.is_valid_name(args.channel):
        _err(f"Unable to create new channel. The channel name {args.channel} is "
             "invalid (alphanumeric/dash, 1-16 chars).")
        return 1
    channels = storage.get_meta_data_channels()
    if any(c.name == args.channel for c in channels.get_by_app_id(app.id)):
        _err(f"Unable to create new channel. Channel {args.channel} already exists.")
        return 1
    channel_id = channels.insert(Channel(0, args.channel, app.id))
    storage.get_events().init(app.id, channel_id)
    _out(f"Channel {args.channel} (ID {channel_id}) created for app {args.app_name}.")
    return 0


def cmd_channel_delete(args, storage: Storage) -> int:
    app = storage.get_meta_data_apps().get_by_name(args.app_name)
    if app is None:
        _err(f"App {args.app_name} does not exist. Aborting.")
        return 1
    channels = storage.get_meta_data_channels()
    channel = next((c for c in channels.get_by_app_id(app.id)
                    if c.name == args.channel), None)
    if channel is None:
        _err(f"Channel {args.channel} does not exist.")
        return 1
    if not args.force and not _confirm(f"Delete channel {args.channel}?"):
        return 1
    storage.get_events().remove(app.id, channel.id)
    channels.delete(channel.id)
    _out(f"Deleted channel {args.channel}.")
    return 0


def cmd_accesskey_new(args, storage: Storage) -> int:
    app = storage.get_meta_data_apps().get_by_name(args.app_name)
    if app is None:
        _err(f"App {args.app_name} does not exist. Aborting.")
        return 1
    key = storage.get_meta_data_access_keys().insert(
        AccessKey(args.access_key or "", app.id, tuple(args.event or ()))
    )
    _out(f"Created new access key: {key}")
    return 0


def cmd_accesskey_list(args, storage: Storage) -> int:
    keys = storage.get_meta_data_access_keys()
    if args.app_name:
        app = storage.get_meta_data_apps().get_by_name(args.app_name)
        if app is None:
            _err(f"App {args.app_name} does not exist. Aborting.")
            return 1
        listed = keys.get_by_app_id(app.id)
    else:
        listed = keys.get_all()
    for k in listed:
        events = ", ".join(k.events) if k.events else "(all)"
        _out(f"{k.key} | app {k.app_id} | {events}")
    _out(f"Finished listing {len(listed)} access key(s).")
    return 0


def cmd_accesskey_delete(args, storage: Storage) -> int:
    if storage.get_meta_data_access_keys().delete(args.key):
        _out(f"Deleted access key {args.key}.")
        return 0
    _err(f"Error deleting access key {args.key}.")
    return 1


def _confirm(prompt: str) -> bool:
    answer = input(f"{prompt} (Y/n) ")
    return answer.strip().lower() in ("", "y", "yes")


# ---------------------------------------------------------------------------
# train / eval / deploy / batchpredict / servers
# ---------------------------------------------------------------------------

def cmd_train(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.core.workflow.create_workflow import (
        WorkflowConfig,
        create_workflow,
    )

    axes = json.loads(args.mesh_axes) if args.mesh_axes else None
    config = WorkflowConfig(
        engine_variant=args.engine_variant,
        batch=args.batch,
        verbose=args.verbose,
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
        mesh_axes=axes,
        distributed=getattr(args, "distributed", False),
    )
    if getattr(args, "profile_dir", None):
        from incubator_predictionio_tpu.obs.profile import profile_trace

        trace = profile_trace(args.profile_dir)
    else:
        trace = contextlib.nullcontext()
    with trace:
        instance_id = create_workflow(config, storage)
    if getattr(args, "profile_dir", None):
        _out(f"Profiler trace written to {args.profile_dir} "
             "(TensorBoard 'profile' plugin layout).")
    if instance_id == "<secondary>":
        _out("Training completed (secondary process; the primary wrote the "
             "engine instance).")
    else:
        _out(f"Training completed. Engine instance ID: {instance_id}")
    return 0


def cmd_eval(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.core.workflow.create_workflow import (
        WorkflowConfig,
        create_workflow,
    )

    axes = json.loads(args.mesh_axes) if getattr(args, "mesh_axes", None) else None
    config = WorkflowConfig(
        engine_variant=args.engine_variant,
        evaluation_class=args.evaluation_class,
        engine_params_generator_class=args.engine_params_generator_class,
        batch=args.batch,
        mesh_axes=axes,
        distributed=getattr(args, "distributed", False),
        fast_eval=not getattr(args, "no_fast_eval", False),
    )
    instance_id = create_workflow(config, storage)
    if instance_id == "<secondary>":
        _out("Evaluation completed (secondary process; the primary wrote "
             "the evaluation instance).")
        return 0
    inst = storage.get_meta_data_evaluation_instances().get(instance_id)
    _out(f"Evaluation completed. Instance ID: {instance_id}")
    if inst is not None and inst.evaluator_results:
        _out(inst.evaluator_results)
    return 0


def cmd_deploy(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.server.query_server import ServerConfig, serve_forever

    config = ServerConfig(
        engine_variant=args.engine_variant,
        ip=args.ip,
        port=args.port,
        feedback=args.feedback,
        event_server_ip=args.event_server_ip,
        event_server_port=args.event_server_port,
        access_key=args.access_key,
        server_access_key=args.server_access_key,
        ssl_cert=args.ssl_cert,
        ssl_key=args.ssl_key,
        log_url=args.log_url,
        log_prefix=args.log_prefix,
        query_timeout_sec=args.query_timeout_sec,
        algo_deadline_sec=args.algo_deadline_sec,
        algo_breaker_threshold=args.algo_breaker_threshold,
        algo_breaker_reset_sec=args.algo_breaker_reset_sec,
        smoke_queries=tuple(
            json.loads(q) for q in (args.smoke_query or ())),
        reload_probation_sec=args.reload_probation_sec,
        # unset flags keep the PIO_FLEET_SHARD_* env defaults
        **{k: v for k, v in (
            ("shard_id", args.shard_id),
            ("shard_count", args.shard_count),
            ("shard_state_dir", args.shard_state_dir),
        ) if v is not None},
        # unset flags keep the PIO_ADMISSION_* env defaults
        **{k: v for k, v in (
            ("admission_max_queue", args.admission_max_queue),
            ("admission_target_ms", args.admission_target_ms),
        ) if v is not None},
        **({"admission_adaptive": False}
           if args.no_adaptive_admission else {}),
    )
    # multi-tenant mode (docs/tenancy.md): a tenant table via --tenants
    # or PIO_TENANTS hosts N engines behind this one process; the classic
    # single-engine path below stays byte-identical without one
    tenants_src = args.tenants or os.environ.get("PIO_TENANTS", "").strip()
    if tenants_src:
        from incubator_predictionio_tpu.server.tenancy import (
            load_tenant_specs,
            serve_forever_tenants,
        )

        serve_forever_tenants(config, load_tenant_specs(tenants_src),
                              storage)
        return 0
    serve_forever(config, storage)
    return 0


def cmd_undeploy(args, storage: Storage) -> int:
    import urllib.request

    url = f"http://{args.ip}:{args.port}/stop"
    if args.server_access_key:
        url += f"?accessKey={args.server_access_key}"
    try:
        with urllib.request.urlopen(
            urllib.request.Request(url, method="POST"), timeout=10
        ) as resp:
            _out(resp.read().decode())
        return 0
    except Exception as e:  # noqa: BLE001
        _err(f"Undeploy failed: {e}")
        return 1


def cmd_batchpredict(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.core.workflow.batch_predict import (
        BatchPredictConfig,
        part_path,
        run_batch_predict,
    )
    from incubator_predictionio_tpu.parallel.mesh import MeshContext

    ctx = None
    if getattr(args, "distributed", False):
        # under `pio-tpu launch -n N batchpredict --distributed` each
        # process scores a slice and writes <output>.part-<pid>
        ctx = MeshContext.from_conf({"distributed": True})
    n = run_batch_predict(
        BatchPredictConfig(
            engine_variant=args.engine_variant,
            input_path=args.input,
            output_path=args.output,
            query_chunk=args.query_partitions or 1024,
        ),
        storage,
        ctx,
    )
    if ctx is not None and ctx.process_count > 1:
        _out(f"Batch predict completed: {n} predictions written to "
             f"{part_path(args.output, ctx.process_index)} "
             f"(slice {ctx.process_index + 1}/{ctx.process_count})")
    else:
        _out(f"Batch predict completed: {n} predictions written to {args.output}")
    return 0


def cmd_dashboard(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.tools.dashboard import DashboardConfig, serve_forever

    serve_forever(DashboardConfig(
        ip=args.ip, port=args.port,
        ssl_cert=args.ssl_cert, ssl_key=args.ssl_key,
        server_access_key=args.server_access_key), storage)
    return 0


def cmd_adminserver(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.tools.admin import AdminConfig, serve_forever

    serve_forever(AdminConfig(
        ip=args.ip, port=args.port,
        ssl_cert=args.ssl_cert, ssl_key=args.ssl_key,
        server_access_key=args.server_access_key), storage)
    return 0


def cmd_eventserver(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.server.event_server import (
        EventServerConfig,
        serve_forever,
    )

    kw = {}
    if args.wal_dir:  # unset keeps the PIO_EVENT_WAL_DIR env default
        kw["wal_dir"] = args.wal_dir
    if args.client_rate is not None:  # unset keeps the env default
        kw["client_rate"] = args.client_rate
    if args.client_burst is not None:
        kw["client_burst"] = args.client_burst
    serve_forever(EventServerConfig(ip=args.ip, port=args.port,
                                    stats=args.stats, ssl_cert=args.ssl_cert,
                                    ssl_key=args.ssl_key, **kw), storage)
    return 0


def cmd_storageserver(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.server.storage_server import (
        StorageServerConfig,
        serve_forever,
    )

    kw = {}
    if args.client_inflight is not None:  # unset keeps the env default
        kw["client_inflight"] = args.client_inflight
    if getattr(args, "repl_role", None):
        kw["repl_role"] = args.repl_role
    if getattr(args, "repl_peer", None):
        kw["repl_peers"] = tuple(args.repl_peer)
    if getattr(args, "repl_sync", None):
        kw["repl_sync"] = args.repl_sync
    serve_forever(StorageServerConfig(
        ip=args.ip, port=args.port,
        ssl_cert=args.ssl_cert, ssl_key=args.ssl_key,
        server_access_key=args.server_access_key, **kw), storage)
    return 0


def cmd_start_all(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.tools.ops import StartAllConfig, start_all

    _, unhealthy = start_all(StartAllConfig(
        ip=args.ip,
        event_server_port=args.event_server_port,
        with_dashboard=args.with_dashboard,
        dashboard_port=args.dashboard_port,
        with_adminserver=args.with_adminserver,
        adminserver_port=args.adminserver_port,
        with_storageserver=args.with_storageserver,
        storageserver_port=args.storageserver_port,
        storageserver_access_key=args.storageserver_access_key,
        stats=args.stats,
        wait_secs=args.wait_secs,
    ))
    return 1 if unhealthy else 0


def cmd_stop_all(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.tools.ops import stop_all

    stop_all()
    return 0


def cmd_redeploy(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.tools.ops import (
        RedeployConfig,
        redeploy,
        redeploy_via_jobs,
    )

    server_url = None if args.no_reload else f"http://{args.ip}:{args.port}"
    runner = redeploy if args.legacy else redeploy_via_jobs
    instance_id = runner(RedeployConfig(
        engine_variant=args.engine_variant,
        batch=args.batch,
        retries=args.retries,
        retry_wait_secs=args.retry_wait,
        server_url=server_url,
        server_access_key=args.server_access_key,
        interval_secs=args.interval,
        mesh_axes=json.loads(args.mesh_axes) if args.mesh_axes else None,
    ), storage)
    return 0 if instance_id else 1


def cmd_shell(args, storage: Storage) -> int:
    """Interactive console with the pypio-style bootstrap preloaded
    (bin/pio-shell + python/pypio/shell.py slot): ``storage``,
    ``l_event_store``, ``p_event_store``, and ``mesh(**axes)``."""
    import incubator_predictionio_tpu.shell as sh

    ns = {name: getattr(sh, name) for name in sh.__all__}
    if args.shell_code:
        exec(compile(args.shell_code, "<pio-tpu shell -c>", "exec"), ns)
        return 0
    import code

    banner = (
        f"incubator-predictionio-tpu shell (v{piotpu.__version__})\n"
        "preloaded: storage, l_event_store, p_event_store, mesh(**axes)"
    )
    code.interact(banner=banner, local=ns, exitmsg="")
    return 0


#: In-package template registry (commands/Template.scala:33-69 points at the
#: external gallery; templates ship in-package here, so list/get are real).
TEMPLATES = {
    "recommendation": {
        "factory": "incubator_predictionio_tpu.templates.recommendation."
                   "RecommendationEngine",
        "algorithms": [{"name": "als", "params": {
            "rank": 64, "numIterations": 20}}],
        "description": "two-tower MF over rate/buy events "
                       "(scala-parallel-recommendation slot)",
    },
    "classification": {
        "factory": "incubator_predictionio_tpu.templates.classification."
                   "ClassificationEngine",
        "algorithms": [{"name": "mlp", "params": {}}],
        "description": "MLP over $set attribute/label snapshots "
                       "(scala-parallel-classification slot)",
    },
    "similarproduct": {
        "factory": "incubator_predictionio_tpu.templates.similarproduct."
                   "SimilarProductEngine",
        "algorithms": [{"name": "als", "params": {}}],
        "description": "implicit MF + cooccurrence over view/like events "
                       "(scala-parallel-similarproduct slot)",
    },
    "recommendeduser": {
        "factory": "incubator_predictionio_tpu.templates.recommended_user."
                   "RecommendedUserEngine",
        "algorithms": [{"name": "als", "params": {}}],
        "description": "user-to-user implicit MF over follow events "
                       "(similarproduct/recommended-user slot)",
    },
    "ecommerce": {
        "factory": "incubator_predictionio_tpu.templates.ecommerce."
                   "ECommerceEngine",
        "algorithms": [{"name": "ecomm", "params": {}}],
        "algo_app_name": True,  # live serving-time event reads
        "description": "two-tower retrieval with live constraints "
                       "(scala-parallel-ecommercerecommendation slot)",
    },
    "sequential": {
        "factory": "incubator_predictionio_tpu.templates.sequential."
                   "SequentialEngine",
        "algorithms": [{"name": "transformer", "params": {}}],
        "algo_app_name": True,  # user-history reads at serving time
        "description": "session transformer next-item recommender "
                       "(long-context flagship; no reference counterpart)",
    },
}


def cmd_template_list(args, storage: Storage) -> int:
    for name, t in TEMPLATES.items():
        _out(f"{name:16s} {t['description']}")
        _out(f"{'':16s}   engineFactory: {t['factory']}")
    return 0


def cmd_template_get(args, storage: Storage) -> int:
    """Scaffold a ready-to-train engine.json for the named template."""
    t = TEMPLATES.get(args.name)
    if t is None:
        _err(f"Unknown template {args.name!r}; try: pio-tpu template list")
        return 1
    import copy
    import os

    os.makedirs(args.directory, exist_ok=True)
    path = os.path.join(args.directory, "engine.json")
    if os.path.exists(path) and not args.force:
        _err(f"{path} already exists (use --force to overwrite)")
        return 1
    app_name = args.app_name or args.name
    algorithms = copy.deepcopy(t["algorithms"])
    if t.get("algo_app_name"):
        # these algorithms read live events at SERVING time through their own
        # appName param (seen items, user history) — it must match the
        # datasource's app or those lookups silently return nothing
        for a in algorithms:
            a["params"]["appName"] = app_name
    variant = {
        "id": args.name,
        "version": "1",
        "engineFactory": t["factory"],
        "datasource": {"params": {"appName": app_name}},
        "algorithms": algorithms,
    }
    with open(path, "w") as f:
        json.dump(variant, f, indent=2)
        f.write("\n")
    _out(f"Wrote {path} — next: pio-tpu app new {args.app_name or args.name}; "
         f"pio-tpu train -v {path}")
    return 0


def cmd_export(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.tools.export_import import export_events

    channel_id = _resolve_channel(args, storage)
    n = export_events(args.appid, args.output, channel_id, storage)
    _out(f"Exported {n} events.")
    return 0


def cmd_import(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.tools.export_import import import_events

    channel_id = _resolve_channel(args, storage)
    n = import_events(args.appid, args.input, channel_id, storage)
    _out(f"Imported {n} events.")
    return 0


def _resolve_channel(args, storage: Storage) -> Optional[int]:
    if not getattr(args, "channel", None):
        return None
    channels = storage.get_meta_data_channels().get_by_app_id(args.appid)
    channel = next((c for c in channels if c.name == args.channel), None)
    if channel is None:
        raise SystemExit(f"Channel {args.channel} does not exist for app {args.appid}")
    return channel.id


def cmd_status(args, storage: Storage) -> int:
    """(commands/Management.scala:99-181 + Storage.verifyAllDataObjects)"""
    from incubator_predictionio_tpu.parallel.mesh import claim_devices

    _out(f"incubator_predictionio_tpu {piotpu.__version__}")
    devices = claim_devices()
    _out(f"Devices: {len(devices)} × {devices[0].platform}"
         f" ({devices[0].device_kind})")
    from incubator_predictionio_tpu.obs.profile import device_memory_report

    for row in device_memory_report():
        if row["bytes_in_use"] is not None:
            _out(f"  {row['device']}: {row['bytes_in_use'] / 2**20:.1f} MiB in use"
                 + (f" / {row['bytes_limit'] / 2**20:.0f} MiB"
                    if row["bytes_limit"] else ""))
    for repo, name, source, type_name in storage.describe():
        _out(f"  {repo}: name={name} source={source} type={type_name}")
    failures = storage.verify_all_data_objects()
    if failures:
        for f in failures:
            _err(f"  [FAILED] {f}")
        _err("Unable to connect to all storage backends successfully.")
        return 1
    _out("Storage: all repositories verified (METADATA/EVENTDATA/MODELDATA).")
    _print_jobs_status(storage)
    _print_jit_status()
    _out("Your system is all ready to go.")
    return 0


def _print_jit_status() -> None:
    """The compile-churn section of ``pio-tpu status``: cumulative
    first-dispatch (compile-dominated) wall time per executable name
    (utils/jitstats.py) — in-process truth, so it is populated when status
    runs after a train/serve in the same process (tests, shell, bench)."""
    from incubator_predictionio_tpu.utils import jitstats

    top = jitstats.top_compiles()
    if not top:
        return
    total = jitstats.compile_seconds_total()
    _out(f"JIT compiles: {total:.2f}s first-dispatch wall across "
         f"{jitstats.count()} cached key(s)")
    for name, sec, n in top:
        _out(f"  {name}: {sec:.3f}s over {n} compile(s)")


def _print_jobs_status(storage: Storage) -> None:
    """The continuous-training section of ``pio-tpu status``: per-kind
    queue counts, tightest remaining lease, last failure (docs/jobs.md).
    Tolerant of backends without a jobs DAO (third-party METADATA)."""
    try:
        from incubator_predictionio_tpu.jobs import Orchestrator

        summary = Orchestrator(storage.get_meta_data_jobs()).summarize()
    except NotImplementedError:
        _out("Jobs: METADATA backend has no jobs DAO (control plane off).")
        return
    except Exception as e:  # noqa: BLE001 — status must not crash on this
        _err(f"Jobs: unreadable ({e})")
        return
    kinds = summary["kinds"]
    if not kinds:
        _out("Jobs: none submitted (docs/jobs.md — `pio-tpu jobs submit`).")
        return
    _out("Jobs:")
    for kind in sorted(kinds):
        k = kinds[kind]
        line = (f"  {kind}: queued {k.get('queued', 0)}, running "
                f"{k.get('running', 0)}, completed {k.get('completed', 0)}, "
                f"failed {k.get('failed', 0)}, refused {k.get('refused', 0)}")
        margin = k.get("oldestLeaseAgeSec")
        if margin is not None:
            line += (f", lease margin {margin:+.0f}s"
                     + (" [EXPIRED — reclaim pending]" if margin < 0 else ""))
        _out(line)
    lf = summary["lastFailure"]
    if lf:
        _out(f"  last failure: {lf['kind']} {lf['id'][:12]} "
             f"[{lf['status']}] {lf['failure']}")


def cmd_version(args, storage) -> int:
    _out(piotpu.__version__)
    return 0


def cmd_wal(args, storage: Storage) -> int:
    """Inspect / verify / replay an event-server spill WAL directory
    (resilience/wal.py; docs/resilience.md "Durability & crash recovery").

    Plain invocation is strictly read-only (safe against a live server):
    per-segment frame counts, CRC/torn-frame defects, the commit cursor,
    pending and dead-letter tallies. ``--replay`` lands every pending
    record in the configured event store (idempotent — ids are
    pre-assigned) and advances the cursor; ``--dead-letter`` prints the
    dead-letter records so a store-rejected batch can be repaired by hand.
    """
    from incubator_predictionio_tpu.resilience.wal import SpillWal, inspect_dir

    info = inspect_dir(args.directory)
    if args.json:
        _out(json.dumps(info, indent=2))
    else:
        _out(f"WAL directory: {info['directory']}")
        _out(f"  committed seq: {info['committedSeq']}")
        for seg in info["segments"]:
            line = (f"  {os.path.basename(seg['path'])}: "
                    f"{seg['frames']} frame(s), {seg['bytes']} bytes")
            if seg["maxSeq"] is not None:
                line += f", max seq {seg['maxSeq']}"
            if seg["defect"]:
                line += (f"  [DEFECT: {seg['defect']} @ byte "
                         f"{seg['defectOffset']}]")
            _out(line)
        _out(f"  pending (uncommitted): {info['pending']}")
        if info.get("firstCorrupt"):
            fc = info["firstCorrupt"]
            _out(f"  first corrupt frame: "
                 f"{os.path.basename(fc['segment'])} @ byte "
                 f"{fc['offset']} ({fc['defect']})")
        _out(f"  dead letters: {len(info['deadLetters'])}"
             + (f"  [DEFECT: {info['deadLetterDefect']} @ byte "
                f"{info['deadLetterDefectOffset']}]"
                if info["deadLetterDefect"] else ""))
    if args.dead_letter and info["deadLetters"]:
        for rec in info["deadLetters"]:
            _out(json.dumps(rec))
    if not args.replay:
        return 0

    from incubator_predictionio_tpu.data.event import Event

    wal = SpillWal(args.directory)
    pending = wal.replay()
    if not pending:
        _out("Nothing to replay.")
        wal.close()
        return 0
    events_store = storage.get_events()
    replayed = 0
    try:
        i = 0
        while i < len(pending):
            # one insert_batch per (app, channel) run, ≤ 50 like the server
            app_id = pending[i]["app_id"]
            channel_id = pending[i].get("channel_id")
            batch = []
            while (i < len(pending) and len(batch) < 50
                   and pending[i]["app_id"] == app_id
                   and pending[i].get("channel_id") == channel_id):
                batch.append(pending[i])
                i += 1
            events_store.init(app_id, channel_id)
            events_store.insert_batch(
                [Event.from_json_dict(r["event"]) for r in batch],
                app_id, channel_id)
            wal.commit(max(r["seq"] for r in batch))
            replayed += len(batch)
    except Exception as e:  # noqa: BLE001 - partial progress is committed
        _err(f"Replay stopped after {replayed}/{len(pending)} event(s): {e}")
        wal.close()
        return 1
    finally:
        if replayed:
            _out(f"Replayed {replayed} event(s) into the configured "
                 "event store.")
    wal.close()
    return 0


def cmd_stream(args, storage: Storage) -> int:
    """Streaming incremental updates (docs/streaming.md): tail the
    eventlog change feed, fold events into embedding-row deltas, and ship
    them to the given replicas as versioned delta deploys — crash-safe and
    exactly-once (cursor + delta archive live in ``--state-dir``).

    ``--status`` prints the stream state (cursor, quarantine, dead
    letters) without folding; ``--dead-letter`` prints the dead-lettered
    poison events as JSON lines; ``--once`` runs a single
    poll→fold→ship→commit round and exits (the chaos tests drive this)."""
    from incubator_predictionio_tpu.streaming.feed import resolve_feed_path
    from incubator_predictionio_tpu.streaming.updater import (
        StreamUpdater,
        UpdaterConfig,
        inspect_state_dir,
        load_base_model,
    )

    if args.status:
        # strictly read-only: no model load, no cursor creation, no
        # instance-change state reset — safe beside a live updater
        info = inspect_state_dir(args.state_dir)
        _out(json.dumps(info, indent=2, default=str))
        return 1 if info["quarantine"] else 0
    if args.dead_letter:
        from incubator_predictionio_tpu.resilience.wal import tail_frames

        path = os.path.join(args.state_dir, "deadletter.log")
        if not os.path.exists(path):
            _out("No dead letters.")
            return 0
        records, _, status = tail_frames(path)
        for _, rec in records:
            _out(json.dumps(rec))
        if status == "corrupt":
            _err("dead-letter file has a corrupt frame past the listed "
                 "records")
            return 1
        return 0
    model, instance_id, event_names, defaults = load_base_model(
        args.engine_variant, storage)
    feed_path = args.feed_path or resolve_feed_path(
        storage, args.app, args.channel)
    cfg = UpdaterConfig(
        state_dir=args.state_dir,
        feed_path=feed_path,
        replicas=tuple(args.replica or ()),
        access_key=args.server_access_key,
        batch_events=args.batch_events,
        poll_interval=args.interval,
        from_start=args.from_start,
    )
    updater = StreamUpdater(cfg, model, instance_id,
                            event_names=event_names,
                            default_values=defaults)
    obs_handle = None
    if args.obs_port:
        # the updater has no HTTP surface of its own; this thread serves
        # the shared /metrics + /traces.json so pio_stream_* is scrapeable
        from incubator_predictionio_tpu.obs.http import start_obs_server

        obs_handle = start_obs_server("stream_updater", args.obs_port,
                                      ip=args.obs_ip)
    try:
        if args.once:
            out = updater.run_once()
            _out(json.dumps(out, default=str))
            return 1 if out["status"] == "quarantined" else 0
        updater.run_forever(max_batches=args.max_batches)
        return 1 if updater.quarantined else 0
    finally:
        if obs_handle is not None:
            obs_handle.close()


def _fetch_health(url: str, timeout: float = 5.0) -> dict:
    """GET <url>/health, parsed. Module-level so tests can stub it; the
    single implementation lives in fleet/health.py (the router's watcher
    probes with exactly the same fetch)."""
    from incubator_predictionio_tpu.fleet.health import fetch_health

    return fetch_health(url, timeout)


def _health_row(url: str, h: Optional[dict], err: Optional[str]) -> dict:
    """One table row from any of the three servers' /health shapes:
    red = unreachable, draining, or degraded; the detail column names the
    reason (open breakers, spill depth, brownout, shed/throttle tallies)."""
    if h is None:
        return {"url": url, "status": "unreachable", "red": True,
                "detail": err or ""}
    breakers: dict[str, dict] = {}
    for k, v in h.items():
        if k.endswith("Breakers") and isinstance(v, dict):
            breakers.update(v)
        elif k.endswith("Breaker") and isinstance(v, dict):
            breakers[k] = v
    parts = []
    open_names = sorted(n for n, s in breakers.items()
                        if isinstance(s, dict) and s.get("state") != "closed")
    if open_names:
        parts.append("breakers open: " + ", ".join(open_names[:4]))
    if h.get("spillQueueDepth"):
        parts.append(f"spill {h['spillQueueDepth']}/{h.get('spillQueueMax')}")
    if h.get("deadLettered"):
        parts.append(f"deadLettered {h['deadLettered']}")
    adm = h.get("admission") or {}
    if adm.get("brownoutActive"):
        parts.append("BROWNOUT")
    if adm.get("queueDepth"):
        parts.append(f"queue {adm['queueDepth']}/{adm.get('queueMax')}")
    if adm.get("rejected"):
        parts.append(f"rejected {adm['rejected']}")
    if adm.get("shedExpired"):
        parts.append(f"shed {adm['shedExpired']}")
    throttled = adm.get("throttled") or (adm.get("fairness") or {}).get(
        "throttled")
    if throttled:
        parts.append(f"throttled {throttled}")
    # streaming update lag (docs/streaming.md): chain position + freshness
    stream = (h.get("deployment") or {}).get("streaming") or {}
    if stream.get("lastDeltaSeq") is not None:
        lag = stream.get("stalenessSeconds")
        parts.append(
            f"deltaSeq {stream['lastDeltaSeq']}"
            + (f", staleness {lag:.0f}s" if lag is not None else ""))
    # storage replication (docs/replication.md): role/epoch/lag rows so a
    # lagging or fenced store turns the fleet probe red
    from incubator_predictionio_tpu.fleet.health import replication_flags

    repl = replication_flags(h)
    repl_red = False
    if repl is not None:
        parts.append(f"repl {repl['role']}@{repl['epoch']}")
        if repl["fenced"]:
            parts.append(f"FENCED ({repl.get('fencedWrites') or 0} writes "
                         "rejected)")
        if repl.get("lagBytes"):
            parts.append(f"lag {repl['lagBytes']}B"
                         + (" EXCEEDED" if repl["lagExceeded"] else ""))
        repl_red = repl["red"]
    # SLO burn-rate verdicts (obs/slo.py): a breaching objective turns the
    # row red even while the server itself answers "ok" — error budget is
    # burning NOW regardless of breaker state
    slo = h.get("slo") or {}
    slo_red = bool(slo.get("breaching"))
    if slo_red:
        bad = [o.get("name", "?") for o in slo.get("objectives", [])
               if o.get("breaching")]
        parts.append("SLO BREACH: " + ", ".join(bad[:4]))
    status = h.get("status", "unknown")
    return {"url": url, "status": status,
            "red": status != "ok" or repl_red or slo_red,
            "replication": repl,
            "slo": slo or None,
            "detail": "; ".join(parts)}


def cmd_health(args, storage) -> int:
    """Aggregate ``GET /health`` from every given server (event, query,
    storage — any mix) into one table: status, draining, breaker, spill,
    and admission/overload state. Exit non-zero when ANY server is red
    (unreachable, draining, or degraded) — the fleet smoke gate the
    overload chaos test uses (docs/resilience.md).

    Probes run CONCURRENTLY (fleet/health.py — the same fan-out the fleet
    router's health watcher uses): a fleet with slow or dead replicas
    answers in ~one probe timeout, not O(N × timeout)."""
    from incubator_predictionio_tpu.fleet.health import probe_health_urls

    # fetch resolved through the module global so tests can stub it
    probed = probe_health_urls(
        args.urls, args.timeout,
        fetch=lambda url, timeout: _fetch_health(url, timeout))
    rows = [_health_row(url, *probed[url]) for url in args.urls]
    rows.extend(_shard_coverage_rows(args.urls, probed))
    if getattr(args, "stream_state_dir", None):
        rows.append(_quarantine_row(args.stream_state_dir,
                                    args.quarantine_max_age))
    if getattr(args, "backup_dir", None):
        rows.append(_backup_row(args.backup_dir, args.backup_max_age))
    if getattr(args, "dist_state_dir", None):
        rows.append(_mesh_row(args.dist_state_dir))
    if not rows:
        _err("health: nothing to probe (give server URLs and/or "
             "--stream-state-dir / --backup-dir)")
        return 2
    if args.json:
        _out(json.dumps(rows, indent=2))
    else:
        w = max(len(r["url"]) for r in rows)
        for r in rows:
            mark = "!!" if r["red"] else "ok"
            line = f"{mark} {r['url']:<{w}}  {r['status']}"
            if r["detail"]:
                line += f"  [{r['detail']}]"
            _out(line)
    return 1 if any(r["red"] for r in rows) else 0


def _shard_coverage_rows(urls: list, probed: dict) -> list[dict]:
    """Synthetic fleet rows (the quarantine-row pattern) for multi-host
    shard ownership (docs/sharding.md "Multi-host shard owners"): one row
    per announced shard range, RED when the range has zero live owners —
    those catalog rows can no longer appear in any merged answer, which a
    per-replica table hides (every surviving replica still looks green).
    An owner announcing below the range's max epoch is a deposed process
    restarted with stale rows: counted fenced, never live (the router's
    epoch-fencing discipline, fleet/topology.py)."""
    ranges: dict[int, dict] = {}
    for url in urls:
        h, _err = probed[url]
        owner = ((h or {}).get("deployment") or {}).get("shardOwner")
        if not isinstance(owner, dict):
            continue
        rows, sid = owner.get("rows"), owner.get("shardId")
        if sid is None or not rows or len(rows) != 2:
            continue
        g = ranges.setdefault(int(sid), {
            "lo": int(rows[0]), "hi": int(rows[1]),
            "max_epoch": 0, "owners": []})
        g["lo"] = min(g["lo"], int(rows[0]))
        g["hi"] = max(g["hi"], int(rows[1]))
        epoch = int(owner.get("epoch") or 0)
        g["max_epoch"] = max(g["max_epoch"], epoch)
        g["owners"].append(
            (url, epoch,
             h.get("status") == "ok" and not h.get("draining")))
        g["count"] = max(g.get("count", 0),
                         int(owner.get("shardCount") or 0))
    # a shard id whose owners are ALL unreachable never announces at all
    # — the announced shardCount from the reachable owners reveals the
    # hole (without it the dead range would silently vanish from the
    # report, the exact failure this table exists to catch)
    if ranges:
        expect = max(g.get("count", 0) for g in ranges.values())
        for sid in range(expect):
            if sid not in ranges:
                ranges[sid] = {"lo": -1, "hi": -1, "max_epoch": 0,
                               "owners": []}
    out: list[dict] = []
    for sid in sorted(ranges, key=lambda s: (ranges[s]["lo"], s)):
        g = ranges[sid]
        live = [u for u, e, ok in g["owners"]
                if ok and e >= g["max_epoch"]]
        fenced = [u for u, e, _ok in g["owners"] if e < g["max_epoch"]]
        known = g["lo"] >= 0
        span = f"{g['lo']}-{g['hi']}" if known else "?"
        url = f"shard:{sid}:rows={span}"
        if live:
            detail = f"live owners: {', '.join(live)}"
            if fenced:
                detail += ("; FENCED stale-epoch: " + ", ".join(fenced)
                           + " (resync + POST /shard/promote to re-admit)")
            out.append({"url": url, "status": "ok", "red": False,
                        "detail": detail})
        else:
            rows_txt = (f"rows [{g['lo']},{g['hi']})" if known
                        else "its rows (range unannounced — every owner "
                             "unreachable)")
            out.append({
                "url": url, "status": "no-live-owner", "red": True,
                "detail": (f"{rows_txt} unservable — promote a standby "
                           f"(`pio-tpu deploy --shard-id {sid}` + POST "
                           "/shard/promote) or answers go partial/504 "
                           "(docs/sharding.md)")})
    return out


def _quarantine_row(state_dir: str, max_age: Optional[float]) -> dict:
    """The stuck-control-loop probe (docs/jobs.md): a stream quarantine
    marker older than the retrain trigger interval means the auto-retrain
    loop that should have cleared it is not running — red. A younger
    marker is the control loop mid-recovery — reported, not red."""
    from incubator_predictionio_tpu.jobs import quarantine_age_seconds

    age = quarantine_age_seconds(state_dir)
    url = f"stream:{state_dir}"
    if age is None:
        return {"url": url, "status": "ok", "red": False,
                "detail": "no quarantine marker"}
    if max_age is None:
        max_age = float(os.environ.get("PIO_JOBS_INTERVAL", "0")) or 300.0
    stuck = age > max_age
    detail = (f"QUARANTINED {age:.0f}s"
              + (f" > trigger interval {max_age:.0f}s — control loop "
                 "stuck (is `pio-tpu jobs triggers` + a worker running?)"
                 if stuck else f" (retrain due within {max_age:.0f}s)"))
    return {"url": url, "status": "quarantined", "red": stuck,
            "detail": detail}


def _mesh_row(state_dir: str) -> dict:
    """Synthetic health row for a distributed-training mesh (the
    quarantine-row pattern): red when live members are below quorum — a
    mesh that can no longer make training progress or commit a checkpoint
    (docs/sharding.md "Multi-host training")."""
    from incubator_predictionio_tpu.distributed.context import DistConfig
    from incubator_predictionio_tpu.distributed.meshdir import MeshDirectory

    conf = DistConfig.from_env()
    snap = MeshDirectory(state_dir).health_snapshot(
        conf.heartbeat_ms, quorum=conf.quorum or None)
    url = f"mesh:{state_dir}"
    commit = snap.get("lastCommit") or {}
    commit_txt = (f"last commit step {commit['step']} "
                  f"(gen {commit['generation']})" if commit else "no commit yet")
    detail = (f"generation {snap['generation']}, members "
              f"{snap['aliveMembers']}/{snap['expectedMembers']} alive "
              f"(quorum {snap['quorum']}); {commit_txt}")
    if snap["degraded"]:
        detail += (" — BELOW QUORUM: training cannot progress; restart the "
                   "lost members or their supervisor (docs/sharding.md)")
        return {"url": url, "status": "degraded", "red": True,
                "detail": detail}
    if snap["expectedMembers"] == 0:
        return {"url": url, "status": "no-mesh", "red": False,
                "detail": "no generation announced yet"}
    return {"url": url, "status": "ok", "red": False, "detail": detail}


def cmd_dist_status(args, storage) -> int:
    """``pio-tpu dist status`` — the operator view of a training mesh:
    generation, per-member heartbeat ages, last coordinated commit, and
    the quorum verdict. Exits non-zero when the mesh is degraded (the
    ``pio-tpu health`` convention)."""
    from incubator_predictionio_tpu.distributed.context import DistConfig
    from incubator_predictionio_tpu.distributed.meshdir import MeshDirectory

    conf = DistConfig.from_env()
    state_dir = getattr(args, "state_dir", None) or conf.state_dir
    if not state_dir:
        _err("dist status: no coordination dir (--state-dir or "
             "PIO_DIST_STATE_DIR)")
        return 2
    snap = MeshDirectory(state_dir).health_snapshot(
        conf.heartbeat_ms, quorum=conf.quorum or None)
    if getattr(args, "json", False):
        _out(json.dumps(snap, indent=2))
        return 1 if snap["degraded"] else 0
    _out(f"Mesh {state_dir}")
    _out(f"  generation: {snap['generation']}   members: "
         f"{snap['aliveMembers']}/{snap['expectedMembers']} alive   "
         f"quorum: {snap['quorum']}   "
         f"{'DEGRADED' if snap['degraded'] else 'ok'}")
    commit = snap.get("lastCommit")
    if commit:
        _out(f"  last commit: step {commit['step']} "
             f"(generation {commit['generation']})")
    else:
        _out("  last commit: none")
    for mrec in snap["members"]:
        state = "alive" if mrec["alive"] else (
            "fenced" if mrec["generation"] != snap["generation"] else "STALE")
        _out(f"  member {mrec['rank']}: pid {mrec['pid']} gen "
             f"{mrec['generation']} step {mrec['step']} "
             f"beat {mrec['ageMs']:.0f}ms ago [{state}]")
    return 1 if snap["degraded"] else 0


def format_index_stats(models) -> list[str]:
    """Human-readable two-stage retrieval state for a deployed engine's
    models — separated from cmd_index so tests drive it with hand-built
    models instead of a full storage round trip."""
    lines: list[str] = []
    for i, m in enumerate(models):
        info = m.serving_info() if hasattr(m, "serving_info") else {}
        name = type(m).__name__
        mode = info.get("retrieval_mode", "exact")
        lines.append(f"model {i} ({name}): path={info.get('path', '?')} "
                     f"catalog_rows={info.get('catalog_rows', '?')} "
                     f"retrieval={mode} "
                     f"pruned={info.get('pruned') or 'none'}")
        stats = info.get("index")
        if isinstance(stats, list):
            # sharded serving: one IVF per shard (docs/sharding.md)
            live = [s for s in stats if s]
            if live:
                parts = [s["n_partitions"] for s in live]
                lines.append(
                    f"  per-shard IVF over {len(stats)} shards: "
                    f"{sum(parts)} partitions total "
                    f"({min(parts)}–{max(parts)}/shard) covering "
                    f"{sum(s['n_items'] for s in live)} items; "
                    f"rerank {'int8' if live[0]['quantized'] else 'fp32'}, "
                    f"index bytes {sum(s['index_bytes'] for s in live)} "
                    "— `pio-tpu shards` prints the layout")
                saved = sum(s.get("bytes_saved", 0) for s in live)
                if saved:
                    lines.append(
                        f"  quantization: int8 member rows + "
                        f"{'int8' if live[0].get('quant_coarse') else 'fp32'}"
                        f" coarse — saves {saved} bytes vs fp32 rerank "
                        "storage across shards")
                continue
            stats = None
        if not stats:
            lines.append("  no partition index (exact full-catalog retrieval"
                         " — see PIO_RETRIEVAL_MODE in docs/serving.md)")
            continue
        lines.append(
            f"  partitions: {stats['n_partitions']} over "
            f"{stats['n_items']} items  "
            f"(size min/mean/max {stats['partition_size_min']}/"
            f"{stats['partition_size_mean']}/{stats['partition_size_max']}, "
            f"skew {stats['size_skew']}, "
            f"{stats['empty_partitions']} empty)")
        lines.append(
            f"  rerank storage: "
            f"{'int8 (quantize_rows)' if stats['quantized'] else 'fp32'}  "
            f"default nprobe: {stats['default_nprobe']}  "
            f"index bytes: {stats['index_bytes']}  "
            f"build: {stats['build_seconds']}s")
        if stats.get("quantized"):
            lines.append(
                f"  quantization: int8 member rows "
                f"({stats.get('rerank_bytes', '?')} bytes, saves "
                f"{stats.get('bytes_saved', 0)} vs fp32) + "
                f"{'int8' if stats.get('quant_coarse') else 'fp32'} coarse")
    return lines


def _fmt_bytes(n) -> str:
    if n is None:
        return "unbounded"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"  # pragma: no cover - loop always returns


def format_shard_stats(models) -> list[str]:
    """Human-readable shard layout for a deployed engine's models —
    separated from cmd_shards so tests drive it with hand-built models
    (the format_index_stats pattern)."""
    lines: list[str] = []
    for i, m in enumerate(models):
        name = type(m).__name__
        if not hasattr(m, "shard_info"):
            lines.append(f"model {i} ({name}): no shard layout "
                         "(not an embedding-table model)")
            continue
        info = m.shard_info()
        if not info.get("sharded"):
            lines.append(f"model {i} ({name}): UNSHARDED single-host layout")
            items = info.get("items") or {}
            lines.append(
                f"  items: {items.get('n_rows', '?')} rows × "
                f"{items.get('width', '?')} "
                f"({_fmt_bytes(items.get('table_bytes'))} f32; "
                f"train+adam {_fmt_bytes(items.get('train_bytes_per_shard'))}"
                "/chip)")
            budget = info.get("hbm_budget")
            lines.append(
                f"  hbm budget: {_fmt_bytes(budget)}"
                + ("  — EXCEEDS one chip: train/serve sharded "
                   "(PIO_SHARD_SERVE, docs/sharding.md)"
                   if info.get("requires_sharding") else ""))
            continue
        items, users = info["items"], info["users"]
        lines.append(
            f"model {i} ({name}): SHARDED ×{info['n_shards']} "
            f"({info['mode']} shards)")
        for label, t in (("items", items), ("users", users)):
            rows = t["shard_rows"]
            lines.append(
                f"  {label}: {t['n_rows']} rows → {t['rows_per_shard']}"
                f"/shard (real min/max {min(rows)}/{max(rows)}), "
                f"{_fmt_bytes(t['table_bytes'] // t['n_shards'])} f32/shard, "
                f"train+adam {_fmt_bytes(t['train_bytes_per_shard'])}/shard")
        # owned row ranges: which rows [lo, hi) each shard id serves —
        # the unit of ownership multi-host shard owners announce on
        # /health.deployment.shardOwner (docs/sharding.md)
        from incubator_predictionio_tpu.sharding.table import ShardSpec

        spec = ShardSpec(items["name"], items["n_rows"], items["width"],
                         items["n_shards"])
        lines.append("  item row ranges: " + "  ".join(
            f"{s}:[{lo},{hi})" for s, (lo, hi) in
            ((s, spec.shard_bounds(s)) for s in range(spec.n_shards))))
        lines.append(
            f"  merge fan-in: {info['merge_fanin']} candidates/query "
            f"({info['n_shards']} shards × per-shard top-k, "
            f"serve_k {info['serve_k']})")
        budget = info.get("hbm_budget")
        if budget is not None:
            lines.append(f"  hbm budget: {_fmt_bytes(budget)}")
        ivf = info.get("ivf")
        if ivf and any(ivf):
            parts = [s["n_partitions"] for s in ivf if s]
            lines.append(
                f"  per-shard IVF: {sum(parts)} partitions total "
                f"({min(parts)}–{max(parts)}/shard) — each shard prunes "
                "locally, the merge reranks")
            if info.get("quantized"):
                lines.append(
                    f"  quantization: int8 rerank/shard "
                    f"({_fmt_bytes(items.get('shard_serve_bytes_int8'))} "
                    f"int8 vs "
                    f"{_fmt_bytes(items.get('table_bytes', 0) // max(info.get('n_shards', 1), 1))}"
                    f" f32 HBM/shard; saves "
                    f"{_fmt_bytes(info.get('rerank_bytes_saved', 0))} total)")
    return lines


def cmd_shards(args, storage: Storage) -> int:
    """Inspect the shard layout of the latest COMPLETED instance's models:
    per-shard row counts, HBM-bytes estimates, merge fan-in
    (docs/sharding.md)."""
    from incubator_predictionio_tpu.server.query_server import (
        ServerConfig,
        load_deployed_engine,
    )

    # warmup=False: inspection only reads shard_info() — XLA bucket
    # compiles would be paid for nothing
    deployed = load_deployed_engine(
        ServerConfig(engine_variant=args.engine_variant, max_batch=1),
        storage, warmup=False)
    _out(f"engine instance {deployed.instance.id}")
    for line in format_shard_stats(deployed.models):
        _out(line)
    return 0


def cmd_index(args, storage: Storage) -> int:
    """Inspect (building if needed) the two-stage retrieval partition of the
    latest COMPLETED instance's models (docs/serving.md "Two-stage
    retrieval")."""
    if args.two_stage:
        # force the build so small/dev catalogs are inspectable too
        os.environ["PIO_RETRIEVAL_MODE"] = "two_stage"
    from incubator_predictionio_tpu.server.query_server import (
        ServerConfig,
        load_deployed_engine,
    )

    # warmup=False: inspection only reads serving_info() — XLA bucket
    # compiles and two-stage priming would be paid for nothing
    deployed = load_deployed_engine(
        ServerConfig(engine_variant=args.engine_variant, max_batch=1),
        storage, warmup=False)
    _out(f"engine instance {deployed.instance.id}")
    for line in format_index_stats(deployed.models):
        _out(line)
    return 0


def cmd_tenants(args, storage) -> int:
    """Per-tenant fleet rollup (docs/tenancy.md): one row per tenant
    aggregated across every given server's ``/health`` + ``/metrics`` —
    requests + qps, p99, quota fill, throttles, cold loads, evictions,
    and resident HBM bytes. Red rows (the `pio-tpu health` row pattern:
    ``!!`` mark + non-zero exit) on quota exhaustion or eviction
    thrash."""
    from incubator_predictionio_tpu.fleet.health import probe_health_urls
    from incubator_predictionio_tpu.obs.metrics import (
        bucket_quantiles,
        parse_prometheus_text,
    )

    probed = probe_health_urls(
        args.urls, args.timeout,
        fetch=lambda url, timeout: _fetch_health(url, timeout))
    agg: dict[str, dict] = {}

    def slot(t: str) -> dict:
        return agg.setdefault(t, {
            "tenant": t, "requests": 0, "throttled": 0, "evictions": 0,
            "coldLoads": 0, "residentBytes": 0, "replicas": 0,
            "resident": 0, "pinned": False, "quotaFill": None,
            "p99Ms": None, "qps": None})

    rows: list[dict] = []
    for url in args.urls:
        h, err = probed[url]
        if h is None:
            rows.append({"url": url, "status": "unreachable", "red": True,
                         "detail": err or ""})
            continue
        tenants = ((h.get("tenancy") or {}).get("tenants")) or {}
        for t, trow in tenants.items():
            a = slot(t)
            a["replicas"] += 1
            a["resident"] += 1 if trow.get("resident") else 0
            a["pinned"] = a["pinned"] or bool(trow.get("pinned"))
            a["requests"] += int(trow.get("requests") or 0)
            a["throttled"] += int(trow.get("throttled") or 0)
            a["evictions"] += int(trow.get("evictions") or 0)
            a["coldLoads"] += int(trow.get("coldLoads") or 0)
            a["residentBytes"] += int(trow.get("residentBytes") or 0)
            fill = (trow.get("quota") or {}).get("fill")
            if fill is not None:
                a["quotaFill"] = (fill if a["quotaFill"] is None
                                  else min(a["quotaFill"], fill))
    # /metrics fold: fleet-merged per-tenant histogram buckets give the
    # p99; a second scrape ``--interval`` later turns the cumulative
    # request counters into a live qps (0 disables the second scrape)
    scrapes: list[dict] = [{}, {}]
    n_scrapes = 2 if args.interval > 0 else 1
    for phase in range(n_scrapes):
        if phase == 1:
            import time as _time

            _time.sleep(args.interval)
        for url in args.urls:
            if probed[url][0] is None:
                continue
            try:
                text = _fetch_metrics_text(_metrics_url(url), args.timeout)
            except Exception:  # noqa: BLE001 - the rollup is best-effort
                continue
            scrapes[phase][url] = parse_prometheus_text(text)
    reqs: list[dict[str, float]] = [{}, {}]
    buckets: dict[str, dict[float, float]] = {}
    last = scrapes[n_scrapes - 1]
    for phase in range(n_scrapes):
        for fams in scrapes[phase].values():
            fam = fams.get("pio_tenant_requests_total") or {}
            for _s, labels, value in fam.get("samples", []):
                t = labels.get("tenant")
                if t:
                    reqs[phase][t] = reqs[phase].get(t, 0.0) + value
    for fams in last.values():
        fam = fams.get("pio_tenant_request_seconds") or {}
        for sname, labels, value in fam.get("samples", []):
            if not sname.endswith("_bucket"):
                continue
            t = labels.get("tenant")
            if not t:
                continue
            le = float({"+Inf": "inf"}.get(labels["le"], labels["le"]))
            b = buckets.setdefault(t, {})
            b[le] = b.get(le, 0.0) + value
    for t, b in buckets.items():
        q = bucket_quantiles(sorted(b.items())).get("p99")
        if q is not None:
            slot(t)["p99Ms"] = round(q * 1e3, 2)
    if n_scrapes == 2:
        for t in list(agg):
            d = reqs[1].get(t, 0.0) - reqs[0].get(t, 0.0)
            agg[t]["qps"] = round(max(0.0, d) / args.interval, 2)
    for t in sorted(agg):
        a = agg[t]
        reasons = []
        fill = a["quotaFill"]
        if a["throttled"] and fill is not None and fill <= args.fill_red:
            reasons.append(f"QUOTA EXHAUSTED (fill {fill:.2f}, "
                           f"{a['throttled']} throttled)")
        if a["evictions"] >= args.thrash_evictions:
            reasons.append(f"EVICTION THRASH ({a['evictions']} evictions "
                           f">= {args.thrash_evictions} — grow "
                           "PIO_TENANT_HBM_BUDGET or pin the tenant)")
        parts = [f"req {a['requests']}"]
        if a["qps"] is not None:
            parts.append(f"qps {a['qps']}")
        if a["p99Ms"] is not None:
            parts.append(f"p99 {a['p99Ms']}ms")
        if fill is not None:
            parts.append(f"quota fill {fill:.2f}")
        if a["throttled"]:
            parts.append(f"throttled {a['throttled']}")
        parts.append(f"resident {a['resident']}/{a['replicas']}"
                     + (" pinned" if a["pinned"] else ""))
        parts.append(f"hbm {a['residentBytes']}B")
        if a["coldLoads"]:
            parts.append(f"coldLoads {a['coldLoads']}")
        if a["evictions"]:
            parts.append(f"evictions {a['evictions']}")
        parts.extend(reasons)
        rows.append({"url": f"tenant:{t}", **a,
                     "status": ("over-quota" if reasons else "ok"),
                     "red": bool(reasons), "detail": "; ".join(parts)})
    if not rows:
        _err("tenants: nothing to report (are these multi-tenant "
             "query servers? docs/tenancy.md)")
        return 2
    if args.json:
        _out(json.dumps(rows, indent=2))
    else:
        w = max(len(r["url"]) for r in rows)
        for r in rows:
            mark = "!!" if r["red"] else "ok"
            line = f"{mark} {r['url']:<{w}}  {r['status']}"
            if r["detail"]:
                line += f"  [{r['detail']}]"
            _out(line)
    return 1 if any(r["red"] for r in rows) else 0


def _fetch_metrics_text(url: str, timeout: float = 10.0,
                        exemplars: bool = False) -> str:
    """GET one /metrics page. Module-level so tests can stub it. The
    pretty-printer asks for exemplars explicitly (``?exemplars=1``);
    ``--raw`` output must stay strict 0.0.4 — its consumers (promtool, a
    pasted scrape) never asked for exemplar suffixes."""
    import urllib.request

    if exemplars:
        url = f"{url}{'&' if '?' in url else '?'}exemplars=1"
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _metrics_url(url: str) -> str:
    url = url.rstrip("/")
    return url if url.endswith("/metrics") else url + "/metrics"


def _hist_by_labelset(samples) -> dict:
    """Histogram samples → {labelset_key: {"buckets": [(le, cum)],
    "sum": x, "count": n}}."""
    by_key: dict[tuple, dict] = {}
    for sname, labels, value in samples:
        key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
        slot = by_key.setdefault(key, {"buckets": [], "sum": 0.0,
                                       "count": 0.0})
        if sname.endswith("_bucket"):
            slot["buckets"].append((float(labels["le"]), value))
        elif sname.endswith("_sum"):
            slot["sum"] = value
        elif sname.endswith("_count"):
            slot["count"] = value
    return by_key


def _label_str(key: tuple) -> str:
    return ",".join(f"{k}={v}" for k, v in key) or "(no labels)"


def _render_metrics_single(families, args) -> None:
    import math

    from incubator_predictionio_tpu.obs.metrics import bucket_quantiles

    for name in sorted(families):
        fam = families[name]
        kind, samples = fam["type"] or "untyped", fam["samples"]
        if args.filter and args.filter not in name:
            continue
        _out(f"{name} ({kind})" + (f" — {fam['help']}" if fam["help"] else ""))
        if kind == "histogram":
            ex_by_key: dict[tuple, list] = {}
            for sname, labels, ex in fam.get("exemplars", []):
                k = tuple(sorted((lk, lv) for lk, lv in labels.items()
                                 if lk != "le"))
                ex_by_key.setdefault(k, []).append((labels.get("le", "?"),
                                                    ex))
            # per label-set: count, sum, mean, estimated quantiles
            for key, slot in sorted(_hist_by_labelset(samples).items()):
                count = slot.get("count", 0)
                mean = (slot.get("sum", 0.0) / count) if count else 0.0
                qs = bucket_quantiles(slot["buckets"])
                _out(f"  {_label_str(key)}: count={int(count)} "
                     f"mean={mean * 1e3:.3f}ms "
                     + " ".join(f"~{k}={v * 1e3:.3f}ms"
                                for k, v in qs.items()))
                for le, ex in ex_by_key.get(key, []):
                    # the bucket's exemplar links the latency straight to
                    # a showable trace (`pio-tpu trace show <id>`)
                    tid = ex.get("labels", {}).get("trace_id", "?")
                    _out(f"    exemplar le={le}: "
                         f"{ex['value'] * 1e3:.3f}ms trace={tid}")
        else:
            for sname, labels, value in sorted(
                    samples, key=lambda s: sorted(s[1].items())):
                label = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
                v = int(value) if float(value).is_integer() \
                    and not math.isinf(value) else value
                _out(f"  {label or '(no labels)'}: {v}")


def _render_metrics_fleet(pages: dict, args) -> None:
    """Merged multi-server table: one row per sample with a per-server
    column and an aggregate (sum for monotonic counters and histogram
    count/sum, max for gauges; histogram quantiles re-estimated from the
    bucket-merged fleet distribution)."""
    import math

    from incubator_predictionio_tpu.obs.metrics import bucket_quantiles

    urls = list(pages)
    aliases = {url: f"s{i + 1}" for i, url in enumerate(urls)}
    _out("servers:")
    for url in urls:
        _out(f"  {aliases[url]} = {url}")
    names = sorted({n for fams in pages.values() for n in fams})
    for name in names:
        if args.filter and args.filter not in name:
            continue
        kinds = [pages[u][name]["type"] for u in urls
                 if name in pages[u] and pages[u][name]["type"]]
        kind = kinds[0] if kinds else "untyped"
        helps = [pages[u][name]["help"] for u in urls
                 if name in pages[u] and pages[u][name]["help"]]
        _out(f"{name} ({kind})"
             + (f" — {helps[0]}" if helps else ""))
        if kind == "histogram":
            per_server = {u: _hist_by_labelset(pages[u][name]["samples"])
                          for u in urls if name in pages[u]}
            keys = sorted({k for slots in per_server.values()
                           for k in slots})
            for key in keys:
                cols = []
                merged_buckets: dict[float, float] = {}
                total_count = total_sum = 0.0
                for url in urls:
                    slot = per_server.get(url, {}).get(key)
                    if slot is None:
                        cols.append(f"{aliases[url]}=-")
                        continue
                    count = slot.get("count", 0)
                    p99 = bucket_quantiles(slot["buckets"],
                                           qs=(0.99,))["p99"]
                    cols.append(f"{aliases[url]} count={int(count)} "
                                f"~p99={p99 * 1e3:.3f}ms")
                    total_count += count
                    total_sum += slot.get("sum", 0.0)
                    for le, cum in slot["buckets"]:
                        merged_buckets[le] = merged_buckets.get(le, 0) + cum
                p99_all = bucket_quantiles(sorted(merged_buckets.items()),
                                           qs=(0.99,))["p99"]
                mean = (total_sum / total_count) if total_count else 0.0
                cols.append(f"all count={int(total_count)} "
                            f"mean={mean * 1e3:.3f}ms "
                            f"~p99={p99_all * 1e3:.3f}ms")
                _out(f"  {_label_str(key)}: " + " | ".join(cols))
        else:
            # counters sum across the fleet; gauges take the max (a depth
            # or limit summed across servers is not a meaningful number)
            agg = max if kind == "gauge" else sum
            keys = sorted({tuple(sorted(labels.items()))
                           for u in urls if name in pages[u]
                           for _, labels, _ in pages[u][name]["samples"]})
            for key in keys:
                cols, values = [], []
                for url in urls:
                    vals = [
                        v for _, labels, v
                        in pages.get(url, {}).get(name, {}).get("samples", [])
                        if tuple(sorted(labels.items())) == key]
                    if not vals:
                        cols.append(f"{aliases[url]}=-")
                        continue
                    v = vals[0]
                    values.append(v)
                    iv = int(v) if float(v).is_integer() \
                        and not math.isinf(v) else v
                    cols.append(f"{aliases[url]}={iv}")
                a = agg(values) if values else 0
                a = int(a) if float(a).is_integer() and not math.isinf(a) \
                    else a
                label = "max" if kind == "gauge" else "sum"
                cols.append(f"{label}={a}")
                _out(f"  {_label_str(key)}: " + " ".join(cols))


def cmd_metrics(args, storage) -> int:
    """Fetch and pretty-print one or more servers' ``/metrics`` pages
    (docs/observability.md). Multiple URLs (or ``--fleet``) render a merged
    table with per-server columns plus a summed/max aggregate — probes run
    concurrently (the fleet/health.py fan-out pattern), so one dead server
    costs one timeout, not O(N)."""
    from concurrent.futures import ThreadPoolExecutor

    from incubator_predictionio_tpu.obs.metrics import (
        MetricError,
        parse_prometheus_text,
    )

    urls = [_metrics_url(u) for u in args.urls]
    texts: dict[str, str] = {}
    failures: list[str] = []
    with ThreadPoolExecutor(max_workers=min(16, len(urls))) as pool:
        futures = {url: pool.submit(_fetch_metrics_text, url, args.timeout,
                                    not args.raw)
                   for url in urls}
        for url, fut in futures.items():
            try:
                texts[url] = fut.result()
            except Exception as e:  # noqa: BLE001 - a dead server is a row
                failures.append(f"{url}: {e}")
    for f in failures:
        _err(f"Unable to fetch {f}")
    if not texts:
        return 1
    if args.raw:
        for url, text in texts.items():
            if len(texts) > 1:
                _out(f"# ---- {url} ----")
            _out(text.rstrip())
        return 1 if failures else 0
    pages: dict[str, dict] = {}
    for url, text in texts.items():
        try:
            pages[url] = parse_prometheus_text(text)
        except MetricError as e:
            _err(f"{url} served malformed metrics: {e}")
            failures.append(url)
    if not pages:
        return 1
    if len(pages) == 1 and not args.fleet:
        _render_metrics_single(next(iter(pages.values())), args)
    else:
        _render_metrics_fleet(pages, args)
    return 1 if failures else 0


def _fetch_json(url: str, timeout: float = 10.0) -> dict:
    """GET one JSON document. Module-level so tests can stub it."""
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def cmd_profile(args, storage) -> int:
    """Fetch and render a server's ``GET /profile.json`` — the continuous
    profiler's live document (docs/observability.md "Profiling"): per-scope
    phase attribution (where the step time goes), the wall-stack sampler's
    top-N (when PIO_PROFILE_HZ > 0), training MFU, and device-memory
    watermarks."""
    url = args.url.rstrip("/") + "/profile.json"
    try:
        doc = _fetch_json(url, args.timeout)
    except Exception as e:  # noqa: BLE001 - a dead server is the answer
        _err(f"Unable to fetch {url}: {e}")
        return 1
    if args.json:
        _out(json.dumps(doc, indent=2))
        return 0
    _out(f"service: {doc.get('service', '?')}")
    phases = doc.get("phases") or {}
    if not phases:
        _out("phases: none recorded yet")
    for scope in sorted(phases):
        e = phases[scope]
        wall = e.get("wall_seconds", 0.0)
        _out(f"{scope}: wall {wall:.3f}s over {e.get('count', 0)} scope(s)")
        for p, ph in sorted((e.get("phases") or {}).items(),
                            key=lambda kv: -kv[1]["seconds"]):
            pct = 100.0 * ph["seconds"] / wall if wall else 0.0
            _out(f"  {p:<12} {ph['seconds']:9.3f}s  {pct:5.1f}%  "
                 f"({ph['count']} interval(s))")
    tr = doc.get("training") or {}
    if tr.get("mfu"):
        peak = tr.get("peak_flops")
        _out(f"training MFU: {tr['mfu'] * 100:.1f}%"
             + (f" of {peak:.3g} FLOP/s peak" if peak else ""))
    for dev, v in sorted((doc.get("deviceWatermark") or {}).items()):
        _out(f"device {dev}: peak {v / 2**20:.1f} MiB")
    sampler = doc.get("sampler")
    if sampler is None:
        _out("sampler: off (set PIO_PROFILE_HZ to enable the wall-stack "
             "profiler)")
        return 0
    _out(f"sampler: {sampler['hz']:g} Hz, {sampler['samples']} sample(s)")
    for i, row in enumerate(sampler.get("top") or [], 1):
        stack = row.get("stack") or ["?"]
        _out(f"  #{i:<3}{row['pct']:5.1f}%  ({row['samples']})  {stack[0]}")
        for frame in stack[1:]:
            _out(f"          {frame}")
    return 0


def _load_history_records(source: str, since, timeout: float) -> list:
    """History records from a PIO_HISTORY_DIR (durable segments) or a
    server base URL (the live in-memory ring via /history.json)."""
    from incubator_predictionio_tpu.obs import history as hist

    if source.startswith("http://") or source.startswith("https://"):
        url = source.rstrip("/") + "/history.json"
        if since is not None:
            url += f"?since={since:g}"
        return _fetch_json(url, timeout).get("records") or []
    return hist.read_history(source, since=since)


def cmd_history(args, storage) -> int:
    """Inspect the durable metrics history (docs/observability.md "Metrics
    history & SLOs"): a PIO_HISTORY_DIR's CRC-framed segments, or a live
    server's in-memory ring over ``GET /history.json``. Without --series,
    summarizes what is recorded; with --series (glob over family names),
    prints the matching time series (counters additionally as per-interval
    rates)."""
    from incubator_predictionio_tpu.obs import history as hist

    try:
        records = _load_history_records(args.source, args.since, args.timeout)
    except Exception as e:  # noqa: BLE001 - dead server / bad dir is the answer
        _err(f"history: unable to read {args.source}: {e}")
        return 1
    if not records:
        _out(f"history: no records in {args.source}")
        return 1
    if args.json and not args.series:
        _out(json.dumps(records, indent=2))
        return 0
    services = sorted({r.get("service", "?") for r in records})
    span = records[-1]["t"] - records[0]["t"]
    if not args.series:
        _out(f"{len(records)} snapshot(s) over {span:.0f}s from "
             f"{', '.join(services)}")
        types = hist.merged_types(records)
        for name in hist.list_series(records):
            count = sum(1 for r in records
                        if any(s[0] == name for s in r["samples"]))
            kind = types.get(name.split("_bucket")[0], "")
            _out(f"  {name:<48} {count:>6} point(s)"
                 + (f"  [{kind}]" if kind else ""))
        return 0
    types = hist.merged_types(records)
    matched = hist.list_series(records, pattern=args.series)
    if not matched:
        _err(f"history: no series match {args.series!r}")
        return 1
    out_doc = {}
    for name in matched:
        points = hist.series(records, name)
        kind = types.get(name, "")
        if args.json:
            out_doc[name] = points
            continue
        _out(f"{name}" + (f" ({kind})" if kind else ""))
        shown = (hist.rate_series(points)
                 if kind == "counter" and len(points) > 1 else points)
        for t, v in shown[-args.limit:]:
            vv = int(v) if float(v).is_integer() else round(v, 6)
            _out(f"  {t:.0f}  {vv}")
        if kind == "counter" and len(points) > 1:
            _out(f"  (per-second rates; cumulative "
                 f"{points[-1][1]:g} at t={points[-1][0]:.0f})")
    if args.json:
        _out(json.dumps(out_doc, indent=2))
    return 0


def _top_snapshot(url: str, timeout: float) -> dict:
    """One server's 'top' row source: the parsed /metrics families."""
    from incubator_predictionio_tpu.obs.metrics import parse_prometheus_text

    return parse_prometheus_text(
        _fetch_metrics_text(_metrics_url(url), timeout))


def _top_row(url: str, fams: dict, prev: Optional[tuple],
             now: float) -> tuple[str, tuple]:
    """Render one server's top line; returns (line, state-for-next-tick).
    qps derives from the pio_http_requests_total delta between refreshes."""
    from incubator_predictionio_tpu.obs.metrics import bucket_quantiles

    def total(family: str) -> Optional[float]:
        fam = fams.get(family)
        if fam is None:
            return None
        vals = [v for n, _l, v in fam["samples"] if n == family]
        return sum(vals) if vals else None

    reqs = total("pio_http_requests_total")
    qps = None
    if reqs is not None and prev is not None and now > prev[0]:
        qps = max(0.0, (reqs - prev[1])) / (now - prev[0])
    parts = []
    parts.append(f"qps={qps:.1f}" if qps is not None else "qps=-")
    lat = fams.get("pio_http_request_seconds")
    if lat is not None:
        merged: dict[float, float] = {}
        for n, labels, v in lat["samples"]:
            if n.endswith("_bucket"):
                le = float(labels["le"])
                merged[le] = merged.get(le, 0.0) + v
        if merged:
            p99 = bucket_quantiles(sorted(merged.items()), qs=(0.99,))["p99"]
            parts.append(f"p99={p99 * 1e3:.1f}ms")
    rss = total("pio_process_rss_bytes")
    if rss:
        parts.append(f"rss={rss / 2**20:.0f}MiB")
    fds = total("pio_process_open_fds")
    if fds:
        parts.append(f"fds={int(fds)}")
    lag_fam = fams.get("pio_process_loop_lag_seconds")
    if lag_fam is not None and lag_fam["samples"]:
        lag = max(v for _n, _l, v in lag_fam["samples"])
        parts.append(f"lag={lag * 1e3:.1f}ms")
    mfu = total("pio_training_mfu")
    if mfu:
        parts.append(f"mfu={mfu * 100:.1f}%")
    compiles = total("pio_jit_compile_seconds_total")
    if compiles:
        parts.append(f"jit={compiles:.1f}s")
    breaching = total("pio_slo_breaching")
    mark = "ok"
    if breaching:
        parts.append(f"SLO_BREACH={int(breaching)}")
        mark = "!!"
    return f"{mark} {url}  " + " ".join(parts), (now, reqs)


def cmd_top(args, storage) -> int:
    """Live-refreshing one-line-per-server view of the performance plane
    (docs/observability.md): qps (from the requests-counter delta between
    refreshes), fleet p99, RSS/FDs/loop-lag, training MFU, cumulative jit
    compile seconds, and SLO breach state. ``-n 1`` prints once (scripts);
    the default refreshes until interrupted."""
    import time as _time

    prev: dict[str, tuple] = {}
    iteration = 0
    while True:
        iteration += 1
        lines = []
        for url in args.urls:
            now = _time.time()
            try:
                fams = _top_snapshot(url, args.timeout)
            except Exception as e:  # noqa: BLE001 - a dead server is a row
                lines.append(f"!! {url}  unreachable: {e}")
                continue
            line, state = _top_row(url, fams, prev.get(url), now)
            prev[url] = state
            lines.append(line)
        if args.iterations != 1 and sys.stdout.isatty():
            sys.stdout.write("\x1b[2J\x1b[H")
        _out(_time.strftime("%H:%M:%S") + f"  refresh {iteration}")
        for line in lines:
            _out(line)
        if args.iterations and iteration >= args.iterations:
            return 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


def cmd_slo(args, storage) -> int:
    """SLO config validation and offline burn-rate verdicts
    (docs/observability.md "Metrics history & SLOs").

    ``--check <config>`` validates the objectives file and exits non-zero
    with named-position errors on any defect — the CI gate for config
    drift. With a history source (PIO_HISTORY_DIR or server URL), loads
    the config (--config, else $PIO_SLO_CONFIG), evaluates every objective
    over the recorded windows, prints the verdict table, and exits
    non-zero when any objective is breaching."""
    from incubator_predictionio_tpu.obs import slo as slomod

    if args.check:
        try:
            objectives = slomod.load_config(args.check)
        except slomod.SloConfigError as e:
            _err(f"slo: {args.check} INVALID:")
            for err in e.errors:
                _err(f"  {err}")
            return 1
        _out(f"slo: {args.check} OK — {len(objectives)} objective(s)")
        for o in objectives:
            line = (f"  {o['name']}: {o['type']} on {o['service']} "
                    f"objective={o['objective']:g}")
            if o.get("threshold_ms") is not None:
                line += f" threshold={o['threshold_ms']:g}ms"
            _out(line)
        if not args.source:
            return 0
    if not args.source:
        _err("slo: give a history dir / server URL, or --check <config>")
        return 2
    cfg_path = args.config or (args.check if args.check else None) \
        or os.environ.get(slomod.ENV_CONFIG)
    if not cfg_path:
        _err("slo: no objectives config (--config, --check, or "
             "PIO_SLO_CONFIG)")
        return 2
    try:
        objectives = slomod.load_config(cfg_path)
    except slomod.SloConfigError as e:
        _err(f"slo: {cfg_path} INVALID:")
        for err in e.errors:
            _err(f"  {err}")
        return 1
    try:
        records = _load_history_records(args.source, args.since,
                                        args.timeout)
    except Exception as e:  # noqa: BLE001
        _err(f"slo: unable to read {args.source}: {e}")
        return 1
    if not records:
        _err(f"slo: no history records in {args.source}")
        return 1
    verdicts = slomod.evaluate(objectives, records)
    if args.json:
        _out(json.dumps(verdicts, indent=2))
        return 1 if any(v["breaching"] for v in verdicts) else 0
    for v in verdicts:
        mark = "!!" if v["breaching"] else ("??" if v["no_data"] else "ok")
        line = f"{mark} {v['name']} ({v['type']} on {v['service']})"
        if v["budget_remaining"] is not None:
            line += f"  budget {v['budget_remaining'] * 100:.2f}%"
        _out(line)
        for wname, w in sorted(v["windows"].items()):
            bs = "-" if w["burn_short"] is None else f"{w['burn_short']:.2f}"
            bl = "-" if w["burn_long"] is None else f"{w['burn_long']:.2f}"
            _out(f"    {wname}: burn {bs}x/{bl}x "
                 f"({w['short_sec']:g}s/{w['long_sec']:g}s windows, "
                 f"threshold {w['threshold']:g}x)"
                 + ("  BREACHING" if w["breaching"] else ""))
    return 1 if any(v["breaching"] for v in verdicts) else 0


def cmd_trace(args, storage) -> int:
    """Assemble cross-process traces from span spools and/or live servers
    (docs/observability.md "The trace plane"): ``list`` recent traces,
    ``show <id>`` one trace's terminal waterfall, ``slowest`` the worst
    offenders — the answer to "which hop made this p99 query slow?"."""
    from incubator_predictionio_tpu.obs import collect

    if not getattr(args, "trace_command", None):
        _err("trace: missing subcommand (list|show|slowest)")
        return 1
    spools = list(args.spool or ())
    urls = list(args.url or ())
    if not spools and not urls:
        default_dir = os.environ.get("PIO_TRACE_SPOOL_DIR")
        if default_dir:
            spools = [default_dir]
        else:
            _err("trace: give at least one --spool DIR or --url URL "
                 "(or set PIO_TRACE_SPOOL_DIR)")
            return 2
    spans, problems = collect.gather_spans(
        spools=spools, urls=urls, timeout=args.timeout)
    for p in problems:
        _err(f"trace: {p}")
    traces = collect.assemble(spans)
    if args.trace_command == "show":
        tree, matches = collect.find_trace(traces, args.trace_id)
        if tree is None:
            if matches:
                _err(f"trace prefix {args.trace_id!r} is ambiguous — "
                     f"{len(matches)} match: " + ", ".join(matches[:8]))
            else:
                _err(f"trace {args.trace_id!r} not found "
                     f"({len(traces)} trace(s) in the given sources)")
            return 1
        if args.json:
            _out(json.dumps(tree, indent=2, default=str))
        else:
            for line in collect.waterfall(tree):
                _out(line)
        return 0
    if args.trace_command == "slowest":
        picked = collect.slowest(traces, args.limit)
        if args.json:
            _out(json.dumps(
                {"slowest": collect.list_rows(picked),
                 "waterfall": (collect.waterfall(picked[0])
                               if picked else [])}, indent=2, default=str))
            return 0
        for row in collect.list_rows(picked):
            _out(f"{row['traceId']}  {row['durationMs']:>9.1f}ms  "
                 f"spans={row['spans']} errors={row['errors']} "
                 f"complete={str(row['complete']).lower()}  "
                 f"[{row['services']}]  {row['root']}")
        if picked:
            _out("")
            for line in collect.waterfall(picked[0]):
                _out(line)
        return 0
    # list (default)
    rows = collect.list_rows(traces[:args.limit])
    if args.json:
        _out(json.dumps({"traces": rows}, indent=2, default=str))
        return 0
    if not rows:
        _out("No traces in the given sources.")
        return 0
    for row in rows:
        _out(f"{row['traceId']}  {row['durationMs']:>9.1f}ms  "
             f"spans={row['spans']} errors={row['errors']} "
             f"complete={str(row['complete']).lower()}  "
             f"[{row['services']}]  {row['root']}")
    return 0


# ---------------------------------------------------------------------------
# fleet: router / rolling deploy / experiment (docs/serving.md
# "Fleet serving")
# ---------------------------------------------------------------------------

def cmd_fleet_route(args, storage) -> int:
    """Run the fleet router server over the given replicas."""
    from incubator_predictionio_tpu.fleet.experiments import Experiment
    from incubator_predictionio_tpu.fleet.router import (
        RouterConfig,
        serve_forever,
    )

    experiment = None
    if args.experiment_weight is not None:
        if not args.candidate:
            # refuse rather than silently run 100% control: the operator
            # believes an experiment is live (matches the runtime path,
            # where POST /experiment without candidates answers 409)
            _err("--experiment-weight needs at least one --candidate "
                 "replica to route the candidate arm to")
            return 2
        experiment = Experiment(
            name=args.experiment_name, mode=args.experiment_mode,
            weight=args.experiment_weight,
            hash_field=args.experiment_hash_field)
    kw = {}
    for flag, key in (("deadline", "deadline_sec"),
                      ("retries", "max_attempts"),
                      ("health_interval", "health_interval_sec"),
                      ("probe_timeout", "probe_timeout_sec"),
                      ("eject_threshold", "eject_threshold")):
        v = getattr(args, flag)
        if v is not None:  # unset flags keep the PIO_FLEET_* env defaults
            kw[key] = v
    serve_forever(RouterConfig(
        replicas=tuple(args.replica),
        candidates=tuple(args.candidate or ()),
        ip=args.ip, port=args.port,
        server_access_key=args.server_access_key,
        experiment=experiment, **kw))
    return 0


def cmd_fleet_rollout(args, storage) -> int:
    """Sequential fleet rolling deploy with halt-and-rollback
    (fleet/rollout.py). Exits non-zero on a halt, even when the rollback
    repaired every replica — a halted rollout is a failed deploy."""
    from incubator_predictionio_tpu.fleet.rollout import (
        RolloutConfig,
        run_rollout,
    )

    result = run_rollout(RolloutConfig(
        replicas=tuple(args.replicas),
        server_access_key=args.server_access_key,
        observe_sec=args.observe, poll_sec=args.poll,
        timeout_sec=args.timeout))
    if args.json:
        _out(json.dumps({
            "ok": result.ok, "updated": result.updated,
            "rolledBack": result.rolled_back,
            "haltedAt": result.halted_at, "reason": result.reason,
            "events": result.events}, indent=2))
    else:
        for line in result.events:
            _out(line)
        _out("ROLLOUT " + ("OK" if result.ok else
                           f"HALTED at {result.halted_at}: {result.reason}"))
    return 0 if result.ok else 1


def _arm_stats_from_metrics(families: dict) -> dict:
    """Per-arm request/error/latency stats from a router's /metrics page
    (pio_fleet_arm_* families; docs/observability.md)."""
    from incubator_predictionio_tpu.obs.metrics import bucket_quantiles

    arms: dict[str, dict] = {}

    def slot(arm: str) -> dict:
        return arms.setdefault(arm, {
            "requests": 0, "errors": 0, "buckets": [],
            "latency_sum": 0.0, "latency_count": 0})

    fam = families.get("pio_fleet_arm_requests_total")
    for _, labels, value in (fam["samples"] if fam else ()):
        s = slot(labels.get("arm", "?"))
        s["requests"] += int(value)
        if labels.get("status", "").startswith("5"):
            s["errors"] += int(value)
    fam = families.get("pio_fleet_arm_latency_seconds")
    for sname, labels, value in (fam["samples"] if fam else ()):
        s = slot(labels.get("arm", "?"))
        if sname.endswith("_bucket"):
            s["buckets"].append((float(labels["le"]), value))
        elif sname.endswith("_sum"):
            s["latency_sum"] += value
        elif sname.endswith("_count"):
            s["latency_count"] += int(value)
    out = {}
    for arm, s in arms.items():
        qs = bucket_quantiles(s["buckets"]) if s["buckets"] else {}
        out[arm] = {
            "requests": s["requests"],
            "errorRate": round(s["errors"] / s["requests"], 4)
            if s["requests"] else 0.0,
            "meanMs": round(1e3 * s["latency_sum"]
                            / max(1, s["latency_count"]), 2),
            "p95Ms": round(qs.get("p95", 0.0) * 1e3, 2),
        }
    return out


def _experiment_verdict(arms: dict) -> str:
    """Promote-or-abort reading of the live per-arm evidence. Advisory —
    the operator promotes by redeploying the control fleet, the CLI only
    names what the numbers say."""
    control, candidate = arms.get("control"), arms.get("candidate")
    if not control or not candidate:
        return "insufficient data (need traffic on both arms)"
    if candidate["requests"] < 20:
        return f"continue (candidate has {candidate['requests']} requests)"
    if candidate["errorRate"] > control["errorRate"] + 0.01:
        return (f"ABORT: candidate error rate {candidate['errorRate']:.2%} "
                f"vs control {control['errorRate']:.2%}")
    if control["p95Ms"] and candidate["p95Ms"] > 1.5 * control["p95Ms"]:
        return (f"ABORT: candidate p95 {candidate['p95Ms']}ms vs control "
                f"{control['p95Ms']}ms")
    return "PROMOTE-worthy: error rate and latency within control's band"


def cmd_fleet_experiment(args, storage) -> int:
    """Inspect (default), start (--start), or stop (--stop) the A/B /
    shadow experiment on a running router, with per-arm live evidence
    from the router's /metrics."""
    import urllib.request

    from incubator_predictionio_tpu.obs.metrics import parse_prometheus_text

    base = args.router_url.rstrip("/")
    auth = (f"?accessKey={args.server_access_key}"
            if args.server_access_key else "")
    if args.start or args.stop:
        body = (json.dumps({"stop": True}).encode() if args.stop
                else json.dumps({
                    "name": args.start, "mode": args.mode,
                    "weight": args.weight,
                    "hashField": args.hash_field}).encode())
        req = urllib.request.Request(
            f"{base}/experiment{auth}", data=body, method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                _out(json.loads(resp.read()).get("message", "ok"))
        except Exception as e:  # noqa: BLE001
            _err(f"experiment update failed: {e}")
            return 1
        return 0
    try:
        with urllib.request.urlopen(f"{base}/experiment.json",
                                    timeout=10) as resp:
            state = json.loads(resp.read())
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
            arms = _arm_stats_from_metrics(
                parse_prometheus_text(resp.read().decode()))
    except Exception as e:  # noqa: BLE001
        _err(f"Unable to read {base}: {e}")
        return 1
    exp = state.get("experiment")
    payload = {"experiment": exp, "arms": arms,
               "verdict": _experiment_verdict(arms) if exp else None}
    if args.json:
        _out(json.dumps(payload, indent=2))
        return 0
    if exp is None:
        _out("no experiment running")
        return 0
    _out(f"experiment {exp['name']}: mode={exp['mode']} "
         f"weight={exp['weight']} hashField={exp['hashField']}")
    _out(f"  assigned: {exp['assigned']}")
    for arm in ("control", "candidate"):
        if arm in arms:
            a = arms[arm]
            _out(f"  {arm:<10} requests={a['requests']} "
                 f"errorRate={a['errorRate']:.2%} mean={a['meanMs']}ms "
                 f"p95={a['p95Ms']}ms")
    _out(f"  verdict: {payload['verdict']}")
    return 0


# ---------------------------------------------------------------------------
# jobs: continuous-training control plane (docs/jobs.md)
# ---------------------------------------------------------------------------

def _job_orchestrator(storage: Storage):
    from incubator_predictionio_tpu.jobs import Orchestrator

    return Orchestrator(storage.get_meta_data_jobs())


def _job_params_from_args(args) -> dict:
    params: dict = {"engine_variant": args.engine_variant}
    if getattr(args, "batch", None):
        params["batch"] = args.batch
    if getattr(args, "server_url", None):
        params["server_url"] = args.server_url
    if getattr(args, "replica", None):
        params["replicas"] = list(args.replica)
    if getattr(args, "server_access_key", None):
        params["server_access_key"] = args.server_access_key
    if getattr(args, "mesh_axes", None):
        params["mesh_axes"] = json.loads(args.mesh_axes)
    if getattr(args, "evaluation_class", None):
        params["evaluation_class"] = args.evaluation_class
    if getattr(args, "no_gate", False):
        params["gate"] = "off"
    if getattr(args, "dist", 0):
        if args.kind != "train":
            raise SystemExit("jobs submit: --dist applies to --kind train")
        params["dist"] = int(args.dist)
        if getattr(args, "dist_state_dir", None):
            params["dist_state_dir"] = args.dist_state_dir
    if getattr(args, "params", None):
        params.update(json.loads(args.params))
    return params


def cmd_jobs_submit(args, storage: Storage) -> int:
    orch = _job_orchestrator(storage)
    job = orch.submit(
        args.kind, params=_job_params_from_args(args), trigger="manual",
        dedupe_key=(f"train:{os.path.abspath(args.engine_variant)}"
                    if args.kind == "train" and not args.no_dedupe else ""),
        max_attempts=args.max_attempts)
    _out(f"Submitted {job.kind} job {job.id} (status {job.status}, "
         f"attempt {job.attempt}/{job.max_attempts}).")
    _out("Run `pio-tpu jobs worker` somewhere to execute it; "
         f"`pio-tpu jobs watch {job.id}` follows it.")
    return 0


def _job_row(j, now: float) -> dict:
    lease = None
    if j.status == "RUNNING" and j.lease_expires_at is not None:
        lease = round(j.lease_expires_at.timestamp() - now, 1)
    summary = ""
    if j.status == "COMPLETED":
        summary = j.result.get("instanceId") or ""
        gate = j.result.get("gate") or {}
        if gate.get("verdict"):
            summary += f" gate={gate['verdict']}"
    elif j.failure:
        summary = j.failure.splitlines()[-1][:80]
    return {"id": j.id, "kind": j.kind, "status": j.status,
            "trigger": j.trigger, "attempt": f"{j.attempt}/{j.max_attempts}",
            "fence": j.fence, "leaseSecLeft": lease,
            "owner": j.lease_owner or "",
            "submittedAt": j.submitted_at.isoformat()
            if j.submitted_at else None,
            "summary": summary}


def cmd_jobs_list(args, storage: Storage) -> int:
    import time as _time

    orch = _job_orchestrator(storage)
    jobs = sorted(orch.jobs.get_all(),
                  key=lambda j: (j.submitted_at.timestamp()
                                 if j.submitted_at else 0.0, j.id))
    if not args.all:
        # active + the most recent terminal few — the operator's default view
        terminal = [j for j in jobs if not j.active][-10:]
        jobs = [j for j in jobs if j.active] + terminal
        jobs.sort(key=lambda j: (j.submitted_at.timestamp()
                                 if j.submitted_at else 0.0, j.id))
    rows = [_job_row(j, _time.time()) for j in jobs]
    if args.json:
        _out(json.dumps(rows, indent=2))
        return 0
    if not rows:
        _out("No jobs.")
        return 0
    _out(f"{'ID':<12} {'KIND':<12} {'STATUS':<10} {'TRIGGER':<10} "
         f"{'ATT':<5} {'LEASE':<8} SUMMARY")
    for r in rows:
        lease = ("-" if r["leaseSecLeft"] is None
                 else f"{r['leaseSecLeft']:+.0f}s")
        _out(f"{r['id'][:12]:<12} {r['kind']:<12} {r['status']:<10} "
             f"{r['trigger']:<10} {r['attempt']:<5} {lease:<8} "
             f"{r['summary']}")
    return 0


def cmd_jobs_watch(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.jobs import wait_for_job

    orch = _job_orchestrator(storage)
    try:
        j = wait_for_job(orch, args.id, timeout=args.timeout,
                         poll=args.poll)
    except KeyError:
        _err(f"No job {args.id}.")
        return 1
    except TimeoutError as e:
        _err(str(e))
        return 1
    _out(json.dumps(_job_row(j, __import__("time").time()), indent=2))
    if j.status == "COMPLETED":
        return 0
    if j.failure:
        _err(j.failure.splitlines()[-1])
    return 1


def cmd_jobs_cancel(args, storage: Storage) -> int:
    j = _job_orchestrator(storage).cancel(args.id)
    if j is None:
        _err(f"Job {args.id} is not active (or does not exist).")
        return 1
    _out(f"Cancelled job {j.id} (a running worker is fenced off at its "
         "next heartbeat; no deploy can land).")
    return 0


def cmd_jobs_retry(args, storage: Storage) -> int:
    j = _job_orchestrator(storage).retry(args.id)
    if j is None:
        _err(f"Job {args.id} is not terminal (or does not exist).")
        return 1
    _out(f"Requeued job {j.id} with a fresh attempt budget.")
    return 0


def cmd_jobs_prune(args, storage: Storage) -> int:
    n = _job_orchestrator(storage).prune(
        keep_terminal=args.keep,
        max_age_sec=args.older_than)
    _out(f"Pruned {n} terminal job(s).")
    return 0


def cmd_jobs_worker(args, storage: Storage) -> int:
    from incubator_predictionio_tpu.jobs import JobWorker, WorkerConfig

    cfg = WorkerConfig.from_env()
    if args.lease is not None:
        cfg = dataclasses_replace(cfg, lease_sec=args.lease)
    if args.poll is not None:
        cfg = dataclasses_replace(cfg, poll_sec=args.poll)
    worker = JobWorker(_job_orchestrator(storage), storage, cfg)
    _out(f"jobs worker {worker.config.worker_id} polling "
         f"(lease {worker.config.lease_sec:.0f}s).")
    obs_handle = None
    if args.obs_port:
        # the worker has no HTTP surface of its own; this thread serves
        # the shared /metrics + /traces.json so pio_jobs_* is scrapeable
        from incubator_predictionio_tpu.obs.http import start_obs_server

        obs_handle = start_obs_server("jobs_worker", args.obs_port,
                                      ip=args.obs_ip)
    try:
        if args.once:
            out = worker.run_once()
            if out is None:
                _out("Queue idle.")
                return 0
            _out(json.dumps(out, default=str))
            return 0 if out.get("status") in ("COMPLETED",) else 1
        worker.run_forever(max_jobs=args.max_jobs)
        return 0
    finally:
        if obs_handle is not None:
            obs_handle.close()


def cmd_jobs_triggers(args, storage: Storage) -> int:  # noqa: C901
    from incubator_predictionio_tpu.jobs import TriggerConfig, TriggerLoop

    overrides: dict = {
        "engine_variant": args.engine_variant,
        "server_url": args.server_url,
        "replicas": tuple(args.replica or ()),
        "server_access_key": args.server_access_key,
        "poll_sec": args.poll,
    }
    if args.interval is not None:
        overrides["interval_sec"] = args.interval
    if args.drift_events is not None:
        overrides["drift_events"] = args.drift_events
    if args.state_dir:
        overrides["stream_state_dir"] = args.state_dir
    if args.app:
        overrides["app_name"] = args.app
    loop = TriggerLoop(_job_orchestrator(storage), storage,
                       TriggerConfig.from_env(**overrides))
    if args.once:
        jobs = loop.run_once()
        _out(json.dumps([{"id": j.id, "trigger": j.trigger,
                          "status": j.status} for j in jobs]))
        return 0
    _out("jobs trigger loop running "
         f"(interval={loop.config.interval_sec or 'off'} "
         f"drift={loop.config.drift_events or 'off'} "
         f"quarantine={'on' if loop.config.stream_state_dir else 'off'}).")
    loop.run_forever()
    return 0


# ---------------------------------------------------------------------------
# store: replicated-storage admin (docs/replication.md)
# ---------------------------------------------------------------------------

def _store_rpc(url: str, verb: str, payload: dict, key=None, timeout=10.0):
    from incubator_predictionio_tpu.replication.manager import default_rpc

    return default_rpc(url, verb, payload, key=key, timeout=timeout)


def cmd_store_status(args, storage) -> int:
    """Per-replica replication state from each storage server's /health:
    role, epoch, fenced-write tally, per-peer lag. Exits non-zero when
    any replica is unreachable, fenced, or beyond the lag bound."""
    from incubator_predictionio_tpu.fleet.health import (
        probe_health_urls,
        replication_flags,
    )

    probed = probe_health_urls(args.urls, args.timeout,
                               fetch=lambda u, t: _fetch_health(u, t))
    red = False
    rows = []
    for url in args.urls:
        h, err = probed[url]
        repl = replication_flags(h)
        if h is None:
            rows.append({"url": url, "error": err})
            red = True
            continue
        row = {"url": url, "status": h.get("status"),
               "replication": h.get("replication")}
        rows.append(row)
        if repl is None:
            red = True  # a storage replica without a replication section
        else:
            red = red or repl["red"]
    if args.json:
        _out(json.dumps(rows, indent=2))
        return 1 if red else 0
    w = max(len(r["url"]) for r in rows)
    for r in rows:
        if "error" in r:
            _out(f"!! {r['url']:<{w}}  unreachable  [{r['error']}]")
            continue
        repl = r.get("replication")
        if repl is None:
            # reachable but replication is OFF — red (the operator asked
            # about a replica set; an unreplicated member is the finding)
            _out(f"!! {r['url']:<{w}}  replication not configured "
                 "(--repl-peer / PIO_REPL_PEERS)")
            continue
        line = (f"{'!!' if (repl.get('fenced') or repl.get('lagExceeded')) else 'ok'} "
                f"{r['url']:<{w}}  {repl.get('role', '?')}@"
                f"{repl.get('epoch', '?')}")
        if repl.get("fenced"):
            line += f"  FENCED (writes rejected: {repl.get('fencedWrites', 0)})"
        if repl.get("role") == "primary":
            for peer, st in (repl.get("peers") or {}).items():
                line += (f"\n     -> {peer}: lag {st.get('lagBytes', '?')}B"
                         f"{'' if st.get('reachable') else ' UNREACHABLE'}"
                         f"{' DIVERGED' if st.get('diverged') else ''}")
        elif repl.get("contactAgeSeconds") is not None:
            line += f"  last primary contact {repl['contactAgeSeconds']}s ago"
        _out(line)
    return 1 if red else 0


def cmd_store_promote(args, storage) -> int:
    """Promote a follower storage server to primary (the failover step):
    bumps its persisted epoch, re-opens its logs writable, and (via
    --peer) reconfigures its replica set — on failover the dead primary
    is removed until `store scrub` repairs and rejoins it. The old
    primary, wherever it resurfaces, is epoch-fenced from then on."""
    payload: dict = {}
    if args.peer is not None:
        payload["peers"] = list(args.peer)
    try:
        status, body = _store_rpc(args.url, "promote", payload,
                                  key=args.server_access_key)
    except OSError as e:
        _err(f"promote failed: {args.url} unreachable: {e}")
        return 1
    if status != 200:
        _err(f"promote failed: {status} {body.get('message', body)}")
        return 1
    _out(f"{args.url} promoted: role={body['role']} epoch={body['epoch']}")
    return 0


def cmd_store_scrub(args, storage) -> int:
    """Anti-entropy: exchange per-segment CRC digests between the primary
    and each follower, repair divergence/bitrot by re-fetching the
    authoritative range, and verify the copies come back bit-identical
    (docs/replication.md scrub playbook). --check-only detects without
    repairing. Exits non-zero when any follower could not be verified."""
    from incubator_predictionio_tpu.replication.scrub import (
        ScrubError,
        scrub_follower,
    )

    rpc = lambda url, verb, payload: _store_rpc(  # noqa: E731
        url, verb, payload, key=args.server_access_key)
    ok = True
    out = {}
    for follower in args.followers:
        try:
            report = scrub_follower(args.primary, follower, rpc,
                                    segment_bytes=args.segment_bytes,
                                    repair=not args.check_only)
        except ScrubError as e:
            _err(f"scrub {follower}: {e}")
            ok = False
            continue
        out[follower] = report
        ok = ok and report["clean"]
        if not args.json:
            state = ("clean" if report["divergentSegments"] == 0 else
                     ("REPAIRED" if report["clean"] else "DIVERGENT"))
            _out(f"{follower}: {state} — "
                 f"{report['divergentSegments']} divergent segment(s), "
                 f"{report['repairedBytes']} byte(s) repaired")
            for name, row in sorted(report["logs"].items()):
                if row["divergent"] or not row["verified"]:
                    _out(f"  {name}: divergent at offsets {row['divergent']}"
                         f" (primary {row['sizePrimary']}B / follower "
                         f"{row['sizeFollower']}B) verified="
                         f"{row['verified']}")
    if args.json:
        _out(json.dumps(out, indent=2))
    return 0 if ok else 1


def _backup_source(args, storage):
    from incubator_predictionio_tpu.backup import source_from_storage

    src = source_from_storage(
        storage,
        eventlog_dir=args.eventlog_dir,
        wal_dir=args.wal_dir,
        stream_state_dir=args.stream_state_dir,
        device_models_dir=args.device_models_dir,
        checkpoint_dirs=tuple(args.checkpoint_dir or ()),
    )
    if args.no_meta:
        src = dataclasses_replace(src, storage=None)
    return src


def cmd_backup_create(args, storage: Storage) -> int:
    """Take one consistent point-in-time backup (docs/dr.md): eventlog
    segments up to a cut, the spill WAL, streaming state, model sidecars,
    and a metadata dump via the DAO dump/load contract. Incremental by
    default (append-only segments ⇒ only new extents copied); the entry
    self-verifies before this verb reports success."""
    from incubator_predictionio_tpu.backup import BackupError, create_backup

    src = _backup_source(args, storage)
    if not src.components() and src.storage is None:
        _err("backup create: nothing to back up (no --eventlog-dir / "
             "--wal-dir / --stream-state-dir / ... resolved, and --no-meta "
             "set)")
        return 2
    try:
        report = create_backup(args.backup_dir, src,
                               incremental=not args.full,
                               include_meta=not args.no_meta)
    except BackupError as e:
        _err(f"backup create failed: {e}")
        return 1
    if args.json:
        _out(json.dumps(report, indent=2))
    else:
        v = report.get("verify") or {}
        _out(f"backup {report['backupId']} (seq {report['seq']}"
             + (f", incremental on {report['parent']}" if report["parent"]
                else ", full") + ")")
        _out(f"  files: {report['files']}  stored: {report['bytesStored']}B"
             f"  logical: {report['bytesLogical']}B")
        for path, cut in sorted(report["cuts"].items()):
            _out(f"  cut {path} @ {cut}")
        _out(f"  verify: {'clean' if v.get('clean') else 'FAILED'}")
        for err in (v.get("errors") or [])[:8]:
            _err(f"    {err}")
    return 0 if (report.get("verify") or {}).get("clean") else 1


def cmd_backup_verify(args, storage) -> int:
    """Re-verify a backup entry end to end: chain integrity, per-window
    CRC digests of every logical file, and cut/record-boundary
    consistency. The verdict lands in the entry's verify.json, which the
    `pio-tpu health --backup-dir` row reads."""
    from incubator_predictionio_tpu.backup import BackupError, verify_backup

    try:
        report = verify_backup(args.backup_dir, args.id)
    except BackupError as e:
        _err(f"backup verify failed: {e}")
        return 1
    if args.json:
        _out(json.dumps(report, indent=2))
    else:
        _out(f"backup {report['backupId']}: "
             f"{'clean' if report['clean'] else 'FAILED'} "
             f"({report['filesChecked']} file(s), "
             f"{report['bytesChecked']}B in {report['seconds']}s)")
        for err in report["errors"][:16]:
            _err(f"  {err}")
    return 0 if report["clean"] else 1


def cmd_backup_restore(args, storage: Storage) -> int:
    """Rehydrate a fresh data dir from a backup entry, verified while it
    writes: files land bit-identical to the cut, the metadata dump loads
    into the CONFIGURED backend, the streaming cursor is clamped to the
    cut, the replication epoch is bumped so stale peers fence, and
    --replay-wal finishes the RPO story by replaying the acked-but-
    unstored WAL tail into the restored store."""
    from incubator_predictionio_tpu.backup import (
        BackupError,
        RestoreTargets,
        restore_backup,
    )

    targets = RestoreTargets(
        eventlog_dir=args.eventlog_dir,
        wal_dir=args.wal_dir,
        stream_state_dir=args.stream_state_dir,
        device_models_dir=args.device_models_dir,
        checkpoint_dirs=tuple(args.checkpoint_dir or ()),
    )
    try:
        report = restore_backup(
            args.backup_dir, targets, backup_id=args.id,
            storage=None if args.no_meta else storage,
            epoch_bump=not args.no_epoch_bump,
            replay_wal=args.replay_wal, force=args.force)
    except BackupError as e:
        _err(f"backup restore failed: {e}")
        return 1
    if args.json:
        _out(json.dumps(report, indent=2))
    else:
        _out(f"restored backup {report['backupId']}: "
             f"{report['filesRestored']} file(s), "
             f"{report['bytesRestored']}B in {report['seconds']}s")
        if report.get("meta"):
            loaded = ", ".join(f"{k}={v}" for k, v in
                               sorted(report["meta"]["loaded"].items()))
            _out(f"  metadata: {loaded}; models: "
                 f"{report['meta']['models']}")
        if report.get("cursorClamped"):
            _out("  streaming cursor clamped to the eventlog cut")
        if report.get("epoch"):
            ep = report["epoch"]
            _out(f"  replication epoch {ep['epochBefore']} -> "
                 f"{ep['epochAfter']}"
                 + ("" if ep["bumped"] else " (bump disabled)"))
        if report.get("walReplayed") is not None:
            _out(f"  WAL tail replayed: {report['walReplayed']} event(s)")
        if report.get("skippedComponents"):
            _out("  skipped (no target dir given): "
                 + ", ".join(report["skippedComponents"]))
    return 0


def cmd_backup_list(args, storage) -> int:
    """List committed backup entries: seq, age, chain parent, stored vs
    logical bytes, and the last verification verdict."""
    from incubator_predictionio_tpu.backup import BackupSet, entry_summary

    bset = BackupSet(args.backup_dir)
    try:
        rows = [entry_summary(bset, e) for e in bset.entries()]
    except Exception as e:  # noqa: BLE001 - a damaged entry is the finding
        _err(f"backup list failed: {e}")
        return 1
    if args.json:
        _out(json.dumps(rows, indent=2))
        return 0
    if not rows:
        _out(f"no backups in {args.backup_dir}")
        return 0
    for r in rows:
        mark = "ok" if r["verified"] else "!!"
        _out(f"{mark} {r['backupId']}  seq {r['seq']:>4}  "
             f"{r['createdAt']}  "
             f"{'incr on ' + r['parent'] if r['parent'] else 'full'}  "
             f"{r['files']} file(s) {r['storedBytes']}B stored "
             f"({r['logicalBytes']}B logical)  "
             f"{'verified' if r['verified'] else 'NOT VERIFIED'}")
    return 0


def cmd_backup_prune(args, storage) -> int:
    """Delete old entries, keeping the newest --keep entries plus every
    chain ancestor they reference (an incremental child never loses the
    full copy under it); crashed .tmp- stubs are cleared too."""
    from incubator_predictionio_tpu.backup import BackupError
    from incubator_predictionio_tpu.backup.manifest import prune

    try:
        removed = prune(args.backup_dir, args.keep)
    except BackupError as e:
        _err(f"backup prune failed: {e}")
        return 1
    _out(f"pruned {len(removed)} entr(ies): "
         + (", ".join(removed) if removed else "nothing to remove"))
    return 0


def cmd_lint(args, storage) -> int:
    """Run the project invariant linter (docs/analysis.md): R1
    async-blocking, R2 clock-discipline, R3 durability-ordering, R4
    knob-registry, R5 lock/await-hygiene, plus the S1/S2/B1 audits of
    the suppression surface itself. Exit 0 = clean, 1 = findings,
    2 = usage error (unknown rule id)."""
    from incubator_predictionio_tpu.analysis.engine import (
        render_json,
        render_text,
        run_lint,
    )

    try:
        result = run_lint(
            root=args.root,
            rules=args.rule or None,
            baseline_path=args.baseline,
            update_baseline=args.update_baseline,
        )
    except ValueError as e:
        _err(f"lint: {e}")
        return 2
    if args.update_baseline:
        # stderr under --json: stdout must stay one valid JSON document
        note = (f"baseline updated: {len(result.baselined)} entr(ies) "
                f"({args.baseline or 'conf/lint_baseline.txt'})")
        (_err if args.json else _out)(note)
    _out(render_json(result) if args.json else render_text(result))
    return 0 if result.clean else 1


def _backup_row(backup_dir: str, max_age: Optional[float],
                now: Optional[float] = None) -> dict:
    """The backup-staleness probe for ``pio-tpu health --backup-dir``
    (same alarm pattern as the quarantine row): red when there is no
    verified backup, the newest entry's last verify FAILED, or the newest
    verified entry is older than PIO_BACKUP_MAX_AGE (default 24h). An
    unverified-but-fresh backup is red too — an unverified backup is a
    hope, not a recovery plan (docs/dr.md)."""
    import time

    from incubator_predictionio_tpu.backup import BackupSet, read_verify
    from incubator_predictionio_tpu.backup.manifest import parse_iso

    url = f"backup:{backup_dir}"
    if max_age is None:
        max_age = float(os.environ.get("PIO_BACKUP_MAX_AGE", "86400"))
    try:
        entries = BackupSet(backup_dir).entries()
    except Exception as e:  # noqa: BLE001 - unreadable dir is red
        return {"url": url, "status": "unreadable", "red": True,
                "detail": str(e)}
    if not entries:
        return {"url": url, "status": "missing", "red": True,
                "detail": "no backups — run `pio-tpu backup create`"}
    tip = entries[-1]
    v = read_verify(tip.path)
    if v is not None and not v.get("clean"):
        return {"url": url, "status": "verify-failed", "red": True,
                "detail": f"backup {tip.backup_id} failed verification at "
                          f"{v.get('at')} — the newest backup is not "
                          "restorable"}
    newest_verified = None
    for e in reversed(entries):
        ve = read_verify(e.path)
        if ve is not None and ve.get("clean"):
            newest_verified = e
            break
    if newest_verified is None:
        return {"url": url, "status": "unverified", "red": True,
                "detail": f"{len(entries)} backup(s), none verified — run "
                          "`pio-tpu backup verify`"}
    created = parse_iso(newest_verified.manifest.get("createdAt"))
    now_s = now if now is not None else time.time()
    age = (now_s - created.timestamp()) if created is not None else None
    if age is None or age > max_age:
        return {"url": url, "status": "stale", "red": True,
                "detail": f"newest verified backup "
                          f"{newest_verified.backup_id} is "
                          + (f"{age:.0f}s old > PIO_BACKUP_MAX_AGE "
                             f"{max_age:.0f}s" if age is not None
                             else "undated")
                          + " — backups are not keeping up"}
    return {"url": url, "status": "ok", "red": False,
            "detail": f"backup {newest_verified.backup_id} verified, "
                      f"{age:.0f}s old (max {max_age:.0f}s)"}


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="pio-tpu",
        description="TPU-native PredictionIO-capability ML server framework",
    )
    sub = parser.add_subparsers(dest="command")

    sub.add_parser("version")
    sub.add_parser("status")

    # app
    app = sub.add_parser("app").add_subparsers(dest="app_command")
    p = app.add_parser("new")
    p.add_argument("name")
    p.add_argument("--id", type=int, default=0)
    p.add_argument("--description")
    p.add_argument("--access-key", default="")
    app.add_parser("list")
    p = app.add_parser("show")
    p.add_argument("name")
    p = app.add_parser("delete")
    p.add_argument("name")
    p.add_argument("-f", "--force", action="store_true")
    p = app.add_parser("data-delete")
    p.add_argument("name")
    p.add_argument("--channel")
    p.add_argument("-f", "--force", action="store_true")
    p = app.add_parser("channel-new")
    p.add_argument("app_name")
    p.add_argument("channel")
    p = app.add_parser("channel-delete")
    p.add_argument("app_name")
    p.add_argument("channel")
    p.add_argument("-f", "--force", action="store_true")

    # accesskey
    ak = sub.add_parser("accesskey").add_subparsers(dest="accesskey_command")
    p = ak.add_parser("new")
    p.add_argument("app_name")
    p.add_argument("--access-key", default="")
    p.add_argument("--event", action="append")
    p = ak.add_parser("list")
    p.add_argument("app_name", nargs="?")
    p = ak.add_parser("delete")
    p.add_argument("key")

    # template (commands/Template.scala; in-package registry here)
    tp = sub.add_parser("template").add_subparsers(dest="template_command")
    tp.add_parser("list")
    p = tp.add_parser("get")
    p.add_argument("name")
    p.add_argument("directory", nargs="?", default=".")
    p.add_argument("--app-name")
    p.add_argument("--force", action="store_true")

    # train
    p = sub.add_parser("train")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--batch", default="")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--skip-sanity-check", action="store_true")
    p.add_argument("--stop-after-read", action="store_true")
    p.add_argument("--stop-after-prepare", action="store_true")
    p.add_argument("--mesh-axes", help='JSON, e.g. \'{"data": 4, "model": 2}\'')
    p.add_argument("--distributed", action="store_true",
                   help="join a jax.distributed job (see the launch verb / "
                        "PIO_DIST_* env)")
    p.add_argument("--profile-dir",
                   help="capture a jax.profiler trace of the run into this dir")

    # launch (Runner.runOnSpark counterpart: N coordinated local processes)
    p = sub.add_parser("launch")
    p.add_argument("-n", "--num-processes", type=int, required=True)
    p.add_argument("--coordinator-port", type=int)
    p.add_argument("--cpu-devices-per-process", type=int,
                   help="force a CPU mesh with this many virtual devices per "
                        "process (testing without accelerators)")
    p.add_argument("--timeout", type=float, default=None,
                   help="kill the whole job after this many seconds (a wedged "
                        "peer otherwise hangs the launcher indefinitely)")
    p.add_argument("verb_args", nargs=argparse.REMAINDER,
                   help="the pio-tpu verb (and flags) each process runs")

    # eval
    p = sub.add_parser("eval")
    p.add_argument("evaluation_class")
    p.add_argument("engine_params_generator_class", nargs="?")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--batch", default="")
    p.add_argument("--mesh-axes", help='JSON, e.g. \'{"data": 4}\'')
    p.add_argument("--distributed", action="store_true",
                   help="join a jax.distributed job (see the launch verb)")
    p.add_argument("--no-fast-eval", action="store_true",
                   help="disable prefix memoization across variants "
                        "(FastEvalEngine is the default)")

    # deploy / undeploy
    p = sub.add_parser("deploy")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--feedback", action="store_true")
    p.add_argument("--event-server-ip", default="127.0.0.1")
    p.add_argument("--event-server-port", type=int, default=7070)
    p.add_argument("--accesskey", dest="access_key")
    p.add_argument("--server-access-key")
    p.add_argument("--ssl-cert")
    p.add_argument("--ssl-key")
    p.add_argument("--log-url",
                   help="ship serving errors to this URL "
                        "(reference CreateServer.scala:423-436)")
    p.add_argument("--log-prefix", default="",
                   help="prefix for shipped log messages")
    p.add_argument("--query-timeout", type=float, dest="query_timeout_sec",
                   help="total per-query budget in seconds; blown budgets "
                        "answer degraded-200 from the last-good cache "
                        "instead of 500 (docs/resilience.md)")
    p.add_argument("--algo-deadline", type=float, dest="algo_deadline_sec",
                   help="per-algorithm deadline in seconds; slower answers "
                        "count as circuit-breaker failures")
    p.add_argument("--algo-breaker-threshold", type=int, default=3,
                   help="consecutive failures before an algorithm's "
                        "breaker opens (default 3)")
    p.add_argument("--algo-breaker-reset", type=float, default=10.0,
                   dest="algo_breaker_reset_sec",
                   help="seconds an open algorithm breaker waits before a "
                        "half-open probe (default 10)")
    p.add_argument("--smoke-query", action="append",
                   help="JSON query payload the /reload health gate runs "
                        "against a NEW instance before it may serve "
                        "(repeatable; any failure keeps the live instance "
                        "— docs/resilience.md)")
    p.add_argument("--reload-probation", type=float, default=30.0,
                   dest="reload_probation_sec",
                   help="seconds after a /reload swap during which a "
                        "serving-breaker trip auto-rolls back to the "
                        "previous instance (default 30; 0 disables)")
    p.add_argument("--admission-max-queue", type=int,
                   help="bounded admission queue depth; waiting queries "
                        "beyond it answer 429 + Retry-After "
                        "(PIO_ADMISSION_MAX_QUEUE env, default 256 — "
                        "docs/resilience.md)")
    p.add_argument("--admission-target-ms", type=float,
                   help="explicit target (ms of a batch's dispatch, the "
                        "time it holds a slot) for the adaptive "
                        "concurrency limiter; unset = gradient mode "
                        "(PIO_ADMISSION_TARGET_MS env)")
    p.add_argument("--no-adaptive-admission", action="store_true",
                   help="disable the AIMD concurrency limiter "
                        "(PIO_ADMISSION_ADAPTIVE=0 env)")
    p.add_argument("--shard-id", type=int, default=None,
                   help="this process owns item-catalog shard N of "
                        "--shard-count; announced on /health and served "
                        "via /shard/queries.json (PIO_FLEET_SHARD_ID env "
                        "— docs/sharding.md \"Multi-host shard owners\")")
    p.add_argument("--shard-count", type=int, default=None,
                   help="total shard-owner count the catalog's rows are "
                        "split across (PIO_FLEET_SHARD_COUNT env)")
    p.add_argument("--shard-state-dir", default=None,
                   help="directory persisting this owner's fencing epoch "
                        "across restarts; a corrupt token refuses startup "
                        "rather than guess (PIO_FLEET_SHARD_STATE_DIR env)")
    p.add_argument("--tenants", default=None,
                   help="multi-tenant mode: tenant table as a JSON file "
                        "path or inline JSON array — this process hosts "
                        "every listed engine behind /engines/{id}/... "
                        "(PIO_TENANTS env — docs/tenancy.md)")
    p = sub.add_parser("undeploy")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--server-access-key")

    # batchpredict
    p = sub.add_parser("batchpredict")
    p.add_argument("--input", default="batchpredict-input.json")
    p.add_argument("--output", default="batchpredict-output.json")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--query-partitions", type=int)
    p.add_argument("--distributed", action="store_true",
                   help="score a per-process slice under `launch -n N`; "
                        "writes <output>.part-<pid> files (the reference's "
                        "saveAsTextFile layout)")

    # eventserver
    p = sub.add_parser("eventserver")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7070)
    p.add_argument("--stats", action="store_true")
    p.add_argument("--ssl-cert")
    p.add_argument("--ssl-key")
    p.add_argument("--wal-dir",
                   help="write-ahead log directory for the spill queue: "
                        "spilled events are fsynced before their 201 and "
                        "replayed after a crash (PIO_EVENT_WAL_DIR env; "
                        "docs/resilience.md)")
    p.add_argument("--client-rate", type=float,
                   help="per-access-key ingest rate limit, events/sec; a "
                        "client over it answers 429 alone "
                        "(PIO_EVENTSERVER_CLIENT_RATE env; 0 disables)")
    p.add_argument("--client-burst", type=float,
                   help="per-access-key token-bucket burst capacity "
                        "(PIO_EVENTSERVER_CLIENT_BURST env; default 2× "
                        "the rate)")

    # storageserver — serve this process's storage config to remote clients
    p = sub.add_parser(
        "storageserver",
        help="serve the local storage backends over HTTP (the shared "
             "networked store of a multi-host job; clients use TYPE=remote)")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7072)
    p.add_argument("--ssl-cert")
    p.add_argument("--ssl-key")
    p.add_argument("--server-access-key",
                   help="shared secret required from every client")
    p.add_argument("--client-inflight", type=int,
                   help="concurrent in-flight RPCs allowed per client "
                        "address before 429 (PIO_STORAGE_CLIENT_INFLIGHT "
                        "env, default 64; 0 disables)")
    p.add_argument("--repl-role", choices=("primary", "follower"),
                   help="eventlog replication role (PIO_REPL_ROLE env; "
                        "docs/replication.md)")
    p.add_argument("--repl-peer", action="append",
                   help="base URL of another replica (repeatable; "
                        "PIO_REPL_PEERS env, comma-separated)")
    p.add_argument("--repl-sync", choices=("async", "quorum"),
                   help="replication ack mode: async (bounded lag, "
                        "default) or quorum (a write acks only once a "
                        "majority of the replica set holds it; "
                        "PIO_REPL_SYNC env)")

    # jobs — continuous-training control plane (docs/jobs.md)
    jobs = sub.add_parser(
        "jobs",
        help="continuous-training control plane: submit/list/watch/cancel/"
             "retry durable jobs, run the lease-fenced worker, run the "
             "auto-retrain trigger loop (docs/jobs.md)")
    jb = jobs.add_subparsers(dest="jobs_command")
    p = jb.add_parser("submit")
    p.add_argument("--kind", default="train",
                   choices=("train", "eval", "batchpredict", "rollout"))
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--batch", default="")
    p.add_argument("--server-url",
                   help="query server whose /reload promotes a passing "
                        "candidate (single-server deploy)")
    p.add_argument("--replica", action="append",
                   help="fleet replica base URL (repeatable; 2+ drive the "
                        "halt-and-rollback rollout orchestrator)")
    p.add_argument("--server-access-key")
    p.add_argument("--mesh-axes", help='JSON, e.g. \'{"data": 4}\'')
    p.add_argument("--evaluation-class",
                   help="for --kind eval: the Evaluation to run")
    p.add_argument("--no-gate", action="store_true",
                   help="skip the eval gate for this job "
                        "(PIO_JOBS_GATE=0 equivalent)")
    p.add_argument("--no-dedupe", action="store_true",
                   help="queue even if an identical train job is active")
    p.add_argument("--max-attempts", type=int, default=3)
    p.add_argument("--dist", type=int, default=0, metavar="N",
                   help="for --kind train: run the train as N supervised "
                        "member processes with mesh-generation fencing and "
                        "coordinated slice checkpoints (docs/sharding.md "
                        "\"Multi-host training\")")
    p.add_argument("--dist-state-dir",
                   help="coordination dir for --dist (default: "
                        "PIO_DIST_STATE_DIR, else a per-job dir under "
                        "PIO_FS_BASEDIR)")
    p.add_argument("--params", help="extra params JSON merged into the job")
    p = jb.add_parser("list")
    p.add_argument("--all", action="store_true",
                   help="include every terminal job (default: active + "
                        "the 10 most recent terminal)")
    p.add_argument("--json", action="store_true")
    p = jb.add_parser("watch")
    p.add_argument("id")
    p.add_argument("--timeout", type=float, default=3600.0)
    p.add_argument("--poll", type=float, default=0.5)
    p = jb.add_parser("cancel")
    p.add_argument("id")
    p = jb.add_parser("retry")
    p.add_argument("id")
    p = jb.add_parser("prune")
    p.add_argument("--keep", type=int, default=200,
                   help="terminal jobs to keep (newest first; active jobs "
                        "are never pruned)")
    p.add_argument("--older-than", type=float,
                   help="also drop terminal jobs older than this many "
                        "seconds")
    p = jb.add_parser("worker")
    p.add_argument("--once", action="store_true",
                   help="claim and execute at most one job, then exit")
    p.add_argument("--max-jobs", type=int,
                   help="exit after executing this many jobs")
    p.add_argument("--lease", type=float,
                   help="lease seconds (PIO_JOBS_LEASE_SEC env, default 60);"
                        " a worker dead this long has its job reclaimed")
    p.add_argument("--poll", type=float,
                   help="idle poll seconds (PIO_JOBS_POLL_SEC env)")
    p.add_argument("--obs-port", type=int, default=0,
                   help="serve GET /metrics + /traces.json on this port so "
                        "pio_jobs_* gauges are scrapeable (0 = disabled, "
                        "the default; docs/observability.md)")
    p.add_argument("--obs-ip", default="127.0.0.1",
                   help="bind address for --obs-port (default loopback)")
    p = jb.add_parser("triggers")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--interval", type=float,
                   help="seconds between interval-trigger retrains "
                        "(PIO_JOBS_INTERVAL env; 0 disables)")
    p.add_argument("--drift-events", type=int,
                   help="retrain once this many events land after the last "
                        "trained instance (PIO_JOBS_DRIFT_EVENTS env; "
                        "0 disables)")
    p.add_argument("--state-dir",
                   help="streaming state dir to watch for the quarantine "
                        "marker (a trip auto-submits a full retrain)")
    p.add_argument("--app", help="app whose events feed the drift counter "
                                 "(default: the variant's datasource app)")
    p.add_argument("--server-url",
                   help="forwarded onto submitted train jobs as the deploy "
                        "target")
    p.add_argument("--replica", action="append")
    p.add_argument("--server-access-key")
    p.add_argument("--poll", type=float, default=5.0,
                   help="seconds between trigger evaluations")
    p.add_argument("--once", action="store_true",
                   help="evaluate every trigger once and exit")

    # store — replicated-storage admin (docs/replication.md)
    store = sub.add_parser(
        "store",
        help="replicated storage admin: status (role/epoch/lag per "
             "replica), promote (epoch-fenced failover), scrub "
             "(anti-entropy divergence detection + repair)")
    st = store.add_subparsers(dest="store_command")
    p = st.add_parser("status")
    p.add_argument("urls", nargs="+",
                   help="storage-server base URLs (the whole replica set)")
    p.add_argument("--timeout", type=float, default=5.0)
    p.add_argument("--json", action="store_true")
    p = st.add_parser("promote")
    p.add_argument("url", help="the follower to promote")
    p.add_argument("--peer", action="append",
                   help="replica set AFTER the promotion (repeatable; "
                        "omit to keep the follower's configured peers — "
                        "typically you exclude the dead primary here)")
    p.add_argument("--server-access-key")
    p = st.add_parser("scrub")
    p.add_argument("primary", help="authoritative replica base URL")
    p.add_argument("followers", nargs="+",
                   help="follower base URLs to verify/repair against it")
    p.add_argument("--segment-bytes", type=int, default=1 << 20,
                   help="digest window size (default 1 MiB)")
    p.add_argument("--check-only", action="store_true",
                   help="detect divergence without repairing")
    p.add_argument("--server-access-key")
    p.add_argument("--json", action="store_true")

    # backup — disaster recovery (docs/dr.md)
    backup = sub.add_parser(
        "backup",
        help="disaster recovery: consistent point-in-time backup and "
             "verified restore of the whole state surface — eventlog, "
             "metadata (dump/load), models + sidecars, spill WAL, "
             "streaming state, replication fencing state (docs/dr.md)")
    bk = backup.add_subparsers(dest="backup_command")

    def _backup_component_args(p, restoring: bool) -> None:
        verb = "restore into" if restoring else "back up"
        p.add_argument("--eventlog-dir",
                       help=f"eventlog directory to {verb} (.piolog logs "
                            "+ repl-state.json; default on create: "
                            "resolved from the configured eventlog "
                            "EVENTDATA backend)")
        p.add_argument("--wal-dir",
                       help=f"event-server spill WAL directory to {verb}")
        p.add_argument("--stream-state-dir",
                       help=f"streaming state directory to {verb} "
                            "(cursor, trainer state, delta archive, "
                            "quarantine marker)")
        p.add_argument("--device-models-dir",
                       help=f"device-model sidecar tree to {verb} "
                            "(default on create: $PIO_FS_BASEDIR/"
                            "device_models when present)")
        p.add_argument("--checkpoint-dir", action="append",
                       help=f"TrainCheckpointer directory to {verb} "
                            "(repeatable; mid-epoch training state)")
        p.add_argument("--no-meta", action="store_true",
                       help="skip the metadata dump/load and model blobs")
        p.add_argument("--json", action="store_true")

    p = bk.add_parser("create")
    p.add_argument("--backup-dir", required=True,
                   help="backup set directory (entries chain inside it)")
    _backup_component_args(p, restoring=False)
    p.add_argument("--full", action="store_true",
                   help="force a full copy instead of an incremental "
                        "extent on the previous entry")
    p = bk.add_parser("verify")
    p.add_argument("--backup-dir", required=True)
    p.add_argument("--id", help="backup id (default: the newest entry)")
    p.add_argument("--json", action="store_true")
    p = bk.add_parser("restore")
    p.add_argument("--backup-dir", required=True)
    p.add_argument("--id", help="backup id (default: the newest entry)")
    _backup_component_args(p, restoring=True)
    p.add_argument("--replay-wal", action="store_true",
                   help="after restoring, replay the WAL tail into the "
                        "configured event store (idempotent; otherwise "
                        "the event server replays it at startup)")
    p.add_argument("--no-epoch-bump", action="store_true",
                   help="keep the backed-up replication epoch instead of "
                        "bumping it (bump fences stale peers — only skip "
                        "when restoring an isolated dev copy)")
    p.add_argument("--force", action="store_true",
                   help="restore into a non-empty target directory")
    p = bk.add_parser("list")
    p.add_argument("--backup-dir", required=True)
    p.add_argument("--json", action="store_true")
    p = bk.add_parser("prune")
    p.add_argument("--backup-dir", required=True)
    p.add_argument("--keep", type=int, default=7,
                   help="newest entries to keep (their chain ancestors "
                        "are kept too; default 7)")

    # dashboard / adminserver
    p = sub.add_parser("dashboard")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9000)
    p.add_argument("--ssl-cert")
    p.add_argument("--ssl-key")
    p.add_argument("--server-access-key")
    p = sub.add_parser("adminserver")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=7071)
    p.add_argument("--ssl-cert")
    p.add_argument("--ssl-key")
    p.add_argument("--server-access-key")

    # start-all / stop-all / redeploy
    p = sub.add_parser("start-all")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--event-server-port", type=int, default=7070)
    p.add_argument("--with-dashboard", action="store_true")
    p.add_argument("--dashboard-port", type=int, default=9000)
    p.add_argument("--with-adminserver", action="store_true")
    p.add_argument("--adminserver-port", type=int, default=7071)
    p.add_argument("--with-storageserver", action="store_true")
    p.add_argument("--storageserver-port", type=int, default=7072)
    p.add_argument("--storageserver-access-key",
                   help="shared secret required from remote storage clients")
    p.add_argument("--stats", action="store_true")
    p.add_argument("--wait-secs", type=float, default=60.0)
    sub.add_parser("stop-all")
    p = sub.add_parser("redeploy")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--batch", default="")
    p.add_argument("--retries", type=int, default=3)
    p.add_argument("--retry-wait", type=float, default=30.0)
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--server-access-key")
    p.add_argument("--no-reload", action="store_true")
    p.add_argument("--interval", type=float,
                   help="seconds between passes; omit to run once")
    p.add_argument("--mesh-axes", help='JSON, e.g. \'{"data": 4, "model": 2}\'')
    p.add_argument("--legacy", action="store_true",
                   help="run the old in-process train+reload loop instead "
                        "of submitting through the durable job "
                        "orchestrator (docs/jobs.md)")

    # shell (bin/pio-shell counterpart)
    p = sub.add_parser(
        "shell",
        help="interactive Python with the storage/event-store/mesh "
             "bootstrap preloaded (bin/pio-shell --with-pyspark slot)")
    p.add_argument("-c", "--code", dest="shell_code",
                   help="run this statement instead of going interactive")

    # metrics — scrape + pretty-print any server's /metrics
    p = sub.add_parser(
        "metrics",
        help="fetch and pretty-print one or more servers' Prometheus "
             "/metrics pages (multiple URLs merge into a per-server table "
             "with a summed/max aggregate column; docs/observability.md)")
    p.add_argument("urls", nargs="+",
                   help="server base URL(s), e.g. http://127.0.0.1:8000 "
                        "http://127.0.0.1:8001 — probed concurrently")
    p.add_argument("--fleet", action="store_true",
                   help="force the merged per-server table layout even for "
                        "a single URL (stable format for scripts)")
    p.add_argument("--raw", action="store_true",
                   help="print the raw exposition text instead")
    p.add_argument("--filter", help="only families whose name contains this")
    p.add_argument("--timeout", type=float, default=10.0,
                   help="per-server fetch timeout in seconds (default 10)")

    # profile — the continuous profiler's live document
    p = sub.add_parser(
        "profile",
        help="fetch and render a server's /profile.json: per-scope phase "
             "attribution, wall-stack sampler top-N (PIO_PROFILE_HZ), "
             "training MFU, device-memory watermarks "
             "(docs/observability.md \"Profiling\")")
    p.add_argument("url", help="server base URL, e.g. http://127.0.0.1:8000")
    p.add_argument("--timeout", type=float, default=10.0)
    p.add_argument("--json", action="store_true")

    # history — durable metrics history (docs/observability.md)
    p = sub.add_parser(
        "history",
        help="inspect the self-scraped metrics history: a PIO_HISTORY_DIR's "
             "durable segments or a live server's ring via /history.json; "
             "--series prints matching time series "
             "(docs/observability.md \"Metrics history & SLOs\")")
    p.add_argument("source",
                   help="history directory (PIO_HISTORY_DIR) or server base "
                        "URL")
    p.add_argument("--series", metavar="GLOB",
                   help="print series whose family name matches this glob "
                        "(e.g. 'pio_http_*'); counters also render "
                        "per-interval deltas")
    p.add_argument("--since", type=float,
                   help="only records with unix timestamp >= this")
    p.add_argument("--limit", type=int, default=20,
                   help="points shown per series, newest last (default 20)")
    p.add_argument("--timeout", type=float, default=10.0)
    p.add_argument("--json", action="store_true")

    # top — live-refreshing performance-plane summary
    p = sub.add_parser(
        "top",
        help="live one-line-per-server view from /metrics: qps, p99, "
             "RSS/FDs/loop-lag, MFU, jit compile seconds, SLO breaches; "
             "refreshes until interrupted (-n 1 prints once)")
    p.add_argument("urls", nargs="+",
                   help="server base URL(s), e.g. http://127.0.0.1:8000")
    p.add_argument("-i", "--interval", type=float, default=2.0,
                   help="seconds between refreshes (default 2)")
    p.add_argument("-n", "--iterations", type=int, default=0,
                   help="stop after N refreshes (default 0 = forever)")
    p.add_argument("--timeout", type=float, default=5.0)

    # slo — objectives validation + offline burn-rate verdicts
    p = sub.add_parser(
        "slo",
        help="validate an SLO objectives config (--check, the CI gate) "
             "and/or evaluate burn-rate verdicts over recorded history, "
             "exiting non-zero on invalid config or a breaching objective "
             "(docs/observability.md \"Metrics history & SLOs\")")
    p.add_argument("source", nargs="?",
                   help="history directory (PIO_HISTORY_DIR) or server base "
                        "URL to evaluate over (omit with --check to only "
                        "validate)")
    p.add_argument("--check", metavar="CONFIG",
                   help="validate this objectives JSON; exit 1 with "
                        "named-position errors on any defect")
    p.add_argument("--config", metavar="CONFIG",
                   help="objectives JSON for evaluation (default: --check "
                        "value, else $PIO_SLO_CONFIG)")
    p.add_argument("--since", type=float,
                   help="only records with unix timestamp >= this")
    p.add_argument("--timeout", type=float, default=10.0)
    p.add_argument("--json", action="store_true")

    # trace — cross-process trace assembly (docs/observability.md)
    tr = sub.add_parser(
        "trace",
        help="assemble cross-process traces from span spools and/or live "
             "servers: list recent traces, show one as a terminal "
             "waterfall, or rank the slowest (docs/observability.md)")
    trs = tr.add_subparsers(dest="trace_command")

    def _trace_source_args(p) -> None:
        p.add_argument("--spool", action="append", metavar="DIR",
                       help="span spool directory (PIO_TRACE_SPOOL_DIR of "
                            "any fleet process; repeatable; default: "
                            "$PIO_TRACE_SPOOL_DIR when set)")
        p.add_argument("--url", action="append", metavar="URL",
                       help="server base URL whose live /traces.json ring "
                            "to include (repeatable)")
        p.add_argument("--timeout", type=float, default=5.0)
        p.add_argument("--json", action="store_true")

    p = trs.add_parser("list")
    _trace_source_args(p)
    p.add_argument("--limit", type=int, default=20,
                   help="traces to list, newest first (default 20)")
    p = trs.add_parser("show")
    p.add_argument("trace_id",
                   help="trace id (or unique prefix) — e.g. from a "
                        "response's X-PIO-Trace header or a /metrics "
                        "exemplar")
    _trace_source_args(p)
    p = trs.add_parser("slowest")
    _trace_source_args(p)
    p.add_argument("-n", "--limit", type=int, default=10,
                   help="slowest traces to rank (default 10); the worst "
                        "one renders as a waterfall")

    # index — two-stage retrieval partition inspection
    p = sub.add_parser(
        "index",
        help="inspect the two-stage retrieval partition (IVF) of the "
             "latest trained model: partition count, size skew, "
             "quantization mode (docs/serving.md)")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--two-stage", action="store_true",
                   help="force PIO_RETRIEVAL_MODE=two_stage so an index is "
                        "built (and shown) even below the auto catalog-size "
                        "threshold")

    # shards — sharded embedding layout inspection (docs/sharding.md)
    p = sub.add_parser(
        "shards",
        help="inspect the sharded embedding layout of the latest trained "
             "model: per-shard row counts, HBM-bytes estimates, merge "
             "fan-in (docs/sharding.md)")
    p.add_argument("-v", "--engine-variant", default="engine.json")

    # health — one-probe fleet state across all three servers
    p = sub.add_parser(
        "health",
        help="aggregate GET /health from the given servers into one "
             "table (draining/breaker/spill/admission state); exits "
             "non-zero when any is unreachable, draining, or degraded")
    p.add_argument("urls", nargs="*",
                   help="server base URLs, e.g. http://127.0.0.1:7070 "
                        "http://127.0.0.1:8000 http://127.0.0.1:7072 "
                        "(may be empty when only --stream-state-dir / "
                        "--backup-dir rows are wanted)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="per-probe timeout in seconds (default 5)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable row output")
    p.add_argument("--stream-state-dir",
                   help="also probe this streaming state dir's quarantine "
                        "marker: red when older than --quarantine-max-age "
                        "(stuck control loop — docs/jobs.md)")
    p.add_argument("--quarantine-max-age", type=float,
                   help="seconds a quarantine marker may age before the "
                        "row turns red (default: PIO_JOBS_INTERVAL, "
                        "else 300)")
    p.add_argument("--backup-dir",
                   help="also probe this backup directory: red when the "
                        "newest verified backup is older than "
                        "--backup-max-age or the last verify failed "
                        "(docs/dr.md)")
    p.add_argument("--backup-max-age", type=float,
                   help="seconds the newest verified backup may age "
                        "before the row turns red (default: "
                        "PIO_BACKUP_MAX_AGE, else 86400)")
    p.add_argument("--dist-state-dir",
                   help="also probe this distributed-training coordination "
                        "dir: red when live members fall below quorum "
                        "(docs/sharding.md \"Multi-host training\")")

    # tenants — per-tenant fleet rollup (docs/tenancy.md)
    p = sub.add_parser(
        "tenants",
        help="per-tenant rollup across the given multi-tenant query "
             "servers: requests/qps/p99/quota/evictions/HBM bytes from "
             "/health + /metrics; red rows on quota exhaustion or "
             "eviction thrash, non-zero exit when any row is red")
    p.add_argument("urls", nargs="+",
                   help="query-server base URLs, e.g. "
                        "http://127.0.0.1:8000 http://127.0.0.1:8001")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="per-probe timeout in seconds (default 5)")
    p.add_argument("--interval", type=float, default=1.0,
                   help="seconds between the two /metrics scrapes the "
                        "qps column derives from (0 = single scrape, "
                        "no qps; default 1)")
    p.add_argument("--fill-red", type=float, default=0.05,
                   help="quota-fill fraction at or below which a tenant "
                        "with throttles paints red (default 0.05)")
    p.add_argument("--thrash-evictions", type=int, default=8,
                   help="total evictions at which a tenant paints red "
                        "for eviction thrash (default 8)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable row output")

    # dist — distributed-training mesh inspection (docs/sharding.md)
    dist = sub.add_parser(
        "dist",
        help="distributed training tier: status (mesh generation, member "
             "heartbeats, last coordinated checkpoint commit, quorum "
             "verdict)")
    ds = dist.add_subparsers(dest="dist_command")
    p = ds.add_parser("status")
    p.add_argument("--state-dir",
                   help="coordination directory (default: "
                        "PIO_DIST_STATE_DIR)")
    p.add_argument("--json", action="store_true")

    # fleet — router / rolling deploy / experiment (docs/serving.md)
    fleet = sub.add_parser(
        "fleet",
        help="fleet serving tier: route (health-aware query router), "
             "rollout (sequential rolling deploy with halt-and-rollback), "
             "experiment (A/B / shadow inspection and control)")
    fl = fleet.add_subparsers(dest="fleet_command")
    p = fl.add_parser("route")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8200)
    p.add_argument("--replica", action="append", required=True,
                   help="query-server replica base URL (repeatable)")
    p.add_argument("--candidate", action="append",
                   help="candidate-arm replica base URL for A/B / shadow "
                        "routing (repeatable; a different engine version "
                        "deployed beside the control fleet)")
    p.add_argument("--deadline", type=float,
                   help="total per-query budget in seconds across every "
                        "forwarding attempt (PIO_FLEET_DEADLINE env, "
                        "default 3)")
    p.add_argument("--retries", type=int,
                   help="forwarding attempts per query, each on a "
                        "different replica (PIO_FLEET_MAX_ATTEMPTS env, "
                        "default 2)")
    p.add_argument("--health-interval", type=float,
                   help="seconds between concurrent /health probe rounds "
                        "(PIO_FLEET_HEALTH_INTERVAL env, default 2)")
    p.add_argument("--probe-timeout", type=float,
                   help="per-replica /health probe timeout "
                        "(PIO_FLEET_PROBE_TIMEOUT env, default 2)")
    p.add_argument("--eject-threshold", type=int,
                   help="consecutive transport errors before a replica is "
                        "ejected until a probe succeeds "
                        "(PIO_FLEET_EJECT_THRESHOLD env, default 3)")
    p.add_argument("--experiment-name", default="candidate")
    p.add_argument("--experiment-mode", choices=("ab", "shadow"),
                   default="ab")
    p.add_argument("--experiment-weight", type=float,
                   help="fraction of traffic on the candidate arm; "
                        "requires --candidate (omit to start without an "
                        "experiment — POST /experiment starts one live)")
    p.add_argument("--experiment-hash-field",
                   help="query field whose value hashes to a sticky arm "
                        "(e.g. user); omitted = weighted rotation")
    p.add_argument("--server-access-key",
                   help="guards POST /experiment")
    p = fl.add_parser("rollout")
    p.add_argument("replicas", nargs="+",
                   help="query-server replica base URLs, deploy order")
    p.add_argument("--server-access-key")
    p.add_argument("--observe", type=float, default=5.0,
                   help="seconds to watch each replica's /health for a "
                        "probation auto-rollback after its swap (keep "
                        "well under the replicas' --reload-probation; "
                        "default 5)")
    p.add_argument("--poll", type=float, default=0.5,
                   help="seconds between /health polls while observing")
    p.add_argument("--timeout", type=float, default=120.0,
                   help="per-replica /reload timeout (load+warm+smoke)")
    p.add_argument("--json", action="store_true")
    p = fl.add_parser("experiment")
    p.add_argument("router_url",
                   help="fleet router base URL, e.g. http://127.0.0.1:8200")
    p.add_argument("--start", metavar="NAME",
                   help="start an experiment with this name")
    p.add_argument("--stop", action="store_true",
                   help="stop the running experiment")
    p.add_argument("--mode", choices=("ab", "shadow"), default="ab")
    p.add_argument("--weight", type=float, default=0.1)
    p.add_argument("--hash-field")
    p.add_argument("--server-access-key")
    p.add_argument("--json", action="store_true")

    # stream — incremental model updates from the live event feed
    p = sub.add_parser(
        "stream",
        help="streaming incremental updates: tail the eventlog change "
             "feed, fold events into embedding-row deltas, ship them to "
             "replicas as exactly-once delta deploys (docs/streaming.md)")
    p.add_argument("-v", "--engine-variant", default="engine.json")
    p.add_argument("--app", default="recommendation",
                   help="app whose eventlog to tail")
    p.add_argument("--channel", help="channel name (default: none)")
    p.add_argument("--state-dir", required=True,
                   help="cursor + trainer state + delta archive + dead "
                        "letters (crash-safe; single-writer)")
    p.add_argument("--feed-path",
                   help="explicit .piolog path (default: resolved from "
                        "the configured eventlog backend and --app)")
    p.add_argument("--replica", action="append",
                   help="query-server base URL to ship deltas to "
                        "(repeatable)")
    p.add_argument("--server-access-key",
                   help="the replicas' --server-access-key (guards "
                        "POST /delta)")
    p.add_argument("--batch-events", type=int, default=512,
                   help="max events folded per delta (PIO_STREAM_BATCH)")
    p.add_argument("--interval", type=float, default=1.0,
                   help="seconds between idle polls")
    p.add_argument("--once", action="store_true",
                   help="one poll→fold→ship→commit round, then exit")
    p.add_argument("--max-batches", type=int,
                   help="exit after this many applied deltas")
    p.add_argument("--from-start", action="store_true",
                   help="start a fresh cursor at the BEGINNING of the log "
                        "instead of its current end (fold history too)")
    p.add_argument("--status", action="store_true",
                   help="print stream state (cursor, quarantine, dead "
                        "letters) and exit; non-zero when quarantined")
    p.add_argument("--dead-letter", action="store_true",
                   help="print dead-lettered poison events as JSON lines")
    p.add_argument("--obs-port", type=int, default=0,
                   help="serve GET /metrics + /traces.json on this port so "
                        "pio_stream_* gauges are scrapeable (0 = disabled, "
                        "the default; docs/observability.md)")
    p.add_argument("--obs-ip", default="127.0.0.1",
                   help="bind address for --obs-port (default loopback)")

    # wal — inspect/verify/replay an event-server spill WAL
    p = sub.add_parser(
        "wal",
        help="inspect, verify, or manually replay an event-server spill "
             "WAL directory (docs/resilience.md)")
    p.add_argument("directory", help="the PIO_EVENT_WAL_DIR to inspect")
    p.add_argument("--dead-letter", action="store_true",
                   help="print the dead-letter records (store-rejected, "
                        "201-acked events) as JSON lines")
    p.add_argument("--replay", action="store_true",
                   help="insert every pending record into the configured "
                        "event store (idempotent) and advance the cursor")
    p.add_argument("--json", action="store_true",
                   help="machine-readable inspection output")

    # lint — project invariant linter (docs/analysis.md)
    p = sub.add_parser(
        "lint",
        help="run the AST-based project invariant linter: R1 async-"
             "blocking, R2 clock-discipline, R3 durability-ordering, "
             "R4 knob-registry (PIO_* knobs + pio_* metrics ↔ docs), "
             "R5 lock/await-hygiene; suppressions and the baseline are "
             "audited too (docs/analysis.md)")
    p.add_argument("--rule", action="append", metavar="R<n>",
                   help="run only this rule id (repeatable, e.g. "
                        "--rule R2 --rule R4; default: all)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable findings (schema in "
                        "docs/analysis.md)")
    p.add_argument("--update-baseline", action="store_true",
                   help="accept every current finding into the baseline "
                        "file — deterministic output (sorted, "
                        "path-relative) so the diff is reviewable")
    p.add_argument("--baseline", metavar="PATH",
                   help="baseline file, repo-relative "
                        "(default conf/lint_baseline.txt)")
    p.add_argument("--root",
                   help="repo root to lint (default: the tree this "
                        "package is installed from)")

    # export / import
    p = sub.add_parser("export")
    p.add_argument("--appid", type=int, required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--channel")
    p = sub.add_parser("import")
    p.add_argument("--appid", type=int, required=True)
    p.add_argument("--input", required=True)
    p.add_argument("--channel")

    return parser


def cmd_launch(args, storage: Storage) -> int:
    """Spawn N coordinated processes of another verb (Runner.scala:185's
    spark-submit construction, minus the JVM)."""
    from incubator_predictionio_tpu.parallel.launcher import launch_local

    verb_args = list(args.verb_args)
    if verb_args and verb_args[0] == "--":
        verb_args = verb_args[1:]
    if not verb_args:
        _out("launch: no verb given (e.g. pio-tpu launch -n 2 train -v engine.json)")
        return 2
    if verb_args[0] not in ("train", "eval", "batchpredict"):
        # without --distributed gating, N processes of any other verb would
        # just run N independent copies against shared storage
        _out(f"launch: only the train/eval/batchpredict verbs join a "
             f"distributed job (got {verb_args[0]!r})")
        return 2
    if "--distributed" not in verb_args:
        verb_args.append("--distributed")
    try:
        result = launch_local(
            verb_args,
            num_processes=args.num_processes,
            coordinator_port=args.coordinator_port,
            cpu_devices_per_process=args.cpu_devices_per_process,
            timeout=args.timeout,
        )
    except RuntimeError as e:  # N local processes on a TPU host: refused
        _err(f"launch: {e}")
        return 2
    if result.timed_out:
        _out(f"launch: timed out after {args.timeout}s; job killed "
             "(per-process logs below show which peer wedged)")
    for pid, (rc, out) in enumerate(zip(result.returncodes, result.outputs)):
        _out(f"--- process {pid} (exit {rc}) ---")
        if out:
            _out(out.rstrip())
    return 0 if result.ok else 1


_COMMANDS = {
    "version": cmd_version,
    "status": cmd_status,
    "train": cmd_train,
    "launch": cmd_launch,
    "eval": cmd_eval,
    "deploy": cmd_deploy,
    "undeploy": cmd_undeploy,
    "batchpredict": cmd_batchpredict,
    "eventserver": cmd_eventserver,
    "storageserver": cmd_storageserver,
    "dashboard": cmd_dashboard,
    "adminserver": cmd_adminserver,
    "export": cmd_export,
    "import": cmd_import,
    "metrics": cmd_metrics,
    "trace": cmd_trace,
    "health": cmd_health,
    "tenants": cmd_tenants,
    "profile": cmd_profile,
    "history": cmd_history,
    "top": cmd_top,
    "slo": cmd_slo,
    "index": cmd_index,
    "shards": cmd_shards,
    "wal": cmd_wal,
    "lint": cmd_lint,
    "stream": cmd_stream,
    "start-all": cmd_start_all,
    "stop-all": cmd_stop_all,
    "redeploy": cmd_redeploy,
    "shell": cmd_shell,
}

_APP_COMMANDS = {
    "new": cmd_app_new,
    "list": cmd_app_list,
    "show": cmd_app_show,
    "delete": cmd_app_delete,
    "data-delete": cmd_app_data_delete,
    "channel-new": cmd_channel_new,
    "channel-delete": cmd_channel_delete,
}

_TEMPLATE_COMMANDS = {
    "list": cmd_template_list,
    "get": cmd_template_get,
}

_ACCESSKEY_COMMANDS = {
    "new": cmd_accesskey_new,
    "list": cmd_accesskey_list,
    "delete": cmd_accesskey_delete,
}

_FLEET_COMMANDS = {
    "route": cmd_fleet_route,
    "rollout": cmd_fleet_rollout,
    "experiment": cmd_fleet_experiment,
}

_STORE_COMMANDS = {
    "status": cmd_store_status,
    "promote": cmd_store_promote,
    "scrub": cmd_store_scrub,
}

_BACKUP_COMMANDS = {
    "create": cmd_backup_create,
    "verify": cmd_backup_verify,
    "restore": cmd_backup_restore,
    "list": cmd_backup_list,
    "prune": cmd_backup_prune,
}

_JOBS_COMMANDS = {
    "submit": cmd_jobs_submit,
    "list": cmd_jobs_list,
    "watch": cmd_jobs_watch,
    "cancel": cmd_jobs_cancel,
    "retry": cmd_jobs_retry,
    "prune": cmd_jobs_prune,
    "worker": cmd_jobs_worker,
    "triggers": cmd_jobs_triggers,
}

_DIST_COMMANDS = {
    "status": cmd_dist_status,
}


def main(argv: Optional[list[str]] = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if not args.command:
        parser.print_help()
        return 1
    # The engine directory is the import path: a variant's ``engineFactory``
    # names a module in the user's engine dir, and `pio train` in that dir
    # must resolve it — the counterpart of the reference putting `pio build`'s
    # jar on the classpath (console/Console.scala). `python -m` adds cwd
    # already; the installed `pio-tpu` script does not.
    if os.getcwd() not in sys.path and "" not in sys.path:
        sys.path.insert(0, os.getcwd())
    # INFO-level console logging, like the reference console's log4j default
    # (WorkflowUtils.modifyLogging); framework INFO lines (mesh layout,
    # sharded reads, checkpoints) are part of the operator surface
    logging.basicConfig(
        level=logging.DEBUG if getattr(args, "verbose", False) else logging.INFO,
        format="[%(levelname)s] [%(name)s] %(message)s",
    )
    storage = get_storage()
    if args.command == "app":
        if not args.app_command:
            parser.parse_args(["app", "--help"])
            return 1
        return _APP_COMMANDS[args.app_command](args, storage)
    if args.command == "accesskey":
        if not args.accesskey_command:
            parser.parse_args(["accesskey", "--help"])
            return 1
        return _ACCESSKEY_COMMANDS[args.accesskey_command](args, storage)
    if args.command == "fleet":
        if not args.fleet_command:
            _err("fleet: missing subcommand (route|rollout|experiment)")
            return 1
        return _FLEET_COMMANDS[args.fleet_command](args, storage)
    if args.command == "store":
        if not args.store_command:
            _err("store: missing subcommand (status|promote|scrub)")
            return 1
        return _STORE_COMMANDS[args.store_command](args, storage)
    if args.command == "backup":
        if not args.backup_command:
            _err("backup: missing subcommand (create|verify|restore|"
                 "list|prune)")
            return 1
        return _BACKUP_COMMANDS[args.backup_command](args, storage)
    if args.command == "jobs":
        if not args.jobs_command:
            _err("jobs: missing subcommand (submit|list|watch|cancel|"
                 "retry|prune|worker|triggers)")
            return 1
        return _JOBS_COMMANDS[args.jobs_command](args, storage)
    if args.command == "dist":
        if not args.dist_command:
            _err("dist: missing subcommand (status)")
            return 1
        return _DIST_COMMANDS[args.dist_command](args, storage)
    if args.command == "template":
        if not args.template_command:
            # parse_args(["template", "--help"]) would SystemExit(0); a
            # missing subcommand must FAIL for scripted callers
            _err("template: missing subcommand (list|get)")
            return 1
        return _TEMPLATE_COMMANDS[args.template_command](args, storage)
    return _COMMANDS[args.command](args, storage)


if __name__ == "__main__":
    try:
        code = main()
        sys.stdout.flush()
    except BrokenPipeError:
        # downstream pager/head closed the pipe — conventional silent exit
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        code = 0
    sys.exit(code)
