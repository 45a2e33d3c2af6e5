"""Standalone service entry points for production deployments.

The reference ships one binary per service (discovery/orchestrator/
validator/worker mains wired by clap CLIs); the devnet here runs them all
in one process. This module is the per-pod equivalent the Helm charts
exec: each subcommand boots ONE service against a shared ledger API
(chain/remote.RemoteLedger — the counterpart of the reference services'
JSON-RPC contract wrappers) and runs its loops.

    python -m protocol_tpu.serve discovery     --ledger-url ... --pool-id N
    python -m protocol_tpu.serve orchestrator  --ledger-url ... --pool-id N
    python -m protocol_tpu.serve validator     --ledger-url ... --pool-id N
    python -m protocol_tpu.serve scheduler     --address 0.0.0.0:50061
    python -m protocol_tpu.serve worker        --ledger-url ... --pool-id N

Secrets come from env (MANAGER_KEY / ADMIN_API_KEY / S3_CREDENTIALS /
PROVIDER_KEY / NODE_KEY), mirroring the reference charts' envFromSecret.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
from typing import Optional

VERSION = os.environ.get("PROTOCOL_TPU_VERSION", "dev")


def _wallet_from_env(var: str):
    """Pod identity from ``var``; PROTOCOL_TPU_WALLET_SCHEME selects the
    signature scheme (``ed25519`` default, ``evm`` = secp256k1/keccak
    with embedded-pubkey wire, ``evm-recovery`` = the reference's literal
    r||s||v EIP-191 wire) — all three verify through the same seam, so
    pods of different schemes interoperate."""
    from protocol_tpu.security import EvmRecoveryWallet, EvmWallet, Wallet

    key = os.environ.get(var, "")
    if not key:
        raise SystemExit(f"{var} env var required")
    scheme = os.environ.get("PROTOCOL_TPU_WALLET_SCHEME", "ed25519")
    cls = {
        "ed25519": Wallet,
        "evm": EvmWallet,
        "evm-recovery": EvmRecoveryWallet,
    }.get(scheme)
    if cls is None:
        raise SystemExit(f"unknown PROTOCOL_TPU_WALLET_SCHEME {scheme!r}")
    return cls.from_hex(key)


def _ledger(args):
    from protocol_tpu.chain.remote import RemoteLedger

    return RemoteLedger(
        args.ledger_url, admin_api_key=os.environ.get("LEDGER_API_KEY", "")
    )


def _storage():
    creds = os.environ.get("S3_CREDENTIALS", "")
    bucket = os.environ.get("BUCKET_NAME", "")
    if creds and bucket:
        from protocol_tpu.utils.cloud_storage import GcsStorageProvider
        from protocol_tpu.utils.tls import public_client_session

        # GCS/S3 are PUBLIC endpoints: their certs chain to system roots,
        # not the pinned deployment CA, so they get their own session.
        # STORAGE_ENDPOINT overrides the real GCS host (emulators, the
        # signature-verifying fake bucket in full-stack drives).
        endpoint = os.environ.get(
            "STORAGE_ENDPOINT", "https://storage.googleapis.com"
        )
        return GcsStorageProvider(
            bucket, creds, public_client_session(), endpoint=endpoint
        )
    root = os.environ.get("STORAGE_DIR", "")
    if root:
        from protocol_tpu.utils.storage import LocalDirStorageProvider

        return LocalDirStorageProvider(
            root, public_base_url=os.environ.get("STORAGE_PUBLIC_URL", "")
        )
    return None


def _client_session():
    """aiohttp session honoring PROTOCOL_TPU_TLS_CA for internal peers."""
    from protocol_tpu.utils.tls import env_client_session

    return env_client_session()


def _public_session():
    """System-trust session for public endpoints (signed-URL storage)."""
    from protocol_tpu.utils.tls import public_client_session

    return public_client_session()


async def _close_sessions(*sessions) -> None:
    """Close aiohttp ClientSessions on graceful exit. The lazily-created
    public-trust sessions (worker signed-URL PUTs, GCS, toploc,
    geolocation) would otherwise leak their connectors when a serve
    coroutine is cancelled."""
    for s in sessions:
        if s is None or isinstance(s, str) or getattr(s, "closed", False):
            continue
        close = getattr(s, "close", None)
        if close is None:
            continue
        try:
            r = close()
            if asyncio.iscoroutine(r):
                await r
        except Exception:
            pass


def _server_ssl(args):
    """TLS server context from --tls-cert/--tls-key (or TLS_CERT/TLS_KEY
    env, the charts' secret mounts). None = plaintext, the pre-TLS
    behavior."""
    cert = getattr(args, "tls_cert", "") or os.environ.get("TLS_CERT", "")
    key = getattr(args, "tls_key", "") or os.environ.get("TLS_KEY", "")
    if not cert and not key:
        return None
    if not (cert and key):
        raise SystemExit("--tls-cert and --tls-key must be given together")
    from protocol_tpu.utils.tls import server_ssl_context

    return server_ssl_context(cert, key)


async def _run_app(app, port: int, ssl_context=None) -> None:
    from aiohttp import web

    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "0.0.0.0", port, ssl_context=ssl_context)
    await site.start()
    scheme = "https" if ssl_context is not None else "http"
    print(f"listening on :{port} ({scheme}, version {VERSION})", flush=True)


async def serve_discovery(args) -> None:
    from protocol_tpu.services.discovery import DiscoveryService
    from protocol_tpu.utils.location import HttpLocationResolver

    resolver = None
    if args.location_url:
        # geolocation is an external endpoint (reference location-service
        # shape): system trust, not the pinned CA. Self-hosting it behind
        # the deployment CA? Add that CA to the container's system trust
        # store (standard CA-bundle mount).
        resolver = HttpLocationResolver(
            args.location_url, _public_session()
        )
    svc = DiscoveryService(
        _ledger(args),
        args.pool_id,
        max_nodes_per_ip=args.max_nodes_per_ip,
        admin_api_key=os.environ.get("ADMIN_API_KEY", "admin"),
        location_resolver=resolver,
        persist_path=(
            os.path.join(args.state_dir, "discovery.aof") if args.state_dir else None
        ),
    )
    await _run_app(svc.make_app(), args.port, ssl_context=_server_ssl(args))
    try:
        while True:
            try:
                await asyncio.to_thread(svc.chain_sync_once)
                await svc.enrich_locations_once()
            except Exception as e:
                print(f"discovery loop error: {e}", file=sys.stderr)
            await asyncio.sleep(args.sync_interval)
    finally:
        await _close_sessions(resolver.http if resolver else None)


async def serve_orchestrator(args) -> None:
    from protocol_tpu.models.node import DiscoveryNode
    from protocol_tpu.security import sign_request
    from protocol_tpu.sched import Scheduler
    from protocol_tpu.sched.node_groups import (
        NodeGroupConfiguration,
        NodeGroupsPlugin,
    )
    from protocol_tpu.sched.tpu_backend import TpuBatchMatcher
    from protocol_tpu.services.orchestrator import OrchestratorService
    from protocol_tpu.store import StoreContext
    from protocol_tpu.store.kv import KVStore

    wallet = _wallet_from_env("MANAGER_KEY")
    ledger = _ledger(args)
    session = _client_session()
    if args.kv_url:
        # shared store pod (the reference's external Redis): api/processor
        # replicas all see the same state
        from protocol_tpu.store.remote_kv import RemoteKVStore

        store = StoreContext(
            RemoteKVStore(
                args.kv_url, api_key=os.environ.get("KV_API_KEY", "admin")
            )
        )
    else:
        if args.mode != "full":
            raise SystemExit(
                f"--mode {args.mode} needs --kv-url: split replicas must "
                "share a kv-api store pod"
            )
        store = StoreContext(
            KVStore(
                persist_path=(
                    os.path.join(args.state_dir, "orchestrator.aof")
                    if args.state_dir
                    else None
                )
            )
        )

    backend = args.scheduler_backend
    if backend != "local" and not (
        backend == "remote" or backend.startswith("remote:")
    ):
        raise SystemExit(
            f"unknown --scheduler-backend {backend!r} "
            "(want local | remote | remote:HOST:PORT)"
        )

    grpc_server = None
    groups_plugin = None
    group_configs = os.environ.get("NODE_GROUP_CONFIGS", "")
    if group_configs:
        configs = [
            NodeGroupConfiguration.from_dict(d) for d in json.loads(group_configs)
        ]
        groups_plugin = NodeGroupsPlugin(store, configs)
        groups_plugin.attach_observers()
    if backend != "local":
        from protocol_tpu.services import scheduler_grpc

        addr = backend.partition(":")[2]
        if not addr:
            # bare "remote": boot an in-process backend (devnet semantics);
            # hold the reference or the grpc.Server is GC'd and stops
            addr = "127.0.0.1:50061"
            grpc_server = scheduler_grpc.serve(addr)
        matcher = scheduler_grpc.RemoteBatchMatcher(
            store,
            addr,
            # wire protocol revision: v2 (tensor frames + delta sessions)
            # falls back to v1 automatically against an old server
            wire=os.environ.get("PROTOCOL_TPU_WIRE", "v2"),
            # the engine knobs ride the wire as the kernel string
            # ("native-mt[:N]" / "sinkhorn-mt[:N]" / "jax[:D]") when the
            # control plane is in degraded mode
            native_fallback=os.environ.get(
                "PROTOCOL_TPU_NATIVE_FALLBACK", ""
            ).lower()
            in ("1", "true", "yes"),
            native_engine=os.environ.get(
                "PROTOCOL_TPU_NATIVE_ENGINE", "native"
            ),
            native_threads=int(
                os.environ.get("PROTOCOL_TPU_NATIVE_THREADS") or 0
            ),
        )
    else:
        matcher = TpuBatchMatcher(
            store,
            native_fallback=os.environ.get(
                "PROTOCOL_TPU_NATIVE_FALLBACK", ""
            ).lower()
            in ("1", "true", "yes"),
            # native | native-mt | sinkhorn-mt | jax[:D]: native-* are
            # the multi-threaded host engines + persistent warm arena
            # for degraded-mode deployments with cores to spare
            # (sinkhorn-mt = the O(nnz) entropic solver with
            # auction-referee rounding); jax[:D] is the first-class JAX
            # engine — sharded candidate gen over D devices + adaptive
            # eps-ladder solve with warm dual carry
            native_engine=os.environ.get(
                "PROTOCOL_TPU_NATIVE_ENGINE", "native"
            ),
            # 0 = all hardware threads
            native_threads=int(
                os.environ.get("PROTOCOL_TPU_NATIVE_THREADS") or 0
            ),
            # deploy-time override of the dense/sparse cutover (cells =
            # p_bucket * s_bucket). Small fleets land on the dense solver
            # by default; soaks and staging set this low to exercise the
            # production sparse + candidate-cache + warm path end to end.
            dense_cell_budget=int(
                os.environ.get("PROTOCOL_TPU_DENSE_CELL_BUDGET", 1 << 24)
            ),
            # multi-chip hosts: phase 1's candidate generation shards
            # over the device mesh (parallel/sparse.py); the solve runs
            # on one device
            use_mesh=os.environ.get("PROTOCOL_TPU_USE_MESH", "").lower()
            in ("1", "true", "yes"),
            # stage-A approx_max_k selection (e.g. 0.95); empty = exact
            approx_recall=(
                float(os.environ["PROTOCOL_TPU_APPROX_RECALL"])
                if os.environ.get("PROTOCOL_TPU_APPROX_RECALL")
                else None
            ),
        )
    matcher.attach_observers()
    if groups_plugin is not None:
        # composed gang scheduling: grouped nodes resolve through the
        # plugin (matcher-ranked selection), ungrouped through the batch
        # solve — no longer mutually exclusive deployments
        matcher.attach_groups(groups_plugin)
        scheduler = Scheduler(
            store, plugins=[groups_plugin], batch_matcher=matcher
        )
    else:
        scheduler = Scheduler(store, batch_matcher=matcher)

    webhook = None
    webhook_configs = os.environ.get("WEBHOOK_CONFIGS", "")
    if webhook_configs:
        from protocol_tpu.sched.webhook import WebhookConfig, WebhookPlugin

        webhook = WebhookPlugin(
            WebhookConfig.from_json_env(webhook_configs), http=session
        )

    discovery_urls = [
        u for u in os.environ.get("DISCOVERY_URLS", "").split(",") if u
    ]

    async def discovery_fetcher():
        for url in discovery_urls:
            headers, _ = sign_request(f"/api/pool/{args.pool_id}", wallet)
            try:
                async with session.get(
                    f"{url}/api/pool/{args.pool_id}", headers=headers
                ) as resp:
                    data = await resp.json()
                    return [
                        DiscoveryNode.from_dict(d) for d in data.get("data", [])
                    ]
            except Exception:
                continue
        return []

    async def invite_sender(node, payload):
        url = (node.p2p_addresses or [None])[0]
        if not url:
            return False
        headers, body = sign_request("/control/invite", wallet, payload)
        try:
            async with session.post(
                f"{url}/invite", json=body, headers=headers
            ) as resp:
                return resp.status == 200
        except Exception:
            return False

    svc = OrchestratorService(
        ledger,
        args.pool_id,
        wallet,
        store=store,
        scheduler=scheduler,
        groups_plugin=groups_plugin,
        storage=_storage(),
        discovery_fetcher=discovery_fetcher if discovery_urls else None,
        invite_sender=invite_sender,
        admin_api_key=os.environ.get("ADMIN_API_KEY", "admin"),
        # default scheme follows the listener: an https listener behind an
        # http:// invite URL is unreachable to every worker dial
        heartbeat_url=os.environ.get(
            "HEARTBEAT_URL",
            f"{'https' if _server_ssl(args) is not None else 'http'}"
            f"://localhost:{args.port}",
        ),
        uploads_per_hour=int(os.environ.get("UPLOADS_PER_HOUR", "3")),
        control_http=session,
        webhook=webhook,
    )
    svc.grpc_server = grpc_server  # keep the in-process backend alive
    if webhook is not None:
        webhook.start()
    # mode-dependent surface (the reference's api/processor/full split,
    # orchestrator/src/main.rs + api/server.rs:202-220): api replicas serve
    # HTTP only, the processor runs the loops, full does both
    if args.mode == "api":
        await _run_app(svc.make_app(), args.port, ssl_context=_server_ssl(args))
        print(f"orchestrator[api] on :{args.port} (version {VERSION})", flush=True)
    elif args.mode == "processor":
        from aiohttp import web as _web

        health_app = _web.Application()
        health_app.router.add_get("/health", svc.health)
        await _run_app(health_app, args.port, ssl_context=_server_ssl(args))
        # only the loops; the HTTP surface lives in the api replicas.
        # keep the task references — the event loop holds tasks weakly
        svc.loop_tasks = svc.start_loops()
        print(
            f"orchestrator[processor] health on :{args.port} (version {VERSION})",
            flush=True,
        )
    else:
        await svc.serve(host="0.0.0.0", port=args.port)
        print(f"orchestrator on :{args.port} (version {VERSION})", flush=True)
    try:
        while True:  # loops run as tasks; keep the process alive
            await asyncio.sleep(3600)
    finally:
        await _close_sessions(
            session, getattr(getattr(svc, "storage", None), "http", None)
        )


async def serve_validator(args) -> None:
    from protocol_tpu.models.node import DiscoveryNode
    from protocol_tpu.security import sign_request
    from protocol_tpu.services.validator import (
        SyntheticDataValidator,
        ToplocClient,
        ValidatorService,
    )

    wallet = _wallet_from_env("VALIDATOR_KEY")
    ledger = _ledger(args)
    session = _client_session()

    synthetic = None
    toploc_session = None
    toploc_configs = os.environ.get("TOPLOC_CONFIGS", "")
    # storage built lazily: _storage() opens its own public session for GCS,
    # which must not sit idle (and unclosed) when toploc is unconfigured
    storage = _storage() if toploc_configs else None
    if toploc_configs and storage is not None:
        # toploc is an EXTERNAL verification service (bearer-auth HTTPS like
        # the reference's toploc API): system trust, not the pinned CA.
        # Self-hosting it behind the deployment CA? Add that CA to the
        # container's system trust store (standard CA-bundle mount).
        toploc_session = _public_session()
        clients = [
            ToplocClient(
                c["url"],
                toploc_session,
                auth_token=c.get("auth_token"),
                file_prefix_filter=c.get("file_prefix_filter"),
            )
            for c in json.loads(toploc_configs)
        ]
        synthetic = SyntheticDataValidator(
            ledger,
            args.pool_id,
            storage,
            clients,
            persist_path=(
                os.path.join(args.state_dir, "validator.aof")
                if args.state_dir
                else None
            ),
        )

    discovery_urls = [
        u for u in os.environ.get("DISCOVERY_URLS", "").split(",") if u
    ]

    async def fetcher():
        for url in discovery_urls:
            headers, _ = sign_request("/api/validator", wallet)
            try:
                async with session.get(
                    f"{url}/api/validator", headers=headers
                ) as resp:
                    data = await resp.json()
                    return [
                        DiscoveryNode.from_dict(d) for d in data.get("data", [])
                    ]
            except Exception:
                continue
        return []

    svc = ValidatorService(
        wallet,
        ledger,
        args.pool_id,
        synthetic=synthetic,
        discovery_fetcher=fetcher if discovery_urls else None,
        http=session,
    )
    await _run_app(svc.make_app(), args.port, ssl_context=_server_ssl(args))
    try:
        while True:
            try:
                await svc.validation_loop_once()
            except Exception as e:
                print(f"validation loop error: {e}", file=sys.stderr)
            await asyncio.sleep(args.loop_interval)
    finally:
        await _close_sessions(
            session, toploc_session, getattr(storage, "http", None)
        )


async def serve_ledger_api(args) -> None:
    """Dev economic substrate as a standalone pod (the reference devnet's
    reth + contracts; production would point LEDGER_URL at a real chain
    gateway instead). With --state-dir the chain survives pod restarts
    via periodic JSON snapshots (reth's durability, approximated)."""
    from protocol_tpu.chain import Ledger
    from protocol_tpu.services.ledger_api import LedgerApiService

    import signal

    ledger_path = (
        os.path.join(args.state_dir, "ledger.json") if args.state_dir else None
    )
    ledger = Ledger.open(ledger_path)
    if ledger_path and os.path.exists(ledger_path):
        print(f"ledger restored from {ledger_path}", flush=True)
    svc = LedgerApiService(
        ledger, admin_api_key=os.environ.get("ADMIN_API_KEY", "admin")
    )
    await _run_app(svc.make_app(), args.port, ssl_context=_server_ssl(args))

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)
    try:
        while not stop.is_set():
            try:
                await asyncio.wait_for(
                    stop.wait(), timeout=10.0 if ledger_path else 3600.0
                )
            except asyncio.TimeoutError:
                pass
            if ledger_path:
                await asyncio.to_thread(ledger.try_snapshot, ledger_path)
    finally:
        if ledger_path:
            # final snapshot on SIGTERM (k8s rolling restart): acknowledged
            # writes must never lose the race with the 10 s tick
            ledger.try_snapshot(ledger_path)


async def serve_kv_api(args) -> None:
    """Shared state store pod (the reference's external Redis)."""
    from protocol_tpu.services.kv_api import KvApiService
    from protocol_tpu.store.kv import KVStore

    kv = KVStore(
        persist_path=(
            os.path.join(args.state_dir, "kv.aof") if args.state_dir else None
        )
    )
    svc = KvApiService(kv, api_key=os.environ.get("KV_API_KEY", "admin"))
    await _run_app(svc.make_app(), args.port, ssl_context=_server_ssl(args))
    while True:
        await asyncio.sleep(3600)


def serve_scheduler(args) -> None:
    """The gRPC kernel backend — the pod that actually holds the TPU."""
    import signal

    from protocol_tpu.services.scheduler_grpc import drain, serve

    fleet = None
    if args.proc_id or args.ckpt_dir or args.endpoint:
        # dfleet pod identity: flags override the PROTOCOL_TPU_FLEET_*
        # env (the charts' surface), same precedence as everywhere else
        import dataclasses

        from protocol_tpu.fleet.fabric import FleetConfig

        fleet = FleetConfig.from_env()
        overrides = {}
        if args.proc_id:
            overrides["proc_id"] = args.proc_id
        if args.ckpt_dir:
            overrides["ckpt_dir"] = args.ckpt_dir
        # precedence: flag > PROTOCOL_TPU_FLEET_ENDPOINT env > bind
        # address (the env value must survive an unrelated flag — a
        # moved:<bind-address> redirect would hand clients 0.0.0.0)
        overrides["endpoint"] = (
            args.endpoint or fleet.endpoint or args.address
        )
        fleet = dataclasses.replace(fleet, **overrides)
    server = serve(
        address=args.address, max_workers=args.max_workers,
        metrics_port=args.metrics_port, fleet=fleet,
    )
    from protocol_tpu.utils.platform import device_summary

    device = device_summary()
    print(
        f"scheduler backend on {args.address} (version {VERSION}) "
        f"platform={device['platform']} "
        f"device_kind={device['device_kind']!r} "
        f"device_count={device['device_count']}",
        flush=True,
    )
    if server.metrics is not None:
        print(
            f"obs /metrics on 127.0.0.1:{server.metrics.port}", flush=True
        )

    def _on_sigterm(signum, frame):
        # graceful drain: stop admitting OpenSession, finish in-flight
        # ticks, flush session checkpoints + trace tails, exit 0 — a
        # rolling restart rehydrates every session warm instead of
        # stampeding clients into cold snapshot reopens
        flushed = drain(server)
        print(
            f"drained: {flushed} session checkpoint(s) flushed",
            flush=True,
        )
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, _on_sigterm)
    server.wait_for_termination()


def serve_dfleet(args) -> int:
    """N scheduler servicer processes + the discovery endpoint — the
    whole distributed fleet from one command (the compose/Helm
    equivalent execs one ``scheduler`` pod per process and a discovery
    pod instead; this is the single-host shape and the local drill)."""
    import signal

    from protocol_tpu.dfleet.discovery import DiscoveryEndpoint
    from protocol_tpu.dfleet.manager import ProcessFleet

    fleet = ProcessFleet(
        processes=args.processes,
        journal_root=args.journal_root,
        shards=args.shards,
        max_sessions=args.max_sessions,
        max_workers=args.max_workers,
    )
    fleet.start()
    disco = DiscoveryEndpoint(
        lambda: fleet.topology, port=args.discovery_port
    )
    print(
        f"dfleet: {args.processes} servicer process(es) "
        f"{[p.address for p in fleet.procs]} (version {VERSION})",
        flush=True,
    )
    print(f"discovery on {disco.url}/fleet.json", flush=True)

    stop = []

    def _on_signal(signum, frame):
        stop.append(signum)

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    try:
        import time as _time

        while not stop:
            _time.sleep(0.5)
            for p in fleet.live():
                if p.popen is not None and p.popen.poll() is not None:
                    # a process died underneath us: re-route its
                    # journals so the survivors serve its sessions warm
                    print(
                        f"dfleet: {p.proc_id} exited "
                        f"(rc={p.popen.returncode}); re-routing "
                        "journals", flush=True,
                    )
                    p.alive = False
                    fleet.drop_endpoint(p.address)
                    moved = fleet.handoff_dead(p.index)
                    print(
                        f"dfleet: {len(moved)} journal(s) re-routed",
                        flush=True,
                    )
    finally:
        # graceful fleet drain: SIGTERM every live process (each
        # flushes its journals and exits 0), then stop discovery
        for p in fleet.live():
            try:
                fleet.drain(p.index)
            except Exception:
                pass
        disco.stop()
        fleet.stop()
    print("dfleet: drained and stopped", flush=True)
    return 0


async def serve_worker(args) -> None:
    from protocol_tpu.services.worker import (
        SubprocessRuntime,
        TaskBridge,
        WorkerAgent,
    )

    provider = _wallet_from_env("PROVIDER_KEY")
    node = _wallet_from_env("NODE_KEY")
    ledger = _ledger(args)
    session = _client_session()
    if args.advertise_ip == "auto":
        # STUN public-IP detection (reference checks/stun.rs via
        # cli/command.rs:332-339); explicit --advertise-ip skips it
        from protocol_tpu.utils.stun import get_public_ip

        detected = await asyncio.to_thread(get_public_ip)
        if detected is None:
            # fail closed: advertising a guessed/loopback address would
            # register an unreachable worker that still looks healthy
            raise SystemExit(
                "STUN public-IP detection failed (no UDP egress?); pass "
                "--advertise-ip explicitly"
            )
        args.advertise_ip = detected
        print(f"advertise ip (stun): {args.advertise_ip}", flush=True)
    from protocol_tpu.services.checks import run_all_checks

    specs, report = run_all_checks(
        "/",
        port=args.port,
        docker_bin=os.environ.get("PROTOCOL_TPU_DOCKER_BIN", "docker"),
        require_docker=args.runtime == "docker",
        probe_accelerator=False,
    )
    for issue in report.issues:
        print(f"check [{issue.level}]: {issue.message}", flush=True)
    if report.critical:
        # checks/issue.rs gating via cli/command.rs:388-397: criticals
        # block startup rather than registering a broken worker
        raise SystemExit("critical readiness issues; aborting (see above)")
    if args.runtime == "docker":
        from protocol_tpu.services.docker_runtime import DockerRuntime

        # the SAME binary the boot gate just validated
        def runtime_factory(slot=None):
            return DockerRuntime(
                socket_path=args.socket_path,
                docker_bin=os.environ.get("PROTOCOL_TPU_DOCKER_BIN", "docker"),
                slot=slot,
            )
    else:
        def runtime_factory(slot=None):
            return SubprocessRuntime(socket_path=args.socket_path)

    runtime = runtime_factory()
    ipfs = None
    if os.environ.get("IPFS_API_URL"):
        from protocol_tpu.utils.ipfs import IpfsMirror

        ipfs = IpfsMirror(os.environ["IPFS_API_URL"], http=session)
    server_ssl = _server_ssl(args)
    agent = WorkerAgent(
        provider,
        node,
        ledger,
        args.pool_id,
        runtime=runtime,
        compute_specs=specs,
        ip_address=args.advertise_ip,
        port=args.port,
        http=session,
        ipfs=ipfs,
        price=args.price,
        # advertise the scheme the control app actually serves: an https
        # listener behind an http:// discovery record is unreachable to
        # every orchestrator/validator dial
        control_scheme="https" if server_ssl is not None else "http",
        public_http="lazy",
        # colocated assignments (ladder #5) run concurrently, one runtime
        # per extra task (docker identities are per task id, so containers
        # never collide)
        runtime_factory=runtime_factory,
    )
    agent.register_on_ledger()
    bridge = TaskBridge(args.socket_path, agent)
    await bridge.start()
    await _run_app(agent.make_control_app(), args.port, ssl_context=server_ssl)
    urls = [u for u in args.discovery_urls.split(",") if u]
    await agent.upload_to_discovery(urls)
    last_monitor = 0.0
    try:
        while True:
            try:
                await agent.heartbeat_once()
                await agent.upload_to_discovery(urls)
                import time as _time

                if _time.monotonic() - last_monitor >= 60.0:
                    # stake/whitelist/membership drift watch
                    # (provider.rs:47-147, compute_node.rs:32-115)
                    last_monitor = _time.monotonic()
                    for alarm in await asyncio.to_thread(
                        agent.stake_monitor_once
                    ):
                        print(f"chain alarm: {alarm}", file=sys.stderr)
                    if agent.deregistered:
                        # a deregistered node must STOP, not keep advertising
                        # itself to discovery forever
                        raise SystemExit(
                            "compute node deregistered on-chain; exiting"
                        )
            except SystemExit:
                raise
            except Exception as e:
                print(f"worker loop error: {e}", file=sys.stderr)
            await asyncio.sleep(10.0)
    finally:
        # the "lazy" sentinel only becomes a session after the first
        # external signed-URL upload; _close_sessions skips the sentinel
        await _close_sessions(session, agent.public_http)


def run_bootstrap(args) -> int:
    """Idempotent economic bootstrap for the compose stack (the reference
    devnet's make-compose chain setup): ensure domain 0 + pool ``pool_id``
    exist and are started, the PROVIDER_KEY wallet is funded/whitelisted,
    and the VALIDATOR_KEY wallet holds the validator role. Safe to re-run;
    waits for the ledger-api pod to come up first."""
    import time

    from protocol_tpu.chain.ledger import LedgerError

    creator = _wallet_from_env("POOL_CREATOR_KEY")
    manager = _wallet_from_env("MANAGER_KEY")
    ledger = _ledger(args)

    deadline = time.monotonic() + float(os.environ.get("BOOTSTRAP_WAIT", "60"))
    while True:
        try:
            ledger.balance_of(creator.address)
            break
        except LedgerError as e:
            if time.monotonic() > deadline:
                print(f"ledger-api unreachable: {e}", file=sys.stderr)
                return 1
            time.sleep(2.0)

    def _pool_probe():
        # "unknown pool" must not be conflated with a transport blip: a
        # create against a ledger that already has the pool would mint a
        # duplicate domain/pool and wire the stack to the wrong id
        while True:
            try:
                return ledger.get_pool_info(args.pool_id)
            except LedgerError as e:
                if not str(e).startswith("unreachable"):
                    return None
                if time.monotonic() > deadline:
                    raise
                time.sleep(2.0)  # ledger blip: pace retries like the wait loop

    pool = _pool_probe()
    if pool is None:
        did = ledger.create_domain("compose", validation_logic="any")
        pid = ledger.create_pool(
            did, creator.address, manager.address,
            os.environ.get("POOL_DATA_URI", ""),
        )
        if pid != args.pool_id:
            print(
                f"created pool {pid} but COMPUTE_POOL_ID={args.pool_id}: "
                "the stack would point at a nonexistent pool",
                file=sys.stderr,
            )
            return 1
        ledger.start_pool(pid, creator.address)
        print(f"created domain {did} pool {pid} (started)", flush=True)
    else:
        # re-run repair: a crash between create_pool and start_pool must
        # not leave the pool PENDING forever behind the exists fast path
        if getattr(pool.status, "name", str(pool.status)) != "ACTIVE":
            ledger.start_pool(args.pool_id, creator.address)
            print(f"pool {args.pool_id} existed but was not active: started", flush=True)
        else:
            print(f"pool {args.pool_id} active; bootstrap already ran", flush=True)

    provider_key = os.environ.get("PROVIDER_KEY", "")
    if provider_key:
        from protocol_tpu.security import Wallet

        provider = Wallet.from_hex(provider_key)
        if ledger.balance_of(provider.address) < 1000:
            ledger.mint(provider.address, 1_000_000)
        if not ledger.provider_exists(provider.address):
            # whitelisting needs a registered provider; register here so
            # the worker's own boot sees it and just adds its node
            ledger.register_provider(
                provider.address, ledger.calculate_stake(1)
            )
        ledger.whitelist_provider(provider.address)
        print(f"provider {provider.address} funded + whitelisted", flush=True)

    validator_key = os.environ.get("VALIDATOR_KEY", "")
    if validator_key:
        from protocol_tpu.security import Wallet

        validator = Wallet.from_hex(validator_key)
        ledger.grant_validator_role(validator.address)
        print(f"validator role granted to {validator.address}", flush=True)
    return 0


def main(argv: Optional[list[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="protocol_tpu.serve")
    parser.add_argument("--version", action="version", version=VERSION)
    sub = parser.add_subparsers(dest="service", required=True)

    def common(p):
        # flags win; env (the charts' configuration surface) is the default
        p.add_argument(
            "--ledger-url", default=os.environ.get("LEDGER_URL", "")
        )
        p.add_argument(
            "--pool-id",
            type=int,
            default=int(os.environ.get("COMPUTE_POOL_ID", "-1")),
        )
        p.add_argument("--state-dir", default=os.environ.get("STATE_DIR", ""))
        # transport confidentiality (the reference's Noise layer,
        # p2p/src/lib.rs:324-335): serve HTTPS when a cert pair is given;
        # clients verify via PROTOCOL_TPU_TLS_CA
        p.add_argument("--tls-cert", default=os.environ.get("TLS_CERT", ""))
        p.add_argument("--tls-key", default=os.environ.get("TLS_KEY", ""))

    p = sub.add_parser("discovery")
    common(p)
    p.add_argument("--port", type=int, default=8089)
    p.add_argument("--max-nodes-per-ip", type=int, default=5)
    p.add_argument("--location-url", default="")
    p.add_argument("--sync-interval", type=float, default=10.0)

    p = sub.add_parser("orchestrator")
    common(p)
    p.add_argument("--port", type=int, default=8090)
    p.add_argument("--scheduler-backend", default="local")
    p.add_argument(
        "--mode",
        choices=["full", "api", "processor"],
        default="full",
        help="api = HTTP replicas, processor = loops; both need --kv-url "
        "(the reference's mode split over shared Redis)",
    )
    p.add_argument(
        "--kv-url",
        default=os.environ.get("KV_URL", ""),
        help="shared kv-api store pod (required for api/processor modes)",
    )

    p = sub.add_parser("kv-api")
    p.add_argument("--port", type=int, default=8096)
    p.add_argument("--state-dir", default=os.environ.get("STATE_DIR", ""))
    p.add_argument("--tls-cert", default=os.environ.get("TLS_CERT", ""))
    p.add_argument("--tls-key", default=os.environ.get("TLS_KEY", ""))

    p = sub.add_parser("validator")
    common(p)
    p.add_argument("--port", type=int, default=9879)
    p.add_argument("--loop-interval", type=float, default=5.0)

    p = sub.add_parser("scheduler")
    p.add_argument("--address", default="0.0.0.0:50061")
    p.add_argument("--max-workers", type=int, default=4)
    p.add_argument(
        "--metrics-port", type=int, default=None,
        help="consolidated /metrics scrape endpoint (obs plane); also "
             "via PROTOCOL_TPU_METRICS_PORT",
    )
    p.add_argument(
        "--proc-id", default=None,
        help="dfleet process id: namespaces this pod's checkpoint "
             "journals under the shared --ckpt-dir root (also "
             "PROTOCOL_TPU_FLEET_PROC_ID)",
    )
    p.add_argument(
        "--ckpt-dir", default=None,
        help="shared checkpoint-journal root (warm restart + live "
             "migration handoff; also PROTOCOL_TPU_FLEET_CKPT_DIR)",
    )
    p.add_argument(
        "--endpoint", default=None,
        help="advertised endpoint for moved:<endpoint> migration "
             "redirects (default: --address; also "
             "PROTOCOL_TPU_FLEET_ENDPOINT)",
    )

    p = sub.add_parser(
        "dfleet",
        help="N scheduler servicer processes behind the consistent-"
        "hash endpoint ring with a discovery endpoint, over one shared "
        "journal root (the multi-process deployment shape)",
    )
    p.add_argument("--processes", type=int, default=3)
    p.add_argument("--journal-root", required=True)
    p.add_argument("--shards", type=int, default=2)
    p.add_argument("--max-sessions", type=int, default=64)
    p.add_argument("--max-workers", type=int, default=8)
    p.add_argument("--discovery-port", type=int, default=0,
                   help="discovery endpoint port (0 = ephemeral)")

    p = sub.add_parser("ledger-api")
    p.add_argument("--port", type=int, default=8095)
    p.add_argument("--state-dir", default=os.environ.get("STATE_DIR", ""))
    p.add_argument("--tls-cert", default=os.environ.get("TLS_CERT", ""))
    p.add_argument("--tls-key", default=os.environ.get("TLS_KEY", ""))

    p = sub.add_parser(
        "bootstrap",
        help="idempotent dev/e2e economic bootstrap against a ledger-api "
        "pod: domain + pool + start + provider mint/whitelist + validator "
        "role (the compose stack's init container)",
    )
    common(p)

    p = sub.add_parser("worker")
    common(p)
    p.add_argument("--port", type=int, default=8091)
    p.add_argument(
        "--advertise-ip",
        default="127.0.0.1",
        help='"auto" = STUN public-IP detection (checks/stun.rs)',
    )
    p.add_argument("--discovery-urls", default="")
    p.add_argument("--runtime", choices=["subprocess", "docker"], default="docker")
    p.add_argument("--socket-path", default="/var/run/protocol-tpu/bridge.sock")
    p.add_argument(
        "--price",
        type=float,
        default=None,
        help="advertised ask price (cost units/hour) fed to the matcher's "
        "price cost term via discovery",
    )

    args = parser.parse_args(argv)
    from protocol_tpu.utils.logging import setup_logging

    setup_logging(
        level=os.environ.get("LOG_LEVEL", "info"),
        loki_url=os.environ.get("LOKI_URL") or None,
        labels={
            "service": args.service,
            "pool": str(getattr(args, "pool_id", "")),
        },
    )
    if args.service in ("scheduler", "orchestrator"):
        # the services whose process can hold the chip (the orchestrator
        # through its in-process matcher): their jit executables go to a
        # compile cache that does not move between restarts
        from protocol_tpu.utils.platform import place_compile_cache

        place_compile_cache()
    if args.service not in ("scheduler", "dfleet", "ledger-api", "kv-api"):
        if not args.ledger_url:
            parser.error("--ledger-url (or LEDGER_URL env) required")
        if args.pool_id < 0:
            parser.error("--pool-id (or COMPUTE_POOL_ID env) required")
    if args.service == "scheduler":
        serve_scheduler(args)
        return 0
    if args.service == "dfleet":
        return serve_dfleet(args)
    if args.service == "bootstrap":
        return run_bootstrap(args)
    coro = {
        "discovery": serve_discovery,
        "orchestrator": serve_orchestrator,
        "validator": serve_validator,
        "worker": serve_worker,
        "ledger-api": serve_ledger_api,
        "kv-api": serve_kv_api,
    }[args.service](args)
    asyncio.run(coro)
    return 0


if __name__ == "__main__":
    sys.exit(main())
