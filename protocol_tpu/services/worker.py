"""Worker agent: provider-side node daemon.

Reference: crates/worker (7,545 LoC; SURVEY.md §2.5, boot call-stack §3.1).
Kept behaviors:

  - system checks -> ComputeSpecs + issue report with minimums
    (checks/hardware/hardware_check.rs:67-95: 4 cores / 8 GB / 1 TB)
  - pool ComputeRequirements gate before starting (cli/command.rs:398-436)
  - provider registration + stake + compute-node registration on the ledger
    (operations/provider.rs, compute_node.rs)
  - signed discovery upload with multi-URL failover + periodic re-upload
    (services/discovery.rs:26-102)
  - invite handling: verify the orchestrator's signed invite, join the pool
    on the ledger from the provider wallet, start heartbeating the invite
    URL (worker/src/p2p/mod.rs:396-497)
  - 10 s signed heartbeat carrying task state + metrics + runtime details;
    the response's current_task drives the runtime
    (operations/heartbeat/service.rs:140-293)
  - task runtime reconcile loop: name = task-{id}-{confighash} so config
    changes force a restart; restart backoff; state mapping
    (docker/service.rs:56-295). The runtime is pluggable: a subprocess
    runtime (dev; containers are orthogonal to this framework's scope) and
    a mock runtime for tests stand where the reference drives dockerd.
  - TaskBridge: unix-socket JSON intake from the running workload — metrics
    -> heartbeat metrics; sha256+flops -> upload request + ledger work
    submission, deduped by sha (docker/taskbridge/bridge.rs:150-419)

Control plane deviation (by design): the reference's libp2p
request-response protocols (Invite / HardwareChallenge / GetTaskLogs /
Restart) are served here as wallet-signed HTTP endpoints on the worker
(/control/*) with the same payloads and authorization (only the pool's
compute-manager key or known validators) — one security scheme across the
whole framework instead of two.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import json
import os
import shutil
import time
from abc import ABC, abstractmethod
from dataclasses import dataclass, field
from typing import Optional

from aiohttp import web

from protocol_tpu.chain import Ledger, LedgerError
from protocol_tpu.models.heartbeat import TaskDetails
from protocol_tpu.models.node import ComputeRequirements, ComputeSpecs, CpuSpecs, GpuSpecs, Node
from protocol_tpu.models.task import Task, TaskState
from protocol_tpu.security.middleware import validate_signature_middleware
from protocol_tpu.security.signer import sign_request
from protocol_tpu.security.wallet import Wallet
from protocol_tpu.store.kv import KVStore

RESTART_BACKOFF_SECONDS = 10.0  # docker/service.rs:30


class SystemState:
    """Crash-recovery state (reference: worker/src/state/system_state.rs —
    persisted heartbeat endpoint + p2p keypair in the platform data dir,
    enabling `--no-auto-recover`-style resume after restart).

    Persists the orchestrator heartbeat URL and the node wallet key as JSON
    under ``state_dir``; a restarted worker resumes heartbeating without
    waiting for a fresh invite.
    """

    def __init__(self, state_dir: str):
        self.state_dir = state_dir
        self.path = os.path.join(state_dir, "worker_state.json")

    def save(self, orchestrator_url: Optional[str], node_key_hex: str) -> None:
        # the file holds a private key: owner-only permissions throughout
        os.makedirs(self.state_dir, mode=0o700, exist_ok=True)
        tmp = self.path + ".tmp"
        fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, "w") as f:
            json.dump(
                {
                    "orchestrator_url": orchestrator_url,
                    "node_key_hex": node_key_hex,
                },
                f,
            )
        os.replace(tmp, self.path)  # atomic: a crash never leaves half-state

    def load(self) -> Optional[dict]:
        try:
            with open(self.path) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def clear(self) -> None:
        try:
            os.unlink(self.path)
        except OSError:
            pass


# ---------------------------------------------------------------- checks

@dataclass
class Issue:
    level: str  # "critical" | "warning"
    message: str


@dataclass
class IssueReport:
    issues: list[Issue] = field(default_factory=list)

    def add(self, level: str, message: str) -> None:
        self.issues.append(Issue(level, message))

    @property
    def critical(self) -> list[Issue]:
        return [i for i in self.issues if i.level == "critical"]


def detect_compute_specs(
    storage_path: str = "/", probe_accelerator: bool = True
) -> tuple[ComputeSpecs, IssueReport]:
    """Host introspection (checks/hardware/): CPU cores, RAM, disk; TPU/GPU
    detection via the JAX device list when available.

    ``probe_accelerator=False`` skips the jax.devices() call — backend
    initialization claims the accelerator for this process, and a
    control-plane process must not take the chip the scheduler pod owns.
    """
    report = IssueReport()
    cores = os.cpu_count() or 1
    ram_mb = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    ram_mb = int(line.split()[1]) // 1024
                    break
    except OSError:
        report.add("warning", "could not read /proc/meminfo")
    storage_gb = shutil.disk_usage(storage_path).total // (1024**3)

    # minimums (hardware_check.rs:67-95)
    if cores < 4:
        report.add("warning", f"only {cores} CPU cores (minimum 4)")
    if ram_mb < 8 * 1024:
        report.add("warning", f"only {ram_mb} MB RAM (minimum 8 GB)")
    if storage_gb < 1000:
        report.add("warning", f"only {storage_gb} GB storage (minimum 1 TB)")

    gpu = None
    if probe_accelerator:
        try:  # accelerator presence via jax, the framework's device layer
            import jax

            devs = [d for d in jax.devices() if d.platform != "cpu"]
            if devs:
                gpu = GpuSpecs(count=len(devs), model=devs[0].device_kind)
        except Exception:
            pass

    specs = ComputeSpecs(
        gpu=gpu,
        cpu=CpuSpecs(cores=cores),
        ram_mb=ram_mb,
        storage_gb=storage_gb,
        storage_path=storage_path,
    )
    return specs, report


# ---------------------------------------------------------------- runtime

class TaskRuntime(ABC):
    """Pluggable task executor (the reference's DockerService seam)."""

    @abstractmethod
    async def apply(self, task: Optional[Task], node_address: str) -> None: ...

    @abstractmethod
    def state(self) -> tuple[Optional[str], TaskState, Optional[TaskDetails]]: ...


class MockRuntime(TaskRuntime):
    """Test runtime: tracks the applied task, reports RUNNING."""

    def __init__(self):
        self.current: Optional[Task] = None
        self.applied: list[Optional[str]] = []

    async def apply(self, task, node_address):
        self.current = task
        self.applied.append(task.id if task else None)

    def state(self):
        if self.current is None:
            return None, TaskState.UNKNOWN, None
        return self.current.id, TaskState.RUNNING, TaskDetails(container_status="running")


class SubprocessRuntime(TaskRuntime):
    """Subprocess-based executor: runs ``task.cmd`` with the task's env.

    Reconcile semantics mirror docker/service.rs: a task is identified by
    id + config hash, so an env/cmd change restarts the process; failures
    get RESTART_BACKOFF_SECONDS backoff with a consecutive-failure count.
    """

    def __init__(self, socket_path: Optional[str] = None):
        self.proc: Optional[asyncio.subprocess.Process] = None
        self.current: Optional[Task] = None
        self.current_hash: Optional[str] = None
        self.last_exit: Optional[int] = None
        self.failures = 0
        self.backoff_until = 0.0
        self.socket_path = socket_path
        self.logs: list[str] = []

    async def apply(self, task: Optional[Task], node_address: str) -> None:
        new_hash = task.generate_config_hash() if task else None
        if task and self.current and task.id == self.current.id and new_hash == self.current_hash:
            if self.proc and self.proc.returncode is None:
                return  # already running the right config
            # crashed: restart with backoff (docker/service.rs:160-167)
            if time.monotonic() < self.backoff_until:
                return
        await self._stop()
        self.current, self.current_hash = task, new_hash
        if task is None or not task.cmd:
            return
        env = dict(os.environ)
        env.update(task.env_vars or {})
        env["NODE_ADDRESS"] = node_address  # service.rs:190-201
        env["PRIME_TASK_ID"] = task.id
        if self.socket_path:
            env["SOCKET_PATH"] = self.socket_path
        cmd = list(task.entrypoint or []) + list(task.cmd)
        try:
            self.proc = await asyncio.create_subprocess_exec(
                *cmd,
                env=env,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.STDOUT,
            )
            asyncio.get_running_loop().create_task(self._pump_logs(self.proc))
        except (OSError, ValueError) as e:
            self.logs.append(f"spawn failed: {e}")
            self.failures += 1
            self.backoff_until = time.monotonic() + RESTART_BACKOFF_SECONDS

    async def _pump_logs(self, proc) -> None:
        while True:
            line = await proc.stdout.readline()
            if not line:
                break
            self.logs.append(line.decode(errors="replace").rstrip())
            if len(self.logs) > 1000:
                del self.logs[:500]
        self.last_exit = await proc.wait()
        if self.last_exit != 0:
            self.failures += 1
            self.backoff_until = time.monotonic() + RESTART_BACKOFF_SECONDS
        else:
            self.failures = 0

    async def _stop(self) -> None:
        if self.proc and self.proc.returncode is None:
            self.proc.terminate()
            try:
                await asyncio.wait_for(self.proc.wait(), timeout=5)
            except asyncio.TimeoutError:
                self.proc.kill()
        self.proc = None

    def state(self):
        """Process state -> TaskState (docker/service.rs:267-281)."""
        if self.current is None:
            return None, TaskState.UNKNOWN, None
        if self.proc is None:
            st = TaskState.FAILED if self.failures else TaskState.PENDING
            return self.current.id, st, TaskDetails(exit_code=self.last_exit)
        if self.proc.returncode is None:
            return self.current.id, TaskState.RUNNING, TaskDetails(
                container_id=str(self.proc.pid), container_status="running"
            )
        st = TaskState.COMPLETED if self.proc.returncode == 0 else TaskState.FAILED
        return self.current.id, st, TaskDetails(exit_code=self.proc.returncode)


# ---------------------------------------------------------------- bridge

class TaskBridge:
    """Unix-socket JSON intake from the running workload
    (docker/taskbridge/bridge.rs). Messages, newline-or-concatenated JSON:
      {"task_id": ..., "<label>": <float>, ...}          -> metrics
      {"output": {"sha256": ..., "output_flops": N,
                  "file_name"/"save_path": ...}}          -> work submission
    """

    def __init__(self, socket_path: str, agent: "WorkerAgent"):
        self.socket_path = socket_path
        self.agent = agent
        self.server: Optional[asyncio.AbstractServer] = None
        self.seen_shas: set[str] = set()  # dedup (bridge.rs:150-156)

    async def start(self) -> None:
        os.makedirs(os.path.dirname(self.socket_path), exist_ok=True)
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)
        self.server = await asyncio.start_unix_server(self._handle, self.socket_path)
        os.chmod(self.socket_path, 0o666)

    async def stop(self) -> None:
        if self.server:
            self.server.close()
            await self.server.wait_closed()

    async def _handle(self, reader, writer) -> None:
        # stream parser for concatenated JSON objects (json_helper.rs)
        buf = ""
        decoder = json.JSONDecoder()
        while True:
            chunk = await reader.read(65536)
            if not chunk:
                break
            buf += chunk.decode(errors="replace")
            while True:
                s = buf.lstrip()
                if not s:
                    buf = ""
                    break
                try:
                    obj, end = decoder.raw_decode(s)
                except json.JSONDecodeError:
                    buf = s  # incomplete object: wait for more bytes
                    break
                await self._dispatch(obj)
                buf = s[end:]
        writer.close()

    async def _dispatch(self, obj: dict) -> None:
        if not isinstance(obj, dict):
            return
        if "output" in obj and isinstance(obj["output"], dict):
            out = obj["output"]
            sha = out.get("sha256")
            if sha and sha not in self.seen_shas:
                self.seen_shas.add(sha)
                # save_path names a file the workload wrote: read and ship
                # the bytes through the signed-URL path (the reference's
                # file_handler.rs:21-118 watches the output dir the same
                # way). Integrity-gated: bytes that don't hash to the
                # claimed sha are not uploaded — the work submission then
                # follows the bodyless best-effort path unchanged.
                data = None
                save_path = out.get("save_path")
                if save_path:

                    def _read_verified(path=save_path, want=sha):
                        # runs off the event loop: reading + hashing up
                        # to 100 MB synchronously would stall heartbeats
                        # and the control server for the whole window
                        if os.path.getsize(path) > 100 * 1024 * 1024:
                            return None, "exceeds the 100 MB upload cap"
                        with open(path, "rb") as f:
                            raw = f.read()
                        if hashlib.sha256(raw).hexdigest() != want:
                            return None, "does not hash to the claimed sha"
                        return raw, None

                    try:
                        data, why = await asyncio.to_thread(_read_verified)
                    except OSError as e:
                        data, why = None, f"unreadable: {e}"
                    if data is None and why:
                        logging.getLogger(__name__).warning(
                            "bridge output %s %s; uploading nothing",
                            save_path, why,
                        )
                await self.agent.submit_output(
                    sha=sha,
                    flops=int(out.get("output_flops", 0)),
                    file_name=out.get("file_name") or out.get("save_path") or sha,
                    data=data,
                    # colocated workloads share ONE bridge socket: the
                    # message's own task id (either placement) keeps an
                    # extra task's artifact from being attributed to the
                    # primary; absent -> current_task (legacy workloads)
                    task_id=out.get("task_id") or obj.get("task_id"),
                )
            return
        task_id = obj.get("task_id")
        if task_id:
            for key, value in obj.items():
                if key == "task_id":
                    continue
                try:
                    self.agent.metrics[(str(task_id), str(key))] = float(value)
                except (TypeError, ValueError):
                    continue


# ---------------------------------------------------------------- agent

class WorkerAgent:
    def __init__(
        self,
        provider_wallet: Wallet,
        node_wallet: Wallet,
        ledger: Ledger,
        pool_id: int,
        runtime: Optional[TaskRuntime] = None,
        compute_specs: Optional[ComputeSpecs] = None,
        ip_address: str = "127.0.0.1",
        port: int = 8091,
        http=None,  # aiohttp.ClientSession-compatible (tests inject)
        known_orchestrators: Optional[list[str]] = None,
        known_validators: Optional[list[str]] = None,
        state: Optional[SystemState] = None,
        auto_recover: bool = True,
        ipfs=None,  # utils.ipfs.IpfsMirror: best-effort artifact mirroring
        price: Optional[float] = None,
        control_scheme: str = "http",  # "https" when the control app serves TLS
        public_http=None,  # session for EXTERNAL signed-URL PUTs (GCS/S3).
        # None = reuse ``http`` (tests, plaintext devnets); "lazy" = build a
        # system-trust session on first external PUT (serve.py) so a pinned
        # deployment CA can't break GCS uploads and a worker that never
        # uploads never holds the extra session
        runtime_factory=None,  # (slot: str) -> TaskRuntime: enables
        # CONCURRENT execution of colocated assignments (heartbeat
        # assigned_tasks, ladder #5) — one runtime per extra task, slot
        # is a stable 8-hex discriminator the DockerRuntime uses to keep
        # sibling reconciles from sweeping each other's containers.
        # None = legacy single-task behavior (extras ignored)
    ):
        self.ipfs = ipfs
        # advertised ask price (cost units/hour), carried through discovery
        # into the orchestrator's batch-matcher cost term
        self.price = price
        if control_scheme not in ("http", "https"):
            raise ValueError(f"control_scheme must be http/https, got {control_scheme!r}")
        self.control_scheme = control_scheme
        self.provider_wallet = provider_wallet
        self.node_wallet = node_wallet
        self.ledger = ledger
        self.pool_id = pool_id
        self.runtime = runtime or MockRuntime()
        self.compute_specs = compute_specs
        self.ip_address = ip_address
        self.port = port
        self.http = http
        self.public_http = public_http
        self.kv = KVStore()
        self.metrics: dict[tuple[str, str], float] = {}
        self.orchestrator_url: Optional[str] = None
        self.current_task: Optional[Task] = None
        self.heartbeat_active = False
        self._discovery_rejections: set[tuple] = set()
        self.runtime_factory = runtime_factory
        self.extra_runtimes: dict[str, TaskRuntime] = {}  # task id -> runtime
        self.known_orchestrators = [a.lower() for a in (known_orchestrators or [])]
        self.known_validators = [a.lower() for a in (known_validators or [])]
        self.p2p_id = f"worker-{node_wallet.address[:10]}"
        # chain drift monitor state (stake_monitor_once)
        self._chain_state: dict[str, bool] = {}
        self._chain_error = False
        self.chain_alarms: list[str] = []
        self.deregistered = False
        self.state = state
        if state is not None and auto_recover:
            # crash recovery (cli/command.rs:832-835): resume heartbeating
            # the persisted endpoint without waiting for a new invite —
            # but only when the persisted identity IS this wallet; stale
            # state from another identity would leave the worker signing
            # beats the orchestrator never invited
            saved = state.load()
            if (
                saved
                and saved.get("orchestrator_url")
                and saved.get("node_key_hex") == node_wallet.private_key_hex()
            ):
                self.orchestrator_url = saved["orchestrator_url"]
                self.heartbeat_active = True

    # ----- boot (cli/command.rs:194-848) -----

    def check_pool_requirements(self) -> bool:
        pool = self.ledger.get_pool_info(self.pool_id)
        if not pool.pool_data_uri:
            return True
        try:
            reqs = ComputeRequirements.parse(pool.pool_data_uri)
        except ValueError:
            return True
        return self.compute_specs is not None and self.compute_specs.meets(reqs)

    def register_on_ledger(self) -> None:
        """Provider registration + stake + node registration
        (operations/provider.rs:175-331, compute_node.rs:32-115)."""
        stake = self.ledger.calculate_stake(1)
        if not self.ledger.provider_exists(self.provider_wallet.address):
            self.ledger.register_provider(self.provider_wallet.address, stake)
        if not self.ledger.node_exists(self.node_wallet.address):
            required = self.ledger.calculate_stake(
                self.ledger.get_provider_total_compute(self.provider_wallet.address) + 1
            )
            current = self.ledger.get_stake(self.provider_wallet.address)
            if current < required:
                self.ledger.increase_stake(
                    self.provider_wallet.address, required - current
                )
            self.ledger.add_compute_node(
                self.provider_wallet.address, self.node_wallet.address
            )

    def discovery_node_payload(self) -> dict:
        node = Node(
            id=self.node_wallet.address,
            provider_address=self.provider_wallet.address,
            ip_address=self.ip_address,
            port=self.port,
            compute_pool_id=self.pool_id,
            compute_specs=self.compute_specs,
            worker_p2p_id=self.p2p_id,
            worker_p2p_addresses=[
                f"{self.control_scheme}://{self.ip_address}:{self.port}/control"
            ],
            price=self.price,
        )
        return node.to_dict()

    async def upload_to_discovery(self, urls: list[str]) -> bool:
        """Signed PUT /api/nodes with multi-URL failover
        (services/discovery.rs:26-102). Rejections are logged once per
        distinct reason: a gate rejection (per-IP cap, whitelist, pool
        membership) repeats every beat forever, and a silently-invisible
        worker is an operator-hostile failure mode (a soak spent an hour
        on exactly this)."""
        payload = self.discovery_node_payload()
        for url in urls:
            headers, body = sign_request("/api/nodes", self.node_wallet, payload)
            try:
                async with self.http.put(
                    f"{url}/api/nodes", json=body, headers=headers
                ) as resp:
                    if resp.status == 200:
                        return True
                    # dedup on (url, status) only: bodies can carry
                    # per-request noise (timestamps, request ids) that
                    # would defeat the dedup AND grow the set forever on
                    # the every-beat retry loop
                    key = (url, resp.status)
                    if key not in self._discovery_rejections:
                        self._discovery_rejections.add(key)
                        logging.getLogger(__name__).warning(
                            "discovery %s rejected registration (%d): %s",
                            url, resp.status, (await resp.text())[:200],
                        )
            except Exception:
                continue
        return False

    # ----- control-plane HTTP (the libp2p-equivalent surface) -----

    def make_control_app(self) -> web.Application:
        allowed = set(self.known_orchestrators + self.known_validators)
        if not allowed:
            # Fail closed: with no configured orchestrator/validator
            # allowlist, derive it from the substrate exactly like the
            # reference (cli/command.rs:717-734): pool creator + compute
            # manager + every wallet holding the validator role
            # (prime_network.get_validator_role) — never "any valid
            # signature". If the lookup fails the surface rejects all.
            try:
                pool = self.ledger.get_pool_info(self.pool_id)
                allowed = {pool.creator, pool.compute_manager_key}
                allowed.update(self.ledger.get_validator_role())
            except Exception:
                allowed = set()
        app = web.Application(
            middlewares=[
                validate_signature_middleware(
                    self.kv, ["/control"], allowed_addresses=allowed
                )
            ]
        )
        app.router.add_post("/control/invite", self.handle_invite)
        app.router.add_post("/control/challenge", self.handle_challenge)
        app.router.add_get("/control/logs", self.handle_logs)
        app.router.add_post("/control/restart", self.handle_restart)
        return app

    async def handle_invite(self, request: web.Request) -> web.Response:
        """Verify + accept a pool invite (worker/src/p2p/mod.rs:396-497):
        join the pool on the ledger from the provider wallet, then start
        heartbeating the invite URL."""
        body = request.get("auth_body") or {}
        try:
            pool_id = int(body["pool_id"])
            nonce = str(body["invite_nonce"])
            expiration = float(body["expiration"])
            signature = str(body["invite_signature"])
            heartbeat_url = str(body["heartbeat_url"])
        except (KeyError, ValueError):
            return web.json_response(
                {"success": False, "error": "malformed invite"}, status=400
            )
        if pool_id != self.pool_id:
            return web.json_response(
                {"success": False, "error": "wrong pool"}, status=400
            )
        try:
            self.ledger.join_compute_pool(
                pool_id,
                self.provider_wallet.address,
                self.node_wallet.address,
                nonce,
                expiration,
                signature,
            )
        except LedgerError as e:
            if "already in a pool" not in str(e):
                return web.json_response(
                    {"success": False, "error": str(e)}, status=400
                )
        self.orchestrator_url = heartbeat_url
        self.heartbeat_active = True
        if self.state is not None:
            self.state.save(heartbeat_url, self.node_wallet.private_key_hex())
        return web.json_response({"success": True})

    async def handle_challenge(self, request: web.Request) -> web.Response:
        """Hardware challenge: dense matmul computed on this worker's
        accelerator via jnp (the reference's nalgebra calc_matrix,
        p2p/src/message/hardware_challenge.rs:74-89, made device-native)."""
        body = request.get("auth_body") or {}
        import numpy as np

        from protocol_tpu.utils import fixedf64

        fixed_wire = "matrix_a_fixed" in body
        try:
            if fixed_wire:
                # FixedF64 wire (utils/fixedf64.py — a deliberate Q31.32
                # deviation from hardware_challenge.rs's decimal-string
                # wire, equivalent determinism; see PARITY.md): decode to
                # the bit-exact float64s the validator encoded
                a = fixedf64.decode_array(body["matrix_a_fixed"]).astype(np.float32)
                b = fixedf64.decode_array(body["matrix_b_fixed"]).astype(np.float32)
            else:  # legacy float-JSON wire
                a = np.asarray(body["matrix_a"], np.float32)
                b = np.asarray(body["matrix_b"], np.float32)
        except (KeyError, ValueError, TypeError):
            return web.json_response(
                {"success": False, "error": "missing matrices"}, status=400
            )

        def compute():
            # device work off the event loop: jax calls are synchronous and
            # must not stall the control plane if the accelerator is slow
            import jax.numpy as jnp

            return np.asarray(jnp.asarray(a) @ jnp.asarray(b))

        result = await asyncio.to_thread(compute)
        if fixed_wire:
            try:
                encoded = fixedf64.encode_array(result)
            except ValueError:
                # adversarially-huge (but decodable) inputs can overflow
                # the float32 matmul to inf/nan — a clean rejection, not
                # a 500
                return web.json_response(
                    {"success": False, "error": "non-finite result"},
                    status=400,
                )
            return web.json_response({"success": True, "result_fixed": encoded})
        return web.json_response({"success": True, "result": result.tolist()})

    async def handle_logs(self, request: web.Request) -> web.Response:
        fetch = getattr(self.runtime, "get_logs", None)
        logs = await fetch() if fetch is not None else getattr(self.runtime, "logs", [])
        return web.json_response({"success": True, "logs": logs[-100:]})

    async def handle_restart(self, request: web.Request) -> web.Response:
        restart = getattr(self.runtime, "restart_task", None)
        if restart is not None:
            # runtimes with an in-place restart (DockerRuntime -> docker
            # restart, service.rs:332-343) keep the container identity and
            # avoid the remove->backoff window a stop/start cycle would hit
            await restart()
        elif self.current_task is not None:
            await self.runtime.apply(None, self.node_wallet.address)
            await self.runtime.apply(self.current_task, self.node_wallet.address)
        return web.json_response({"success": True})

    # ----- heartbeat (operations/heartbeat/service.rs:140-293) -----

    # ----- stake / chain-event monitor (provider.rs:47-147,
    # compute_node.rs:32-115) -----

    def stake_monitor_once(self) -> list[str]:
        """One tick of the reference's continuous provider monitors:
        re-check stake sufficiency, whitelist status, node registration,
        and pool membership. Returns the NEW alarms (True->False
        transitions since the previous tick — levels alone would re-alarm
        every tick), accumulates them on ``self.chain_alarms``, and stops
        heartbeating when the node itself was deregistered on-chain.

        The reference registers once at boot and then watches drift in
        dedicated loops; round 2 of this framework only did the former, so
        a mid-run slash went unnoticed by the worker (VERDICT r2 item 8).
        """
        state: dict[str, bool] = {}
        alarms: list[str] = []
        provider = self.provider_wallet.address
        node = self.node_wallet.address
        try:
            units = max(self.ledger.get_provider_total_compute(provider), 1)
            required = self.ledger.calculate_stake(units)
            current = self.ledger.get_stake(provider)
            state["stake_sufficient"] = current >= required
            state["whitelisted"] = self.ledger.is_provider_whitelisted(provider)
            state["node_registered"] = self.ledger.node_exists(node)
            state["in_pool"] = self.ledger.is_node_in_pool(self.pool_id, node)
        except Exception as e:
            # transition-deduped like the drift alarms: a weekend-long
            # ledger outage must not grow chain_alarms unboundedly
            if not self._chain_error:
                self._chain_error = True
                alarms.append(f"chain monitor error: {e}")
                self._record_alarms(alarms)
            return alarms
        self._chain_error = False

        detail = {
            "stake_sufficient": (
                f"stake {current} below required {required} "
                "(slashed or reclaimed?)"
            ),
            "whitelisted": "provider whitelist revoked",
            "node_registered": "compute node deregistered on-chain",
            "in_pool": "node no longer in pool (ejected?)",
        }
        prev = self._chain_state
        if not prev:
            # first tick establishes the baseline: a worker that boots
            # before its invite is legitimately not in a pool yet — only
            # True -> False TRANSITIONS are drift
            self._chain_state = state
            return []
        for key, msg in detail.items():
            if prev.get(key, True) and not state[key]:
                alarms.append(msg)
        self._chain_state = state
        if alarms:
            self._record_alarms(alarms)
        if prev.get("node_registered", True) and not state["node_registered"]:
            # a deregistered node signing heartbeats would just be rejected
            # by the orchestrator's validator — stop cleanly instead (the
            # serve loop exits on this flag)
            self.heartbeat_active = False
            self.deregistered = True
        return alarms

    def _record_alarms(self, alarms: list[str]) -> None:
        for a in alarms:
            logging.getLogger(__name__).warning("worker chain alarm: %s", a)
        self.chain_alarms.extend(alarms)
        del self.chain_alarms[:-100]  # bounded history

    def _host_load(self) -> float:
        """Self-reported host utilization 0..1 (1-min loadavg over cores),
        shipped with every heartbeat. External to the pool's own assignment
        state on purpose: the matcher's load cost term must not feed back
        into the solve that produces it."""
        try:
            return min(os.getloadavg()[0] / max(os.cpu_count() or 1, 1), 1.0)
        except OSError:
            return 0.0

    def _collect_metrics(self) -> list[dict]:
        return [
            {"key": {"task_id": tid, "label": label}, "value": value}
            for (tid, label), value in self.metrics.items()
        ]

    async def heartbeat_once(self) -> Optional[Task]:
        if not self.heartbeat_active or not self.orchestrator_url:
            return None
        task_id, task_state, details = self.runtime.state()
        payload = {
            "address": self.node_wallet.address,
            "task_id": task_id,
            "task_state": task_state.value if task_state else None,
            "metrics": self._collect_metrics(),
            "version": "0.1.0",
            "timestamp": time.time(),
            "p2p_id": self.p2p_id,
            "p2p_addresses": [
                f"{self.control_scheme}://{self.ip_address}:{self.port}/control"
            ],
            "task_details": details.to_dict() if details else None,
            "load": self._host_load(),
        }
        if self.extra_runtimes:
            # colocated extras report alongside the primary task (additive
            # field; the orchestrator's FSM keys off the primary)
            states: dict[str, Optional[str]] = {}
            for tid, rt in self.extra_runtimes.items():
                _tid, st, _details = rt.state()
                states[tid] = st.value if st else None
            payload["extra_task_states"] = states
        headers, body = sign_request("/heartbeat", self.node_wallet, payload)
        try:
            async with self.http.post(
                f"{self.orchestrator_url}/heartbeat", json=body, headers=headers
            ) as resp:
                if resp.status != 200:
                    return None
                data = await resp.json()
        except Exception:
            return None

        body_data = data.get("data") or {}
        task_dict = body_data.get("current_task")
        new_task = Task.from_dict(task_dict) if task_dict else None
        old_id = self.current_task.id if self.current_task else None
        if (new_task.id if new_task else None) != old_id:
            # metrics reset on task switch (:267-280) — but ONLY the
            # departing primary's entries: colocated extras are still
            # running and their queued samples must survive the swap
            for key in [k for k in self.metrics if k[0] == old_id]:
                del self.metrics[key]
        self.current_task = new_task
        await self.runtime.apply(new_task, self.node_wallet.address)
        if self.runtime_factory is not None:
            # colocated extras (ladder #5): every assigned task beyond
            # the primary runs CONCURRENTLY in its own runtime; without a
            # factory, legacy single-task behavior (extras ignored)
            primary_id = new_task.id if new_task else None
            extras = [
                Task.from_dict(d)
                for d in body_data.get("assigned_tasks") or []
                if d.get("id") != primary_id
            ]
            await self._apply_extra_tasks(extras)
        return new_task

    async def _apply_extra_tasks(self, extras: list[Task]) -> None:
        """Reconcile the per-task extra runtimes against the assignment:
        new colocated tasks get a fresh runtime, departed ones are stopped
        and their runtime dropped (same apply(None) semantics the primary
        runtime uses for task switches)."""
        want = {t.id: t for t in extras}
        for tid in [t for t in self.extra_runtimes if t not in want]:
            rt = self.extra_runtimes.pop(tid)
            try:
                await rt.apply(None, self.node_wallet.address)
            except Exception:
                logging.getLogger(__name__).exception(
                    "stopping colocated task %s failed", tid
                )
        for tid, task in want.items():
            rt = self.extra_runtimes.get(tid)
            if rt is None:
                slot = hashlib.sha256(tid.encode()).hexdigest()[:8]
                rt = self.extra_runtimes[tid] = self.runtime_factory(slot)
            try:
                await rt.apply(task, self.node_wallet.address)
            except Exception:
                logging.getLogger(__name__).exception(
                    "applying colocated task %s failed", tid
                )

    # ----- bridge output -> upload + work submission -----

    def _upload_session(self, url: str):
        """Pick the trust root by the signed URL's DESTINATION: an
        orchestrator-origin URL (LocalDirStorageProvider's /storage/upload
        route) is a control-plane peer behind the pinned CA, while GCS/S3
        signed URLs are public hosts under system trust — one session
        cannot verify both."""
        if self.orchestrator_url:
            from urllib.parse import urlsplit

            # compare scheme://host:port, not a raw string prefix: an
            # orchestrator at https://orch:80 must not capture
            # https://orch:8090/... (which is a different, public origin).
            # Ports normalized so an explicit :443/:80 matches the default.
            def origin(s):
                u = urlsplit(s)
                default = {"https": 443, "http": 80}.get(u.scheme)
                return (u.scheme, u.hostname, u.port or default)

            if origin(self.orchestrator_url) == origin(url):
                return self.http
        if self.public_http == "lazy":
            from protocol_tpu.utils.tls import public_client_session

            self.public_http = public_client_session()
        return self.public_http if self.public_http is not None else self.http

    async def submit_output(
        self,
        sha: str,
        flops: int,
        file_name: str,
        data: Optional[bytes] = None,
        max_retries: int = 5,
        task_id: Optional[str] = None,
    ) -> bool:
        """Upload the artifact then submit the work key on the ledger
        (docker/taskbridge/file_handler.rs:21-118): request a signed URL
        from the orchestrator with exponential-backoff retries, PUT the
        bytes through it, then submitWork(sha, flops). With no ``data``
        the URL request is best-effort (the workload may upload out of
        band) and the work is submitted regardless."""
        if data is not None and (not self.orchestrator_url or self.http is None):
            return False  # nowhere to upload: no artifact -> no work claim
        if self.orchestrator_url and self.http is not None:
            payload = {
                "file_name": file_name,
                "file_size": len(data) if data is not None else 0,
                "file_type": "application/octet-stream",
                "sha256": sha,
                "task_id": task_id
                or (self.current_task.id if self.current_task else None),
            }

            class _Fatal(Exception):
                """Deterministic 4xx: retrying re-signs the same doomed
                request (and 429 retries dig the rate-limit hole deeper)."""

            for attempt in range(max_retries):
                try:
                    headers, body = sign_request(
                        "/storage/request-upload", self.node_wallet, payload
                    )
                    async with self.http.post(
                        f"{self.orchestrator_url}/storage/request-upload",
                        json=body,
                        headers=headers,
                    ) as resp:
                        if 400 <= resp.status < 500:
                            raise _Fatal(f"request-upload {resp.status}")
                        if resp.status != 200:
                            raise RuntimeError(
                                f"request-upload {resp.status}"
                            )
                        url = (await resp.json())["data"]["signed_url"]
                    if data is not None:
                        async with self._upload_session(url).put(
                            url,
                            data=data,
                            headers={"Content-Length": str(len(data))},
                        ) as up:
                            if 400 <= up.status < 500 and up.status not in (408, 429):
                                raise _Fatal(f"upload {up.status}")
                            if up.status not in (200, 201):
                                raise RuntimeError(f"upload {up.status}")
                        if self.ipfs is not None:
                            # best-effort mirror, never blocks the primary
                            # path (file_handler.rs:109-118)
                            await self.ipfs.add(data, file_name=file_name)
                    break
                except _Fatal:
                    if data is not None:
                        return False  # no artifact -> no work claim
                    break  # bodyless legacy path stays best-effort
                except Exception:
                    if attempt == max_retries - 1:
                        if data is not None:
                            return False
                        break
                    await asyncio.sleep(min(0.1 * 2**attempt, 2.0))
        try:
            self.ledger.submit_work(self.pool_id, self.node_wallet.address, sha, flops)
            return True
        except LedgerError:
            return False
