"""DockerRuntime lifecycle against the fake docker CLI: confighash
identity, env/volume injection, stale removal, state mapping, restart
backoff (reference worker/src/docker/service.rs:56-295,
docker_manager.rs)."""

import pytest

# Environment guard: this module's import chain reaches
# protocol_tpu.security / protocol_tpu.utils.tls, which need the
# third-party `cryptography` package (wallet signing + TLS material).
# On hosts without it, report the whole module as SKIPPED instead of a
# collection error (tier-1 keeps an honest skip count; CI installs
# cryptography and runs everything).
pytest.importorskip(
    "cryptography", reason="cryptography not installed (signing/TLS dependency)"
)

import asyncio
import json
import os
import stat
import sys

import pytest

from protocol_tpu.models.task import Task, TaskState, VolumeMount
from protocol_tpu.services.docker_runtime import DockerRuntime


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


@pytest.fixture()
def fake_docker(tmp_path):
    """Wrapper script invoking tests/fake_docker.py with a per-test state
    file; returns (docker_bin_path, state_loader)."""
    state_file = tmp_path / "docker_state.json"
    script = tmp_path / "docker"
    fake = os.path.join(os.path.dirname(__file__), "fake_docker.py")
    # -S skips site-packages setup: the fake needs only the stdlib, and
    # it is spawned once per docker call
    script.write_text(
        "#!/bin/sh\n"
        f"FAKE_DOCKER_STATE={str(state_file)!r} "
        f"exec {sys.executable} -S {fake!r} \"$@\"\n"
    )
    script.chmod(script.stat().st_mode | stat.S_IEXEC)

    def state():
        return json.loads(state_file.read_text())

    state.path = str(state_file)
    return str(script), state


def make_task(tid="t1", image="busybox", cmd=None, env=None, volumes=None):
    return Task(
        id=tid,
        name=f"task-{tid}",
        image=image,
        cmd=cmd or ["echo", "hi"],
        env_vars=env or {},
        volume_mounts=volumes,
    )


def test_start_injects_identity_env_volumes(fake_docker, tmp_path):
    docker_bin, state = fake_docker
    rt = DockerRuntime(
        socket_path=str(tmp_path / "sock" / "metrics.sock"),
        docker_bin=docker_bin,
        system_memory_mb=1024,
    )
    task = make_task(
        env={"FOO": "x", "SOCK": "${SOCKET_PATH}"},
        cmd=["serve", "--sock", "${SOCKET_PATH}"],
        volumes=[VolumeMount(host_path="/data/in", container_path="/in")],
    )
    run(rt.apply(task, "0xnode"))

    name = rt.container_name(task)
    assert name.startswith("prime-task-") and "-t1-" in name
    c = state()["containers"][name]
    sock = str(tmp_path / "sock" / "metrics.sock")
    # ${SOCKET_PATH} expanded in env values and cmd (service.rs:185-201)
    assert c["env"]["SOCK"] == sock
    assert c["cmd"] == ["serve", "--sock", sock]
    assert c["env"]["NODE_ADDRESS"] == "0xnode"
    assert c["env"]["PRIME_TASK_ID"] == "t1"
    assert c["env"]["PRIME_MONITOR__SOCKET__PATH"] == sock
    # socket dir + task volumes mounted (service.rs:203-221)
    sock_dir = os.path.dirname(sock)
    assert f"{sock_dir}:{sock_dir}" in c["volumes"]
    assert "/data/in:/in" in c["volumes"]
    # shm = RAM/2 (service.rs:222-228)
    assert ("--shm-size", str(1024 * 1024 * 1024 // 2)) in [
        tuple(f) for f in c["flags"]
    ]
    # host networking default (docker_manager.rs:397-401)
    assert ("--network", "host") in [tuple(f) for f in c["flags"]]

    tid, ts, details = rt.state()
    assert (tid, ts) == ("t1", TaskState.RUNNING)
    assert details.container_status == "running"
    assert run(rt.get_logs())  # logs fetched on demand, not per tick


def test_config_change_replaces_container(fake_docker):
    docker_bin, state = fake_docker
    rt = DockerRuntime(docker_bin=docker_bin)
    t1 = make_task(env={"V": "1"})
    run(rt.apply(t1, "0xn"))
    old_name = rt.container_name(t1)
    assert old_name in state()["containers"]

    # same task id, new env -> new confighash -> old container removed
    t2 = make_task(env={"V": "2"})
    rt.last_started = 0.0  # get past the restart backoff
    run(rt.apply(t2, "0xn"))
    new_name = rt.container_name(t2)
    assert new_name != old_name
    containers = state()["containers"]
    assert new_name in containers and old_name not in containers


def test_stale_containers_removed_and_none_clears(fake_docker):
    docker_bin, state = fake_docker
    rt = DockerRuntime(docker_bin=docker_bin)
    t1 = make_task(tid="a")
    run(rt.apply(t1, "0xn"))
    assert state()["containers"]
    run(rt.apply(None, "0xn"))
    assert state()["containers"] == {}
    assert rt.state() == (None, TaskState.UNKNOWN, None)


def test_exit_code_maps_to_completed_or_failed(fake_docker):
    docker_bin, state = fake_docker
    rt = DockerRuntime(docker_bin=docker_bin)

    done = make_task(tid="ok", env={"FAKE_EXIT": "0"})
    run(rt.apply(done, "0xn"))
    _, ts, details = rt.state()
    assert ts == TaskState.COMPLETED and details.exit_code == 0

    rt2 = DockerRuntime(docker_bin=docker_bin)
    bad = make_task(tid="bad", env={"FAKE_EXIT": "3"})
    run(rt2.apply(bad, "0xn"))
    _, ts2, details2 = rt2.state()
    assert ts2 == TaskState.FAILED and details2.exit_code == 3
    assert rt2.failures == 1
    # failure count rises only on state CHANGES (service.rs:283-295);
    # within the backoff window the crashed container is left in place
    run(rt2.apply(bad, "0xn"))
    assert rt2.failures == 1
    assert rt2.state()[1] == TaskState.FAILED

    # past the backoff, the crashed container is removed and restarted
    rt2.last_started = 0.0
    run(rt2.apply(bad, "0xn"))
    # fake docker restarts it with FAKE_EXIT again -> exited; the failure
    # transition FAILED->FAILED doesn't double count, but the restart
    # attempt happened (a fresh container id)
    _, ts3, details3 = rt2.state()
    assert ts3 == TaskState.FAILED
    assert details3.container_id != details2.container_id


def test_restart_backoff_blocks_immediate_restart(fake_docker):
    docker_bin, state = fake_docker
    rt = DockerRuntime(docker_bin=docker_bin)
    task = make_task(tid="r")
    run(rt.apply(task, "0xn"))
    name = rt.container_name(task)

    # container vanishes (e.g. external rm); within backoff -> PENDING,
    # no restart attempt
    s = state()
    del s["containers"][name]
    with open(state.path, "w") as f:
        json.dump(s, f)

    run(rt.apply(task, "0xn"))
    assert rt.state()[1] == TaskState.PENDING
    assert name not in state()["containers"]

    # past the backoff -> restarted
    rt.last_started = 0.0
    run(rt.apply(task, "0xn"))
    assert name in state()["containers"]
    assert rt.state()[1] == TaskState.RUNNING


def test_explicit_restart_and_gpu_flag(fake_docker):
    docker_bin, state = fake_docker
    rt = DockerRuntime(docker_bin=docker_bin, gpu_device_ids=["0", "1"])
    task = make_task(tid="g", env={"FAKE_EXIT": "1"})
    run(rt.apply(task, "0xn"))
    name = rt.container_name(task)
    c = state()["containers"][name]
    assert ("--gpus", "device=0,1") in [tuple(f) for f in c["flags"]]

    run(rt.restart_task())
    assert state()["containers"][name]["status"] == "running"


def test_two_workers_share_daemon_without_mutual_teardown(fake_docker):
    """Workers sharing one dockerd (devnet) must not reconcile away each
    other's containers: identity is scoped per node address."""
    docker_bin, state = fake_docker
    rt_a = DockerRuntime(docker_bin=docker_bin)
    rt_b = DockerRuntime(docker_bin=docker_bin)
    ta, tb = make_task(tid="a"), make_task(tid="b")
    run(rt_a.apply(ta, "0xaaaa1111"))
    run(rt_b.apply(tb, "0xbbbb2222"))
    # both containers alive after each side reconciles again
    run(rt_a.apply(ta, "0xaaaa1111"))
    run(rt_b.apply(tb, "0xbbbb2222"))
    names = set(state()["containers"])
    assert rt_a.container_name(ta) in names
    assert rt_b.container_name(tb) in names
    assert rt_a.state()[1] == TaskState.RUNNING
    assert rt_b.state()[1] == TaskState.RUNNING


def test_entrypoint_without_cmd_gets_no_sleep_fallback(fake_docker):
    docker_bin, state = fake_docker
    rt = DockerRuntime(docker_bin=docker_bin)
    task = make_task(tid="e", cmd=[])
    task.cmd = None
    task.entrypoint = ["/app/run.sh"]
    run(rt.apply(task, "0xn"))
    c = state()["containers"][rt.container_name(task)]
    assert c["entrypoint"] == "/app/run.sh"
    assert c["cmd"] == []  # no bogus "sleep infinity" args to the entrypoint


def test_docker_unavailable_reports_unknown_not_stale(fake_docker, tmp_path):
    docker_bin, state = fake_docker
    rt = DockerRuntime(docker_bin=docker_bin)
    t1 = make_task(tid="s1")
    run(rt.apply(t1, "0xn"))
    assert rt.state()[1] == TaskState.RUNNING

    # daemon dies; a new task is applied: state must not echo t1's RUNNING
    rt.cli.docker_bin = str(tmp_path / "missing-docker")
    t2 = make_task(tid="s2")
    run(rt.apply(t2, "0xn"))
    tid, ts, details = rt.state()
    assert (tid, ts) == ("s2", TaskState.UNKNOWN)
    assert any("docker unavailable" in line for line in rt.logs)


def test_worker_agent_heartbeat_with_docker_runtime(fake_docker):
    """DockerRuntime behind the real WorkerAgent heartbeat application
    path (the e2e seam MockRuntime covers elsewhere)."""
    from protocol_tpu.services.worker import WorkerAgent
    from protocol_tpu.security import Wallet
    from protocol_tpu.chain import Ledger

    docker_bin, state = fake_docker
    ledger = Ledger()
    provider, node = Wallet.from_seed(b"dp"), Wallet.from_seed(b"dn")
    ledger.mint(provider.address, 1000)
    did = ledger.create_domain("d")
    creator, manager = Wallet.from_seed(b"dc"), Wallet.from_seed(b"dm")
    pid = ledger.create_pool(did, creator.address, manager.address, "")
    ledger.register_provider(provider.address, 100)
    ledger.add_compute_node(provider.address, node.address)

    rt = DockerRuntime(docker_bin=docker_bin)
    agent = WorkerAgent(provider, node, ledger, pid, runtime=rt)
    task = make_task(tid="hb")
    run(agent.runtime.apply(task, agent.node_wallet.address))
    tid, ts, details = agent.runtime.state()
    assert (tid, ts) == ("hb", TaskState.RUNNING)
    assert details.container_id.startswith("cid-")


def test_colocated_slots_do_not_sweep_each_other(fake_docker):
    """Ladder #5 on docker: a node's primary (slotless) and colocated
    extra (slotted) runtimes share one scope; each one's stale-container
    reconcile must never remove the sibling's container, and a departing
    extra's apply(None) must clean ONLY its own slot."""
    docker_bin, state = fake_docker
    addr = "0xabcdef0123456789"
    primary = DockerRuntime(docker_bin=docker_bin)
    extra = DockerRuntime(docker_bin=docker_bin, slot="c0ffee12")
    ta, tb = make_task(tid="aaaa1111"), make_task(tid="bbbb2222")

    run(primary.apply(ta, addr))
    run(extra.apply(tb, addr))
    names = set(state()["containers"])
    assert primary.container_name(ta) in names
    assert extra.container_name(tb) in names
    assert "s" + extra.slot + "-" in extra.container_name(tb)

    # reconcile ticks on BOTH sides: nothing of the sibling's is removed
    run(primary.reconcile_once(addr))
    run(extra.reconcile_once(addr))
    names = set(state()["containers"])
    assert primary.container_name(ta) in names
    assert extra.container_name(tb) in names

    # departing extra: apply(None) sweeps its own slot only
    run(extra.apply(None, addr))
    names = set(state()["containers"])
    assert extra.container_name(tb) not in names
    assert primary.container_name(ta) in names

    # primary task switch: its own old container goes, the (readded)
    # extra's survives. Zero the restart backoff so the re-starts happen
    # on THIS tick (the deferral is orthogonal to slot isolation).
    extra.last_started = 0.0
    run(extra.apply(tb, addr))
    tc = make_task(tid="cccc3333")
    primary.last_started = 0.0
    run(primary.apply(tc, addr))
    names = set(state()["containers"])
    assert primary.container_name(ta) not in names
    assert primary.container_name(tc) in names
    assert extra.container_name(tb) in names
