"""One process for each chip: what rpc/cluster.launch hands its children.

A chip belongs to one process at a time. The launcher therefore pins
the store server to the CPU backend by role, lets a service host inherit
the platform untouched (no default to the CPU: a host on a TPU machine
must land on the TPU or fail), and refuses — before anything starts — a
fleet in which two processes would open the same accelerator.
"""
import pytest

from cadence_tpu.rpc import cluster

SERVING = {"CADENCE_TPU_SERVING": "1"}


def _envs(num_hosts, env_extra=None, env_per_role=None):
    envs = {f"host-{i}": ("host", cluster.child_env(
        f"host-{i}", "host", env_extra, env_per_role))
        for i in range(num_hosts)}
    envs["store"] = ("store", cluster.child_env(
        "store", "store", env_extra, env_per_role))
    return envs


@pytest.mark.parametrize("launcher_platform", [None, "tpu", "cpu"])
def test_store_is_pinned_by_role_and_hosts_inherit(monkeypatch,
                                                   launcher_platform):
    if launcher_platform is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", launcher_platform)
    envs = _envs(1)
    assert envs["store"][1]["JAX_PLATFORMS"] == "cpu"
    assert envs["host-0"][1].get("JAX_PLATFORMS") == launcher_platform


def test_two_device_tier_hosts_on_one_chip_are_refused(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError) as err:
        cluster.check_one_process_per_chip(_envs(2, env_extra=SERVING))
    assert "host-0, host-1" in str(err.value)
    assert "a chip belongs to one process" in str(err.value)


def test_launch_refuses_before_it_starts_anything(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    started = []
    monkeypatch.setattr(cluster.subprocess, "Popen",
                        lambda *a, **k: started.append(a))
    with pytest.raises(ValueError, match="one process per chip"):
        cluster.launch(num_hosts=2, env_extra=SERVING)
    with pytest.raises(ValueError, match="one process per chip"):
        cluster.launch_group(num_hosts=1, env_extra=SERVING)
    assert started == []


def test_a_fleet_with_the_others_on_the_cpu_passes(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    cluster.check_one_process_per_chip(_envs(
        2, env_extra=SERVING,
        env_per_role={"host-1": {"JAX_PLATFORMS": "cpu"}}))


@pytest.mark.parametrize("per_role", [
    {"host-0": {"TPU_VISIBLE_DEVICES": "0"},
     "host-1": {"TPU_VISIBLE_DEVICES": "1"}},
    {"host": {"TPU_VISIBLE_DEVICES": "0"}},
], ids=["a-chip-each", "the-same-chip"])
def test_naming_chips_exempts_no_fleet(monkeypatch, per_role):
    """Two accelerator-taking processes are refused whatever chips they
    name: no run has shown two hosts of this launcher side by side on
    one machine's chips, so the launcher promises no such mode."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError, match="one process per chip"):
        cluster.check_one_process_per_chip(
            _envs(2, env_extra=SERVING, env_per_role=per_role))


def test_cpu_fleets_and_tierless_hosts_never_collide(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cluster.check_one_process_per_chip(_envs(3, env_extra=SERVING))
    monkeypatch.delenv("JAX_PLATFORMS")
    cluster.check_one_process_per_chip(_envs(3))  # no device tier at all


def test_store_visibility_tier_and_serving_host_cannot_share(monkeypatch):
    """The store server's device view runs on ITS backend: pinned to the
    CPU by role it never collides; moved onto the accelerator next to a
    serving host it is the second process on the chip."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    vis = {"store": {"CADENCE_TPU_VISIBILITY": "1"}}
    cluster.check_one_process_per_chip(
        _envs(1, env_extra=SERVING, env_per_role=vis))
    vis["store"]["JAX_PLATFORMS"] = "tpu"
    with pytest.raises(ValueError) as err:
        cluster.check_one_process_per_chip(
            _envs(1, env_extra=SERVING, env_per_role=vis))
    assert "host-0, store" in str(err.value)


def test_store_server_states_the_backend_of_its_visibility_view():
    """Pinned to the CPU by role, the store server's device view scans on
    XLA's CPU backend even on a machine with a chip: the process says so
    on its error stream before it listens."""
    import subprocess
    import sys

    port = cluster.free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "cadence_tpu.rpc.storeserver",
         "--port", str(port)],
        env=cluster.child_env("store", "store", env_per_role={
            "store": {"CADENCE_TPU_VISIBILITY": "1"}}),
        stderr=subprocess.PIPE, text=True)
    try:
        cluster._wait_listening(port, proc)
    finally:
        proc.kill()
    stated = [line for line in proc.communicate()[1].splitlines()
              if line.startswith("cadence-tpu-store:")]
    assert len(stated) == 1 and "backend cpu (" in stated[0], stated
