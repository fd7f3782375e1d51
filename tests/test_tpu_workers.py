"""Workers that hold chips (core/node_agent.py + accelerators/tpu.py).

Host logic only: the chips are faked with RT_NUM_TPUS=4, nothing here
touches a JAX backend. What a chip run cannot be without: a ``tpu``
worker is started with exactly its chips visible and the compile cache
placed, every other worker is pinned to the CPU, chip ids are handed out
disjointly, a fifth holder waits, and an id is reused only after its
holder is gone.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu.accelerators import tpu as tpu_mod
from ray_tpu.core.node_agent import worker_spawn_env


def test_tpu_worker_env_carries_chips_and_cache():
    env = worker_spawn_env({"PATH": "/bin"}, "tpu", chips=(2,), host_chips=4)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    # a subset of the host needs the process bounds, or libtpu expects
    # the whole 2x2 and refuses to start
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env[tpu_mod.COMPILE_CACHE_ENV] == tpu_mod.DEFAULT_COMPILE_CACHE_DIR
    assert "JAX_PLATFORMS" not in env

    whole = worker_spawn_env({}, "tpu", chips=(0, 1, 2, 3), host_chips=4)
    assert whole["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert "TPU_PROCESS_BOUNDS" not in whole  # the host's own topology

    with pytest.raises(ValueError):
        worker_spawn_env({}, "tpu", chips=(0, 1), host_chips=4)


def test_cpu_worker_env_is_pinned_to_cpu():
    # even when the agent's own environment names the TPU first
    env = worker_spawn_env({"JAX_PLATFORMS": "tpu,cpu"}, "cpu")
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env
    assert tpu_mod.COMPILE_CACHE_ENV not in env


def test_compile_cache_dir_from_outside_is_untouched():
    base = {tpu_mod.COMPILE_CACHE_ENV: "/some/dir", "JAX_PLATFORMS": "tpu,cpu"}
    env = worker_spawn_env(base, "tpu", chips=(0,), host_chips=1)
    assert env[tpu_mod.COMPILE_CACHE_ENV] == "/some/dir"
    assert env["JAX_PLATFORMS"] == "tpu,cpu"  # inherited, never rewritten
    # the fixed default is inside the checkout, not under a session dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert tpu_mod.DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")


def test_tpu_allowed_by_env():
    assert tpu_mod.tpu_allowed_by_env({})
    assert tpu_mod.tpu_allowed_by_env({"JAX_PLATFORMS": "tpu,cpu"})
    assert not tpu_mod.tpu_allowed_by_env({"JAX_PLATFORMS": "cpu"})


@pytest.fixture
def four_chips(monkeypatch):
    monkeypatch.setenv("RT_NUM_TPUS", "4")
    ray_tpu.init(num_cpus=2)
    from ray_tpu.core import worker as worker_mod

    yield worker_mod.global_worker().agent
    ray_tpu.shutdown()


def _lease(agent, wait_s):
    return agent.call(
        "lease_worker", resources={"TPU": 1.0}, wait_s=wait_s,
        timeout_s=wait_s + 30.0,
    )


def _chips_of(agent, lease):
    state = agent.call("get_state")
    wid = state["leases"][lease["lease_id"]]["worker_id"]
    return tuple(state["workers"][wid]["chips"]), state["workers"][wid]["pid"]


def test_chip_ids_disjoint_fifth_waits_released_id_reused(four_chips):
    agent = four_chips
    assert agent.call("get_state")["tpu_chips_free"] == [0, 1, 2, 3]
    leases = [_lease(agent, 30.0) for _ in range(4)]
    assert all(l.get("granted") for l in leases), leases
    held = [_chips_of(agent, l) for l in leases]
    assert sorted(c for chips, _ in held for c in chips) == [0, 1, 2, 3]
    assert len({pid for _, pid in held}) == 4  # one process per chip
    assert agent.call("get_state")["tpu_chips_free"] == []

    # every chip has a holder: a fifth lease waits and times out
    fifth = _lease(agent, 1.0)
    assert not fifth.get("granted"), fifth

    # release is not kill=True, and still the holder is not parked idle:
    # its process goes, and only then does its id go to the next lease
    chips, old_pid = held[1]
    assert agent.call("release_worker", lease_id=leases[1]["lease_id"])
    fifth = _lease(agent, 30.0)
    assert fifth.get("granted"), fifth
    new_chips, new_pid = _chips_of(agent, fifth)
    assert new_chips == chips
    assert new_pid != old_pid
    with pytest.raises(ProcessLookupError):
        os.kill(old_pid, 0)  # reaped before its chip was handed on

    for l in leases[:1] + leases[2:] + [fifth]:
        agent.call("release_worker", lease_id=l["lease_id"], kill=True)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if agent.call("get_state")["tpu_chips_free"] == [0, 1, 2, 3]:
            break
        time.sleep(0.05)
    assert agent.call("get_state")["tpu_chips_free"] == [0, 1, 2, 3]


def test_leased_worker_sees_its_chips_in_its_environment(four_chips):
    @ray_tpu.remote(num_cpus=0, num_tpus=1)
    class Holder:
        def env(self):
            return {
                k: os.environ.get(k)
                for k in ("TPU_VISIBLE_CHIPS", "TPU_PROCESS_BOUNDS",
                          tpu_mod.COMPILE_CACHE_ENV)
            }

    @ray_tpu.remote(num_cpus=1)
    def on_cpu():
        return os.environ.get("JAX_PLATFORMS"), os.environ.get("TPU_VISIBLE_CHIPS")

    holders = [Holder.remote() for _ in range(2)]
    envs = ray_tpu.get([h.env.remote() for h in holders], timeout=60)
    assert len({e["TPU_VISIBLE_CHIPS"] for e in envs}) == 2
    for e in envs:
        assert e["TPU_VISIBLE_CHIPS"] in {"0", "1", "2", "3"}
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e[tpu_mod.COMPILE_CACHE_ENV]
    assert ray_tpu.get(on_cpu.remote(), timeout=60) == ("cpu", None)
    for h in holders:
        ray_tpu.kill(h)
