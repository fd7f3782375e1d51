"""Multi-node-on-one-machine tests (parity model: reference tests using
python/ray/cluster_utils.py Cluster, e.g. test_placement_group_2.py)."""

import time

import pytest

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.core.placement import PlacementGroupSchedulingStrategy


@pytest.fixture(scope="module")
def _shared_cluster():
    # ONE head for the whole module (the other of the tier-1 sweep's
    # two slowest cluster spinners): tests add nodes under test-unique
    # resource tags and kill only nodes they added, so sharing the head
    # never leaks scheduling surface between tests.
    c = Cluster()
    try:
        yield c
    finally:
        c.shutdown()


@pytest.fixture
def cluster(_shared_cluster):
    try:
        yield _shared_cluster
    finally:
        ray_tpu.shutdown()


def test_multi_node_spread(cluster):
    node_a = cluster.add_node(num_cpus=2, resources={"tag_a": 1})
    node_b = cluster.add_node(num_cpus=2, resources={"tag_b": 1})
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote
    def where():
        import ray_tpu as rt

        return rt.get_runtime_context().get_node_id()

    # custom-resource targeting lands tasks on specific nodes
    a = ray_tpu.get(where.options(resources={"tag_a": 1}).remote())
    b = ray_tpu.get(where.options(resources={"tag_b": 1}).remote())
    assert a != b
    assert {a, b} == {node_a.node_id, node_b.node_id}


def test_strict_spread_pg_across_nodes(cluster):
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    ray_tpu.init(address=cluster.address)

    pg = ray_tpu.placement_group(
        [{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD"
    )
    assert pg.wait(20)
    locs = pg.table()["bundle_locations"]
    assert len(set(locs.values())) == 2


def test_pg_bundle_task_on_remote_node(cluster):
    """Tasks pinned to a PG bundle hosted on a different node than the
    caller's local agent must spill back to the bundle's node, not hang."""
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    ray_tpu.init(address=cluster.address)

    pg = ray_tpu.placement_group(
        [{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD"
    )
    assert pg.wait(20)
    locs = pg.table()["bundle_locations"]

    @ray_tpu.remote
    def where():
        import ray_tpu as rt

        return rt.get_runtime_context().get_node_id()

    for idx in (0, 1):
        node = ray_tpu.get(
            where.options(
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=pg, placement_group_bundle_index=idx
                )
            ).remote(),
            timeout=60,
        )
        assert node == locs[idx]


def test_cross_node_large_object_get(cluster):
    """A borrower on a different host can read a >max_direct object: the
    owner's reply routes through the hosting agent's chunked read instead
    of handing back a useless local shm path."""
    import numpy as np

    cluster.add_node(num_cpus=2, resources={"site_a": 1})
    cluster.add_node(num_cpus=2, resources={"site_b": 1})
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote(resources={"site_a": 1})
    def produce():
        return np.arange(1_000_000, dtype=np.int64)  # ~8MB, plasma-backed

    @ray_tpu.remote(resources={"site_b": 1})
    def consume(arr):
        return int(arr.sum())

    ref = produce.remote()
    got = ray_tpu.get(consume.remote(ref), timeout=90)
    assert got == 499999500000


def test_actor_survives_node_death(cluster):
    cluster.add_node(num_cpus=2, resources={"pin": 1})
    victim = cluster.add_node(num_cpus=2, resources={"doomed": 1})
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote
    class Stateful:
        def node(self):
            import ray_tpu as rt

            return rt.get_runtime_context().get_node_id()

    a = Stateful.options(
        max_restarts=-1, resources={"CPU": 1}
    ).remote()
    first_node = ray_tpu.get(a.node.remote(), timeout=60)

    if first_node == victim.node_id:
        cluster.kill_node(victim)
        # in-flight/new calls should eventually reach the restarted actor
        deadline = time.monotonic() + 60
        second_node = None
        while time.monotonic() < deadline:
            try:
                second_node = ray_tpu.get(a.node.remote(), timeout=15)
                break
            except Exception:
                time.sleep(0.3)
        assert second_node is not None and second_node != victim.node_id
    else:
        # actor landed on the survivor; killing the other node must not hurt
        cluster.kill_node(victim)
        assert ray_tpu.get(a.node.remote(), timeout=30) == first_node


def test_pg_replaced_after_node_death(cluster):
    """A PG with a bundle on a dead node is partially re-placed: the lost
    bundle moves to a live node, surviving bundle locations are untouched,
    and new leases against the re-placed bundle succeed (reference:
    GcsPlacementGroupManager reschedules bundles on node death)."""
    # the "pgz" tag pins bundles to THIS test's three nodes (the shared
    # module cluster has live nodes from earlier tests)
    keeper = cluster.add_node(num_cpus=2, resources={"pgz": 2})
    victim = cluster.add_node(num_cpus=2, resources={"pgz": 2})
    spare = cluster.add_node(num_cpus=2, resources={"pgz": 2})
    ray_tpu.init(address=cluster.address)

    pg = ray_tpu.placement_group(
        [{"CPU": 1, "pgz": 1}, {"CPU": 1, "pgz": 1}],
        strategy="STRICT_SPREAD",
    )
    assert pg.wait(20)
    locs = pg.table()["bundle_locations"]
    nodes_used = set(locs.values())
    # kill a node hosting one bundle (pick whichever of the three it is)
    doomed = next(n for n in (keeper, victim, spare) if n.node_id in nodes_used)
    survivor_locs = {i: nid for i, nid in locs.items() if nid != doomed.node_id}
    cluster.kill_node(doomed)

    deadline = time.monotonic() + 60
    table = None
    while time.monotonic() < deadline:
        table = pg.table()
        if (
            table["state"] == "CREATED"
            and doomed.node_id not in set(table["bundle_locations"].values())
            and len(table["bundle_locations"]) == 2
        ):
            break
        time.sleep(0.3)
    assert table is not None and table["state"] == "CREATED"
    new_locs = table["bundle_locations"]
    assert doomed.node_id not in set(new_locs.values())
    # surviving bundle kept its location
    for i, nid in survivor_locs.items():
        assert new_locs[i] == nid

    @ray_tpu.remote
    def where():
        import ray_tpu as rt

        return rt.get_runtime_context().get_node_id()

    for idx in (0, 1):
        node = ray_tpu.get(
            where.options(
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=pg, placement_group_bundle_index=idx
                )
            ).remote(),
            timeout=60,
        )
        assert node == new_locs[idx]
