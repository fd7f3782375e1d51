"""ray_tpu.data tests (parity model: python/ray/data/tests/ —
test_map.py, test_consumption.py, test_split.py subset)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rtd


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def test_range_count_take(rt):
    ds = rtd.range(100, parallelism=4)
    assert ds.count() == 100
    rows = ds.take(5)
    assert [r["id"] for r in rows] == [0, 1, 2, 3, 4]


def test_map_batches_streaming(rt):
    ds = rtd.range(1000, parallelism=8).map_batches(
        lambda b: {"x": b["id"] * 2}
    )
    total = 0
    seen = []
    for batch in ds.iter_batches(batch_size=128):
        assert set(batch.keys()) == {"x"}
        total += len(batch["x"])
        seen.append(batch["x"])
    assert total == 1000
    all_x = np.concatenate(seen)
    assert sorted(all_x.tolist()) == [2 * i for i in range(1000)]


def test_exact_batch_sizes(rt):
    ds = rtd.range(1000, parallelism=7)
    sizes = [len(b["id"]) for b in ds.iter_batches(batch_size=128)]
    assert all(s == 128 for s in sizes[:-1])
    assert sum(sizes) == 1000
    # drop_last drops the remainder
    sizes = [
        len(b["id"])
        for b in ds.iter_batches(batch_size=128, drop_last=True)
    ]
    assert all(s == 128 for s in sizes)
    assert sum(sizes) == 1000 - (1000 % 128)


def test_fused_map_filter_chain(rt):
    ds = (
        rtd.range(100, parallelism=4)
        .map_batches(lambda b: {"id": b["id"] + 1})
        .filter(lambda r: r["id"] % 2 == 0)
        .map_batches(lambda b: {"id": b["id"] // 2})
    )
    got = sorted(r["id"] for r in ds.take_all())
    assert got == sorted((i + 1) // 2 for i in range(100) if (i + 1) % 2 == 0)


def test_from_items_map_rows(rt):
    ds = rtd.from_items([{"v": i} for i in range(20)], parallelism=3)
    out = ds.map(lambda r: {"v": r["v"] ** 2}).take_all()
    assert sorted(r["v"] for r in out) == [i * i for i in range(20)]


def test_flat_map_and_limit(rt):
    ds = rtd.from_items(list(range(10)), parallelism=2).flat_map(
        lambda x: [x, x]
    )
    assert ds.count() == 20
    assert len(ds.limit(7).take_all()) == 7


def test_limit_stops_pipeline_early(rt):
    # limit over a large range must not require materializing everything:
    # streaming executor stops submitting upstream once satisfied
    ds = rtd.range(1_000_000, parallelism=100).limit(10)
    rows = ds.take_all()
    assert [r["id"] for r in rows] == list(range(10))


def test_repartition(rt):
    ds = rtd.range(100, parallelism=7).repartition(3)
    assert ds.num_blocks() == 3
    assert ds.count() == 100
    got = sorted(r["id"] for r in ds.take_all())
    assert got == list(range(100))


def test_random_shuffle(rt):
    ds = rtd.range(200, parallelism=4).random_shuffle(seed=7)
    got = [r["id"] for r in ds.take_all()]
    assert sorted(got) == list(range(200))
    assert got != list(range(200))  # astronomically unlikely to be sorted


def test_union(rt):
    a = rtd.range(10, parallelism=2)
    b = rtd.range(5, parallelism=1).map_batches(lambda x: {"id": x["id"] + 100})
    assert a.union(b).count() == 15


def test_materialize_and_reuse(rt):
    ds = rtd.range(50, parallelism=4).map_batches(
        lambda b: {"id": b["id"] * 3}
    )
    mat = ds.materialize()
    assert mat.count() == 50
    assert mat.count() == 50  # second pass over cached blocks
    assert sorted(r["id"] for r in mat.take_all()) == [3 * i for i in range(50)]


def test_split_and_shard(rt):
    ds = rtd.range(100, parallelism=10)
    shards = ds.split(3)
    counts = [s.count() for s in shards]
    assert sum(counts) == 100
    ids = sorted(
        r["id"] for s in shards for r in s.take_all()
    )
    assert ids == list(range(100))
    # lazy shard() partitions the block stream the same way
    lazy = [ds.shard(3, i) for i in range(3)]
    lazy_ids = sorted(r["id"] for s in lazy for r in s.take_all())
    assert lazy_ids == list(range(100))


def test_actor_pool_map_batches(rt):
    class AddConst:
        def __init__(self, c):
            self.c = c

        def __call__(self, batch):
            return {"id": batch["id"] + self.c}

    ds = rtd.range(40, parallelism=4).map_batches(
        AddConst, fn_constructor_args=(1000,), concurrency=2
    )
    got = sorted(r["id"] for r in ds.take_all())
    assert got == [1000 + i for i in range(40)]


def test_udf_error_propagates(rt):
    def boom(batch):
        raise ValueError("bad udf")

    ds = rtd.range(10, parallelism=2).map_batches(boom)
    with pytest.raises(Exception, match="bad udf"):
        ds.take_all()


def test_read_text_json_csv(rt, tmp_path):
    p = tmp_path / "a.txt"
    p.write_text("hello\nworld\n")
    ds = rtd.read_text(str(p))
    assert [r["text"] for r in ds.take_all()] == ["hello", "world"]

    j = tmp_path / "b.jsonl"
    j.write_text('{"x": 1}\n{"x": 2}\n')
    assert [r["x"] for r in rtd.read_json(str(j)).take_all()] == [1, 2]

    c = tmp_path / "c.csv"
    c.write_text("a,b\n1,2\n3,4\n")
    rows = rtd.read_csv(str(c)).take_all()
    assert [r["a"] for r in rows] == [1.0, 3.0]


def test_zero_copy_numpy_block(rt):
    arr = np.arange(300_000, dtype=np.float32)  # >100KB -> plasma path
    ds = rtd.from_numpy(arr).map_batches(lambda b: {"data": b["data"] + 1})
    out = ds.take_all()
    assert len(out) == 300_000


def test_iter_epochs(rt):
    ds = rtd.range(64, parallelism=2)
    it = ds.iterator()
    epochs = list(it.iter_epochs(2, batch_size=32))
    assert len(epochs) == 2
    for ep in epochs:
        assert sum(len(b["id"]) for b in ep) == 64


def test_prefetched_blocks_wait_under_a_named_span(rt, monkeypatch):
    """The consumer's wait for the prefetch thread is the Train path's
    one boundary inside the program: every block (and the end marker) is
    taken under ``rt/data/wait_block``, and nothing else is."""
    import contextlib

    from ray_tpu.observability import tracing

    entered = []

    @contextlib.contextmanager
    def span(name, **args):
        entered.append(name)
        yield

    monkeypatch.setattr(tracing, "span", span)
    ds = rtd.range(64, parallelism=4)
    batches = list(ds.iterator().iter_batches(batch_size=16, prefetch_batches=2))
    assert sum(len(b["id"]) for b in batches) == 64
    assert entered == ["rt/data/wait_block"] * 5  # four blocks and the end
    entered.clear()
    list(ds.iterator().iter_batches(batch_size=16, prefetch_batches=0))
    assert entered == []  # no prefetch thread, nothing to wait for


def test_train_dataset_shards(rt, tmp_path):
    """datasets= flows to workers; each rank consumes a disjoint shard and
    together the shards cover the whole dataset exactly once (parity:
    ray.train.get_dataset_shard). Requires a deterministic block-stream
    order: each worker executes the pipeline independently, so shard()
    would overlap/drop blocks if completion order leaked through."""
    import json

    from ray_tpu import train as rt_train
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    ds = rtd.range(64, parallelism=8).map_batches(lambda b: {"id": b["id"]})
    out_dir = str(tmp_path)

    def loop():
        ctx = rt_train.get_context()
        it = rt_train.get_dataset_shard("train")
        got = []
        for batch in it.iter_batches(batch_size=8):
            got.extend(int(x) for x in batch["id"])
        with open(f"{out_dir}/ids_{ctx.get_world_rank()}.json", "w") as f:
            json.dump(got, f)
        rt_train.report({"n": len(got)})

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
        datasets={"train": ds},
    )
    result = trainer.fit()
    assert result.error is None
    ids0 = json.load(open(f"{out_dir}/ids_0.json"))
    ids1 = json.load(open(f"{out_dir}/ids_1.json"))
    assert ids0 and ids1
    assert not (set(ids0) & set(ids1)), "shards overlap"
    assert sorted(ids0 + ids1) == list(range(64)), "shards don't cover dataset"


def test_train_dataset_shards_reexecute(rt, tmp_path):
    """reexecute split mode: per-rank streaming re-execution with the
    FIFO-deterministic block order still yields disjoint full coverage."""
    import json

    from ray_tpu import train as rt_train
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    ds = rtd.range(64, parallelism=8).random_shuffle()
    out_dir = str(tmp_path)

    def loop():
        ctx = rt_train.get_context()
        it = rt_train.get_dataset_shard("train")
        got = []
        for batch in it.iter_batches(batch_size=8):
            got.extend(int(x) for x in batch["id"])
        with open(f"{out_dir}/ids_{ctx.get_world_rank()}.json", "w") as f:
            json.dump(got, f)
        rt_train.report({"n": len(got)})

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
        datasets={"train": ds},
        dataset_split_mode="reexecute",
    )
    result = trainer.fit()
    assert result.error is None
    ids0 = json.load(open(f"{out_dir}/ids_0.json"))
    ids1 = json.load(open(f"{out_dir}/ids_1.json"))
    assert not (set(ids0) & set(ids1)), "shards overlap"
    assert sorted(ids0 + ids1) == list(range(64))


# ---------------------------------------------------------------------------
# all-to-all tier: sort / groupby / join / exact shuffle
# (parity model: python/ray/data/tests/test_sort.py, test_groupby.py)
# ---------------------------------------------------------------------------


def test_sort_global_order(rt):
    rng = np.random.RandomState(3)
    vals = rng.randint(0, 10_000, size=500).tolist()
    ds = rtd.from_items(
        [{"v": v} for v in vals], parallelism=8
    ).sort(key="v")
    out = [r["v"] for r in ds.take_all()]
    assert out == sorted(vals)


def test_sort_descending_callable_key(rt):
    vals = [5, 3, 9, 1, 7, 2, 8]
    ds = rtd.from_items(vals, parallelism=3).sort(
        key=lambda x: x, descending=True
    )
    assert ds.take_all() == sorted(vals, reverse=True)


def test_groupby_aggregate_matches_pandas(rt):
    """>16 blocks; compare against a pandas groupby oracle (VERDICT
    round-3 item 6)."""
    import pandas as pd

    rng = np.random.RandomState(7)
    rows = [
        {"k": int(k), "v": float(v)}
        for k, v in zip(
            rng.randint(0, 23, size=800), rng.randn(800) * 10
        )
    ]
    ds = rtd.from_items(rows, parallelism=20)
    out = (
        ds.groupby("k")
        .aggregate(
            rtd.AggregateFn.count("n"),
            rtd.AggregateFn.of_column("sum", "v", "v_sum"),
            rtd.AggregateFn.of_column("mean", "v", "v_mean"),
            rtd.AggregateFn.of_column("max", "v", "v_max"),
        )
        .take_all()
    )
    got = {r["k"]: r for r in out}
    pdf = pd.DataFrame(rows).groupby("k")["v"].agg(["count", "sum", "mean", "max"])
    assert set(got) == set(pdf.index)
    for k, row in pdf.iterrows():
        assert got[k]["n"] == row["count"]
        np.testing.assert_allclose(got[k]["v_sum"], row["sum"], rtol=1e-9)
        np.testing.assert_allclose(got[k]["v_mean"], row["mean"], rtol=1e-9)
        np.testing.assert_allclose(got[k]["v_max"], row["max"], rtol=1e-9)


def test_groupby_map_groups(rt):
    rows = [{"k": i % 3, "v": i} for i in range(30)]
    out = (
        rtd.from_items(rows, parallelism=5)
        .groupby("k")
        .map_groups(lambda grp: {"k": grp[0]["k"], "total": sum(r["v"] for r in grp)})
        .take_all()
    )
    got = {r["k"]: r["total"] for r in out}
    assert got == {
        0: sum(i for i in range(30) if i % 3 == 0),
        1: sum(i for i in range(30) if i % 3 == 1),
        2: sum(i for i in range(30) if i % 3 == 2),
    }


def test_join_inner_and_left(rt):
    left = rtd.from_items(
        [{"id": i, "a": i * 10} for i in range(8)], parallelism=3
    )
    right = rtd.from_items(
        [{"id": i, "b": i * 100} for i in range(4, 12)], parallelism=3
    )
    inner = left.join(right, on="id").take_all()
    assert sorted(r["id"] for r in inner) == [4, 5, 6, 7]
    for r in inner:
        assert r["a"] == r["id"] * 10 and r["b"] == r["id"] * 100
    lf = left.join(right, on="id", how="left").take_all()
    assert sorted(r["id"] for r in lf) == list(range(8))
    assert sum(1 for r in lf if "b" not in r) == 4


def test_random_shuffle_is_exact_permutation(rt):
    n = 400
    ds = rtd.range(n, parallelism=8).random_shuffle(seed=11)
    out = [r["id"] for r in ds.take_all()]
    assert sorted(out) == list(range(n))
    assert out != list(range(n))  # actually shuffled
    # deterministic under the same seed
    out2 = [r["id"] for r in rtd.range(n, parallelism=8)
            .random_shuffle(seed=11).take_all()]
    assert out == out2


def test_write_and_read_roundtrip(rt, tmp_path):
    """write_json / write_csv / write_numpy produce one file per block
    via distributed tasks; reading them back restores the rows
    (reference Dataset.write_* datasink parity)."""
    from ray_tpu import data as rd

    ds = rd.range(100, parallelism=4).map(
        lambda r: {"id": r["id"], "sq": r["id"] * r["id"]}
    )

    out_json = ds.write_json(str(tmp_path / "j"))
    assert len(out_json) == 4 and all(p.endswith(".jsonl") for p in out_json)
    back = rd.read_json([str(tmp_path / "j" / "*.jsonl")])
    rows = sorted(back.take_all(), key=lambda r: r["id"])
    assert len(rows) == 100 and rows[7] == {"id": 7, "sq": 49}

    out_csv = ds.write_csv(str(tmp_path / "c"))
    assert len(out_csv) == 4
    back_csv = rd.read_csv([str(tmp_path / "c" / "*.csv")])
    rows_csv = sorted(
        back_csv.take_all(), key=lambda r: int(r["id"])
    )
    assert len(rows_csv) == 100 and int(rows_csv[7]["sq"]) == 49

    out_npz = ds.write_numpy(str(tmp_path / "n"))
    assert len(out_npz) == 4
    import numpy as np

    total = sum(
        len(np.load(p)["id"]) for p in out_npz
    )
    assert total == 100


def test_streaming_split_concurrent_consumers(rt):
    """Two consumers drain ONE streaming execution concurrently and see
    disjoint, together-complete data (reference streaming_split)."""
    import threading

    from ray_tpu import data as rd

    ds = rd.range(64, parallelism=8).map(lambda r: {"v": r["id"]})
    splits = ds.streaming_split(2)
    seen = [[], []]

    def consume(i):
        for batch in splits[i].iter_batches(batch_size=None):
            seen[i].extend(int(v) for v in batch["v"])

    threads = [
        threading.Thread(target=consume, args=(i,)) for i in (0, 1)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert seen[0] and seen[1]  # both consumers got data
    assert not (set(seen[0]) & set(seen[1]))  # disjoint
    assert sorted(seen[0] + seen[1]) == list(range(64))  # complete
