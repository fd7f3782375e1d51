"""The trace reduction: interval arithmetic on hand-made traces, and the
whole reduction on a small trace recorded on the chip (one step and a
bit of ``small-pretrain``, ``data/small_train_trace.json``)."""

import json
import os

import pytest

from benchmark import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v}
                                    for k, v in lines.items()]}


def hand_made():
    """10 ms window; the device runs 0-4 and 5-9 ms, a copy overlapping
    the first program's end; the host sits in np.asarray during the gap."""
    return {"planes": [
        plane("/device:TPU:0",
              XLA_Modules=[["jit_step", 0, 4 * MS], ["jit_step", 5 * MS, 4 * MS]],
              XLA_Ops=[["fusion f32[8] 2in", 0, 3 * MS], ["copy f32[8] 1in", 2.5 * MS, 1.5 * MS],
                       ["fusion f32[8] 2in", 5 * MS, 4 * MS]]),
        plane("/host:CPU",
              main=[["bench/window", 0, 10 * MS], ["PjitFunction(step)", 0.1 * MS, 0.2 * MS],
                    ["np.asarray", 3.9 * MS, 1.2 * MS], ["$threading.py:323 wait", 0, 10 * MS],
                    ["PjRtCompile", 6 * MS, 1 * MS], ["$compiler.py:9 compile", 6 * MS, 1 * MS]],
              other=[["$queue.py:154 get", 0, 10 * MS]]),
    ]}


def test_union_clip_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3
    assert tr.clip([(0, 5), (8, 12), (20, 30)], 4, 10) == [(4, 5), (8, 10)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []


def test_busy_idle_and_per_operation_time_by_hand():
    got = tr.reduce(hand_made())
    assert got["window_s"] == pytest.approx(0.010)
    assert got["busy_s"] == pytest.approx(0.008)  # the union: 0-4 and 5-9
    assert got["modules"] == {"jit_step": pytest.approx(0.008)}
    assert got["module_calls"] == {"jit_step": 2.0}
    assert got["ops"]["fusion f32[8] 2in"] == pytest.approx(0.007)
    assert got["ops"]["copy f32[8] 1in"] == pytest.approx(0.0015)
    assert got["device_ops"][0] == ["module jit_step", pytest.approx(0.008)]
    assert got["host_calls"] == {"PjRtCompile": 1.0}  # the runtime's, not a Python frame


def test_gaps_go_to_what_the_dispatching_thread_was_doing():
    got = tr.reduce(hand_made())
    gaps = dict(got["idle_gaps"])
    # 4-5 ms falls inside np.asarray; 9-10 ms has only wrappers and a
    # parked thread over it, which say nothing
    assert gaps["np.asarray"] == pytest.approx(0.001)
    assert gaps["unattributed"] == pytest.approx(0.001)
    assert "$queue.py:154 get" not in gaps and "bench/window" not in gaps


def test_a_gap_goes_to_the_programs_innermost_span_not_to_a_frame_with_a_line_number():
    """``$llm.py:1455 run_round`` renames on any edit above that line;
    ``rt/engine/round`` does not. Where no ``rt/`` span overlaps the gap,
    the innermost host event of any kind still names it."""
    host = [["rt/engine/round", 0, 10 * MS], ["$llm.py:1455 run_round", 0.5 * MS, 9 * MS],
            ["rt/engine/dispatch", 6 * MS, 2 * MS], ["$llm.py:1353 dispatch", 6.1 * MS, 1.8 * MS],
            ["jnp.asarray", 6.2 * MS, 0.5 * MS], ["$tail.py:7 flush", 12 * MS, 2 * MS]]
    gaps = [(1 * MS, 2 * MS), (6.2 * MS, 6.7 * MS), (12.5 * MS, 13 * MS), (20 * MS, 21 * MS)]
    assert tr.attribute_gaps(gaps, host) == {
        "rt/engine/round": pytest.approx(0.001), "rt/engine/dispatch": pytest.approx(0.0005),
        "$tail.py:7 flush": pytest.approx(0.0005), "unattributed": pytest.approx(0.001),
    }
    # with no preference, the innermost event of any kind, as before PR 27
    assert set(tr.attribute_gaps(gaps[:2], host, prefer="none/")) == {
        "$llm.py:1455 run_round", "jnp.asarray"}
    # the tool beside the benchmark names gaps through the same function
    from benchmark.tools import span_gaps

    trace = hand_made()
    trace["planes"][1]["lines"][0]["events"] += [
        ["rt/engine/harvest_sync", 3.8 * MS, 1.4 * MS], ["$llm.py:1400 harvest", 3.9 * MS, 1.2 * MS]]
    assert dict(tr.reduce(trace)["idle_gaps"])["rt/engine/harvest_sync"] == pytest.approx(0.001)
    got = span_gaps.reduce_spans(trace)
    assert got["idle_s_by_span"] == {"rt/engine/harvest_sync": pytest.approx(0.001),
                                     "unattributed": pytest.approx(0.001)}
    assert got["spans"]["rt/engine/harvest_sync"] == {"count": 1, "ms": pytest.approx(1.4)}


def test_the_window_falls_back_to_the_devices_extent():
    t = hand_made()
    t["planes"][1]["lines"][0]["events"] = t["planes"][1]["lines"][0]["events"][1:]
    assert tr.window(t) == (0, 9 * MS)
    assert tr.reduce(t)["busy_s"] == pytest.approx(0.008)


def test_no_device_operation_gives_nothing():
    assert tr.reduce({"planes": [plane("/host:CPU", main=[["bench/window", 0, MS]])]}) is None


def test_exposed_collective_time():
    p = plane("/device:TPU:0", XLA_Ops=[
        ["fusion f32[8] 2in", 0, 4 * MS],
        ["all-reduce f32[768] 1in", 3 * MS, 3 * MS],  # 1 ms hidden, 2 ms exposed
        ["while (s32[]) 1in", 0, 10 * MS],  # a loop's own span is not compute
        ["fusion f32[8] 2in", 6 * MS, 2 * MS],
    ])
    assert tr.exposed_seconds(p, (0, 10 * MS), r"^all-reduce") == pytest.approx(0.002)


def test_averages_over_chips():
    t = hand_made()
    second = plane("/device:TPU:1", XLA_Modules=[["jit_step", 0, 2 * MS]],
                   XLA_Ops=[["fusion f32[8] 2in", 0, 2 * MS]])
    t["planes"].insert(1, second)
    got = tr.reduce(t)
    assert got["chips"] == 2
    assert got["busy_s"] == pytest.approx((0.008 + 0.002) / 2)
    assert got["modules"]["jit_step"] == pytest.approx((0.008 + 0.002) / 2)


@pytest.mark.parametrize("text,want", [
    ("%closed_call.19 = bf16[384,1024,64]{2,1,0:T(8,128)(2,1)} custom-call("
     "bf16[384,1024,64]{2,1,0:T(8,128)(2,1)} %bitcast.1, bf16[384,1024,64]{2,1,0} %x, "
     "bf16[384,1024,64]{2,1,0} %y), custom_call_target=\"tpu_custom_call\"",
     "closed_call bf16[384,1024,64] 3in"),
    ("%closed_call.12 = (bf16[384,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[384,1024,64]{2,1,0}) "
     "custom-call(bf16[384,1024,64]{2,1,0} %a, f32[384,1024]{1,0} %c)",
     "closed_call (bf16[384,1024,64],bf16[384,1024,64]) 2in"),
    ("%while.7 = (s32[]{:T(128)}, bf16[24,1,1600]{2,0,1:T(8,128)(2,1)S(1)}, "
     "bf16[48,97,64,25,64]{4,3,2,1,0}) while((s32[]{:T(128)}, bf16[24,1,1600]{2,0,1}) "
     "%tuple.454), condition=%wide.region_12.23, body=%wide.region_7.22.sunk",
     "while (s32[],bf16[24,1,1600],..) 1in"),
    ("%copy.65.remat_uncompressed = bf16[48,97,64,25,64]{4,3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[48,97,64,25,64]{1,4,3,2,0:T(8,128)(2,1)} %copy.1)",
     "copy.65.remat_uncompressed bf16[48,97,64,25,64] 1in"),
    ("%all-reduce.5 = f32[768]{0} all-reduce(f32[768]{0} %x), replica_groups={}, to_apply=%add",
     "all-reduce f32[768] 1in"),
    ("jit_train_step", "jit_train_step"),
])
def test_short_operation_names(text, want):
    assert tr.short_op_name(text) == want


RECORDED = os.path.join(HERE, "data", "small_train_trace.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_a_busy_chip_and_names_its_kernels(recorded):
    got = tr.reduce(recorded)
    assert got["chips"] == 1
    assert 0.25 < got["window_s"] <= 0.33
    assert 0.97 < got["busy_s"] / got["window_s"] <= 1.0  # a train step leaves no gaps
    assert set(got["modules"]) == {"jit_train_step"}
    flash = {k: v for k, v in got["ops"].items() if k.startswith("closed_call ")}
    assert flash, sorted(got["ops"])[:20]
    # the Mosaic kernels of one layer-call work on [32 x 12, 1024, 64]
    assert all("bf16[384,1024,64]" in k for k in flash)
    assert 0.05 < sum(flash.values()) / got["busy_s"] < 0.5
    assert got["device_ops"][0][0] == "module jit_train_step"
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_recorded_trace_feeds_the_kernel_roofline_reader(recorded):
    from types import SimpleNamespace

    from benchmark.readers import kernel_roofline

    with open(os.path.join(HERE, "..", "..", "benchmark", "metrics", "flash_fwd_roofline.json")) as f:
        fwd = json.load(f)
    with open(os.path.join(HERE, "..", "..", "benchmark", "metrics", "flash_bwd_roofline.json")) as f:
        bwd = json.load(f)
    obs = {"trace": tr.reduce(recorded), "device": {"kind": "TPU v5 lite"},
           "attention_shape": {"bh": 384, "t": 1024, "d": 64}}
    ctx = SimpleNamespace(platform="tpu")
    f_share = kernel_roofline.read(obs, fwd["args"], ctx)
    b_share = kernel_roofline.read(obs, bwd["args"], ctx)
    assert 3 < f_share < 100 and 3 < b_share < 100
