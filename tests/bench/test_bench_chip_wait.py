"""The wait for the chips around a run: what it opens, what it returns,
and that no metric holds it. The opener, the clock and the sleep are
passed in; nothing here touches a device file."""

import errno
import json
import os

import pytest

from benchmark import harness, run

FILES = ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2", "/dev/vfio/3"]


class Machine:
    """Device files that refuse with EBUSY a given number of times, on a
    clock that only the sleep moves."""

    def __init__(self, refusals=None, error=errno.EBUSY):
        self.refusals = dict(refusals or {})
        self.error = error
        self.opened, self.slept, self.now = [], [], 1000.0

    def opener(self, path):
        self.opened.append(path)
        if self.refusals.get(path, 0) > 0:
            self.refusals[path] -= 1
            raise OSError(self.error, os.strerror(self.error), path)

    def clock(self):
        return self.now

    def sleep(self, s):
        self.slept.append(s)
        self.now += s

    def wait(self, platform="tpu", **kw):
        kw.setdefault("files", FILES)
        kw.setdefault("held", ())
        return harness.wait_for_chips(platform, "before the run", opener=self.opener,
                                      clock=self.clock, sleep=self.sleep, **kw)


def test_free_at_once_returns_0_and_opens_each_file_once(capsys):
    m = Machine()
    assert m.wait() == 0.0
    assert m.opened == FILES and m.slept == []
    assert "4 device file(s) probed, none busy" in capsys.readouterr().out


def test_busy_twice_then_free_returns_the_seconds_slept_and_names_the_file(capsys):
    m = Machine({"/dev/vfio/3": 2})
    assert m.wait() == pytest.approx(2 * harness.CHIP_WAIT_STEP_S)
    assert m.slept == [harness.CHIP_WAIT_STEP_S] * 2
    # a file that opened is not opened again
    assert m.opened.count("/dev/vfio/3") == 3 and m.opened.count("/dev/vfio/0") == 1
    said = capsys.readouterr().out
    assert "waited 1.00s" in said and "/dev/vfio/3" in said and "/dev/vfio/0" not in said


def test_the_limit_running_out_returns_and_raises_nothing(capsys):
    m = Machine({"/dev/vfio/1": 10**6})
    waited = m.wait(limit_s=3.0)
    assert waited == pytest.approx(3.0)
    assert len(m.slept) == 6
    assert "STILL BUSY after the limit of 3s: /dev/vfio/1" in capsys.readouterr().out


def test_an_open_that_blocks_and_then_succeeds_counts_as_waited(capsys):
    """One chip: the file is not refused, the open() takes seconds."""
    m = Machine()
    plain = m.opener

    def slow(path):
        m.now += 1.9
        plain(path)

    m.opener = slow
    assert m.wait(files=["/dev/vfio/0"]) == pytest.approx(1.9)
    assert m.slept == []
    assert "none busy, waited 1.90s" in capsys.readouterr().out


def test_a_cpu_configuration_opens_nothing_and_says_nothing(capsys):
    m = Machine({"/dev/vfio/0": 5})
    assert m.wait(platform="cpu") == 0.0
    assert m.opened == [] and m.slept == []
    assert capsys.readouterr().out == ""


def test_a_file_the_process_itself_holds_is_skipped(capsys):
    m = Machine()
    assert m.wait(held={"/dev/vfio/2", "/dev/null"}) == 0.0
    assert m.opened == ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/3"]
    said = capsys.readouterr().out
    assert "/dev/vfio/2 is held by this process itself" in said
    assert "3 device file(s) probed" in said


def test_a_refusal_that_is_not_busy_is_named_and_not_waited_for(capsys):
    m = Machine({"/dev/vfio/0": 10**6}, error=errno.EACCES)
    assert m.wait() == 0.0
    assert m.opened == FILES and m.slept == []
    assert "/dev/vfio/0 cannot be probed (Permission denied)" in capsys.readouterr().out


def test_own_files_lists_what_this_process_holds_open(tmp_path):
    path = str(tmp_path / "held")
    with open(path, "w"):
        assert path in harness.own_files()
    assert path not in harness.own_files()


# ---- through run.py ---------------------------------------------------------

WALL0 = 2_000_000_000.0  # the instant the fake window opens


class Generator:
    """Stands in for a generator module: set-up as the real ones count
    it, the window's first instant less the phases' origin."""

    __name__ = "a generator"

    def __init__(self, fail=False):
        self.fail = fail

    def run(self, ctx):
        if self.fail:
            raise RuntimeError("the program failed")
        return {"problems": [], "failed": 0, "attempted": 1, "notes": [],
                "setup_s": WALL0 - ctx.phases.t0, "compared": {"logit_gap": [0.07, 0.2]},
                "device": {"platform": ctx.platform, "kind": "none", "count": 1,
                           "memory_peak_bytes": 1}}


def drive(monkeypatch, tmp_path, waits, platform="tpu", process_start=WALL0 - 30.0,
          fail=False, extra=()):
    """``run.main`` on a one-cell benchmark whose generator is ``Generator``
    and whose chips make it wait ``waits`` seconds, before and after."""
    calls = []

    def wait_for_chips(got_platform, when):
        calls.append((got_platform, when))
        print(f"[bench] chips {when}")
        return waits.pop(0)

    bench = {
        "paths": ["benchmark"], "run_seconds": 3,
        "configs": [{"name": "c", "file": os.path.relpath(tmp_path / "c.json", run.ROOT)}],
        "workloads": [{"name": "cell", "config": "c", "traffic": "pretrain-1k", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "source": "host_clock"}],
        "per_layer": [],
    }
    (tmp_path / "c.json").write_text(json.dumps({"platform": platform}))
    (tmp_path / "bench.json").write_text(json.dumps(bench))
    generator = Generator(fail)
    real_module = harness.module
    monkeypatch.setattr(run, "module", lambda b, kind, name: (
        generator if kind == "generators" else real_module(b, kind, name)))
    monkeypatch.setattr(harness, "require_platform", lambda platform, chips: None)
    monkeypatch.setattr(harness, "wait_for_chips", wait_for_chips)
    monkeypatch.setattr(harness, "compile_cache_dir", lambda: str(tmp_path))
    monkeypatch.setattr(run, "T_PROCESS_START", process_start)
    rc = run.main(["--bench-file", str(tmp_path / "bench.json"), "--workload", "cell",
                   "--seed", "1", *extra])
    return rc, calls


def test_setup_s_of_a_run_that_waited_equals_that_of_one_that_did_not(
        monkeypatch, tmp_path, capsys):
    rc, _ = drive(monkeypatch, tmp_path, [0.0, 0.0])
    calm = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the same run started 12.5 s earlier, which it spent waiting
    rc2, _ = drive(monkeypatch, tmp_path, [12.5, 0.0], process_start=WALL0 - 42.5)
    waited = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == rc2 == 0
    assert calm["metrics"]["setup_s"]["value"] == pytest.approx(30.0)
    assert waited["metrics"]["setup_s"] == calm["metrics"]["setup_s"]


def test_the_closing_wait_speaks_on_standard_error_after_the_result_line(
        monkeypatch, tmp_path, capsys):
    rc, calls = drive(monkeypatch, tmp_path, [0.0, 17.0])
    out, err = capsys.readouterr()
    assert rc == 0 and [c[1] for c in calls] == ["before the run", "after the run"]
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert "chips before the run" in out and "chips after the run" not in out
    # then what was compared, beside its limit, as the last lines there
    assert err.strip().splitlines()[-3:] == [
        "[bench] chips after the run", "correct: true", "compared: logit_gap 0.07 limit 0.2"]


def test_a_run_that_fails_still_waits_for_its_chips_and_keeps_its_error(
        monkeypatch, tmp_path, capsys):
    with pytest.raises(RuntimeError, match="the program failed"):
        drive(monkeypatch, tmp_path, [0.0, 17.0], fail=True)
    out, err = capsys.readouterr()
    assert "chips after the run" in err and "{" not in out


def test_dry_opens_nothing(monkeypatch, tmp_path, capsys):
    rc, calls = drive(monkeypatch, tmp_path, [], extra=["--dry"])
    assert rc == 0 and calls == []
