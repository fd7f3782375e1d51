"""chip_smoke.py on the CPU: it must refuse to run without a chip, and
its phases must pass at gpt2-tiny when told the platform is the CPU —
the rehearsal to make before chip time is spent on the real thing."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(env_overrides, drop=()):
    env = {k: v for k, v in os.environ.items()
           if k not in ("RT_NUM_TPUS", "TPU_VISIBLE_CHIPS", *drop)}
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )


def test_refuses_when_the_machine_exposes_no_chip():
    proc = _run_smoke({}, drop=("JAX_PLATFORMS",))
    assert proc.returncode != 0
    assert "TPU v5 lite" in proc.stderr and "no chip" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_refuses_when_jax_platforms_keeps_it_off_the_tpu():
    proc = _run_smoke({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "TPU v5 lite" in proc.stderr and "JAX_PLATFORMS='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def smoke():
    import ray_tpu

    sys.path.insert(0, REPO)
    import chip_smoke

    ray_tpu.init(num_cpus=8)
    yield chip_smoke
    ray_tpu.shutdown()


def test_serve_phase_rehearsal_on_cpu(smoke):
    out = smoke.serve_phase("cpu", 2, "gpt2-tiny")
    assert out["requests_failed"] == 0 and out["requests_sent"] >= 8
    assert len(out["replicas"]) == 2
    assert all(d["platform"] == "cpu"
               for r in out["replicas"] for d in r["devices"])


def test_train_phase_rehearsal_on_cpu(smoke):
    # two virtual devices: the flash kernel runs per shard under the mesh
    out = smoke.train_phase("cpu", 2, "gpt2-tiny")
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 2}

