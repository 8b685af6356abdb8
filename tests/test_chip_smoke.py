"""``chip_smoke.py`` on the CPU: its phases at ``.smoke()`` sizes, its
refusal to run off a TPU, and the backend decision it reports.

The phases' control flow is the same here as on the chip; what differs is
the platform decision (:func:`repro.kernels.pallas_compiled`), which the
tests steer by patching ``jax.default_backend``.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.cluster.simulator import (
    JobSpec,
    PallasReduction,
    resolve_reduction_backend,
)
from repro.cluster.spec import ModelSpec
from repro.configs.base import get_config
from repro.core import bocd
from repro.data.pipeline import DataConfig
from repro.launch import REPO_ROOT, init_compile_cache

SMOKE_DATA = DataConfig(seq_len=32, global_batch=4, slots=2, dp_groups=2)


@pytest.fixture(scope="module")
def smoke():
    path = os.path.join(REPO_ROOT, "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trainer_phase_dispatches_a_strategy(smoke, tmp_path):
    out = smoke.trainer_phase(
        get_config("granite-3-8b").smoke(), SMOKE_DATA, steps=20,
        inject_step=8, out_dir=str(tmp_path), compiles=smoke.CompileLog(),
    )
    assert out["steps"] == 20
    assert out["strategies"]
    assert out["reduction"] == "vectorized"  # off a TPU


def test_fleet_phase_backends_agree(smoke, tmp_path):
    out = smoke.fleet_phase(
        n_jobs=8, max_ticks=600, out_dir=str(tmp_path),
        compiles=smoke.CompileLog(),
    )
    assert out["diagnoses"] > 0 and out["flags"] > 0
    for label in ("auto", "numpy"):
        assert (tmp_path / label / "mixed_fleet-j8-s0.json").exists()


def test_kernel_phase_sees_interpret_mode_off_tpu(smoke):
    compiles = smoke.CompileLog()
    from repro.kernels.cell_reduce import cell_reduce
    import jax.numpy as jnp

    f = jnp.ones
    cell_reduce(f((2, 2)), f((2, 2, 2)), f((2, 2, 2)), f((1, 2)), f((2,)),
                1.0, 1.0, 1.0, 1.0, 1.0)
    assert (2, 2, 2) in compiles.kernel_shapes["cell_reduce"]
    from repro.kernels.bocd_step import PallasBOCD
    PallasBOCD(3, max_hypotheses=16, interpret=True).update(f(3))
    assert (16, 3) in compiles.kernel_shapes["bocd_step"]
    got = smoke.kernel_phase(
        {"bocd_step": {(32, 5)}, "cell_reduce": {(2, 2, 2)}}
    )
    assert got == {
        "bocd_step(K=32,B=5)": False, "cell_reduce(pp=2,dp=2,tp=2)": False,
    }


def test_even_batch_of_weighs_the_s2_micro_batches_alike(smoke):
    """At counts [2, 1] the S2 step runs group 0's slots 0 and 1 and
    group 1's slot 0; the even step's batch holds each of those twice."""
    import numpy as np

    tokens = np.arange(4).reshape(2, 2, 1)  # (slot, group, S): 2*slot + group
    got = smoke._even_batch_of({"tokens": tokens}, [2, 1])["tokens"][..., 0]
    assert got.shape == (3, 2)
    assert sorted(got.ravel().tolist()) == [0, 0, 1, 1, 2, 2]
    same = smoke._even_batch_of({"tokens": tokens}, [2, 2])["tokens"]
    assert sorted(same.ravel().tolist()) == [0, 0, 1, 1, 2, 2, 3, 3]


def test_train_cli_wiring_is_a_full_hybrid_job():
    """The train CLI's defaults (4 DP groups) and the smoke's (2) both get
    a TP=2 x DP x PP=2 simulated job on just enough nodes, the topology
    the reduction kernel needs."""
    from repro.cluster.simulator import fits_pallas_reduction
    from repro.launch.train import build_trainer

    for dp in (4, 2):
        trainer = build_trainer(
            get_config("granite-3-8b").smoke(),
            DataConfig(seq_len=32, global_batch=4 * dp, slots=4, dp_groups=dp),
            steps=1,
        )
        sim = trainer.perf_model
        assert (sim.job.tp, sim.job.dp, sim.job.pp) == (2, dp, 2)
        assert sim.cluster.n_nodes * sim.cluster.gpus_per_node == 4 * dp
        assert fits_pallas_reduction(sim.job)


def test_main_refuses_off_tpu(smoke, capsys):
    assert smoke.main(["--out", "unused"]) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "not a TPU" in err


def test_backend_decision_follows_the_platform(monkeypatch):
    job = JobSpec(
        model=ModelSpec(layers=2, hidden=4096, seq_len=2048, vocab=49155),
        tp=2, dp=2, pp=2, micro_batches=4,
    )
    assert bocd.select_backend("auto").name == "batched"
    assert resolve_reduction_backend("auto", job) is None  # vectorized
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert bocd.select_backend("auto").name == "pallas"
    assert isinstance(resolve_reduction_backend("auto", job), PallasReduction)


def test_compile_cache_location(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert init_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert init_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


FOUR_CHIP_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
sys.path.insert(0, {root!r})
import chip_smoke as cs
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig
out = cs.four_chip_phase(
    get_config("granite-3-8b").smoke(),
    DataConfig(seq_len=32, global_batch=4, slots=2, dp_groups=2),
    jax.devices(), cs.CompileLog(),
)
print("FOUR-CHIP-OK", out["loss_rel"], out["grad_rel"], out["fault_grad_rel"])
"""


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path on four CPU devices (subprocess: the host
    device count must be fixed before JAX initializes)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", FOUR_CHIP_SCRIPT.format(root=REPO_ROOT)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "FOUR-CHIP-OK" in out.stdout


class _FakeTPU:
    platform, device_kind, id = "tpu", "TPU v5 lite", 0

    def memory_stats(self):
        return None


class _StubCompileLog:
    kernel_shapes = {"bocd_step": {(32, 1)}, "cell_reduce": {(2, 2, 2)}}


@pytest.mark.parametrize("fail", [False, True])
def test_contract_line_only_on_success(smoke, capsys, monkeypatch, tmp_path,
                                       fail):
    """With the phases stubbed on a platform that reports a TPU, the last
    stdout line is exactly the contract's JSON object; a failing phase
    exits non-zero and prints no such line."""

    def trainer_phase(*a, **k):
        if fail:
            raise AssertionError("stub failure")
        return {}

    monkeypatch.setattr(jax, "devices", lambda: [_FakeTPU()])
    monkeypatch.setattr(smoke, "init_compile_cache", lambda: "unused")
    monkeypatch.setattr(smoke, "CompileLog", _StubCompileLog)
    monkeypatch.setattr(smoke, "trainer_phase", trainer_phase)
    monkeypatch.setattr(smoke, "fleet_phase", lambda *a, **k: {})
    monkeypatch.setattr(smoke, "kernel_phase", lambda shapes: {"k": True})
    rc = smoke.main(["--out", str(tmp_path)])
    out = capsys.readouterr().out.strip().splitlines()
    if fail:
        assert rc == 1
        assert not any(line.startswith("{") for line in out)
    else:
        assert rc == 0
        assert json.loads(out[-1]) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        }}
