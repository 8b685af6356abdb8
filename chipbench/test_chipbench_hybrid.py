"""The four-chip hybrid training cell on the CPU: its whole run at the
rehearsal size on four virtual devices, the readers of its new metrics,
its FLOP count and its reference's layer-by-layer gradient.

A sound run is correct; each fault planted in the program's timed path
(``calibrate_hybrid.FAULTS``) and the float8-operand control are not, by
the rehearsal's limits and by the cell's limits on the chip, in a whole
run and in the calibration sweep alike. The runs share one subprocess,
since JAX takes its device count when it starts."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import calibrate_hybrid, harness, op_scopes

CELL = "granite4h_small.train_failslow_4chip"
ROOT = harness.ROOT

SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [os.environ["ROOT"], os.path.join(os.environ["ROOT"], "src")]
import jax
jax.config.update("jax_compilation_cache_dir", os.environ["CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from chipbench import calibrate_hybrid, harness, run

CELL = sys.argv[1]


def result(name, argv):
    import contextlib, io
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(argv)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    print("RESULT " + json.dumps({"name": name, "rc": rc, "line": line}), flush=True)


def argv(seed, seconds):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", "0", "--rehearse-cpu"]


result("sound", argv(2**33 + 5, 3))
for fault in sorted(calibrate_hybrid.FAULTS):
    undo = []

    def set_attr(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    calibrate_hybrid.plant(fault, set_attr)
    try:
        result(fault, argv(2**33 + 6, 1))
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)
cell = harness.resolve_cell(harness.load_manifest(), CELL)
job = harness.Job(cell=cell, seed=2**33 + 7, seconds=0.0, tracer=None,
                  compiles=None, t_start=0.0, rehearsal=True)
for got in calibrate_hybrid.sweep(job):
    name = got["variant"] if got["variant"] == "control" else "sweep:" + got["variant"]
    print("RESULT " + json.dumps({"name": name, "rc": 0, "line": got}, default=float),
          flush=True)
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory) -> dict:
    env = dict(os.environ, ROOT=ROOT, CACHE_DIR=str(tmp_path_factory.mktemp("jax_cache")),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", SCRIPT, CELL], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=1200)
    assert done.returncode == 0, done.stderr[-4000:]
    out = {}
    for ln in done.stdout.splitlines():
        if ln.startswith("RESULT "):
            got = json.loads(ln[len("RESULT "):])
            out[got["name"]] = got
    return out


def _chip_limits() -> dict:
    return harness.resolve_cell(harness.load_manifest(), CELL).limits["limits"]


def _compared(line: dict) -> dict:
    """The comparison's readings of a result line, without the fail-slow's
    dispatch (a short window does not reach it)."""
    return {k: c["value"] for k, c in line["checks"].items()
            if k != "strategies_dispatched"}


def _fails(readings: dict, limits: dict) -> bool:
    return any(v > limits[k] for k, v in readings.items() if k in limits)


def test_sound_run_is_correct(runs):
    got = runs["sound"]
    line = got["line"]
    assert got["rc"] == 0 and line["correct"] is True, line["checks"]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0, "rehearsal": True}
    assert line["checks"]["moe_dropped_tokens"]["value"] == 0.0
    assert line["checks"]["strategies_dispatched"]["value"] >= 1


@pytest.mark.parametrize("fault", sorted(calibrate_hybrid.FAULTS))
def test_planted_fault_is_not_correct(runs, fault):
    line = runs[fault]["line"]
    readings = _compared(line)
    rehearsal = harness.load_json(harness.limits_path(CELL))
    assert _fails(readings, {**rehearsal["limits"], **rehearsal["rehearsal"]}), readings
    assert _fails(readings, _chip_limits()), readings


def _gaps(reading: dict) -> dict:
    """A sweep reading's compared numbers."""
    keys = ("loss_gap", "grad_norm_gap", "change_norm_gap", "moe_dropped_tokens")
    return {k: reading[k] for k in keys if k in reading}


def test_control_is_not_correct(runs):
    control = _gaps(runs["control"]["line"])
    rehearsal = harness.load_json(harness.limits_path(CELL))
    assert _fails(control, {**rehearsal["limits"], **rehearsal["rehearsal"]}), control
    assert _fails(control, _chip_limits()), control


def test_sweep_sound_program_is_within_the_limits(runs):
    sound = _gaps(runs["sweep:sound"]["line"])
    rehearsal = harness.load_json(harness.limits_path(CELL))
    assert not _fails(sound, {**rehearsal["limits"], **rehearsal["rehearsal"]}), sound
    assert sound["moe_dropped_tokens"] == 0


@pytest.mark.parametrize("fault", sorted(calibrate_hybrid.FAULTS))
def test_sweep_reads_each_planted_fault(runs, fault):
    """The sweep plants each fault in the program it compiles, as the
    whole run does: none is within the limits."""
    got = _gaps(runs["sweep:" + fault]["line"])
    rehearsal = harness.load_json(harness.limits_path(CELL))
    assert _fails(got, {**rehearsal["limits"], **rehearsal["rehearsal"]}), got
    assert _fails(got, _chip_limits()), got


def test_layerwise_gradient_is_the_autodiff_gradient():
    """The reference's layer-by-layer backward gives what
    ``jax.value_and_grad`` of its whole loss gives, over two periods."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import granite_hybrid as ref
    from chipbench.systems.sharded_trainer import program_arch
    from chipbench.weights import make_weights
    from repro.models import model as model_lib

    cfg = dict(harness.load_json(os.path.join(ROOT, "chipbench/configs/granite-4.0-h-small.json")))
    cfg.update(cfg["rehearsal"], num_hidden_layers=4)
    template = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                            model_lib.param_shapes(program_arch(cfg)))
    params = make_weights(template, 11, 0.1)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, 32)), jnp.int32)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.slot_loss(p, tokens, labels, cfg)))(params)
    loss, got = ref.LayerwiseGrad(cfg)(params, tokens, labels)
    # float32 sums in another order, each gradient leaf rounded to its
    # bfloat16 storage: within one bfloat16 ulp (2**-8) of the leaf's largest
    assert abs(loss - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= 2**-8 * np.max(np.abs(b)), np.max(np.abs(a - b))


def test_expert_units_split_the_expert_leaves():
    import jax.numpy as jnp

    from chipbench.reference import granite_hybrid as ref

    tree = {"moe": {"wo": jnp.ones((1, 3, 2, 2)), "router": jnp.ones((1, 2, 5))},
            "norm": jnp.ones((4,))}
    assert ref.unit_names(tree) == ["['moe']['router']", "['moe']['wo'][0, expert 0]",
                                    "['moe']['wo'][0, expert 1]",
                                    "['moe']['wo'][0, expert 2]", "['norm']"]
    np.testing.assert_allclose(ref.unit_norms(tree), [np.sqrt(10), 2, 2, 2, 2])


def test_flop_count_of_the_published_shapes():
    from chipbench import flops_hybrid

    cfg = harness.load_json(os.path.join(ROOT, "chipbench/configs/granite-4.0-h-small.json"))
    # 9 Mamba mixers of 102.24M, 1 attention layer of 41.94M, 10 MoE layers
    # of 0.29M router + 18.87M shared + 1.67 x 9.44M held experts, and the
    # 411.04M tied head
    assert flops_hybrid.expected_held_experts_per_token(cfg) == pytest.approx(10 * 12 / 72)
    assert flops_hybrid.hybrid_matmul_params(cfg) == pytest.approx(1.722089e9, rel=1e-6)
    assert flops_hybrid.hybrid_train_flops_per_token(cfg, 4096) == pytest.approx(10.6042e9, rel=1e-5)


def _xspace(ops: list[tuple[str, str | None, int, int]], window: tuple[int, int]) -> bytes:
    """A serialized XSpace: one TPU plane whose XLA Ops line holds ``ops``
    (name, scope path or None, start ns, duration ns), their scope paths
    as an interned (``ref_value``) ``tf_op`` stat of the event metadata,
    and a host plane with the harness's window span."""
    from jax.profiler import ProfileData

    metas, events, refs = [], [], {}
    for i, (name, path, start, dur) in enumerate(ops, start=1):
        stats = ""
        if path is not None:
            rid = refs.setdefault(path, 100 + len(refs))
            stats = f"stats {{ metadata_id: 1 ref_value: {rid} }}"
        metas.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "%{name} = f32[] '
                     f'fusion()" {stats} }} }}')
        events.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                      f"duration_ps: {dur * 1000} }}")
    stat_meta = ['stat_metadata { key: 1 value { id: 1 name: "tf_op" } }']
    stat_meta += [f'stat_metadata {{ key: {rid} value {{ id: {rid} name: "{p}" }} }}'
                  for p, rid in refs.items()]
    w0, w1 = window
    text = (
        'planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
        + " ".join(events) + " } " + " ".join(metas) + " " + " ".join(stat_meta) + " } "
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" timestamp_ns: 0 '
        f'events {{ metadata_id: 1 offset_ps: {w0 * 1000} duration_ps: {(w1 - w0) * 1000} }} }} '
        'event_metadata { key: 1 value { id: 1 name: "chipbench.window" } } }'
    )
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_scope_times_from_a_trace(tmp_path):
    fwd = "jit(train_step)/while/body/checkpoint/mamba/dot_general"
    bwd = "jit(train_step)/transpose(jvp())/checkpoint/rematted_computation/moe/ragged_dot"
    ops = [("fusion.1", fwd, 100, 50),
           ("fusion.2", bwd, 200, 30),
           ("all-reduce.3", "jit(train_step)/mamba/psum", 240, 20),  # a collective
           ("while.4", None, 300, 100),                              # holds fusion.5
           ("fusion.5", "jit(train_step)/shared_mlp/dot_general", 320, 40),
           ("fusion.6", fwd, 950, 100)]                              # half in the window
    data = _xspace(ops, (50, 1000))
    meta = op_scopes.event_metadata_stats(data)
    assert meta["/device:TPU:0"]["%fusion.1 = f32[] fusion()"] == {"tf_op": fwd}
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(data)
    chips, paths, (w0, w1), carriers = op_scopes.load(str(tmp_path))
    assert carriers == {"tf_op": 5} and (w0, w1) == (50.0, 1000.0)
    got = op_scopes.reduce_ops(chips, paths, w0, w1)
    assert got["mamba"] == pytest.approx(100e-9)       # 50 + the 50 inside
    assert got["moe"] == pytest.approx(30e-9)
    assert got["collective"] == pytest.approx(20e-9)  # not counted as mamba
    assert got["shared_mlp"] == pytest.approx(40e-9)
    assert got["busy"] == pytest.approx((50 + 30 + 20 + 100 + 50) * 1e-9)
    assert op_scopes.scope_of("a/transpose(jvp(mamba))/b") is None
    assert op_scopes.scope_of("a/mamba/b/moe/c") == "moe"


def test_readers_of_the_counters():
    import types

    cfg = harness.load_json(os.path.join(ROOT, "chipbench/configs/granite-4.0-h-small.json"))
    counters = {"steps": 25, "window_s": 10.0, "tokens_per_step": 8192, "seq_len": 4096,
                "chips": 4, "moe_routed_tokens": 25 * 120 * 1138,
                "moe_expert_load_max": 25 * 1500, "moe_layer_experts": 120}
    ctx = types.SimpleNamespace(counters=counters, trace=None, config=cfg,
                                peaks={"bf16_flops_per_s": 197e12})
    mfu = harness.load_reader("hybrid_step_mfu")(ctx)
    assert mfu == pytest.approx(100 * 10.6042e9 * 25 * 8192 / 10.0 / (4 * 197e12), rel=1e-4)
    assert harness.load_reader("hybrid_expert_load_max_over_mean")(ctx) == pytest.approx(1500 / 1138)
    # untraced: the device-time readers find nothing to read
    for name in ("hybrid_mamba_ms_per_step", "hybrid_moe_ms_per_step",
                 "hybrid_collective_ms_per_step"):
        assert harness.load_reader(name)(ctx) is None
    assert harness.load_reader("hybrid_step_mfu")(types.SimpleNamespace(
        counters={}, trace=None, config=cfg, peaks=None)) is None
