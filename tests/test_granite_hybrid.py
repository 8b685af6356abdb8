"""granite-4.0-h-small's block against its plain reference, on the CPU.

A small model of the same family: the published 10-layer period (nine
Mamba-2 mixers, attention at index 5), d 64, a router over 12 experts with
top-3 of which this host holds 4 (experts 4-7), one shared expert, and
Granite's scalars. The program runs in float32 here, on float32 copies
of its parameters where gradients are compared (a bfloat16 parameter's
gradient comes out rounded to bfloat16), so the comparison with the
float32 reference sees summation order and the SSD's chunked against the
quadratic form, not rounding.
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.data.pipeline import DataConfig
from repro.models import model as model_lib
from repro.models import moe
from repro.models import reference_granite_hybrid as ref
from repro.optim import adamw
from repro.train import train_step as ts_lib
from repro.train.trainer import FalconTrainer

B, S = 2, 32


def small(**kw):
    sizes = dict(
        num_layers=10, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        vocab_size=256, num_experts=12, top_k=3, moe_d_ff=32, shared_d_ff=48,
        experts_held=4, expert_offset=4, ssm_state=16, ssm_head_dim=16,
        ssm_chunk=16, attention_multiplier=0.2, dtype="float32",
    )
    return dataclasses.replace(get_config("granite-4.0-h-small"), **{**sizes, **kw})


def weights(cfg, seed=0, std=0.1):
    """Every leaf normal(0, std) but norm scales (ones), as the benchmark
    makes weights."""
    shapes = model_lib.param_shapes(cfg)
    leaves, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.key(seed)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        if str(path[-1].key).endswith("norm"):
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        else:
            k = jax.random.fold_in(key, i)
            out.append((std * jax.random.normal(k, leaf.shape)).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes), out)


def tokens(cfg, seed=1):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, cfg.vocab_size, (B, S + 1)).astype(np.int32)
    return jnp.asarray(t[:, :-1]), jnp.asarray(t[:, 1:])


def test_loss_and_first_gradients_match_the_plain_reference():
    cfg = small()
    params = jax.tree.map(lambda a: a.astype(jnp.float32), weights(cfg))
    toks, labels = tokens(cfg)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: model_lib.loss_fn(p, {"tokens": toks, "labels": labels}, cfg,
                                    remat=False), has_aux=True))(params)
    rloss, rgrads = jax.jit(jax.value_and_grad(
        lambda p: ref.slot_loss(p, toks, labels, ref.config_dict(cfg))))(params)
    # float32 on both sides: a few ulp of summation order and exp/log
    assert abs(float(loss) - float(rloss)) <= 2e-6 * abs(float(rloss))
    assert int(metrics["moe_dropped"]) == 0
    # 10 layers x 4 held experts; each token routes top-3 of 12
    assert metrics["moe_counts"].shape == (10, 4)
    # gradients per leaf, against the larger of the leaf's norm and the
    # median leaf's (as the benchmark compares them): the SSD's chunked
    # form against the quadratic form and the scatter-add combine reorder
    # float32 sums (seen: 1.0e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    pairs = [(jax.tree_util.keystr(path), np.asarray(g, np.float64), np.asarray(r, np.float64))
             for (path, g), r in zip(flat, jax.tree.leaves(rgrads))]
    median = np.median([np.linalg.norm(r) for _, _, r in pairs])
    gaps = {k: np.linalg.norm(g - r) / max(np.linalg.norm(r), median) for k, g, r in pairs}
    assert max(gaps.values()) <= 1e-5, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]


def _moe_params(cfg, seed=2):
    return weights(cfg, seed)["blocks"]["sub0"]["moe"]


def _layer_input(cfg, seed=3):
    return jax.random.normal(jax.random.key(seed), (B, S, cfg.d_model), jnp.float32)


def test_three_expert_shares_sum_to_the_uncut_layer():
    full = small(experts_held=12, expert_offset=0)
    p = jax.tree.map(lambda a: a[0], _moe_params(full))
    x = _layer_input(full)
    y_full, aux_full, st_full = moe.apply_moe(p, x, full)
    parts, counts = [], []
    for off in (0, 4, 8):
        share = small(experts_held=4, expert_offset=off)
        ps = dict(p, **{k: p[k][off:off + 4] for k in ("wi_gate", "wi_up", "wo")})
        y, aux, st = moe.apply_moe(ps, x, share)
        parts.append(y)
        counts.append(st["counts"])
        np.testing.assert_allclose(float(aux), float(aux_full), rtol=1e-6)
    shared = moe._shared(p, moe.layers.rmsnorm(x, p["norm"], full.norm_eps).reshape(B * S, -1))
    # the shared expert is in each share's output: count it once
    total = sum(parts) - 2 * shared.reshape(B, S, -1)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(st_full["counts"]))
    assert int(np.sum(st_full["counts"])) == B * S * full.top_k


def test_padding_rows_of_the_grouped_product_are_never_read(monkeypatch):
    """The TPU's grouped product leaves the rows past the last group
    unwritten, forward and backward. With those rows NaN here, the loss and
    every gradient are what they are with zeros there."""
    real = jax.lax.ragged_dot

    def poison(out, sizes):
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    @jax.custom_vjp
    def ragged_dot(x, w, sizes):
        return poison(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return ragged_dot(x, w, sizes), (x, w, sizes)

    def bwd(res, ct):
        x, w, sizes = res
        _, vjp = jax.vjp(lambda x, w: real(x, w, sizes), x, w)
        dx, dw = vjp(ct)
        return poison(dx, sizes), dw, np.zeros(sizes.shape, jax.dtypes.float0)

    ragged_dot.defvjp(fwd, bwd)
    cfg = small()
    params = jax.tree.map(lambda a: a.astype(jnp.float32), weights(cfg))
    toks, labels = tokens(cfg)

    def loss_and_grad():  # a new function each time: no trace is reused
        return jax.jit(jax.value_and_grad(lambda p: model_lib.loss_fn(
            p, {"tokens": toks, "labels": labels}, cfg, remat=False), has_aux=True))

    (want, metrics), want_g = loss_and_grad()(params)
    # 64 tokens a layer, top-3 of which 4 experts are held: rungs of 192
    # and 128 rows, and each layer's ~64 held assignments take 128, so
    # poisoned rows sit inside the smaller dispatch too
    full = B * S * cfg.top_k
    assert moe.dispatch_rungs(B * S, cfg.top_k, cfg.held_experts, cfg.num_experts) == (full, 128)
    assert int(metrics["moe_rows"]) < cfg.num_layers * full
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    (got, _), got_g = loss_and_grad()(params)
    assert float(got) == float(want)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dropless_when_every_token_picks_one_expert():
    cfg = small()
    p = jax.tree.map(lambda a: a[0], _moe_params(cfg))
    # one large input feature, which only expert 5's router column reads:
    # expert 5 (held, second of the share) is in every token's top-3
    x = _layer_input(cfg).at[..., 0].set(100.0)
    p["router"] = p["router"].at[0, :].set(0.0).at[0, 5].set(10.0)
    y, _, st = moe.apply_moe(p, x, cfg)
    counts = np.asarray(st["counts"])
    assert counts[1] == B * S
    assert int(st["dropped"]) == 0
    # a capacity buffer at factor 1.25 would have held far fewer
    assert counts[1] > 1.25 * B * S * cfg.top_k / cfg.num_experts + 1
    want, _ = ref._moe(p, x.reshape(B * S, -1), ref.config_dict(cfg),
                       lambda a: a.astype(jnp.float32),
                       lambda a, w: jnp.dot(a, w.astype(jnp.float32),
                                            precision=jax.lax.Precision.HIGHEST))
    np.testing.assert_allclose(np.asarray(y).reshape(B * S, -1), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t, k, e_local, experts, held, rungs, rows", [
    # the hybrid cell's chip: 8,192 tokens, top-10, 3 of 72 experts
    (8192, 10, 3, 72, 3413, (24576, 12288, 6144), 6144),
    (8192, 10, 3, 72, 6145, (24576, 12288, 6144), 12288),
    (8192, 10, 3, 72, 24576, (24576, 12288, 6144), 24576),
    # every assignment held (the meshless olmoe, mixtral): one rung
    (8192, 8, 64, 64, 65536, (65536,), 65536),
    (4096, 2, 8, 8, 0, (8192,), 8192),
    # halves round up to 128 rows, and stop at the expected load
    (1000, 2, 2, 16, 250, (2000, 1024, 512, 256), 256),
    (1000, 2, 2, 16, 257, (2000, 1024, 512, 256), 512),
    # a decode step: below 128 rows every half rounds to the bound
    (4, 2, 2, 16, 3, (8,), 8),
])
def test_dispatch_takes_the_smallest_rung_that_holds_the_held_rows(
        t, k, e_local, experts, held, rungs, rows):
    assert moe.dispatch_rungs(t, k, e_local, experts) == rungs
    assert rungs[int(moe.rung_index(rungs, jnp.int32(held)))] == rows


def test_every_expert_held_runs_one_dispatch_without_a_switch():
    cfg = small(experts_held=12, expert_offset=0)
    p = jax.tree.map(lambda a: a[0], _moe_params(cfg))
    x = _layer_input(cfg)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(moe.apply_moe(p, x, cfg)[0])))(p))
    assert " cond[" not in jaxpr and "custom_vjp" not in jaxpr
    _, _, st = moe.apply_moe(p, x, cfg)
    assert int(st["rows"]) == B * S * cfg.top_k


EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, jax.numpy as jnp, numpy as np
sys.path[:0] = [os.environ["TEST_DIR"], os.environ["REPO_DIR"]]
from test_granite_hybrid import small, weights
from chipbench.reference import granite_hybrid as ref
from repro.launch.mesh import make_host_mesh
from repro.models import moe
from repro.models.reference_granite_hybrid import config_dict

# 480 tokens; experts 4-7 held, one a chip: rungs of 480, 256, 128 rows
cfg = small()
t, d = 480, cfg.d_model
p = jax.tree.map(lambda a: a[0], weights(cfg, 2)["blocks"]["sub0"]["moe"])
ct = jax.random.normal(jax.random.key(5), (1, t, d), jnp.float32)
cast = lambda a: a.astype(jnp.float32)
mm = lambda a, w: jnp.dot(a, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
rcfg = config_dict(cfg)
balanced = np.zeros((cfg.num_experts, cfg.num_experts), np.float32)
for i in range(cfg.num_experts):  # token of feature i: experts i, i+1, i+2
    for m in range(cfg.top_k):
        balanced[i, (i + m) % cfg.num_experts] = 1.0 - 0.1 * m
one_hot = jax.nn.one_hot(np.arange(t) % cfg.num_experts, d) * 10.0
cases = {
    # each expert in the top-3 of exactly a quarter of the tokens
    "balanced": (dict(p, router=jnp.zeros_like(p["router"]).at[:cfg.num_experts].set(balanced)),
                 one_hot + 0.01 * jax.random.normal(jax.random.key(3), (t, d))),
    # expert 5 (held by chip 1) in every token's top-3
    "one_expert": (dict(p, router=p["router"].at[0, :].set(0.0).at[0, 5].set(10.0)),
                   jax.random.normal(jax.random.key(3), (t, d)).at[:, 0].set(100.0)),
}
mesh = make_host_mesh(1, 4)
out = {}
for name, (q, x) in cases.items():
    x = x[None].astype(jnp.float32)
    def prog(q, x):
        y, _, st = moe.apply_moe(q, x, cfg)
        return jnp.sum(y * ct), (y, st)
    def plain(q, x):
        y, _ = ref._moe(q, x[0], rcfg, cast, mm)
        return jnp.sum(y * ct[0]), y
    with jax.set_mesh(mesh):
        (_, (y, st)), g = jax.jit(jax.value_and_grad(prog, has_aux=True))(q, x)
    (_, want), want_g = jax.jit(jax.value_and_grad(plain, has_aux=True))(q, x)
    rungs = moe.dispatch_rungs(t, cfg.top_k, 1, cfg.num_experts)
    counts = np.asarray(st["counts"])
    expect = sum(rungs[int(moe.rung_index(rungs, c))] for c in counts)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), rtol=1e-4, atol=1e-5)
    leaves = ("wi_gate", "wi_up", "wo", "router")
    median = np.median([np.linalg.norm(np.asarray(want_g[k], np.float64)) for k in leaves])
    gaps = {k: float(np.linalg.norm(np.asarray(g[k], np.float64) - np.asarray(want_g[k]))
                     / max(np.linalg.norm(np.asarray(want_g[k], np.float64)), median))
            for k in leaves}
    out[name] = {"gaps": gaps, "counts": counts.tolist(), "rows": int(st["rows"]), "expect": expect,
                 "dropped": int(st["dropped"]), "rungs": list(rungs)}
print("RESULT " + json.dumps(out))
"""


def test_expert_parallel_dispatch_takes_its_rung_and_matches_the_reference():
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    env["TEST_DIR"], env["REPO_DIR"] = here, repo
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", EP_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    import json

    line = [ln for ln in done.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    got = json.loads(line[len("RESULT "):])
    bal, one = got["balanced"], got["one_expert"]
    assert bal["rungs"] == [480, 256, 128]
    # 480 tokens x top-3 over 12 experts: 120 on each chip's one expert
    assert bal["counts"] == [120] * 4 and bal["rows"] == 4 * 128
    # chip 1 holds expert 5, in every token's top-3: the top rung
    assert one["counts"][1] == 480 and one["rows"] == one["expect"] >= 480 + 3 * 128
    assert bal["dropped"] == one["dropped"] == 0
    for case in (bal, one):
        # each leaf's gradient against the reference's, over the larger of
        # its norm and the median leaf's (as the benchmark compares them)
        assert max(case["gaps"][k] for k in ("wi_gate", "wi_up", "wo")) <= 1e-4, case["gaps"]
        # the expert-parallel path's router gradient sits further off (5e-4
        # here in float32), with a dispatch of fixed rows too
        assert case["gaps"]["router"] <= 2e-3, case["gaps"]


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json
import jax, numpy as np
sys_path = os.environ["TEST_DIR"]
import sys; sys.path.insert(0, sys_path)
from test_granite_hybrid import small, weights
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_host_mesh
from repro.train.trainer import FalconTrainer

cfg = small()
data = DataConfig(seq_len=32, global_batch=4, slots=2, dp_groups=2, seed=7)
host = jax.device_get(weights(cfg))
out = {}
for name, mesh in (("one", None), ("mesh", make_host_mesh(1, 4))):
    t = FalconTrainer(cfg=cfg, data=data, mesh=mesh)
    t.params = host if mesh is None else jax.device_put(host, t.param_shardings)
    rec = t.run(2)
    out[name] = {
        "losses": [r.loss for r in rec],
        "mu": [float(np.linalg.norm(np.asarray(m, np.float64)))
               for m in jax.tree.leaves(t.opt_state.mu)],
        "routed": t.moe_routed_tokens, "load_max": t.moe_expert_load_max,
        "dropped": t.moe_dropped_tokens, "rows": t.moe_dispatch_rows,
        "expert_sharding": str(getattr(
            t.params["blocks"]["sub0"]["moe"]["wi_gate"].sharding, "spec", None)),
    }
print("RESULT " + json.dumps(out))
"""


def test_four_device_mesh_step_matches_the_one_device_step():
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.path.join(here, "..", "src") + os.pathsep + env.get("PYTHONPATH", "")
    env["TEST_DIR"] = here
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    import json

    line = [ln for ln in done.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    got = json.loads(line[len("RESULT "):])
    one, mesh = got["one"], got["mesh"]
    # held experts split 1 a device over the model axis
    assert "model" in mesh["expert_sharding"]
    np.testing.assert_allclose(mesh["losses"], one["losses"], rtol=1e-5)
    np.testing.assert_allclose(mesh["mu"], one["mu"], rtol=1e-3)
    for k in ("routed", "load_max", "dropped"):
        assert mesh[k] == one[k], k
    assert one["dropped"] == 0 and one["routed"] > 0
    # 2 steps x 2 slots x 10 layers of 64 tokens: on the mesh each chip's
    # one expert has a single rung of 64 rows; on one device the four held
    # experts' rungs are 192 and 128 rows
    assert mesh["rows"] == 2 * 2 * 10 * 4 * 64
    assert 2 * 2 * 10 * 128 <= one["rows"] <= 2 * 2 * 10 * 192


def test_moe_trainer_counts_routing_on_one_device():
    cfg = small()
    data = DataConfig(seq_len=S, global_batch=4, slots=2, dp_groups=2, seed=3)
    t = FalconTrainer(cfg=cfg, data=data)
    t.run(1)
    # every token's top-3 over 12 experts, the held 4 of them counted
    assert 0 < t.moe_routed_tokens <= 4 * S * cfg.top_k * 10
    assert t.moe_expert_load_max >= t.moe_routed_tokens / (10 * 4)
    assert t.moe_dropped_tokens == 0
    # 2 slots x 10 layers, each dispatch 128 or 192 rows
    assert 2 * 10 * 128 <= t.moe_dispatch_rows <= 2 * 10 * 192


def test_dense_step_without_a_mesh_is_the_plain_jitted_step():
    cfg = get_config("granite-3-8b").smoke()
    data = DataConfig(seq_len=16, global_batch=4, slots=2, dp_groups=2, seed=5)
    t = FalconTrainer(cfg=cfg, data=data)
    assert t.param_shardings is None
    p0 = jax.device_get(t.params)
    opt_cfg = adamw.AdamWConfig()
    from repro.data.pipeline import make_batch

    batch = jax.tree.map(jnp.asarray, make_batch(cfg, data, 0))
    want_p, _, want_m = jax.jit(ts_lib.make_train_step(cfg, opt_cfg))(
        jax.tree.map(jnp.asarray, p0), adamw.init(p0), batch)
    rec = t.run(1)[-1]
    assert set(want_m) == {"loss"}  # no routing counters for a dense model
    assert rec.loss == float(want_m["loss"])
    for a, b in zip(jax.tree.leaves(t.params), jax.tree.leaves(want_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert t.moe_routed_tokens == 0


def test_config_scalars_and_counting():
    cfg = get_config("granite-4.0-h-small")
    assert cfg.attn_scale == 1 / 128 and cfg.held_experts == 72
    # tied: one vocab table; ~32B total, ~9B active
    assert 30e9 < cfg.total_params() < 34e9
    assert 8e9 < cfg.active_params() < 10e9
    held = dataclasses.replace(cfg, num_layers=10, experts_held=12)
    per_expert = 3 * 4096 * 768
    full = dataclasses.replace(cfg, num_layers=10)
    assert full.total_params() - held.total_params() == pytest.approx(10 * 60 * per_expert)
    assert "head" not in model_lib.param_shapes(cfg.smoke())
    assert cfg.smoke().held_experts == 4
    dense = get_config("granite-3-8b")
    assert dense.attn_scale == 128**-0.5 and not dense.tie_word_embeddings
