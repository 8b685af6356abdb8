"""Per-architecture smoke tests: reduced variant of each assigned family,
one forward/train step + one decode step on CPU; shape and NaN checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import INPUT_SHAPES, get_config, list_archs
from repro.models import model as model_lib
from repro.models import transformer

ARCHS = [a for a in list_archs() if a != "falcon-demo-100m"]

#: architectures whose smoke step takes >10 s on CPU — slow-marked so the
#: tier-1 default stays fast; CI's slow step still covers every family
HEAVY_ARCHS = {
    "jamba-1.5-large-398b", "qwen2-vl-72b", "mamba2-2.7b",
    "musicgen-large", "qwen2-moe-a2.7b", "olmoe-1b-7b",
}
ARCH_PARAMS = [
    pytest.param(a, marks=pytest.mark.slow) if a in HEAVY_ARCHS else a
    for a in ARCHS
]

B, S = 2, 32


def make_batch(cfg, rng):
    if cfg.modality == "vision_embeds":
        batch = {
            "embeds": jnp.asarray(
                rng.normal(size=(B, S, cfg.d_model)), cfg.activation_dtype
            ),
            "positions": jnp.asarray(
                np.broadcast_to(np.arange(S), (3, B, S)).copy(), jnp.int32
            ),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
        }
    elif cfg.modality == "audio_codes":
        k = cfg.num_codebooks
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S, k))),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S, k))),
        }
    else:
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
        }
    return batch


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_forward_and_grad(arch, rng):
    cfg = get_config(arch).smoke()
    assert cfg.d_model <= 512 and cfg.num_layers <= 2 * len(cfg.period)
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    params = model_lib.init_params(cfg, seed=0)
    batch = make_batch(cfg, rng)

    logits, aux = jax.jit(
        lambda p, b: model_lib.forward(p, b, cfg, remat=False)
    )(params, batch)
    if cfg.modality == "audio_codes":
        assert logits.shape == (B, S, cfg.num_codebooks, cfg.padded_vocab)
    else:
        assert logits.shape == (B, S, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())

    # One training step worth of gradients.
    loss, grads = jax.jit(
        lambda p, b: jax.value_and_grad(
            lambda q: model_lib.loss_fn(q, b, cfg)[0]
        )(p)
    )(params, batch)
    assert np.isfinite(float(loss))
    leaf_norms = [float(jnp.linalg.norm(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in leaf_norms)
    assert any(n > 0 for n in leaf_norms)


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_smoke_decode_step(arch, rng):
    cfg = get_config(arch).smoke()
    params = model_lib.init_params(cfg, seed=0)
    max_len = 16
    caches = transformer.init_caches(cfg, B, max_len)

    if cfg.modality == "vision_embeds":
        tok = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), cfg.activation_dtype)
    elif cfg.modality == "audio_codes":
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1, cfg.num_codebooks)))
    else:
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)))

    step = jax.jit(
        lambda p, t, c, pos: model_lib.decode_step(p, t, c, pos, cfg)
    )
    pos = jnp.int32(0)
    logits, caches2 = step(params, tok, caches, pos)
    if cfg.modality == "audio_codes":
        assert logits.shape == (B, 1, cfg.num_codebooks, cfg.padded_vocab)
    else:
        assert logits.shape == (B, 1, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    # Cache must actually change.
    changed = jax.tree.map(
        lambda a, b2: bool(jnp.any(a != b2)), caches, caches2
    )
    assert any(jax.tree.leaves(changed))

    # Second step at pos=1 still finite.
    logits2, _ = step(params, tok, caches2, jnp.int32(1))
    assert not bool(jnp.isnan(logits2.astype(jnp.float32)).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_parameter_count_sane(arch):
    """Full (unreduced) configs must be registered with believable sizes."""
    cfg = get_config(arch)
    n = cfg.total_params()
    expected = {
        "qwen2-vl-72b": 72e9,
        "musicgen-large": 3.3e9,
        "mamba2-2.7b": 2.7e9,
        "olmoe-1b-7b": 6.9e9,
        "granite-20b": 20e9,
        "mistral-nemo-12b": 12e9,
        "yi-9b": 8.8e9,
        "granite-3-8b": 8e9,
        "granite-4.0-h-small": 32e9,
        "jamba-1.5-large-398b": 398e9,
        "qwen2-moe-a2.7b": 14.3e9,
    }[arch]
    assert 0.55 * expected < n < 1.6 * expected, (arch, n / 1e9)


def test_input_shapes_registry():
    assert set(INPUT_SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}
    assert INPUT_SHAPES["long_500k"]["seq_len"] == 524288


@pytest.mark.slow
def test_serve_launcher_end_to_end():
    """The serving driver runs prefill + decode with FALCON latency
    monitoring attached (subprocess: exercises the CLI path)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "mamba2-2.7b",
         "--requests", "2", "--prompt-len", "16", "--gen", "4"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "decode throughput" in out.stdout
