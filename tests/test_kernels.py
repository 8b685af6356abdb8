"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode executes the exact TPU kernel logic on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.attention import blocked_attention
from repro.models.ssm import ssd_scan as ssd_jnp


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,skv,h,kvh,hd,blk",
    [
        (1, 128, 128, 4, 4, 64, 64),
        (2, 256, 256, 4, 2, 64, 128),
        (1, 64, 64, 8, 1, 32, 32),  # MQA, tiny blocks
        (1, 192, 192, 2, 2, 64, 64),  # non-power-of-two seq with padding
    ],
)
def test_flash_attention_matches_ref(b, sq, skv, h, kvh, hd, blk, dtype):
    q = rand(0, (b, sq, h, hd), dtype)
    k = rand(1, (b, skv, kvh, hd), dtype)
    v = rand(2, (b, skv, kvh, hd), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype],
    )


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    b, s, h, kvh, hd = 1, 128, 4, 2, 64
    q = rand(3, (b, s, h, hd), jnp.float32)
    k = rand(4, (b, s, kvh, hd), jnp.float32)
    v = rand(5, (b, s, kvh, hd), jnp.float32)
    got = ops.flash_attention(
        q, k, v, causal=True, window=window, block_q=32, block_k=32, interpret=True
    )
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    b, s, h, hd = 1, 128, 2, 64
    q = rand(6, (b, s, h, hd), jnp.float32)
    k = rand(7, (b, s, h, hd), jnp.float32)
    v = rand(8, (b, s, h, hd), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_blocked_attention_jnp_matches_ref():
    """The model's jnp online-softmax path is itself validated vs the oracle."""
    b, s, h, kvh, hd = 2, 160, 4, 2, 32
    q = rand(9, (b, s, h, hd), jnp.float32)
    k = rand(10, (b, s, kvh, hd), jnp.float32)
    v = rand(11, (b, s, kvh, hd), jnp.float32)
    got = blocked_attention(q, k, v, causal=True, kv_block=64)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    got_w = blocked_attention(q, k, v, causal=True, window=48, kv_block=64)
    want_w = ref.attention_ref(q, k, v, causal=True, window=48)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w), rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ SSD
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,p,g,n,chunk",
    [
        (1, 64, 2, 32, 1, 16, 16),
        (2, 128, 4, 64, 2, 32, 32),
        (1, 96, 2, 16, 1, 8, 32),  # 3 chunks
    ],
)
def test_ssd_kernel_matches_sequential_ref(b, s, h, p, g, n, chunk, dtype):
    x = rand(20, (b, s, h, p), dtype)
    dt = jax.nn.softplus(rand(21, (b, s, h), jnp.float32)) * 0.5
    a = -jnp.exp(rand(22, (h,), jnp.float32) * 0.2)
    bm = rand(23, (b, s, g, n), dtype)
    cm = rand(24, (b, s, g, n), dtype)
    y_k, st_k = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    y_r, st_r = ref.ssd_ref(x, dt, a, bm, cm)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(y_r, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st_k, np.float32), np.asarray(st_r, np.float32), **tol)


def test_ssd_jnp_chunked_matches_sequential_ref():
    """The model's chunked jnp SSD is validated against the recurrence."""
    b, s, h, p, g, n = 2, 64, 4, 16, 1, 8
    x = rand(30, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(rand(31, (b, s, h), jnp.float32)) * 0.5
    a = -jnp.exp(rand(32, (h,), jnp.float32) * 0.2)
    bm = rand(33, (b, s, g, n), jnp.float32)
    cm = rand(34, (b, s, g, n), jnp.float32)
    y_c, st_c = ssd_jnp(x, dt, a, bm, cm, chunk=16)
    y_r, st_r = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_c), np.asarray(st_r), rtol=2e-4, atol=2e-4)


def test_ssd_kernel_initial_state_threading():
    """Decode consistency: chunked scan final state equals running the
    sequential reference — then one more decode step matches too."""
    from repro.models.ssm import decode_mamba  # noqa: F401  (smoke covered elsewhere)

    b, s, h, p, g, n = 1, 32, 2, 16, 1, 8
    x = rand(40, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(rand(41, (b, s, h), jnp.float32))
    a = -jnp.exp(rand(42, (h,), jnp.float32) * 0.1)
    bm = rand(43, (b, s, g, n), jnp.float32)
    cm = rand(44, (b, s, g, n), jnp.float32)
    _, st1 = ssd_jnp(x, dt, a, bm, cm, chunk=8)
    _, st2 = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------- flash decode
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,skv,h,kvh,hd,blk,valid",
    [
        (2, 256, 4, 4, 64, 128, 256),
        (2, 512, 8, 2, 64, 128, 300),   # GQA, partial fill
        (1, 384, 8, 1, 32, 256, 100),   # MQA, non-pow2 cache w/ padding
        (3, 128, 4, 2, 64, 512, 1),     # one valid position
    ],
)
def test_flash_decode_matches_reference(b, skv, h, kvh, hd, blk, valid, dtype):
    q = rand(1, (b, h, hd), dtype)
    k = rand(2, (b, skv, kvh, hd), dtype)
    v = rand(3, (b, skv, kvh, hd), dtype)
    got = ops.flash_decode(q, k, v, jnp.int32(valid), block_k=blk, interpret=True)
    want = ref.decode_attention_ref(q, k, v, jnp.int32(valid))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_flash_decode_per_sequence_lengths():
    b, skv, h, kvh, hd = 4, 256, 4, 2, 64
    q = rand(4, (b, h, hd), jnp.float32)
    k = rand(5, (b, skv, kvh, hd), jnp.float32)
    v = rand(6, (b, skv, kvh, hd), jnp.float32)
    lens = jnp.asarray([1, 17, 128, 256], jnp.int32)
    got = ops.flash_decode(q, k, v, lens, block_k=128, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_decode_step_kernel_path_matches_jnp():
    """Full serve decode step with use_kernel=True (flash-decode in interpret
    mode) must match the pure-jnp decode path, incl. sliding window."""
    import numpy as np
    from repro.configs.base import get_config
    from repro.models import model as model_lib, transformer

    for arch, window in (("granite-3-8b", 0), ("mistral-nemo-12b", 0)):
        cfg = get_config(arch).smoke()
        B, S = 2, 32
        params = model_lib.init_params(cfg, 0)
        caches = transformer.init_caches(cfg, B, S)
        tok = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (B, 1)),
            jnp.int32,
        )
        pos = jnp.asarray(9, jnp.int32)
        ref_logits, _ = jax.jit(
            lambda p, t, c, q: model_lib.decode_step(p, t, c, q, cfg, window=window)
        )(params, tok, caches, pos)
        ker_logits, _ = jax.jit(
            lambda p, t, c, q: model_lib.decode_step(
                p, t, c, q, cfg, window=window, use_kernel=True
            )
        )(params, tok, caches, pos)
        np.testing.assert_allclose(
            np.asarray(ref_logits, np.float32),
            np.asarray(ker_logits, np.float32),
            rtol=2e-2, atol=2e-2,
        )


# ----------------------------------------------- BOCD screening kernel
# Tolerance policy (docs/kernels.md): the Pallas kernel must match the
# same math as a plain traced-jnp function *bit for bit* in interpret
# mode (same ops, same order); the float32 kernel state is allowed
# <=1e-4 relative drift vs the float64 numpy oracle.
from repro.core import bocd  # noqa: E402
from repro.kernels import bocd_step as bk  # noqa: E402
from repro.kernels import cell_reduce as ck  # noqa: E402


@pytest.mark.parametrize(
    "k,b", [(32, 8193), (64, 4097), (8, 32769), (66, 3600), (3, 32769)]
)
def test_pallas_bocd_refuses_state_above_vmem_bound(k, b, monkeypatch):
    """K·B above the measured v5e VMEM bound raises before any array is
    built or any kernel compiles, naming the limit. The bound counts the
    (8, 128) tile padding: 66 x 3,600 is under it as a plain product but
    pads to 72 x 3,712, and 3 slots pad to 8."""
    assert bk.padded_slot_streams(k, b) > bk.MAX_SLOT_STREAMS
    monkeypatch.setattr(bk, "bocd_step", None)  # must never be reached
    monkeypatch.setattr(bk.jnp, "asarray", None)
    with pytest.raises(ValueError, match="MAX_SLOT_STREAMS = 262144"):
        bk.PallasBOCD(b, max_hypotheses=k)


def test_pallas_bocd_accepts_state_at_vmem_bound():
    det = bk.PallasBOCD(8192, max_hypotheses=32)
    assert det.max_hypotheses * det.n_series == bk.MAX_SLOT_STREAMS
    with pytest.raises(ValueError, match="MAX_SLOT_STREAMS"):
        det.retune(max_hypotheses=33)  # growing the frontier is checked too
    assert det.max_hypotheses == 32


def test_pallas_bocd_snapshot_restore_is_bit_exact():
    """A restored instance (built fresh, as FleetDetect.restore builds
    it) continues exactly like the one that was never interrupted, and
    the same snapshot seeds a second fork after the first ran on."""
    b = 6
    rng = np.random.default_rng(5)
    x = rng.normal(1.0, 0.05, (20, b))
    x[12:] *= 1.3
    ref = bk.PallasBOCD(b, mu0=x[0], max_hypotheses=8, interpret=True)
    for t in range(10):
        ref.update(x[t])
    ref.retune(hazard=0.02, max_hypotheses=6)
    snap = ref.snapshot()
    want = [ref.update(x[t]) for t in range(10, 20)]
    for _ in range(2):
        fork = bk.PallasBOCD(b, max_hypotheses=8, interpret=True)
        fork.restore(snap)
        got = [fork.update(x[t]) for t in range(10, 20)]
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(
            fork.p_recent_change(), ref.p_recent_change()
        )
        np.testing.assert_array_equal(
            fork.map_runlength(), ref.map_runlength()
        )
        assert (fork.max_hypotheses, fork.hazard) == (6, 0.02)


def _bocd_state(k, b, seed=0, dtype=jnp.float32):
    det = bk.PallasBOCD(b, max_hypotheses=k, dtype=dtype, interpret=True)
    return det


@pytest.mark.parametrize("b", [1, 7, 64])
def test_bocd_step_kernel_bitmatches_traced_reference(b):
    """pallas_call(interpret) vs the identical math traced without
    pallas_call: zero tolerance, every state array, several steps."""
    k = 16
    det = _bocd_state(k, b)
    state_r = (det._log_r, det._mu, det._beta, det._kappa, det._alpha,
               det._rl)
    rng = np.random.default_rng(0)
    x = rng.normal(1.0, 0.05, (12, b))
    x[8:] += 0.5  # a change, so growth/truncation/recycling all fire
    for t in range(12):
        xs = jnp.asarray(x[t], det.dtype)
        out_k = bk.bocd_step(xs, *state_r, det._mu0, det.hazard,
                             interpret=True)
        out_r = bk.bocd_step_reference(xs, *state_r, det._mu0, det.hazard)
        for a, bref in zip(out_k, out_r, strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bref))
        state_r = out_r[:6]


def test_bocd_step_kernel_nan_isolation():
    """NaN (censored) observations poison only their own column: clean
    columns stay finite and match a NaN-free run. The victim-slot choice
    is shared across columns (module docstring), so the isolation
    guarantee is tolerance-level, not bit-level."""
    k, b = 16, 5
    full = _bocd_state(k, b, seed=1)
    clean = _bocd_state(k, b - 1, seed=1)
    rng = np.random.default_rng(1)
    x = rng.normal(1.0, 0.05, (10, b))
    x[3:, -1] = np.nan  # censor the last column mid-stream
    p_full = [full.update(x[t]) for t in range(10)]
    p_clean = [clean.update(x[t, :-1]) for t in range(10)]
    for pf, pc in zip(p_full, p_clean, strict=True):
        assert np.isfinite(pf[:-1]).all()
        np.testing.assert_allclose(pf[:-1], pc, rtol=1e-3, atol=1e-3)
    assert np.isnan(p_full[-1][-1])  # the censored column is marked
    # Posterior statistics on the clean columns stay usable: finite,
    # in-range probabilities and valid run lengths. (The shared victim
    # slot means their exact values legitimately shift a little, so no
    # tight equality here — FleetDetect re-verifies flags exactly.)
    prc = full.p_recent_change()[:-1]
    assert np.isfinite(prc).all() and ((prc >= 0) & (prc <= 1)).all()
    assert (full.map_runlength()[:-1] >= 0).all()


@pytest.mark.parametrize("b", [1, 7, 64])
def test_pallas_bocd_matches_float64_numpy_oracle(b):
    """Float32 fixed-slot frontier vs the float64 BatchedBOCD oracle,
    while the frontier is not truncating (documented <=1e-4 drift)."""
    t_max, k = 24, 32
    rng = np.random.default_rng(2)
    x = rng.normal(1.0, 0.05, (t_max, b))
    x[16:] *= 1.3
    pal = bk.PallasBOCD(b, mu0=x[0], max_hypotheses=k, interpret=True)
    ora = bocd.BatchedBOCD(b, mu0=x[0], max_hypotheses=k)
    for t in range(t_max):
        p_pal = pal.update(x[t])
        p_ora = ora.update(x[t])
        np.testing.assert_allclose(p_pal, p_ora, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        pal.p_recent_change(), ora.p_recent_change(), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(pal.map_runlength(), ora.map_runlength())


@pytest.mark.parametrize("k", [2, 4])
def test_pallas_bocd_frontier_truncation_edges(k):
    """Tightest legal slot caps: the frontier recycles its victim slot
    every tick and the posterior stays a valid distribution throughout."""
    b, t_max = 7, 30
    rng = np.random.default_rng(3)
    x = rng.normal(1.0, 0.05, (t_max, b))
    x[20:] *= 1.4
    det = bk.PallasBOCD(b, mu0=x[0], max_hypotheses=k, interpret=True)
    p_hist = []
    for t in range(t_max):
        p0 = det.update(x[t])
        p_hist.append(p0)
        assert np.all((p0 >= 0.0) & (p0 <= 1.0))
        lr = np.asarray(det._log_r, np.float64)
        assert lr.shape[0] == k  # the cap held
        mass = np.exp(lr[np.isfinite(lr).any(axis=1)]).sum(axis=0)
        np.testing.assert_allclose(mass, 1.0, rtol=1e-3)
    # the break still registers through the tight cap: the change-point
    # mass right after the fault exceeds anything the quiet period produced
    p = np.asarray(p_hist)
    assert p[20:23].max() > 2.0 * p[5:20].max()


def test_pallas_bocd_take_columns_equals_fresh_slice():
    b = 10
    rng = np.random.default_rng(4)
    x = rng.normal(1.0, 0.05, (15, b))
    full = bk.PallasBOCD(b, mu0=x[0], interpret=True)
    keep = np.asarray([0, 3, 7])
    sub = bk.PallasBOCD(keep.size, mu0=x[0, keep], interpret=True)
    for t in range(15):
        full.update(x[t])
        sub.update(x[t, keep])
    full.take_columns(keep)
    np.testing.assert_array_equal(
        np.asarray(full._log_r), np.asarray(sub._log_r)
    )
    np.testing.assert_array_equal(
        full.p_recent_change(), sub.p_recent_change()
    )



# The posterior statistics reduce on the device in the state's dtype; the
# host path they replaced read the state back and reduced it in float64.
# Under x64 the two differ only by XLA's float64 exp/log against numpy's
# (one ulp on some inputs), so that case is held to a few ulp.
STAT_TOL = {"float32": dict(rtol=0, atol=1e-6),
            "float64": dict(rtol=1e-14, atol=0)}


def _host_p_recent(det, window):
    """The host path: float64 logsumexp over the read-back slots."""
    lr = np.asarray(det._log_r, np.float64)
    recent = np.asarray(det._rl)[:, 0] <= window
    if not recent.any():
        return np.zeros(det.n_series)
    return np.exp(bocd._logsumexp_cols(lr[recent]))


def _stats_run(dtype, b=24, k=8, ticks=30):
    """A detector through a change in every third stream, and the
    samples it has not seen yet."""
    rng = np.random.default_rng(6)
    x = rng.normal(1.0, 0.05, (ticks + 4, b))
    x[ticks // 2:, ::3] *= 1.3
    det = bk.PallasBOCD(b, mu0=x[0], max_hypotheses=k, dtype=dtype,
                        interpret=True)
    for t in range(ticks):
        det.update(x[t])
    return det, x[ticks:]


def _plant_edges(det):
    """Stream 0 loses every slot of run length <= 2; stream 1 ties every
    slot; stream 2 ties two live slots above the rest; stream 3 is all
    -inf (np.argmax gives slot 0)."""
    lr = np.asarray(det._log_r).copy()
    rl = np.asarray(det._rl)[:, 0]
    assert (rl <= 2).any() and (rl > 2).any()
    lr[rl <= 2, 0] = -np.inf
    lr[:, 1] = np.log(1.0 / lr.shape[0])
    lr[:, 2] = np.log(0.01)
    lr[[2, 5], 2] = np.log(0.3)
    lr[:, 3] = -np.inf
    det._log_r = jnp.asarray(lr, det.dtype)


def _assert_stats_match_host(det, dtype):
    rl = np.asarray(det._rl)[:, 0]
    for window in (0, 2, int(rl.max()) + 1):
        got = det.p_recent_change(window)
        assert got.dtype == det.dtype and got.shape == (det.n_series,)
        np.testing.assert_allclose(got, _host_p_recent(det, window),
                                   **STAT_TOL[dtype])
    lr = np.asarray(det._log_r)
    got = det.map_runlength()
    np.testing.assert_array_equal(got, rl[np.argmax(lr, axis=0)])
    return got


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("stage", ["run", "take_columns", "retune", "restore"])
def test_pallas_bocd_statistics_match_the_host_path(stage, dtype):
    """``p_recent_change`` (windows 0, 2 and past every run length) and
    ``map_runlength`` reduced on the device equal the host path over the
    same state, ties and dead streams included, after each way the state
    can change besides a step."""
    with jax.enable_x64(dtype == "float64"):
        det, x = _stats_run(jnp.dtype(dtype))
        if stage == "take_columns":
            det.take_columns(np.arange(0, det.n_series, 2))
            x = x[:, ::2]
        elif stage == "retune":
            det.retune(hazard=0.03)
        elif stage == "restore":
            snap = det.snapshot()
            det.retune(hazard=0.03, max_hypotheses=6)
            det.update(x[0])
            det.restore(snap)
        det.update(x[1])
        _assert_stats_match_host(det, dtype)
        _plant_edges(det)
        assert (det.p_recent_change(2)[[0, 3]] == 0).all()
        rl = np.asarray(det._rl)[:, 0]
        run_lengths = _assert_stats_match_host(det, dtype)
        assert run_lengths[1] == rl[0] and run_lengths[2] == rl[2]
        assert run_lengths[3] == rl[0]


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_pallas_bocd_retuned_step_bitmatches_bocd_step(dtype):
    """After ``retune(hazard=...)``, and after a restore across it, the
    next steps equal ``bocd_step`` given the new hazard as a Python float,
    bit for bit."""
    with jax.enable_x64(dtype == "float64"):
        det, x = _stats_run(jnp.dtype(dtype))
        snap = det.snapshot()
        det.retune(hazard=0.0371)
        for run in range(2):
            for t in range(len(x)):
                state = (det._log_r, det._mu, det._beta, det._kappa,
                         det._alpha, det._rl)
                want = bk.bocd_step(
                    jnp.asarray(x[t], det.dtype), *state, det._mu0, 0.0371,
                    det.kappa0, det.alpha0, det.beta0, det.truncation,
                    interpret=True,
                )
                p0 = det.update(x[t])
                got = (det._log_r, det._mu, det._beta, det._kappa,
                       det._alpha, det._rl, p0)
                for g, w in zip(got, want, strict=True):
                    np.testing.assert_array_equal(
                        np.asarray(g), np.asarray(w).reshape(g.shape)
                    )
            retuned = det.snapshot()
            det.restore(snap)
            assert det.hazard != 0.0371
            det.restore(retuned)


def test_pallas_bocd_statistics_compile_with_the_first_update():
    """A tick that asks for a statistic, with any window, compiles
    nothing once the detector has stepped at its shape: the first flag may
    come long after set-up."""
    b, k = 37, 5
    det = bk.PallasBOCD(b, max_hypotheses=k, interpret=True)
    det.update(np.ones(b))
    compiles = []

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for window in (2, 7, 10_000):
            det.update(np.full(b, 1.1))
            det.p_recent_change(window)
            det.map_runlength()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []

# ---------------------------------------------- simulator cell reduce
def _reduce_inputs(pp, tp, dp, seed=0):
    rng = np.random.default_rng(seed)
    return dict(
        cell_speed=jnp.asarray(rng.uniform(0.5, 1.0, (pp, dp)),
                               jnp.float32),
        tp_edge=jnp.asarray(rng.uniform(5.0, 40.0, (pp, dp, tp)),
                            jnp.float32),
        dp_edge=jnp.asarray(rng.uniform(5.0, 40.0, (pp, dp, tp)),
                            jnp.float32),
        hop_bw=jnp.asarray(rng.uniform(5.0, 40.0, (pp - 1, dp)),
                           jnp.float32),
        alloc_off=jnp.asarray(rng.uniform(1.0, 3.0, (dp,)), jnp.float32),
    )


@pytest.mark.parametrize("pp,tp,dp", [(2, 2, 2), (4, 8, 4), (8, 8, 16)])
def test_cell_reduce_kernel_bitmatches_traced_reference(pp, tp, dp):
    ins = _reduce_inputs(pp, tp, dp, seed=pp)
    scalars = dict(c_flops=3.0, c_speed=1.1, c_tp=0.4, pp_vol=0.2,
                   c_dp=0.9)
    out_k = ck.cell_reduce(**ins, **scalars, interpret=True)
    out_r = ck.cell_reduce_reference(**ins, **scalars)
    for a, b in zip(out_k, out_r, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cell_reduce_matches_float64_formula():
    """Float32 fused tree vs the float64 numpy reduction formulas."""
    pp, tp, dp = 4, 4, 8
    ins = _reduce_inputs(pp, tp, dp, seed=9)
    c_flops, c_speed, c_tp, pp_vol, c_dp = 3.0, 1.1, 0.4, 0.2, 0.9
    t, stage_max, tp_bw, dp_bw = ck.cell_reduce(
        **ins, c_flops=c_flops, c_speed=c_speed, c_tp=c_tp,
        pp_vol=pp_vol, c_dp=c_dp, interpret=True,
    )
    cs = np.asarray(ins["cell_speed"], np.float64)
    te = np.asarray(ins["tp_edge"], np.float64)
    de = np.asarray(ins["dp_edge"], np.float64)
    hb = np.asarray(ins["hop_bw"], np.float64)
    ao = np.asarray(ins["alloc_off"], np.float64)
    tp_bw64 = te.min(axis=2)                       # (pp, dp)
    stage = c_flops / (c_speed * cs) + c_tp / tp_bw64
    stage_max64 = stage.max(axis=0)                # (dp,)
    dp_bw64 = de.min(axis=1)                       # (pp, tp)
    pipe = ao * stage_max64 + 2.0 * (pp_vol / hb).sum(axis=0)
    want_t = pipe.max() + c_dp / dp_bw64.min()
    np.testing.assert_allclose(float(t[0, 0]), want_t, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(tp_bw, np.float64), tp_bw64, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(dp_bw, np.float64), dp_bw64, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(stage_max, np.float64)[0], stage_max64, rtol=1e-4
    )
