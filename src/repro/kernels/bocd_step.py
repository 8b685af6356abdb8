"""Fused BatchedBOCD step kernel (Pallas) — one launch per fleet tick.

The numpy :class:`repro.core.bocd.BatchedBOCD` advances the run-length
posterior of B streams with ~15 separate (K, B) array passes per tick
(predictive, normalize, Normal-Gamma update, truncate, frontier kill,
renormalize). This module fuses the whole step — predict / update /
truncate, including the shared ``max_hypotheses`` frontier as an in-kernel
threshold + victim-selection pass — into a single Pallas kernel launch
over the entire (K, B) state, so on a compiled backend every pass runs out
of VMEM with no HBM round-trips between them.

Fixed-slot frontier
-------------------
``BatchedBOCD`` stores a *growing* list of hypothesis rows and compacts /
kills rows per tick; a kernel needs static shapes. The slot model used
here is provably step-equivalent: keep exactly ``K = max_hypotheses`` rows
("slots"), and each tick overwrite the **victim** slot — the row with the
lowest shared strength ``max_b log_r[k, b]``, ties broken on smallest run
length, then smallest slot index — with the new ``r = 0`` hypothesis.
Fully-dead rows (all columns ``-inf``, the state BatchedBOCD compacts
away) have strength ``-inf`` and are recycled first, so below the cap no
live hypothesis is ever evicted; at the cap the evicted row is exactly the
one BatchedBOCD's stable argsort kills (its rows are rl-ascending, so
"smallest index" == "smallest run length"). The one intended difference:
the kernel renormalizes every column after the kill, where BatchedBOCD
renormalizes only affected columns — a ``log(1) ~ 0`` shift that moves
untouched columns by at most a few ulp (see docs/kernels.md for the
tolerance policy; NaN inputs additionally perturb victim choice, which the
numpy path leaves to argsort's NaN ordering).

``bocd_step`` (the Pallas launch) and ``bocd_step_reference`` (the same
traced math without ``pallas_call``) share one step function, so
interpret-mode kernel output is bit-identical to the reference by
construction — the parity tests assert exact equality.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bocd import DEFAULT_CP_THRESHOLD
from repro.kernels import pallas_compiled
from repro.obs.host import span

#: default frontier when the caller passes ``max_hypotheses=None`` — the
#: fixed-slot kernel needs *some* static K (uncapped growth is a
#: numpy-backend feature; 64 comfortably covers the fleet screen's caps).
DEFAULT_SLOTS = 64

#: Largest padded K·B (slots x streams, see :func:`padded_slot_streams`)
#: whose whole float32 state fits one kernel's VMEM on a TPU v5e: K=32
#: compiles at B = 8,192 and runs out of VMEM at B = 12,288; K=64
#: compiles at B = 4,096 and fails at B = 8,192 (compile-only for
#: ``v5e:2x2``, tests/test_tpu_compile.py).
MAX_SLOT_STREAMS = 32 * 8192


def padded_slot_streams(k: int, b: int) -> int:
    """K·B as VMEM holds it: a float32 ``(K, B)`` array is laid out in
    (8, 128) tiles, so K rounds up to a multiple of 8 and B to one of 128."""
    return -(-k // 8) * 8 * (-(-b // 128) * 128)


def _fused_step(
    x, log_r, mu, beta, kappa, alpha, rl, tconst, mu0,
    log_h, log_1mh, log_trunc, kappa0, alpha0, beta0, cp_const,
):
    """One fused BOCD step over (K, B) fixed-slot state (pure jnp).

    Shapes: ``x``/``mu0`` (1, B); ``log_r``/``mu``/``beta`` (K, B);
    ``kappa``/``alpha``/``tconst`` (K, 1); ``rl`` (K, 1) int32. Scalars are
    0-d arrays. Returns the updated state tuple plus ``p0`` (1, B) =
    Pr(r_t = 0) per stream.
    """
    dt = log_r.dtype
    k_slots = log_r.shape[0]
    # Growth: Student-t posterior predictive per slot (gammaln terms are
    # precomputed outside the kernel into tconst — Mosaic has no lgamma).
    df = 2.0 * alpha
    scale2 = beta * ((kappa + 1.0) / (alpha * kappa))
    z2 = (x - mu) ** 2 / scale2 / df
    logpred = tconst - 0.5 * jnp.log(jnp.pi * df * scale2)
    logpred -= 0.5 * (df + 1.0) * jnp.log1p(z2)
    growth = logpred + log_r + log_1mh  # dead (-inf) slots stay dead
    # Change-point row: x scored under the fresh-segment prior.
    df0 = 2.0 * alpha0
    s20 = beta0 * (kappa0 + 1.0) / (alpha0 * kappa0)
    z20 = (x - mu0) ** 2 / s20 / df0
    cp = cp_const - 0.5 * jnp.log(jnp.pi * df0 * s20)
    cp -= 0.5 * (df0 + 1.0) * jnp.log1p(z20)
    cp = cp + log_h
    # Normalize over the K + 1 conceptual rows (K grown slots + cp row).
    m = jnp.maximum(jnp.max(growth, axis=0, keepdims=True), cp)
    shift = jnp.where(jnp.isfinite(m), m, jnp.zeros((), dt))
    tot = jnp.sum(jnp.exp(growth - shift), axis=0, keepdims=True)
    tot += jnp.exp(cp - shift)
    lse = jnp.log(tot) + shift
    growth = growth - lse
    cp = cp - lse
    # Per-column mass truncation (the cp row is exempt, like numpy).
    neg_inf = jnp.asarray(-jnp.inf, dt)
    growth = jnp.where(growth <= log_trunc, neg_inf, growth)
    # Victim slot = lowest shared strength, ties -> smallest run length,
    # then smallest slot index. NaN strengths (NaN observations) are
    # treated as +inf so a poisoned column never hijacks the frontier.
    strength = jnp.max(growth, axis=1, keepdims=True)
    key = jnp.where(jnp.isnan(strength), jnp.asarray(jnp.inf, dt), strength)
    smin = jnp.min(key)
    rl_f = rl.astype(dt)
    tie = key == smin
    rmin = jnp.min(jnp.where(tie, rl_f, jnp.asarray(jnp.inf, dt)))
    victim = tie & (rl_f == rmin)
    rows = jax.lax.broadcasted_iota(jnp.int32, (k_slots, 1), 0)
    first = jnp.min(jnp.where(victim, rows, k_slots))
    victim = rows == first  # (K, 1) one-hot
    # Normal-Gamma update: survivors advance their posterior; the victim
    # slot restarts from the prior and absorbs x as its first observation.
    kap = jnp.where(victim, kappa0, kappa)
    alp = jnp.where(victim, alpha0, alpha)
    mu_b = jnp.where(victim, mu0, mu)
    beta_b = jnp.where(victim, beta0, beta)
    denom = kap + 1.0
    beta_out = beta_b + 0.5 * kap * (x - mu_b) ** 2 / denom
    mu_out = (kap * mu_b + x) / denom
    alpha_out = alp + 0.5
    rl_out = jnp.where(victim, 0, rl + 1)
    log_r_new = jnp.where(victim, cp, growth)
    # Renormalize (all columns — see module docstring re: tolerance).
    m2 = jnp.max(log_r_new, axis=0, keepdims=True)
    shift2 = jnp.where(jnp.isfinite(m2), m2, jnp.zeros((), dt))
    lse2 = jnp.log(jnp.sum(jnp.exp(log_r_new - shift2), axis=0,
                           keepdims=True)) + shift2
    log_r_out = log_r_new - lse2
    p0 = jnp.sum(jnp.where(victim, jnp.exp(log_r_out), jnp.zeros((), dt)),
                 axis=0, keepdims=True)
    return log_r_out, mu_out, beta_out, denom, alpha_out, rl_out, p0


def _step_kernel(
    params_ref, x_ref, log_r_ref, mu_ref, beta_ref, kappa_ref, alpha_ref,
    rl_ref, tconst_ref, mu0_ref,
    log_r_out, mu_out, beta_out, kappa_out, alpha_out, rl_out, p0_out,
):
    p = params_ref
    outs = _fused_step(
        x_ref[:], log_r_ref[:], mu_ref[:], beta_ref[:], kappa_ref[:],
        alpha_ref[:], rl_ref[:], tconst_ref[:], mu0_ref[:],
        log_h=p[0, 0], log_1mh=p[0, 1], log_trunc=p[0, 2],
        kappa0=p[0, 3], alpha0=p[0, 4], beta0=p[0, 5], cp_const=p[0, 6],
    )
    for ref, val in zip(
        (log_r_out, mu_out, beta_out, kappa_out, alpha_out, rl_out, p0_out),
        outs,
    ):
        ref[:] = val


def _tconst(alpha, dt):
    """The Student-t predictive's gammaln constant per slot, computed
    outside the kernel (Mosaic has no lgamma)."""
    gammaln = jax.scipy.special.gammaln
    df = 2.0 * alpha.astype(dt)
    return gammaln((df + 1.0) / 2.0) - gammaln(df / 2.0)


def _priors(hazard, alpha0, truncation, dt):
    """The step's scalars that depend on the priors alone:
    ``(log_h, log_1mh, log_trunc, cp_const)``."""
    gammaln = jax.scipy.special.gammaln
    a0 = jnp.asarray(alpha0, dt)
    cp_const = gammaln((2.0 * a0 + 1.0) / 2.0) - gammaln(a0)
    hz = jnp.asarray(hazard, dt)
    log_trunc = jnp.log(jnp.asarray(truncation, dt))
    return jnp.log(hz), jnp.log1p(-hz), log_trunc, cp_const


def _params(hazard, kappa0, alpha0, beta0, truncation, dt):
    """The kernel's (1, 8) SMEM row: ``log_h, log_1mh, log_trunc, kappa0,
    alpha0, beta0, cp_const`` and a pad."""
    log_h, log_1mh, log_trunc, cp_const = _priors(
        hazard, alpha0, truncation, dt
    )
    return jnp.stack([
        log_h, log_1mh, log_trunc,
        jnp.asarray(kappa0, dt), jnp.asarray(alpha0, dt),
        jnp.asarray(beta0, dt), cp_const, jnp.zeros((), dt),
    ]).reshape(1, 8)


def _launch(params, x, log_r, mu, beta, kappa, alpha, rl, mu0, interpret):
    """The ``pallas_call`` over the state, given its params row."""
    dt = log_r.dtype
    k_slots, b = log_r.shape
    tconst = _tconst(alpha, dt)
    x = x.astype(dt).reshape(1, -1)
    mu0 = mu0.astype(dt).reshape(1, -1)
    vec = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _step_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((k_slots, b), dt),   # log_r
            jax.ShapeDtypeStruct((k_slots, b), dt),   # mu
            jax.ShapeDtypeStruct((k_slots, b), dt),   # beta
            jax.ShapeDtypeStruct((k_slots, 1), dt),   # kappa
            jax.ShapeDtypeStruct((k_slots, 1), dt),   # alpha
            jax.ShapeDtypeStruct((k_slots, 1), jnp.int32),  # rl
            jax.ShapeDtypeStruct((1, b), dt),          # p0
        ),
        in_specs=[smem] + [vec] * 9,
        out_specs=(vec,) * 7,
        interpret=interpret,
        name="bocd_step",
    )(params, x, log_r, mu, beta, kappa, alpha, rl, tconst, mu0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bocd_step(
    x, log_r, mu, beta, kappa, alpha, rl, mu0,
    hazard, kappa0=1.0, alpha0=1.0, beta0=1.0, truncation=1e-6,
    *, interpret=None,
):
    """One fused step as a single ``pallas_call`` launch.

    State dtypes/shapes as in :func:`_fused_step`; ``hazard`` may be a
    traced scalar (retunes don't recompile). Returns
    ``(log_r, mu, beta, kappa, alpha, rl, p0)``.
    """
    if interpret is None:
        interpret = not pallas_compiled()
    params = _params(hazard, kappa0, alpha0, beta0, truncation, log_r.dtype)
    return _launch(params, x, log_r, mu, beta, kappa, alpha, rl, mu0,
                   interpret)


@jax.jit
def bocd_step_reference(
    x, log_r, mu, beta, kappa, alpha, rl, mu0,
    hazard, kappa0=1.0, alpha0=1.0, beta0=1.0, truncation=1e-6,
):
    """The kernel's math as a plain traced function (no ``pallas_call``) —
    the bit-match oracle for interpret-mode parity tests."""
    dt = log_r.dtype
    log_h, log_1mh, log_trunc, cp_const = _priors(
        hazard, alpha0, truncation, dt
    )
    return _fused_step(
        x.astype(dt).reshape(1, -1), log_r, mu, beta, kappa, alpha, rl,
        _tconst(alpha, dt), mu0.astype(dt).reshape(1, -1),
        log_h, log_1mh, log_trunc,
        jnp.asarray(kappa0, dt), jnp.asarray(alpha0, dt),
        jnp.asarray(beta0, dt), cp_const,
    )


@functools.partial(jax.jit, static_argnames=("dt",))
def _params_row(priors, dt):
    """:func:`_params` on the device from the uploaded ``(hazard, kappa0,
    alpha0, beta0, truncation)``."""
    return _params(*(priors[i] for i in range(5)), dt)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _advance(params, x, log_r, mu, beta, kappa, alpha, rl, mu0, *,
             interpret):
    """:class:`PallasBOCD`'s tick: the launch over device arrays alone,
    with ``p0`` as a (B,) row."""
    if interpret is None:
        interpret = not pallas_compiled()
    *state, p0 = _launch(params, x, log_r, mu, beta, kappa, alpha, rl, mu0,
                         interpret)
    return (*state, p0.reshape(-1))


@jax.jit
def _p_recent(log_r, rl, window):
    """Pr(run length <= ``window``) per stream, in the state's dtype: the
    max-shifted logsumexp of the slots with ``rl <= window``,
    exponentiated. A stream with no live recent slot gives 0."""
    lr = jnp.where(rl <= window, log_r, -jnp.inf)
    m = jnp.max(lr, axis=0)
    shift = jnp.where(jnp.isfinite(m), m, 0.0)
    return jnp.exp(jnp.log(jnp.sum(jnp.exp(lr - shift), axis=0)) + shift)


@jax.jit
def _map_runlength(log_r, rl):
    """The run length of each stream's most probable slot; ties go to the
    first slot, as ``np.argmax`` breaks them. The slot is picked by a
    masked sum, not a gather, which a TPU runs far slower."""
    slots = jax.lax.broadcasted_iota(jnp.int32, log_r.shape, 0)
    best = slots == jnp.argmax(log_r, axis=0)
    return jnp.sum(jnp.where(best, rl, 0), axis=0)


def _check_fits(k: int, b: int) -> None:
    padded = padded_slot_streams(k, b)
    if padded > MAX_SLOT_STREAMS:
        raise ValueError(
            f"PallasBOCD state of {k} slots x {b} streams ({padded} once "
            f"padded to (8, 128) tiles) exceeds the VMEM limit of "
            f"MAX_SLOT_STREAMS = {MAX_SLOT_STREAMS}; split the streams "
            "across instances"
        )


class PallasBOCD:
    """Fixed-slot batched BOCD screening backend driven by the fused kernel.

    Drop-in for :class:`repro.core.bocd.BatchedBOCD` behind the
    ``ScreeningBackend`` interface (``update`` / ``p_recent_change`` /
    ``map_runlength`` / ``take_columns`` / ``retune``). State lives as jax
    arrays and advances one kernel launch per tick. ``update`` returns the
    ``p0`` row as a device array, unread: a caller that wants it on the
    host calls ``np.asarray``. ``p_recent_change`` and ``map_runlength``
    reduce the state on the device, in the state's dtype, and read back
    only their (B,) answer.

    ``dtype`` defaults to float32 (the accelerator-native width — see
    docs/kernels.md for the documented tolerance vs the float64 numpy
    oracle); pass ``jnp.float64`` with jax x64 enabled for tight-parity
    testing. ``interpret`` defaults to auto (compiled on a TPU, interpreted
    elsewhere). The kernel keeps the whole (K, B) state in VMEM, so K·B,
    padded to (8, 128) tiles, may not exceed :data:`MAX_SLOT_STREAMS`: at
    the default 32 slots that is 8,192 streams per instance — shard wider
    fleets across instances (cohorts already do). A larger frontier raises
    ``ValueError`` here and in :meth:`retune`, on every platform, rather
    than at the TPU compile.

    :meth:`snapshot` / :meth:`restore` carry the state for the campaign
    engine's forks. jax arrays are immutable and every step rebinds them,
    so a snapshot holds the arrays themselves: no device copy, and any
    number of restores may share one.

    The kernel's params row (the priors and the hazard, as
    :func:`_params` derives them) is built on the device once and again
    only on ``retune(hazard=...)``, so in the steady state a tick copies up
    only ``x``, in the state dtype, and reads nothing back until a
    statistic is asked for. Host-device traffic is counted where it
    happens: ``h2d_bytes`` (host arrays copied to the device),
    ``d2h_bytes`` and ``host_reads`` (the blocking reads the backend
    itself makes). A statistic asked for again while the state is
    unchanged is served from the host copy JAX keeps, and counts once.
    Each copy is a wall-clock span, ``bocd.upload`` or ``bocd.readback``
    (:mod:`repro.obs.host`), carrying the step number; a read-back also
    carries the ``bytes`` it adds to ``d2h_bytes``, so a trace shows the
    counter.
    """

    def __init__(
        self,
        n_series: int,
        hazard: float = 1.0 / 100.0,
        mu0: float | np.ndarray = 0.0,
        kappa0: float = 1.0,
        alpha0: float = 1.0,
        beta0: float = 1.0,
        cp_threshold: float = DEFAULT_CP_THRESHOLD,
        truncation: float = 1e-6,
        max_hypotheses: int | None = 32,
        *,
        dtype=jnp.float32,
        interpret: bool | None = None,
    ) -> None:
        b = int(n_series)
        k = DEFAULT_SLOTS if max_hypotheses is None else int(max_hypotheses)
        if k < 2:
            raise ValueError("PallasBOCD needs at least 2 hypothesis slots")
        _check_fits(k, b)
        self.n_series = b
        self.hazard = float(hazard)
        self.kappa0 = float(kappa0)
        self.alpha0 = float(alpha0)
        self.beta0 = float(beta0)
        self.cp_threshold = float(cp_threshold)
        self.truncation = float(truncation)
        self.max_hypotheses = k
        self.dtype = jnp.dtype(dtype)
        self.interpret = interpret
        self._t = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.host_reads = 0
        #: state array or statistic -> the array of it last read (_read)
        self._read_last: dict[str, weakref.ref] = {}
        #: statistic -> (log_r, rl, args, its device answer); see :meth:`_stat`
        self._stats: dict[str, tuple] = {}
        #: window -> it as a device scalar, copied up once
        self._windows: dict[int, jax.Array] = {}
        #: the (K, B) shape whose statistics are compiled (see :meth:`update`)
        self._warm_shape: tuple | None = None
        self._params = self._params_for(self.hazard)
        mu0 = np.broadcast_to(np.asarray(mu0, dtype=np.float64), (b,))
        self._mu0 = self._put(mu0, self.dtype)
        # Slot 0 holds the prior hypothesis; slots 1..K-1 start dead
        # (-inf mass) and are recycled as the frontier fills.
        log_r = np.full((k, b), -np.inf)
        log_r[0] = 0.0
        self._log_r = self._put(log_r, self.dtype)
        self._mu = jnp.broadcast_to(self._mu0[None, :], (k, b)).astype(
            self.dtype
        )
        self._beta = jnp.full((k, b), beta0, self.dtype)
        self._kappa = jnp.full((k, 1), kappa0, self.dtype)
        self._alpha = jnp.full((k, 1), alpha0, self.dtype)
        self._rl = jnp.zeros((k, 1), jnp.int32)

    def _params_for(self, hazard: float) -> jax.Array:
        """The kernel's params row for ``hazard`` and the priors, built on
        the device."""
        priors = np.array([hazard, self.kappa0, self.alpha0, self.beta0,
                           self.truncation])
        return _params_row(self._put(priors, self.dtype), self.dtype)

    # -- host-device copies ----------------------------------------------
    def _put(self, host: np.ndarray, dtype=None) -> jax.Array:
        """``host`` copied to the device."""
        with span("bocd.upload", step=self._t):
            dev = jnp.asarray(host, dtype)
        self.h2d_bytes += dev.nbytes
        return dev

    def _read(self, name: str, dev: jax.Array) -> np.ndarray:
        """``dev`` read to the host, blocking. ``name`` names what ``dev``
        holds (a state array or a statistic); reading the same array under
        it again costs no copy (JAX keeps the host copy) and is not
        counted."""
        last = self._read_last.get(name)
        crosses = last is None or last() is not dev
        nbytes = dev.nbytes if crosses else 0
        with span("bocd.readback", step=self._t, bytes=nbytes):
            host = np.asarray(dev)
        if crosses:
            self.d2h_bytes += nbytes
            self.host_reads += 1
            self._read_last[name] = weakref.ref(dev)
        return host

    # -- ScreeningBackend interface ------------------------------------
    @property
    def n_hypotheses(self) -> int:
        lr = self._read("log_r", self._log_r)
        return int(np.isfinite(lr).any(axis=1).sum())

    def update(self, x: np.ndarray) -> jax.Array:
        x = np.asarray(x, dtype=self.dtype)
        if x.shape != (self.n_series,):
            raise ValueError(f"expected shape ({self.n_series},), got {x.shape}")
        x_dev = self._put(x)
        (self._log_r, self._mu, self._beta, self._kappa, self._alpha,
         self._rl, p0) = _advance(
            self._params, x_dev, self._log_r, self._mu, self._beta,
            self._kappa, self._alpha, self._rl, self._mu0,
            interpret=self.interpret,
        )
        self._t += 1
        if self._log_r.shape != self._warm_shape:
            # Compile the statistics at this shape now, so the first tick
            # that asks for one (a flag may come late) compiles nothing.
            _p_recent(self._log_r, self._rl, jnp.zeros((), jnp.int32))
            _map_runlength(self._log_r, self._rl)
            self._warm_shape = self._log_r.shape
        return p0

    def _window(self, window: int) -> jax.Array:
        """``window`` as a device int32 scalar: a Python int passed to the
        reduction would be copied up on every call."""
        dev = self._windows.get(window)
        if dev is None:
            dev = self._windows[window] = self._put(np.int32(window))
        return dev

    def _stat(self, name: str, fn, *args) -> np.ndarray:
        """``fn(log_r, rl, *args)`` computed on the device and read back.
        Asked again with the same state and ``args`` (device arrays, so
        compared by identity), the same device answer is read, which
        costs no copy."""
        hit = self._stats.get(name)
        if (hit is None or hit[0]() is not self._log_r
                or hit[1]() is not self._rl
                or any(a is not b for a, b in zip(hit[2], args))):
            dev = fn(self._log_r, self._rl, *args)
            hit = (weakref.ref(self._log_r), weakref.ref(self._rl), args, dev)
            self._stats[name] = hit
        return self._read(name, hit[3])

    def p_recent_change(self, window: int = 2) -> np.ndarray:
        return self._stat("p_recent_change", _p_recent,
                          self._window(int(window)))

    def map_runlength(self) -> np.ndarray:
        rl = self._stat("map_runlength", _map_runlength)
        return rl.astype(np.int64)

    def take_columns(self, idx: np.ndarray) -> None:
        idx = self._put(np.asarray(idx, dtype=np.int64))
        self.n_series = int(idx.size)
        self._mu0 = self._mu0[idx]
        self._log_r = self._log_r[:, idx]
        self._mu = self._mu[:, idx]
        self._beta = self._beta[:, idx]

    def retune(
        self,
        hazard: float | None = None,
        max_hypotheses: int | None = None,
    ) -> None:
        if hazard is not None:
            self.hazard = float(hazard)
            self._params = self._params_for(self.hazard)
        if max_hypotheses is None or max_hypotheses == self.max_hypotheses:
            return
        # Resize the slot frontier: keep the strongest rows (ties to the
        # smallest run length / slot, like the per-tick victim rule), pad
        # with dead slots when growing.
        k_new = int(max_hypotheses)
        _check_fits(k_new, self.n_series)
        lr = np.asarray(self._read("log_r", self._log_r), dtype=np.float64)
        k, b = lr.shape
        if k_new < k:
            strength = np.where(
                np.isnan(lr).any(axis=1), -np.inf, np.max(lr, axis=1)
            )
            rl = self._read("rl", self._rl)[:, 0]
            order = np.lexsort((np.arange(k), -rl, -strength))
            keep = np.sort(order[:k_new])
            sel = self._put(keep)
            self._log_r = self._log_r[sel]
            self._mu = self._mu[sel]
            self._beta = self._beta[sel]
            self._kappa = self._kappa[sel]
            self._alpha = self._alpha[sel]
            self._rl = self._rl[sel]
        elif k_new > k:
            pad = k_new - k
            self._log_r = jnp.concatenate(
                [self._log_r, jnp.full((pad, b), -jnp.inf, self.dtype)]
            )
            self._mu = jnp.concatenate(
                [self._mu, jnp.zeros((pad, b), self.dtype)]
            )
            self._beta = jnp.concatenate(
                [self._beta, jnp.full((pad, b), self.beta0, self.dtype)]
            )
            self._kappa = jnp.concatenate(
                [self._kappa, jnp.full((pad, 1), self.kappa0, self.dtype)]
            )
            self._alpha = jnp.concatenate(
                [self._alpha, jnp.full((pad, 1), self.alpha0, self.dtype)]
            )
            self._rl = jnp.concatenate(
                [self._rl, jnp.zeros((pad, 1), jnp.int32)]
            )
        self.max_hypotheses = k_new

    # -- state capture (campaign fork/restore contract) ------------------
    _STATE = ("_mu0", "_log_r", "_mu", "_beta", "_kappa", "_alpha", "_rl",
              "_params", "_t", "n_series", "hazard", "max_hypotheses")

    def snapshot(self) -> dict:
        """The full mutable state (arrays shared, not copied: see the
        class docstring)."""
        return {name: getattr(self, name) for name in self._STATE}

    def restore(self, snap: dict) -> None:
        """Reinstate a :meth:`snapshot` bit-exactly. The instance must have
        been built with the same dtype and priors."""
        for name in self._STATE:
            setattr(self, name, snap[name])
