"""Bayesian Online Change-point Detection (paper §4.2 + Appendix 9.1).

Implements the Adams/MacKay-style run-length recursion the paper uses
(eqs. 2-5): at each step maintain the run-length posterior Pr(r_t | x_{1:t}),
with a Normal-Gamma underlying probabilistic model (Student-t predictive) and
a constant-hazard change-point prior. A timestamp t is reported as a
change-point when Pr(r_t = 0 | x_{1:t}) exceeds a threshold (0.9 in the
paper's experiments). Time and memory are kept linear by truncating
negligible run-length mass.

Fast-path architecture (fleet scale)
------------------------------------
Two implementations share the recursion:

* :class:`BOCD` — one scalar series. Sufficient statistics live in
  capacity-doubling buffers that are shifted and updated **in place**, so an
  update allocates O(1) small temporaries instead of re-concatenating the
  prior onto every array (the seed did four ``np.concatenate`` per
  observation).
* :class:`BatchedBOCD` — B independent series advanced in lockstep as 2-D
  ``(K, B)`` array operations: one vectorized Student-t log-predictive, one
  per-column normalization, one shared truncation frontier per tick. Row
  ``i`` holds the run-length-``rl[i]`` hypothesis of *every* series; a
  ``-inf`` posterior entry marks a hypothesis that one series has truncated
  while another still tracks it. Rows dead in every column are compacted
  away, bounding K exactly like the scalar truncation. Per column the
  posterior (and therefore the change-point indices) matches the scalar
  recursion — :class:`repro.core.detector.FleetDetect` relies on this to
  screen thousands of workers per tick and escalate only flagged ones.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Protocol, runtime_checkable

import numpy as np

DEFAULT_CP_THRESHOLD = 0.9

_MIN_CAPACITY = 64


@dataclass
class BOCD:
    """Online change-point detector over a scalar series (iteration times).

    Parameters mirror the standard Normal-Gamma conjugate prior:
      mu0/kappa0: prior mean and its pseudo-count,
      alpha0/beta0: precision-Gamma shape/rate,
      hazard: constant change-point hazard rate 1/expected-run-length.
    """

    hazard: float = 1.0 / 100.0
    mu0: float = 0.0
    kappa0: float = 1.0
    alpha0: float = 1.0
    beta0: float = 1.0
    cp_threshold: float = DEFAULT_CP_THRESHOLD
    truncation: float = 1e-6
    #: optional hard bound on run-length hypotheses (fleet fast path): after
    #: mass truncation, keep r=0 plus the top ``max_hypotheses - 1`` rows by
    #: posterior mass (stable tie-break on run length). None = paper-exact.
    max_hypotheses: int | None = None

    # --- state: views of length _len into the capacity buffers below ---
    _log_r: np.ndarray = field(init=False)
    _mu: np.ndarray = field(init=False)
    _kappa: np.ndarray = field(init=False)
    _alpha: np.ndarray = field(init=False)
    _beta: np.ndarray = field(init=False)
    _rl: np.ndarray = field(init=False)
    _t: int = field(init=False, default=0)
    _len: int = field(init=False, default=1)

    def __post_init__(self) -> None:
        cap = _MIN_CAPACITY
        self._log_r_buf = np.zeros(cap)
        self._mu_buf = np.empty(cap)
        self._kappa_buf = np.empty(cap)
        self._alpha_buf = np.empty(cap)
        self._beta_buf = np.empty(cap)
        self._rl_buf = np.zeros(cap, dtype=np.int64)
        self._mu_buf[0] = self.mu0
        self._kappa_buf[0] = self.kappa0
        self._alpha_buf[0] = self.alpha0
        self._beta_buf[0] = self.beta0
        self._refresh_views()

    def _refresh_views(self) -> None:
        n = self._len
        self._log_r = self._log_r_buf[:n]
        self._mu = self._mu_buf[:n]
        self._kappa = self._kappa_buf[:n]
        self._alpha = self._alpha_buf[:n]
        self._beta = self._beta_buf[:n]
        self._rl = self._rl_buf[:n]

    def _grow(self) -> None:
        cap = 2 * self._log_r_buf.size
        for name in ("_log_r_buf", "_mu_buf", "_kappa_buf", "_alpha_buf",
                     "_beta_buf", "_rl_buf"):
            old = getattr(self, name)
            buf = np.empty(cap, dtype=old.dtype)
            buf[: old.size] = old
            setattr(self, name, buf)

    # ------------------------------------------------------------------
    def _log_pred(self, x: float) -> np.ndarray:
        """Student-t log predictive for each current run-length hypothesis."""
        return _student_t_logpdf(x, self._mu, self._kappa, self._alpha, self._beta)

    def _log_prior_pred(self, x: float) -> float:
        """Student-t log predictive under the (fresh-segment) prior."""
        return float(
            _student_t_logpdf(
                x,
                np.array([self.mu0]),
                np.array([self.kappa0]),
                np.array([self.alpha0]),
                np.array([self.beta0]),
            )[0]
        )

    def update(self, x: float) -> float:
        """Feed one observation; return Pr(r_t = 0 | x_{1:t}).

        Convention: ``r_t = 0`` means x_t is the *first* observation of a new
        segment, so the change-point path scores x_t under the **prior**
        predictive while growth paths score it under each run's posterior
        predictive. (In the alternative Adams-MacKay message convention the
        CP path reuses the old run's predictive and Pr(r_t=0) degenerates to
        the hazard whenever predictives coincide — useless for the paper's
        "probability > 0.9" detection rule.)
        """
        n = self._len
        if n + 1 > self._log_r_buf.size:
            self._grow()
        log_h = math.log(self.hazard)
        log_1mh = math.log1p(-self.hazard)

        # Growth probabilities: run continues (r -> r+1).
        log_growth = self._log_pred(x)
        log_growth += self._log_r
        log_growth += log_1mh
        # Change-point: new segment begins at t; x_t scored under the prior.
        log_cp = self._log_prior_pred(x) + log_h  # sum_r P(r) = 1 (normalized)

        lr = self._log_r_buf
        lr[1 : n + 1] = log_growth
        lr[0] = log_cp
        new_log_r = lr[: n + 1]
        new_log_r -= _logsumexp(new_log_r)

        # Shift the sufficient statistics one slot (the new r=0 hypothesis is
        # the prior) and apply the Normal-Gamma update in place.
        for buf, prior in (
            (self._mu_buf, self.mu0),
            (self._kappa_buf, self.kappa0),
            (self._alpha_buf, self.alpha0),
            (self._beta_buf, self.beta0),
        ):
            buf[1 : n + 1] = buf[:n]
            buf[0] = prior
        mu = self._mu_buf[: n + 1]
        kappa = self._kappa_buf[: n + 1]
        denom = kappa + 1.0
        upd = 0.5 * kappa
        upd *= (x - mu) ** 2
        upd /= denom
        self._beta_buf[: n + 1] += upd
        mu *= kappa
        mu += x
        mu /= denom
        kappa += 1.0
        self._alpha_buf[: n + 1] += 0.5
        rl = self._rl_buf
        rl[1 : n + 1] = rl[:n]
        rl[1 : n + 1] += 1
        rl[0] = 0
        self._len = n + 1
        self._t += 1

        # Truncate negligible run-length mass -> linear time overall (R2).
        keep = new_log_r > math.log(self.truncation)
        keep[0] = True
        if not keep.all():
            self._compact(np.flatnonzero(keep))
        cap = self.max_hypotheses
        if cap is not None and self._len > cap:
            lr = self._log_r_buf[: self._len]
            order = np.argsort(lr[1:], kind="stable")  # ascending mass
            keep = np.ones(self._len, dtype=bool)
            keep[order[: self._len - cap] + 1] = False
            self._compact(np.flatnonzero(keep))
        self._refresh_views()
        return float(math.exp(self._log_r[0]))

    def _compact(self, idx: np.ndarray) -> None:
        """Keep only hypothesis rows ``idx`` (ascending) and renormalize."""
        m = idx.size
        n = self._len
        for buf in (self._log_r_buf, self._mu_buf, self._kappa_buf,
                    self._alpha_buf, self._beta_buf, self._rl_buf):
            buf[:m] = buf[:n][idx]
        self._len = m
        self._log_r_buf[:m] -= _logsumexp(self._log_r_buf[:m])

    def retune(
        self,
        hazard: float | None = None,
        max_hypotheses: int | None = None,
    ) -> None:
        """Adjust the change-point prior / frontier cap mid-stream.

        Both only affect *future* updates (the hazard enters each step's
        growth/change mixture; the cap is applied per update), so the
        adaptive screening layer can re-derive them from observed change
        rates without rebuilding run-length state."""
        if hazard is not None:
            self.hazard = hazard
        if max_hypotheses is not None:
            self.max_hypotheses = max_hypotheses

    # -- detection statistics ------------------------------------------
    def p_recent_change(self, window: int = 2) -> float:
        """Posterior probability that a change-point occurred within the
        last ``window`` observations: Pr(r_t <= window | x_{1:t})."""
        # _rl is strictly increasing, so the recent rows are a prefix.
        j = int(np.searchsorted(self._rl, window, side="right"))
        if j == 0:
            return 0.0
        return float(np.exp(_logsumexp(self._log_r[:j])))

    def map_runlength(self) -> int:
        """MAP run length (distance back to the most likely change-point)."""
        return int(self._rl[int(np.argmax(self._log_r))])


class BatchedBOCD:
    """B independent BOCD recursions advanced in lockstep (fleet fast path).

    All state is ``(K, B)``: row ``i`` holds the run-length-``rl[i]``
    hypothesis of every series. Per-column truncation marks a series'
    negligible hypotheses with ``-inf`` posterior (they can never revive:
    growth adds finite log-predictives to ``-inf``); the shared frontier
    compacts rows that are dead in **every** column, so K stays bounded
    exactly like the scalar detector's. Each series' posterior matches the
    scalar :class:`BOCD` recursion step for step.
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
        max_hypotheses: int | None = None,
    ) -> None:
        b = int(n_series)
        self.n_series = b
        self.hazard = hazard
        self.kappa0 = kappa0
        self.alpha0 = alpha0
        self.beta0 = beta0
        self.cp_threshold = cp_threshold
        self.truncation = truncation
        self.max_hypotheses = max_hypotheses
        self._mu0 = np.broadcast_to(
            np.asarray(mu0, dtype=np.float64), (b,)
        ).copy()
        self._log_r = np.zeros((1, b))
        self._mu = self._mu0[None, :].copy()
        self._beta = np.full((1, b), beta0)
        # kappa/alpha receive the same +1.0/+0.5 per step in every column
        # (shared prior, lockstep updates), so they are row-constant: store
        # them once per run-length hypothesis, not per series. This keeps the
        # expensive gammaln terms of the Student-t at O(K) instead of O(K*B).
        self._kappa_row = np.full(1, kappa0)
        self._alpha_row = np.full(1, alpha0)
        self._rl = np.zeros(1, dtype=np.int64)
        self._t = 0

    @property
    def n_hypotheses(self) -> int:
        return self._rl.size

    def take_columns(self, idx: np.ndarray) -> None:
        """Sub-slice the batch to the series in ``idx`` (dynamic membership).

        Columns are statistically independent — truncation in uncapped mode
        is per-column, and the shared ``max_hypotheses`` frontier only
        couples which *rows* survive — so each kept column's posterior is
        carried over unchanged: in uncapped mode it is exactly what a fresh
        recursion over that column alone would hold. Hypothesis rows now
        dead in every surviving column are compacted away, shrinking the
        frontier like the per-tick truncation does.
        """
        idx = np.asarray(idx, dtype=np.int64)
        self.n_series = int(idx.size)
        self._mu0 = self._mu0[idx]
        self._log_r = self._log_r[:, idx]
        self._mu = self._mu[:, idx]
        self._beta = self._beta[:, idx]
        alive = np.isfinite(self._log_r).any(axis=1)
        if alive.size:
            alive[0] = True
        if not alive.all():
            self._log_r = self._log_r[alive]
            self._mu = self._mu[alive]
            self._beta = self._beta[alive]
            self._kappa_row = self._kappa_row[alive]
            self._alpha_row = self._alpha_row[alive]
            self._rl = self._rl[alive]

    def update(self, x: np.ndarray) -> np.ndarray:
        """Feed one observation per series; return Pr(r_t = 0) per series."""
        x = np.asarray(x, dtype=np.float64)
        if x.shape != (self.n_series,):
            raise ValueError(f"expected shape ({self.n_series},), got {x.shape}")
        log_h = math.log(self.hazard)
        log_1mh = math.log1p(-self.hazard)

        log_growth = _student_t_logpdf_rows(
            x, self._mu, self._kappa_row, self._alpha_row, self._beta
        )
        log_growth += self._log_r  # -inf (dead) rows stay -inf
        log_growth += log_1mh
        log_cp = _student_t_logpdf(
            x, self._mu0, np.float64(self.kappa0), np.float64(self.alpha0),
            np.float64(self.beta0),
        )
        log_cp += log_h

        k, b = self._log_r.shape
        new_log_r = np.empty((k + 1, b))
        new_log_r[0] = log_cp
        new_log_r[1:] = log_growth
        new_log_r -= _logsumexp_cols(new_log_r)

        mu_all = np.empty((k + 1, b))
        mu_all[0] = self._mu0
        mu_all[1:] = self._mu
        beta_all = np.empty((k + 1, b))
        beta_all[0] = self.beta0
        beta_all[1:] = self._beta
        kappa_all = np.empty(k + 1)
        kappa_all[0] = self.kappa0
        kappa_all[1:] = self._kappa_row
        alpha_all = np.empty(k + 1)
        alpha_all[0] = self.alpha0
        alpha_all[1:] = self._alpha_row
        denom = kappa_all + 1.0
        # In-place chains mirror the scalar operation order exactly.
        upd = x - mu_all
        np.multiply(upd, upd, out=upd)
        upd *= (0.5 * kappa_all)[:, None]
        upd /= denom[:, None]
        beta_all += upd
        self._beta = beta_all
        mu_all *= kappa_all[:, None]
        mu_all += x
        mu_all /= denom[:, None]
        self._mu = mu_all
        self._kappa_row = denom
        self._alpha_row = alpha_all + 0.5
        rl = np.empty(k + 1, dtype=np.int64)
        rl[0] = 0
        rl[1:] = self._rl
        rl[1:] += 1
        self._rl = rl
        self._t += 1

        # Per-column truncation: kill sub-threshold live hypotheses
        # (scalar-equivalent), plus the shared truncation frontier: keep r=0
        # and the cap-1 hypothesis rows with the highest column-max mass, so
        # K stays <= cap and every per-tick array op is bounded. With B=1
        # the cap is exactly the scalar rule; for B>1 it trades per-column
        # exactness for bounded fleet cost (flagged workers re-run the exact
        # scalar path during escalation anyway). One renormalization +
        # compaction pass covers both kill sources.
        dead = new_log_r <= math.log(self.truncation)
        dead[0] = False
        dead &= np.isfinite(new_log_r)
        if dead.any():
            new_log_r[dead] = -np.inf
        cap = self.max_hypotheses
        if cap is not None and new_log_r.shape[0] > cap:
            k1 = new_log_r.shape[0]
            strength = np.max(new_log_r, axis=1)
            order = np.argsort(strength[1:], kind="stable")  # ascending
            kill = np.zeros((k1, b), dtype=bool)
            kill[order[: k1 - cap] + 1] = True
            kill &= np.isfinite(new_log_r)
            if kill.any():
                new_log_r[kill] = -np.inf
                dead |= kill
        self._log_r = self._kill(new_log_r, dead)
        return np.exp(self._log_r[0])

    def _kill(self, log_r: np.ndarray, dead: np.ndarray) -> np.ndarray:
        """Renormalize columns with ``dead`` (-inf-marked) entries and
        compact hypothesis rows that are dead in every column."""
        if not dead.any():
            return log_r
        cols = dead.any(axis=0)
        if cols.mean() > 0.5:
            # Most columns affected: renormalizing everything avoids the
            # fancy-index copies (a no-op ~0 shift for untouched columns).
            log_r -= _logsumexp_cols(log_r)
        else:
            log_r[:, cols] -= _logsumexp_cols(log_r[:, cols])
        alive = np.isfinite(log_r).any(axis=1)
        alive[0] = True
        if not alive.all():
            log_r = log_r[alive]
            self._mu = self._mu[alive]
            self._beta = self._beta[alive]
            self._kappa_row = self._kappa_row[alive]
            self._alpha_row = self._alpha_row[alive]
            self._rl = self._rl[alive]
        return log_r

    def retune(
        self,
        hazard: float | None = None,
        max_hypotheses: int | None = None,
    ) -> None:
        """Adjust the change-point prior / shared frontier cap mid-stream
        (future updates only — run-length state carries over unchanged)."""
        if hazard is not None:
            self.hazard = hazard
        if max_hypotheses is not None:
            self.max_hypotheses = max_hypotheses

    # -- detection statistics (vectorized analogues of BOCD's) ----------
    def p_recent_change(self, window: int = 2) -> np.ndarray:
        """Pr(r_t <= window | x_{1:t}) for every series, shape (B,)."""
        # _rl is strictly increasing, so the recent rows are a prefix: a
        # view slice, not a boolean-mask copy of the (K, B) posterior.
        j = int(np.searchsorted(self._rl, window, side="right"))
        if j == 0:
            return np.zeros(self.n_series)
        return np.exp(_logsumexp_cols(self._log_r[:j]))

    def map_runlength(self) -> np.ndarray:
        """MAP run length per series, shape (B,) ints."""
        return self._rl[np.argmax(self._log_r, axis=0)]

    # -- state capture (campaign fork/restore contract) ------------------
    def snapshot(self) -> dict:
        """Full posterior state as private copies (restore-many safe)."""
        return {
            "n_series": self.n_series,
            "hazard": self.hazard,
            "max_hypotheses": self.max_hypotheses,
            "mu0": self._mu0.copy(),
            "log_r": self._log_r.copy(),
            "mu": self._mu.copy(),
            "beta": self._beta.copy(),
            "kappa_row": self._kappa_row.copy(),
            "alpha_row": self._alpha_row.copy(),
            "rl": self._rl.copy(),
            "t": self._t,
        }

    def restore(self, snap: dict) -> None:
        """Reinstate a :meth:`snapshot` bit-exactly (copies again, so the
        same blob can seed any number of forks)."""
        self.n_series = snap["n_series"]
        self.hazard = snap["hazard"]
        self.max_hypotheses = snap["max_hypotheses"]
        self._mu0 = snap["mu0"].copy()
        self._log_r = snap["log_r"].copy()
        self._mu = snap["mu"].copy()
        self._beta = snap["beta"].copy()
        self._kappa_row = snap["kappa_row"].copy()
        self._alpha_row = snap["alpha_row"].copy()
        self._rl = snap["rl"].copy()
        self._t = snap["t"]


def noise_scale(series: np.ndarray) -> float:
    """Robust per-step noise estimate: MAD of first differences.

    First differences cancel slow level drift, so this measures *jitter*;
    BOCD observations are standardized by it, making the detector sensitive
    to any statistically significant level shift regardless of its relative
    size (the 10 % relevance filter is the separate verification step).
    """
    x = np.asarray(series, dtype=np.float64)
    if x.size < 3:
        return max(float(np.median(np.abs(x))) * 1e-2, 1e-9)
    d = np.diff(x)
    mad = float(np.median(np.abs(d - np.median(d))))
    sigma = 1.4826 * mad / np.sqrt(2.0)
    floor = max(float(np.median(np.abs(x))) * 1e-3, 1e-9)
    return max(sigma, floor)


def noise_scale_batch(series: np.ndarray) -> np.ndarray:
    """Column-wise :func:`noise_scale` over a ``(T, B)`` matrix, shape (B,)."""
    x = np.asarray(series, dtype=np.float64)
    if x.ndim != 2:
        raise ValueError("expected a (T, B) matrix")
    absmed = np.median(np.abs(x), axis=0)
    if x.shape[0] < 3:
        return np.maximum(absmed * 1e-2, 1e-9)
    d = np.diff(x, axis=0)
    mad = np.median(np.abs(d - np.median(d, axis=0)), axis=0)
    sigma = 1.4826 * mad / np.sqrt(2.0)
    floor = np.maximum(absmed * 1e-3, 1e-9)
    return np.maximum(sigma, floor)


def detect_change_points(
    series: np.ndarray,
    hazard: float = 1.0 / 100.0,
    cp_threshold: float = DEFAULT_CP_THRESHOLD,
    min_gap: int = 3,
    recent_window: int = 2,
) -> list[int]:
    """Run BOCD over ``series``; return change-point indices.

    A change is reported at index ``i - map_runlength`` whenever the
    posterior probability of a change within the last ``recent_window``
    observations exceeds ``cp_threshold`` (paper: likelihood of r_t = 0
    above 0.9 — evaluated over a tiny window so the single-step hazard
    factor does not suppress genuine onsets). ``min_gap`` merges the burst
    of detections that one physical change produces.
    """
    x = np.asarray(series, dtype=np.float64)
    if x.size == 0:
        return []
    scale = noise_scale(x)
    det = BOCD(
        hazard=hazard,
        mu0=float(x[0] / scale),
        kappa0=1.0,
        alpha0=1.0,
        beta0=1.0,
        cp_threshold=cp_threshold,
    )
    out: list[int] = []
    for i, xi in enumerate(x):
        det.update(float(xi / scale))
        if i <= recent_window:  # p_recent is trivially 1 in the first steps
            continue
        if det.p_recent_change(recent_window) > cp_threshold:
            idx = i - det.map_runlength()
            if idx > 0 and (not out or idx - out[-1] >= min_gap):
                out.append(idx)
    return out


def detect_change_points_batch(
    series: np.ndarray,
    hazard: float = 1.0 / 100.0,
    cp_threshold: float = DEFAULT_CP_THRESHOLD,
    min_gap: int = 3,
    recent_window: int = 2,
) -> list[list[int]]:
    """Batched :func:`detect_change_points` over a ``(T, B)`` matrix.

    Returns one change-point index list per column, matching what the scalar
    routine reports on that column alone.
    """
    x = np.asarray(series, dtype=np.float64)
    if x.ndim != 2:
        raise ValueError("expected a (T, B) matrix")
    t_steps, b = x.shape
    out: list[list[int]] = [[] for _ in range(b)]
    if t_steps == 0 or b == 0:
        return out
    scale = noise_scale_batch(x)
    det = BatchedBOCD(
        b, hazard=hazard, mu0=x[0] / scale, cp_threshold=cp_threshold
    )
    xs = x / scale
    for i in range(t_steps):
        det.update(xs[i])
        if i <= recent_window:
            continue
        flagged = np.flatnonzero(det.p_recent_change(recent_window) > cp_threshold)
        if flagged.size == 0:
            continue
        run_lengths = det.map_runlength()
        for col in flagged:
            idx = i - int(run_lengths[col])
            dst = out[col]
            if idx > 0 and (not dst or idx - dst[-1] >= min_gap):
                dst.append(idx)
    return out


def _student_t_logpdf(
    x: float | np.ndarray,
    mu: np.ndarray,
    kappa: np.ndarray,
    alpha: np.ndarray,
    beta: np.ndarray,
) -> np.ndarray:
    """Posterior-predictive Student-t of the Normal-Gamma model.

    Broadcasts over any leading hypothesis/batch axes: scalar ``x`` against
    1-D stats (scalar BOCD) or ``(B,)`` observations against ``(K, B)``
    stats (batched BOCD).
    """
    df = 2.0 * alpha
    scale2 = beta * (kappa + 1.0) / (alpha * kappa)
    z2 = (x - mu) ** 2 / scale2
    return (
        _gammaln((df + 1.0) / 2.0)
        - _gammaln(df / 2.0)
        - 0.5 * np.log(np.pi * df * scale2)
        - (df + 1.0) / 2.0 * np.log1p(z2 / df)
    )


def _student_t_logpdf_rows(
    x: np.ndarray,
    mu: np.ndarray,
    kappa_row: np.ndarray,
    alpha_row: np.ndarray,
    beta: np.ndarray,
) -> np.ndarray:
    """:func:`_student_t_logpdf` with row-constant kappa/alpha ``(K,)``
    against ``(K, B)`` mu/beta — the gammaln terms collapse to O(K). Applies
    the exact same per-element operation chain, so results are bit-identical
    to the generic version."""
    df = 2.0 * alpha_row
    const = _gammaln((df + 1.0) / 2.0) - _gammaln(df / 2.0)
    scale2 = beta * (kappa_row + 1.0)[:, None]
    scale2 /= (alpha_row * kappa_row)[:, None]
    z2 = x - mu
    np.multiply(z2, z2, out=z2)
    z2 /= scale2
    z2 /= df[:, None]
    np.log1p(z2, out=z2)
    z2 *= ((df + 1.0) / 2.0)[:, None]
    scale2 *= (np.pi * df)[:, None]
    np.log(scale2, out=scale2)
    scale2 *= 0.5
    np.subtract(const[:, None], scale2, out=scale2)
    scale2 -= z2
    return scale2


def _logsumexp(a: np.ndarray) -> float:
    m = float(np.max(a))
    if math.isinf(m):
        return m
    return m + math.log(float(np.sum(np.exp(a - m))))


def _logsumexp_cols(a: np.ndarray) -> np.ndarray:
    """Column-wise logsumexp of a (K, B) matrix; all ``-inf`` columns -> -inf."""
    m = np.max(a, axis=0)
    shift = np.where(np.isfinite(m), m, 0.0)
    with np.errstate(divide="ignore"):
        return np.log(np.sum(np.exp(a - shift), axis=0)) + shift


try:  # scipy is available in this environment; keep a pure fallback anyway.
    from scipy.special import gammaln as _gammaln
except ImportError:  # pragma: no cover
    def _gammaln(x):
        return np.vectorize(math.lgamma)(x)


# ----------------------------------------------------------------------
# Screening backends (docs/kernels.md)
#
# The fleet screening loop talks to an *instance* implementing the batched
# interface below; FleetDetect / ControlPlane select a backend *factory*
# (scalar / batched / pallas) instead of hard-wiring BatchedBOCD, so
# implementations stay interchangeable and equivalence-tested from one
# registry.
# ----------------------------------------------------------------------

@runtime_checkable
class ScreeningBackend(Protocol):
    """Batched run-length screening state over ``n_series`` streams.

    The contract (shapes per :class:`BatchedBOCD`, the reference semantics):
    ``update(x)`` consumes one observation per stream and returns
    ``Pr(r_t = 0)`` per stream; ``p_recent_change``/``map_runlength`` report
    posterior statistics; ``take_columns`` sub-slices streams on membership
    churn; ``retune`` adjusts hazard / frontier cap for future updates.

    A device backend (``PallasBOCD``) copies up only ``x`` per tick and
    returns ``update``'s ``p0`` row as a device array, unread: a caller
    that wants it on the host calls ``np.asarray`` (``FleetDetect``
    discards it). Its statistics are reduced on the device, in the state's
    dtype, and only their (B,) answer is read back.
    """

    n_series: int

    def update(self, x: np.ndarray) -> np.ndarray: ...
    def p_recent_change(self, window: int = 2) -> np.ndarray: ...
    def map_runlength(self) -> np.ndarray: ...
    def take_columns(self, idx: np.ndarray) -> None: ...
    def retune(self, hazard: float | None = None,
               max_hypotheses: int | None = None) -> None: ...


class ScalarFanout:
    """B independent scalar :class:`BOCD` detectors behind the batched
    screening interface — the per-column oracle as "just another backend".

    O(B) Python-loop cost per tick; useful for tiny fleets and as the
    ground truth the vectorized/Pallas backends are equivalence-tested
    against (per column it *is* the scalar recursion, bit for bit).
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
        max_hypotheses: int | None = None,
    ) -> None:
        b = int(n_series)
        mu0 = np.broadcast_to(np.asarray(mu0, dtype=np.float64), (b,))
        self.n_series = b
        self.hazard = hazard
        self.cp_threshold = cp_threshold
        self.max_hypotheses = max_hypotheses
        self._dets = [
            BOCD(
                hazard=hazard, mu0=float(m), kappa0=kappa0, alpha0=alpha0,
                beta0=beta0, cp_threshold=cp_threshold, truncation=truncation,
                max_hypotheses=max_hypotheses,
            )
            for m in mu0
        ]

    def update(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x, dtype=np.float64)
        if x.shape != (self.n_series,):
            raise ValueError(f"expected shape ({self.n_series},), got {x.shape}")
        return np.fromiter(
            (d.update(float(xi)) for d, xi in zip(self._dets, x)),
            dtype=np.float64, count=self.n_series,
        )

    def p_recent_change(self, window: int = 2) -> np.ndarray:
        return np.fromiter(
            (d.p_recent_change(window) for d in self._dets),
            dtype=np.float64, count=self.n_series,
        )

    def map_runlength(self) -> np.ndarray:
        return np.fromiter(
            (d.map_runlength() for d in self._dets),
            dtype=np.int64, count=self.n_series,
        )

    def take_columns(self, idx: np.ndarray) -> None:
        idx = np.asarray(idx, dtype=np.int64)
        self._dets = [self._dets[int(i)] for i in idx]
        self.n_series = int(idx.size)

    def retune(
        self,
        hazard: float | None = None,
        max_hypotheses: int | None = None,
    ) -> None:
        if hazard is not None:
            self.hazard = hazard
        if max_hypotheses is not None:
            self.max_hypotheses = max_hypotheses
        for d in self._dets:
            d.retune(hazard=hazard, max_hypotheses=max_hypotheses)


class ScreeningBackendFactory:
    """Constructs :class:`ScreeningBackend` instances.

    The screening layer creates backend state dynamically (one instance per
    warmed cohort, sized to the cohort and seeded with its per-stream
    ``mu0``), so the pluggable unit is a *factory*, not an instance.
    """

    name = "abstract"

    def make(self, n_series: int, **kwargs) -> ScreeningBackend:
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"<ScreeningBackendFactory {self.name!r}>"


class ScalarScreening(ScreeningBackendFactory):
    name = "scalar"

    def make(self, n_series: int, **kwargs) -> ScalarFanout:
        return ScalarFanout(n_series, **kwargs)


class BatchedScreening(ScreeningBackendFactory):
    name = "batched"

    def make(self, n_series: int, **kwargs) -> BatchedBOCD:
        return BatchedBOCD(n_series, **kwargs)


class PallasScreening(ScreeningBackendFactory):
    """Fused Pallas step kernel (``repro.kernels.bocd_step.PallasBOCD``).

    ``interpret``/``dtype`` override the kernel defaults (compiled on a
    TPU, interpreted elsewhere; dtype defaults to float32 — see
    docs/kernels.md for the tolerance policy).
    """

    name = "pallas"

    def __init__(self, interpret: bool | None = None, dtype=None) -> None:
        self.interpret = interpret
        self.dtype = dtype

    def make(self, n_series: int, **kwargs):
        from repro.kernels.bocd_step import PallasBOCD

        if self.interpret is not None:
            kwargs.setdefault("interpret", self.interpret)
        if self.dtype is not None:
            kwargs.setdefault("dtype", self.dtype)
        return PallasBOCD(n_series, **kwargs)


#: Registry enumerated by the backend-equivalence tests.
SCREENING_BACKENDS: dict[str, ScreeningBackendFactory] = {
    "scalar": ScalarScreening(),
    "batched": BatchedScreening(),
    "pallas": PallasScreening(),
}


def select_backend(name: str | None = None) -> ScreeningBackendFactory:
    """Resolve a screening backend by name.

    ``None``/``"auto"`` auto-detects: Pallas where jax compiles it (a TPU —
    :func:`repro.kernels.pallas_compiled`), the vectorized numpy
    ``batched`` backend everywhere else.
    """
    if name is None or name == "auto":
        from repro.kernels import pallas_compiled

        return SCREENING_BACKENDS["pallas" if pallas_compiled() else "batched"]
    try:
        return SCREENING_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown screening backend {name!r}; "
            f"registered: {sorted(SCREENING_BACKENDS)}"
        ) from None


def resolve_screening_backend(spec) -> ScreeningBackendFactory:
    """Accept a backend name, ``None``/``"auto"``, or a factory: any
    instance with a ``make`` method, subclass of
    :class:`ScreeningBackendFactory` or not."""
    if spec is None or isinstance(spec, str):
        return select_backend(spec)
    if not isinstance(spec, type) and hasattr(spec, "make"):
        return spec
    raise TypeError(
        f"screening backend must be a name or a factory; got {spec!r}"
    )
