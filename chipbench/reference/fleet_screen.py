"""Plain reference of a fleet screen over one set of streams (no churn).

What it computes, per tick, from the fleet's iteration times:

* a Normal-Gamma BOCD run-length posterior per stream (Student-t
  predictive, constant hazard, the fresh-segment prior scoring the change
  row), over standardized samples, with per-stream mass truncation and one
  frontier of at most ``max_hypotheses`` run lengths shared by all streams
  (the rows of least mass in every stream go first, ties to the shortest
  run length);
* the noise scale of each stream from its first ``warmup`` samples (MAD of
  first differences, floored at a thousandth of the median level), and the
  prior mean at the first standardized sample;
* escalation of a stream whose posterior mass on run lengths within
  ``recent_window`` exceeds ``cp_threshold``: its change index (tick minus
  MAP run length) is verified by the +/-10 % before/after rule on windows
  of ``verify_window`` and then each of ``verify_windows`` ticks, over the
  retained history of ``history_cap`` ticks;
* a lagged drift screen (trailing ``drift_cur_window`` mean against the
  ``drift_ref_window`` mean ``drift_ref`` ticks back, held ``drift_hold``
  ticks) whose candidates go through the same verification;
* a long-horizon screen (trailing mean against a per-stream EWMA of span
  ``ewma_span``, mature after ``ewma_min_age`` ticks, held ``ewma_hold``
  ticks), which re-estimates the stream's noise scale when it fires;
* ``min_gap`` ticks between flags of one stream; every confirmed flag
  re-anchors the stream's EWMA at the new level.

The posterior runs in ``dtype`` (float64; the control passes a lower one);
everything read from raw samples runs in float64.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import gammaln


def noise_scale_cols(x: np.ndarray) -> np.ndarray:
    """Per-column jitter scale of a (T, B) window."""
    absmed = np.median(np.abs(x), axis=0)
    if x.shape[0] < 3:
        return np.maximum(absmed * 1e-2, 1e-9)
    d = np.diff(x, axis=0)
    mad = np.median(np.abs(d - np.median(d, axis=0)), axis=0)
    return np.maximum(1.4826 * mad / np.sqrt(2.0), np.maximum(absmed * 1e-3, 1e-9))


def _lse_cols(a: np.ndarray) -> np.ndarray:
    m = np.max(a, axis=0)
    shift = np.where(np.isfinite(m), m, 0).astype(a.dtype)
    with np.errstate(divide="ignore"):
        return np.log(np.sum(np.exp(a - shift), axis=0)) + shift


class _Block:
    """The posterior of one block of streams: rows are the run-length
    hypotheses still listed (ascending run length), columns its streams."""

    def __init__(self, mu0: np.ndarray, beta0, dt) -> None:
        self.mu0 = mu0
        self.log_r = np.zeros((1, mu0.size), dt)
        self.mu = mu0[None, :].copy()
        self.beta = np.full((1, mu0.size), beta0, dt)


class Posterior:
    """The shared-frontier BOCD recursion over B streams in ``dtype``.

    Streams are independent except through the shared frontier (which rows
    survive), so the columns are split into blocks that threads advance in
    parallel; each tick meets twice, to pick the frontier's victim and the
    rows that stay listed."""

    def __init__(self, mu0: np.ndarray, cfg: dict, dtype, threads: int = 4) -> None:
        from concurrent.futures import ThreadPoolExecutor

        # A type below float32 is emulated: float32 arithmetic with every
        # stored result rounded to it (numpy has no native bfloat16 math).
        self.store = np.dtype(dtype)
        self.dt = self.store if self.store.itemsize >= 4 else np.dtype(np.float32)
        c = lambda v: self._r(np.asarray(v, self.dt))  # noqa: E731
        self.c = c
        self.k0, self.a0, self.b0 = c(cfg["kappa0"]), c(cfg["alpha0"]), c(cfg["beta0"])
        self.log_h = c(math.log(cfg["hazard"]))
        self.log_1mh = c(math.log1p(-cfg["hazard"]))
        self.log_trunc = c(math.log(cfg["truncation"]))
        self.cap = int(cfg["max_hypotheses"])
        self.window = int(cfg["recent_window"])
        edges = np.linspace(0, mu0.size, min(threads, mu0.size) + 1).astype(int)
        self.cols = [slice(a, b) for a, b in zip(edges[:-1], edges[1:])]
        mu0 = self._r(mu0)
        self.blocks = [_Block(mu0[sl], self.b0, self.dt) for sl in self.cols]
        self.kappa = np.full(1, self.k0, self.dt)
        self.alpha = np.full(1, self.a0, self.dt)
        self.rl = np.zeros(1, np.int64)
        self.pool = ThreadPoolExecutor(len(self.blocks))
        self._p = np.zeros(mu0.size)
        self._map = np.zeros(mu0.size, np.int64)

    def close(self) -> None:
        self.pool.shutdown(wait=True)

    def _r(self, a: np.ndarray) -> np.ndarray:
        a = a.astype(self.dt, copy=False)
        if self.store is not self.dt:
            a = a.astype(self.store).astype(self.dt)
        return a

    def _logpred(self, x, mu, kappa, alpha, beta):
        c = self.c
        df = c(2) * alpha
        scale2 = beta * ((kappa + c(1)) / (alpha * kappa))
        const = (gammaln((df.astype(np.float64) + 1) / 2)
                 - gammaln(df.astype(np.float64) / 2)).astype(self.dt)
        z2 = (x - mu) ** 2 / scale2 / df
        return (const - c(0.5) * np.log(c(math.pi) * df * scale2)
                - c(0.5) * (df + c(1)) * np.log1p(z2))

    def _grow(self, blk: _Block, x: np.ndarray) -> np.ndarray:
        """Predict, normalize, update the statistics and truncate one block;
        returns each listed row's largest mass in the block."""
        c = self.c
        growth = (self._logpred(x, blk.mu, self.kappa[:, None], self.alpha[:, None], blk.beta)
                  + blk.log_r + self.log_1mh)
        cp = self._logpred(x, blk.mu0, self.k0, self.a0, self.b0) + self.log_h
        new = self._r(np.concatenate([cp[None, :], growth]))
        new = self._r(new - _lse_cols(new))
        mu = np.concatenate([blk.mu0[None, :], blk.mu])
        beta = np.concatenate([np.full((1, x.size), self.b0, self.dt), blk.beta])
        kap = self._kap_all[:, None]
        blk.beta = self._r(beta + c(0.5) * kap * (x - mu) ** 2 / (kap + c(1)))
        blk.mu = self._r((kap * mu + x) / (kap + c(1)))
        dead = (new <= self.log_trunc) & np.isfinite(new)
        dead[0] = False
        new[dead] = -np.inf
        blk.new = new
        return np.max(new[1:], axis=1)

    def _kill(self, blk: _Block, victim: int | None) -> np.ndarray:
        if victim is not None:
            blk.new[victim] = -np.inf
        blk.new = self._r(blk.new - _lse_cols(blk.new))
        return np.isfinite(blk.new).any(axis=1)

    def _keep(self, blk: _Block, keep: np.ndarray, recent: np.ndarray, sl: slice) -> None:
        blk.log_r, blk.mu, blk.beta = blk.new[keep], blk.mu[keep], blk.beta[keep]
        del blk.new
        self._p[sl] = np.exp(_lse_cols(blk.log_r[recent]).astype(np.float64))
        self._map[sl] = self._rl_kept[np.argmax(blk.log_r, axis=0)]

    def update(self, x: np.ndarray) -> None:
        x = self._r(np.asarray(x, np.float64))
        self._kap_all = self._r(np.concatenate([[self.k0], self.kappa]))
        strength = np.max(list(self.pool.map(
            lambda j: self._grow(self.blocks[j], x[self.cols[j]]),
            range(len(self.blocks)))), axis=0)
        victim = None
        if strength.size + 1 > self.cap:
            # the listed row of least mass in every stream goes; ties to
            # the shortest run length
            victim = 1 + int(np.argsort(strength, kind="stable")[0])
        alive = np.any(list(self.pool.map(
            lambda blk: self._kill(blk, victim), self.blocks)), axis=0)
        alive[0] = True
        keep = np.flatnonzero(alive)
        rl = np.concatenate([[0], self.rl + 1])
        self.kappa = self._r(self._kap_all + self.c(1))[keep]
        self.alpha = self._r(np.concatenate([[self.a0], self.alpha]) + self.c(0.5))[keep]
        self.rl = self._rl_kept = rl[keep]
        recent = self.rl <= self.window
        list(self.pool.map(lambda j: self._keep(self.blocks[j], keep, recent, self.cols[j]),
                           range(len(self.blocks))))

    def p_recent(self) -> np.ndarray:
        """Pr(run length <= recent_window) per stream after the last update."""
        return self._p.copy()

    def map_runlength(self) -> np.ndarray:
        return self._map.copy()

    def p_within(self, window: int) -> np.ndarray:
        """Pr(run length <= ``window``) per stream after the last update."""
        listed = self.rl <= window
        if not listed.any():
            return np.zeros(self._p.size)
        return np.concatenate([np.exp(_lse_cols(blk.log_r[listed]).astype(np.float64))
                               for blk in self.blocks])


class Screen:
    """The fleet screen's semantics, tick by tick. ``flags`` collects
    ``(tick, stream, change_index)`` of every confirmed flag."""

    def __init__(self, n: int, cfg: dict, dtype=np.float64) -> None:
        self.cfg, self.dtype, self.n = cfg, dtype, n
        lookback = cfg["drift_ref"] + cfg["drift_ref_window"]
        widest = 2 * max(cfg["verify_window"], *cfg["verify_windows"])
        self.cap = max(cfg["history_cap"], cfg["warmup"], 4 * cfg["verify_window"],
                       lookback, widest)
        self.hist = np.full((self.cap, n), np.nan)
        self.t = 0  # rows appended
        self.post: Posterior | None = None
        self.scale = np.full(n, np.nan)
        self.last_flag = np.full(n, -(10 ** 9), np.int64)
        self.drift_count = np.zeros(n, np.int64)
        self.ewma = np.full(n, np.nan)
        self.ewma_age = np.zeros(n, np.int64)
        self.ewma_count = np.zeros(n, np.int64)
        self.flags: list[tuple[int, int, int]] = []

    def close(self) -> None:
        if self.post is not None:
            self.post.close()

    # retained history, absolute tick indices
    @property
    def start(self) -> int:
        return max(0, self.t - self.cap)

    def rows(self, lo: int, hi: int) -> np.ndarray:
        lo, hi = max(lo, self.start, 0), min(hi, self.t)
        if hi <= lo:
            return np.empty((0, self.n))
        return self.hist[np.arange(lo, hi) % self.cap]

    def col(self, w: int, lo: int, hi: int) -> np.ndarray:
        lo, hi = max(lo, self.start, 0), min(hi, self.t)
        if hi <= lo:
            return np.empty(0)
        return self.hist[np.arange(lo, hi) % self.cap, w]

    def verify(self, w: int, idx: int) -> tuple[float, float] | None:
        cfg = self.cfg
        for win in (cfg["verify_window"], *cfg["verify_windows"]):
            before = self.col(w, max(0, idx - win), idx)
            after = self.col(w, idx, min(self.t, idx + win))
            if before.size < 2 or after.size < 2:
                continue
            mb, ma = float(np.mean(before)), float(np.mean(after))
            if mb > 0 and abs(ma - mb) / mb >= cfg["verify_threshold"]:
                return mb, ma
        return None

    def _confirm(self, i: int, w: int, idx: int, level: float) -> None:
        self.last_flag[w] = idx
        self.ewma[w], self.ewma_age[w], self.ewma_count[w] = level, 0, 0
        self.flags.append((i, w, idx))

    def tick(self, times: np.ndarray) -> None:
        cfg = self.cfg
        times = np.asarray(times, np.float64)
        self.hist[self.t % self.cap] = times
        self.t += 1
        n, i = self.t, self.t - 1
        fresh = np.isnan(self.ewma)
        self.ewma[fresh] = times[fresh]
        self.ewma += 2.0 / (cfg["ewma_span"] + 1.0) * (times - self.ewma)
        self.ewma_age += 1
        cur = None
        if self.post is None and n >= cfg["warmup"]:
            warm = self.rows(0, n)
            self.scale = noise_scale_cols(warm)
            self.post = Posterior(warm[0] / self.scale, cfg, self.dtype)
            for row in warm[:-1]:
                self.post.update(row / self.scale)
        if self.post is not None:
            self.post.update(times / self.scale)
        if self.post is not None and i > cfg["recent_window"]:
            p = self.post.p_recent()
            flagged = np.flatnonzero(p > cfg["cp_threshold"])
            if flagged.size:
                rl = self.post.map_runlength()
                for w in flagged:
                    idx = i - int(rl[w])
                    if idx <= 0 or idx - self.last_flag[w] < cfg["min_gap"]:
                        continue
                    v = self.verify(int(w), idx)
                    if v is not None:
                        self._confirm(i, int(w), idx, v[1])
            lag_lo = n - cfg["drift_ref"] - cfg["drift_ref_window"]
            if lag_lo >= self.start:
                ref = self.rows(lag_lo, lag_lo + cfg["drift_ref_window"]).mean(axis=0)
                cur = self.rows(n - cfg["drift_cur_window"], n).mean(axis=0)
                over = np.abs(cur - ref) / np.maximum(ref, 1e-12) >= cfg["verify_threshold"]
                self.drift_count[over] += 1
                self.drift_count[~over] = 0
                for w in np.flatnonzero(over):
                    if self.drift_count[w] < cfg["drift_hold"] or lag_lo <= self.last_flag[w]:
                        continue
                    idx = i - cfg["drift_cur_window"] + 1
                    v = self.verify(int(w), idx)
                    if v is not None:
                        self._confirm(i, int(w), idx, v[1])
        lo = n - cfg["drift_cur_window"]
        if lo >= self.start:
            if cur is None:
                cur = self.rows(lo, n).mean(axis=0)
            base = self.ewma
            with np.errstate(invalid="ignore"):
                ok = (self.ewma_age >= cfg["ewma_min_age"]) & ~np.isnan(cur) & (base > 0)
                over = ok & (np.abs(cur - base) / np.maximum(base, 1e-12) >= cfg["verify_threshold"])
            self.ewma_count[over] += 1
            self.ewma_count[~over] = 0
            for w in np.flatnonzero(over):
                if self.ewma_count[w] < cfg["ewma_hold"] or i - self.last_flag[w] < cfg["min_gap"]:
                    continue
                idx = i - cfg["drift_cur_window"] + 1
                m = min(n - self.start, 4 * cfg["warmup"])
                self.scale[w] = noise_scale_cols(self.col(int(w), n - m, n)[:, None])[0]
                self._confirm(i, int(w), idx, float(cur[w]))
