"""The general traffic generators. A traffic mix is a JSON file of
parameters under ``chipbench/traffic/``; its ``generator`` key names one of
the generators here, and everything else in the file is its parameters.

Every generator draws from ``--seed`` alone, and every seed gets the same
set of sizes and events in another order, so seeds change the order of
the work and never its amount.
"""
from __future__ import annotations

import numpy as np

from chipbench.harness import seed_words


# ---------------------------------------------------------------- training
def train_data_seed(seed: int, step: int) -> int:
    """The data-stream seed of global training step ``step`` (1-based):
    distinct for every step, so no two steps see the same rows."""
    lo, hi = seed_words(seed)
    return ((hi << 32 | lo) << 20) + step


def train_tokens(data_seed: int, slots: int, sequences: int, seq_len: int,
                 vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """Token and label rows ``(slots, sequences, seq_len)`` of one step.

    A copy of the program's synthetic stream (``repro.data.pipeline``) for
    a step fed as step 0 of the data seed ``data_seed``: uniform tokens, each
    repeating its predecessor with probability 1/2, drawn in the same order
    from the same generator, so the reference sees the rows the program
    trained on without taking them from it."""
    rng = np.random.default_rng(np.random.SeedSequence([data_seed, 0]))
    shape = (slots, sequences, seq_len + 1)
    flat = rng.integers(0, vocab, size=shape)
    rep = rng.random(shape) < 0.5
    # out[t] = out[t-1] where rep[t] else flat[t]: each position copies the
    # last position at or before it that did not repeat.
    pos = np.arange(shape[-1])
    src = np.where(rep & (pos > 0), 0, pos)
    src = np.maximum.accumulate(np.broadcast_to(src, shape), axis=-1)
    toks = np.take_along_axis(flat, src, axis=-1).astype(np.int32)
    return toks[..., :-1], toks[..., 1:]


# ------------------------------------------------------------------- fleet
class FleetTraffic:
    """Iteration times of a fleet's streams, one row per tick.

    Healthy streams run at ``level`` with Normal jitter of relative size
    ``jitter``. A share ``fault_share`` of the streams fail slow. The faults
    form one sequence, the same for every seed: fault ``k`` starts at tick
    ``first_onset + k * onset_spacing``, with severity ``severities[k % 3]``
    (for three severities) and as a step for even ``k`` and a linear ramp
    over ``ramp_ticks`` for odd ``k``; the slow-down then stays. The seed
    chooses only which stream each fault hits. Row ``t`` is drawn from its
    own generator, so any tick can be regenerated alone.
    """

    def __init__(self, params: dict, n_streams: int, seed: int) -> None:
        self.n = int(n_streams)
        self.level = float(params["level"])
        self.jitter = float(params["jitter"])
        self._words = seed_words(seed)
        rs = np.random.default_rng(np.random.SeedSequence([*self._words, 0]))
        n_faulty = int(round(params["fault_share"] * self.n))
        k = np.arange(n_faulty)
        sev = np.asarray(params["severities"], dtype=np.float64)
        self.onset = params["first_onset"] + k * params["onset_spacing"]
        self.severity = sev[k % sev.size]
        self.ramp = np.where(k % 2 == 0, 1, int(params["ramp_ticks"]))
        #: the stream that fault ``k`` hits
        self.faulty = rs.permutation(self.n)[:n_faulty]

    def factors(self, t: int) -> np.ndarray:
        """Slow-down factor of each faulty stream at tick ``t``."""
        frac = np.clip((t - self.onset + 1) / self.ramp, 0.0, 1.0)
        return 1.0 + (self.severity - 1.0) * frac

    def sample(self, t: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([*self._words, 1, t]))
        x = self.level * (1.0 + self.jitter * rng.standard_normal(self.n))
        x[self.faulty] *= self.factors(t)
        return x
