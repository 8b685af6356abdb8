"""Training loop with FALCON integrated as a first-class runtime feature.

The trainer executes *real* JAX training steps (params genuinely update) and
feeds FALCON an iteration-time signal. On a real cluster that signal would be
the measured step time (recorded as ``StepRecord.measured``, not yet fed to
the detector); here fail-slows are modeled by an
attached :class:`TrainingSimulator` + :class:`FailSlowInjector` (the same
cluster performance model used in the paper-reproduction benchmarks), so
detection and mitigation operate on honest dynamics while the numerics stay
real. docs/control_plane.md ("Clients") describes how the trainer drives
the control plane.

Detection and mitigation run through the control plane
(:mod:`repro.controlplane`): the trainer registers its performance model as
a job and drives :meth:`ControlPlane.observe` once per step; strategy
dispatch goes through the job's
:class:`~repro.controlplane.strategies.StrategyRegistry` (S1 ignore /
S2 micro-batch / S3 topology / S4 ckpt-restart — each one pluggable class).
The trainer's only mitigation role is mirroring results into its JAX-side
state: S2 allocations into the adaptive train step's trip counts, S4 into
an in-memory checkpoint restore; the runtime analogue of S3 (mesh device
permutation + state re-put) is exposed as :func:`remap_mesh` for
multi-device runs. ``FalconTrainer._apply_strategy`` remains as a thin
deprecation shim over the registry.

Each step is a wall-clock step span (:mod:`repro.obs.host`), ``train.step``,
holding ``train.batch`` (``make_batch`` and the upload),
``train.dispatch`` and ``train.loss_sync`` (together what
``StepRecord.measured`` times), ``train.simulate`` (the performance
model's iteration time), ``controlplane.observe`` and ``train.mitigate``.

With a ``mesh`` (``launch.mesh.make_host_mesh``) the trainer is one SPMD
program over its devices: set-up places parameters and optimizer state by
``sharding.partition.param_specs`` (span ``train.place``), each batch is
put by ``train_batch_specs``, and the step is traced under
``jax.set_mesh`` so the expert-parallel MoE path sees the mesh. Without
one, everything stays on the default device. For a model with MoE layers
the trainer counts routing (``moe_routed_tokens``, ``moe_expert_load_max``,
``moe_dropped_tokens``) from the counts its step returns with the loss;
each ``train.loss_sync`` carries their values as of its start.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
import time
import warnings
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.cluster.injector import FailSlowInjector
from repro.cluster.simulator import TrainingSimulator
from repro.configs.base import ArchConfig
from repro.controlplane import ControlPlane, MitigationResult
from repro.controlplane.strategies import MitigationContext
from repro.core.detector import FalconDetect
from repro.core.events import Strategy, strategy_label
from repro.core.planner import DEFAULT_OVERHEADS
from repro.data.pipeline import DataConfig, make_batch
from repro.models import model as model_lib
from repro.obs.host import span, step as step_span
from repro.optim import adamw
from repro.sharding import partition
from repro.train import train_step as ts_lib
from repro.train.checkpoint import CheckpointManager


@dataclass
class StepRecord:
    step: int
    loss: float
    iter_time: float
    wall_time: float
    strategy: str | None = None
    #: host-clock seconds of the JAX step itself, from dispatch until the
    #: loss is on the host (the first step includes its compilation)
    measured: float = 0.0


@dataclass
class FalconTrainer:
    cfg: ArchConfig
    data: DataConfig
    opt_cfg: adamw.AdamWConfig = field(default_factory=adamw.AdamWConfig)
    #: cluster performance model supplying iteration times (+ fail-slows)
    perf_model: TrainingSimulator | None = None
    injector: FailSlowInjector | None = None
    falcon_enabled: bool = True
    overheads: dict = field(default_factory=lambda: dict(DEFAULT_OVERHEADS))
    ckpt_dir: str = field(
        default_factory=lambda: os.path.join(tempfile.gettempdir(), "repro_ckpt")
    )
    seed: int = 0
    #: a ``(data, model)`` mesh to run the step over (None: one device)
    mesh: Any = None

    params: dict = field(init=False)
    opt_state: adamw.AdamWState = field(init=False)
    control: ControlPlane | None = field(init=False, default=None)
    detector: FalconDetect | None = field(init=False, default=None)
    history: list[StepRecord] = field(init=False, default_factory=list)
    allocation: list[int] = field(init=False)
    _wall: float = field(init=False, default=0.0)
    #: parameter shardings on the mesh (None without one)
    param_shardings: Any = field(init=False, default=None)
    #: routing counters (models with MoE layers): tokens routed to held
    #: experts, the sum over steps of the busiest (layer, expert)'s tokens,
    #: and held assignments left uncomputed
    moe_routed_tokens: int = field(init=False, default=0)
    moe_expert_load_max: int = field(init=False, default=0)
    moe_dropped_tokens: int = field(init=False, default=0)

    def __post_init__(self) -> None:
        if self.mesh is None:
            self.params = model_lib.init_params(self.cfg, self.seed)
            self.opt_state = adamw.init(self.params)
        else:
            self._place()
        self.ckpt = CheckpointManager(self.ckpt_dir)
        self.allocation = [self.data.slots] * self.data.dp_groups
        if self.perf_model is not None:
            self.control = ControlPlane()
            self._job = self.control.register_job(
                "train",
                self.perf_model,
                detector=FalconDetect(cluster=self.perf_model, verify_window=8),
                overheads=dict(self.overheads),
                injector=self.injector,
            )
            self.detector = self._job.detector
        self._step_fn = self.jit_step()

    def jit_step(self):
        """The step as the trainer runs it, traced afresh when first called
        or lowered (under ``jax.set_mesh(self.mesh)`` on a mesh)."""
        # Donate params and optimizer state: the step's outputs reuse their
        # buffers, so the device holds one copy of each, not two.
        placed = {} if self.mesh is None else {
            "in_shardings": (self.param_shardings, self._opt_shardings,
                             self._batch_shardings),
            "out_shardings": (self.param_shardings, self._opt_shardings, None),
        }
        return jax.jit(
            ts_lib.make_train_step(self.cfg, self.opt_cfg), donate_argnums=(0, 1),
            **placed,
        )

    def _place(self) -> None:
        """Parameters and optimizer state made directly in their shardings
        on the mesh (no device ever holds the whole model)."""
        with span("train.place"):
            mesh = self.mesh
            specs = partition.param_specs(self.cfg, mesh)
            shapes = model_lib.param_shapes(self.cfg)
            self.param_shardings = partition.named(specs, mesh)
            self._opt_shardings = partition.named(
                adamw.opt_state_specs(specs, shapes, mesh), mesh
            )
            self._batch_shardings = partition.named(
                partition.train_batch_specs(self.cfg, mesh), mesh
            )
            self.params = jax.jit(
                lambda: model_lib.init_params(self.cfg, self.seed),
                out_shardings=self.param_shardings,
            )()
            self.opt_state = jax.jit(
                adamw.init, out_shardings=self._opt_shardings
            )(self.params)

    def _moe_counters(self) -> dict:
        return {"moe_routed_tokens": self.moe_routed_tokens,
                "moe_expert_load_max": self.moe_expert_load_max,
                "moe_dropped_tokens": self.moe_dropped_tokens}

    def _read_loss_and_routing(self, metrics: dict) -> float:
        """The loss and the step's routing counts, in one read."""
        loss, counts, dropped = jax.device_get(
            (metrics["loss"], metrics["moe_counts"], metrics["moe_dropped"])
        )
        self.moe_routed_tokens += int(counts.sum())
        self.moe_expert_load_max += int(counts.max())
        self.moe_dropped_tokens += int(dropped)
        return float(loss)

    @property
    def planner(self):
        """The active event's mitigation planner (None when healthy)."""
        return self._job.planner if self.control is not None else None

    # ------------------------------------------------------------------
    def _observed_iter_time(self, measured: float, now: float) -> float:
        if self.perf_model is None:
            return measured
        if self.injector is not None:
            self.injector.apply(self.perf_model.state, now)
        return self.perf_model.iteration_time()

    def _apply_strategy(self, strategy: Strategy, event) -> None:
        """Deprecated: dispatch through the control-plane strategy registry
        (kept as a shim for pre-control-plane callers)."""
        warnings.warn(
            "FalconTrainer._apply_strategy is deprecated; strategies are "
            "dispatched through repro.controlplane.StrategyRegistry",
            DeprecationWarning,
            stacklevel=2,
        )
        if self.control is None:
            return
        outcome = self._job.registry.dispatch(
            strategy,
            MitigationContext(
                adapter=self.perf_model, event=event, now=self._wall,
                job_id="train", injector=self.injector,
            ),
        )
        self._mirror_result(
            MitigationResult(
                job_id="train", time=self._wall, strategy=strategy,
                applied=outcome.applied, detail=outcome.detail,
            )
        )

    def _mirror_result(self, ev: MitigationResult) -> None:
        """Reflect a strategy's modeled effects into the JAX-side state."""
        with span("train.mitigate", step=len(self.history)):
            counts = ev.detail.get("allocation")
            if counts is not None and len(counts) == self.data.dp_groups:
                self.allocation = list(counts)
            if ev.strategy is Strategy.CKPT_AND_RESTART and ev.applied:
                # In-memory checkpoint restore (fast path, Fig. 19 'M'); the
                # modeled side (simulator restart + injection relief) already
                # ran inside CkptRestartStrategy.
                self.ckpt.save_memory(self.params)
                self.params = self.ckpt.restore_memory()
                if self.mesh is not None:
                    self.params = jax.device_put(self.params, self.param_shardings)
                self.allocation = [self.data.slots] * self.data.dp_groups

    # ------------------------------------------------------------------
    def run(self, num_steps: int) -> list[StepRecord]:
        for step in range(num_steps):
            # Spans carry the trainer's step count, which runs on across
            # calls (``step`` restarts at 0 in each).
            k = len(self.history)
            with step_span("train.step", k):
                self.history.append(self._step(step, k))
        return self.history

    def _step(self, step: int, k: int) -> StepRecord:
        with span("train.batch", step=k):
            batch = make_batch(self.cfg, self.data, step)
            if self.mesh is None:
                batch = jax.tree.map(jnp.asarray, batch)
            else:
                batch = jax.device_put(batch, self._batch_shardings)
        t0 = time.monotonic()
        on_mesh = contextlib.nullcontext() if self.mesh is None else jax.set_mesh(self.mesh)
        with span("train.dispatch", step=k), on_mesh:
            self.params, self.opt_state, metrics = self._step_fn(
                self.params, self.opt_state, batch
            )
        if "moe_counts" in metrics:
            with span("train.loss_sync", step=k, **self._moe_counters()):
                loss = self._read_loss_and_routing(metrics)
        else:
            with span("train.loss_sync", step=k):
                loss = float(metrics["loss"])
        measured = time.monotonic() - t0

        with span("train.simulate", step=k):
            iter_time = self._observed_iter_time(measured, self._wall)
        self._wall += iter_time

        strategy_applied: str | None = None
        if self.falcon_enabled and self.control is not None:
            for ev in self.control.observe("train", iter_time, self._wall):
                if not isinstance(ev, MitigationResult):
                    continue
                if ev.kind == "relief":
                    # Relief: re-balance micro-batches for the recovered
                    # cluster (S2 with a healthy profile = even split).
                    self._mirror_result(ev)
                    strategy_applied = "REBALANCE"
                else:
                    self._mirror_result(ev)
                    self._wall += ev.overhead
                    strategy_applied = strategy_label(ev.strategy)

        return StepRecord(
            step=step,
            loss=loss,
            iter_time=iter_time,
            wall_time=self._wall,
            strategy=strategy_applied,
            measured=measured,
        )


# ---------------------------------------------------------------- S3 util
def remap_mesh(mesh, perm: list[int]):
    """Runtime analogue of the paper's node swap: rebuild the mesh with a
    permuted device order (state must be re-`device_put` by the caller)."""
    import numpy as _np
    from jax.sharding import Mesh

    devs = _np.asarray(mesh.devices).reshape(-1)[_np.asarray(perm)]
    return Mesh(devs.reshape(mesh.devices.shape), mesh.axis_names)
