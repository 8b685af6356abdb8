"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch falcon-demo-100m \
        --steps 50 --seq-len 256 --global-batch 32 [--no-falcon] \
        [--inject gpu:3:0.5:100:600] [--smoke] [--events] [--mesh 1x4]

``--inject kind:target:severity:start:duration`` adds a fail-slow to the
attached cluster performance model (kind: gpu|cpu|link|nic), a simulated
TP=2 x DP=``--dp-groups`` x PP=2 job (:func:`build_trainer`). Detection and
mitigation run through :mod:`repro.controlplane`; ``--events`` dumps the
control plane's typed event log after the run as JSON lines through the
same :func:`~repro.controlplane.event_log_records` serializer the
campaign reports use (Observations elided; ``--events-stride N`` samples
every Nth per-job Observation into the dump). ``--mesh DATAxMODEL`` runs
the step as one program over a (data, model) mesh of the host's devices
(``FalconTrainer(mesh=...)``), its parameters sharded by
``sharding.partition``.
"""
from __future__ import annotations

import argparse
import json
from collections.abc import Sequence

from repro.cluster.injector import FailSlowInjector, Injection, InjectionKind
from repro.cluster.simulator import JobSpec, TrainingSimulator
from repro.cluster.spec import ClusterSpec, ModelSpec
from repro.configs.base import ArchConfig, get_config
from repro.controlplane import event_log_records
from repro.data.pipeline import DataConfig
from repro.launch import init_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import FalconTrainer

KIND = {
    "gpu": InjectionKind.GPU_SLOW,
    "cpu": InjectionKind.CPU_CONTENTION,
    "link": InjectionKind.LINK_CONGESTION,
    "nic": InjectionKind.NIC_CONGESTION,
}


def parse_injection(text: str) -> Injection:
    kind, target, severity, start, duration = text.split(":")
    tgt = tuple(int(x) for x in target.split("-"))
    return Injection(
        start=float(start),
        duration=float(duration),
        kind=KIND[kind],
        target=tgt,
        severity=float(severity),
    )


def build_trainer(
    cfg: ArchConfig,
    data: DataConfig,
    *,
    steps: int,
    injections: Sequence[Injection] = (),
    falcon_enabled: bool = True,
    sim_nodes: int | None = None,
    **trainer_kwargs,
) -> FalconTrainer:
    """A :class:`FalconTrainer` wired to its simulated cluster.

    The cluster model is a full hybrid TP=2 x DP x PP=2 job, with DP tied
    to ``data.dp_groups`` (one DP group per slot column) and one
    micro-batch per slot and group, on ``sim_nodes`` 4-GPU nodes (default:
    just enough for the job); ``injections`` are its fail-slows.
    ``trainer_kwargs`` pass through to the trainer (e.g. ``ckpt_dir``).
    """
    tp, pp = 2, 2
    if sim_nodes is None:
        sim_nodes = -(-tp * data.dp_groups * pp // 4)
    sim = TrainingSimulator(
        cluster=ClusterSpec(n_nodes=sim_nodes, gpus_per_node=4),
        job=JobSpec(
            model=ModelSpec(
                layers=cfg.num_layers,
                hidden=max(cfg.d_model, 1024),
                seq_len=data.seq_len,
                vocab=cfg.vocab_size,
            ),
            tp=tp,
            dp=data.dp_groups,
            pp=pp,
            micro_batches=data.slots * data.dp_groups,
        ),
    )
    return FalconTrainer(
        cfg=cfg,
        data=data,
        opt_cfg=AdamWConfig(total_steps=steps),
        perf_model=sim,
        injector=FailSlowInjector(list(injections)),
        falcon_enabled=falcon_enabled,
        **trainer_kwargs,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="falcon-demo-100m")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--dp-groups", type=int, default=4)
    ap.add_argument("--no-falcon", action="store_true")
    ap.add_argument("--inject", action="append", default=[])
    ap.add_argument("--sim-nodes", type=int, default=None,
                    help="4-GPU nodes of the simulated cluster "
                         "(default: just enough for the job)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="run the step over a (data, model) mesh of the host's "
                         "devices, e.g. 1x4 (default: one device)")
    ap.add_argument(
        "--events", action="store_true",
        help="dump the control plane's typed event log after the run "
             "(JSON lines, the campaign-report serialization)",
    )
    ap.add_argument(
        "--events-stride", type=int, default=0,
        help="with --events, keep every Nth per-job Observation (0 = none)",
    )
    args = ap.parse_args()
    init_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    data = DataConfig(
        seq_len=args.seq_len,
        global_batch=args.global_batch,
        slots=args.slots,
        dp_groups=args.dp_groups,
    )

    mesh = None
    if args.mesh:
        data_axis, model_axis = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_host_mesh(data_axis, model_axis)
    trainer = build_trainer(
        cfg, data, steps=args.steps,
        injections=[parse_injection(t) for t in args.inject],
        falcon_enabled=not args.no_falcon, sim_nodes=args.sim_nodes, mesh=mesh,
    )
    print(f"# simulated cluster reductions: {trainer.perf_model.reduction_name}")
    history = trainer.run(args.steps)
    print("step,loss,iter_time,wall_time,strategy")
    for r in history:
        print(f"{r.step},{r.loss:.4f},{r.iter_time:.3f},{r.wall_time:.1f},{r.strategy or ''}")
    healthy = min(r.iter_time for r in history)
    mean = sum(r.iter_time for r in history) / len(history)
    print(f"# mean iter {mean:.3f}s vs healthy {healthy:.3f}s "
          f"(slowdown {mean / healthy:.2f}x)")
    if args.events and trainer.control is not None:
        print("# control-plane events:")
        for rec in event_log_records(
            trainer.control.events, observation_stride=args.events_stride
        ):
            print(json.dumps(rec, sort_keys=True))


if __name__ == "__main__":
    main()
