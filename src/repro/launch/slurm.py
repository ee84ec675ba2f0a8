"""Slurm job-script generation (the JUBE platform.xml analog).

CARAML populates job templates from a system config and submits to Slurm;
this module renders equivalent sbatch scripts for TPU pod slices, with the
affinity/binding lessons from the paper's Section V baked in (one task per
host, open CPU masks for collective helper threads, explicit coordinator
address for multi-pod jobs).
"""
from __future__ import annotations

import pathlib
from dataclasses import dataclass, field


@dataclass
class SystemConfig:
    """Per-system template values (the platform.xml analog)."""
    name: str = "v5e-pod"
    hosts_per_pod: int = 64          # v5e-256: 64 hosts x 4 chips
    chips_per_host: int = 4
    partition: str = "tpu"
    account: str = "repro"
    container: str = ""              # optional container image
    env: dict = field(default_factory=dict)


TEMPLATE = """#!/bin/bash
#SBATCH --job-name={job_name}
#SBATCH --partition={partition}
#SBATCH --account={account}
#SBATCH --nodes={n_hosts}
#SBATCH --ntasks-per-node=1
#SBATCH --cpus-per-task={cpus}
#SBATCH --exclusive
#SBATCH --time={time_limit}
#SBATCH --output={log_dir}/%x_%j.out

# one task per host; open CPU mask so collective helper threads can float
# (CARAML Sec. V: over-tight masks starve NCCL/ICI helper threads)
export SLURM_CPU_BIND=none
{env_exports}
# multi-pod rendezvous: first host of the allocation coordinates
export JAX_COORDINATOR_ADDRESS=$(scontrol show hostnames $SLURM_JOB_NODELIST | head -n1):8476
export JAX_NUM_PROCESSES=$SLURM_NTASKS
export JAX_PROCESS_ID=$SLURM_PROCID

srun {container_prefix}python -m {module} {args}
"""


def render_job(*, job_name: str, module: str, args: str,
               system: SystemConfig, n_pods: int = 1,
               n_hosts: int | None = None,
               time_limit: str = "02:00:00", log_dir: str = "logs") -> str:
    """Render one sbatch script. ``n_hosts`` sizes the allocation
    directly (a bench mesh that needs 4 hosts should not reserve a full
    pod); default is whole pods (``hosts_per_pod * n_pods``)."""
    env_exports = "\n".join(f"export {k}={v}" for k, v in system.env.items())
    container_prefix = (f"apptainer exec {system.container} "
                        if system.container else "")
    return TEMPLATE.format(
        job_name=job_name, partition=system.partition, account=system.account,
        n_hosts=system.hosts_per_pod * n_pods if n_hosts is None else n_hosts,
        cpus=112,
        time_limit=time_limit, log_dir=log_dir, env_exports=env_exports,
        container_prefix=container_prefix, module=module, args=args)


def render_bench_job(*, workload: str, placement, point: dict,
                     system: SystemConfig | None = None,
                     out: str = "artifacts/bench",
                     power: str = "auto",
                     warmup: int | None = None,
                     iters: int | None = None,
                     job_suffix: str = "") -> str:
    """The deferred-record script: re-run ONE bench point on a Slurm
    allocation sized to its mesh (``placement`` is a
    ``repro.bench.spec.Placement``). The bench runner renders this when
    a point's mesh exceeds the local device count instead of erroring —
    the sweep's local cells still measure, and the rendered script
    carries the oversized cell to the cluster. ``out``/``power``/
    ``warmup``/``iters`` forward the invoking run's settings so the
    cluster record lands in the same results tree with a point key that
    joins the local sweep (power_source is part of the key)."""
    system = system or SystemConfig()
    n_hosts = max(1, -(-placement.n_devices // system.chips_per_host))
    points = ",".join(f"{k}={point[k]}" for k in sorted(point))
    args = f"run --suite {workload} --out {out} --power {power}"
    if warmup is not None:
        args += f" --warmup {warmup}"
    if iters is not None:
        args += f" --iters {iters}"
    if points:
        args += f" --points {points}"
    return render_job(
        job_name=f"bench_{workload}_{placement.label}{job_suffix}",
        module="repro.bench", args=args, system=system, n_hosts=n_hosts)


def write_launch_scripts(out_dir, archs, system: SystemConfig | None = None):
    """Render train + dry-run scripts for every arch (single & multi pod)."""
    system = system or SystemConfig()
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    written = []
    for arch in archs:
        for pods, tag in ((1, "pod1"), (2, "pod2")):
            script = render_job(
                job_name=f"train_{arch}_{tag}",
                module="repro.launch.train",
                args=f"--arch {arch} --preset full",
                system=system, n_pods=pods)
            p = out / f"train_{arch}_{tag}.sbatch"
            p.write_text(script)
            written.append(str(p))
    dry = render_job(job_name="dryrun", module="repro.launch.dryrun",
                     args="--mesh both", system=system, n_pods=2)
    (out / "dryrun.sbatch").write_text(dry)
    written.append(str(out / "dryrun.sbatch"))
    return written
