"""End-to-end training driver.

Ties together: config registry, elastic mesh, stateless data pipeline,
AdamW, monitoring (paper's regions + metrics), straggler watchdog, and
fault-tolerant checkpointing with auto-resume.

    PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke \
        --steps 50 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt

Runs under the monitoring CLI exactly like any Python program (paper
Listing 1):

    python -m repro.scorep --instrumenter=profile -- \
        -m is not needed; pass the script path or use mod: syntax
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as rmon
from repro.core.memsys import rss_bytes
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig, Prefetcher, SyntheticLM
from repro.dist import sharding as shd
from repro.dist.straggler import StragglerWatchdog
from repro.dist.train import abstract_state, make_train_step
from repro.launch.cache import enable_compile_cache
from repro.models import lm_init
from repro.models.lm import padded_vocab
from repro.optim import adamw


def build_data_config(cfg, global_batch: int, seq_len: int, seed: int) -> DataConfig:
    return DataConfig(
        vocab=cfg.vocab,
        seq_len=seq_len if cfg.frontend is None else seq_len - cfg.frontend.n_tokens,
        global_batch=global_batch,
        seed=seed,
        frontend_tokens=cfg.frontend.n_tokens if cfg.frontend else 0,
        frontend_dim=cfg.frontend.dim if cfg.frontend else 0,
        encoder_len=cfg.encoder.source_len if cfg.encoder else 0,
        encoder_dim=cfg.d_model if cfg.encoder else 0,
    )


def train(
    cfg,
    *,
    steps: int = 50,
    global_batch: int = 8,
    seq_len: int = 128,
    lr: float = 3e-4,
    seed: int = 0,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 20,
    use_mesh: bool = False,
    log_every: int = 10,
    abort_at_step: Optional[int] = None,  # simulate a crash (no final save)
) -> Dict[str, Any]:
    opt_cfg = adamw.AdamWConfig(lr=lr, schedule=adamw.cosine_schedule(max(steps // 10, 1), steps))
    from repro.launch.mesh import elastic_setup

    cfg, mesh, mesh_ctx, topology = elastic_setup(cfg, rmon.current_topology(), use_mesh)
    if topology.world_size > 1 or topology.mesh_shape:
        print(f"topology: {topology.tag()} mesh={topology.mesh_shape or '(none)'}")

    with rmon.region("init", module="train"):
        key = jax.random.PRNGKey(seed)
        # Built in place under out_shardings: made eagerly and then
        # device_put, the whole fp32 state would first sit on device 0.
        p_shard = o_shard = None
        if mesh is not None:
            params_shapes, opt_shapes = abstract_state(cfg)
            p_shard = shd.params_shardings(mesh, params_shapes)
            o_shard = shd.opt_state_shardings(mesh, opt_shapes)
        params = jax.jit(lambda k: lm_init(k, cfg), out_shardings=p_shard)(key)
        opt_state = jax.jit(adamw.init, out_shardings=o_shard)(params)

    start_step = 0
    manager = None
    if ckpt_dir:
        manager = CheckpointManager(ckpt_dir)
        state = {"params": params, "opt": opt_state}
        shardings = None if mesh is None else {"params": p_shard, "opt": o_shard}
        restored = manager.restore_latest(state, shardings)
        if restored is not None:
            start_step, state, extras = restored
            params, opt_state = state["params"], state["opt"]
            print(f"resumed from checkpoint at step {start_step}")

    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))

    data = SyntheticLM(build_data_config(cfg, global_batch, seq_len, seed))
    prefetch = Prefetcher(data.batch, start_step=start_step)
    watchdog = StragglerWatchdog(
        topology=topology,
        on_straggler=lambda ev: print(
            f"straggler: step {ev['step']} {ev['ratio']:.1f}x baseline on rank {ev['rank']}"
        ),
    )

    losses = []
    step_s = []
    t_train0 = time.perf_counter()
    try:
        for i in range(start_step, steps):
            step_i, host_batch = prefetch.next()
            batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
            if "patches" in batch:
                batch["patches"] = batch["patches"].astype(jnp.bfloat16)
            if "frames" in batch:
                batch["frames"] = batch["frames"].astype(jnp.bfloat16)
            t0 = time.perf_counter()
            with rmon.region("train_step", module="train"), mesh_ctx():
                params, opt_state, stats = step_fn(params, opt_state, batch)
                stats = jax.block_until_ready(stats)
            dt = time.perf_counter() - t0
            step_s.append(dt)
            watchdog.observe(step_i, dt)
            loss = float(stats["loss"])
            losses.append(loss)
            rmon.metric("train.loss", loss)
            rmon.metric("train.tokens", global_batch * seq_len)
            # Per-step memory watermark: host RSS after the step completed
            # (device buffers live in RSS on CPU backends; on accelerators
            # this tracks the host-side share — staging, prefetch, optimizer
            # mirrors).  Feeds the mem counter tracks in the trace view.
            rmon.metric("train.rss_mb", rss_bytes() / 1e6)
            if (step_i + 1) % log_every == 0 or step_i == start_step:
                tps = global_batch * seq_len / dt
                print(
                    f"step {step_i + 1:5d}  loss {loss:.4f}  grad_norm "
                    f"{float(stats['grad_norm']):.3f}  {dt * 1e3:.0f} ms  {tps:,.0f} tok/s"
                )
            if manager and (step_i + 1) % ckpt_every == 0:
                with rmon.region("checkpoint", module="train"):
                    manager.save(step_i + 1, {"params": params, "opt": opt_state},
                                 extras={"loss": loss})
            if abort_at_step is not None and step_i + 1 >= abort_at_step:
                # simulated crash: leave without final save; whatever the
                # checkpoint cadence published is what restart sees
                if manager:
                    manager.wait()
                return {
                    "steps": step_i + 1 - start_step,
                    "start_step": start_step,
                    "final_loss": losses[-1],
                    "first_loss": losses[0],
                    "wall_s": time.perf_counter() - t_train0,
                    "aborted": True,
                    "straggler": watchdog.summary(),
                }
        if manager:
            manager.save(steps, {"params": params, "opt": opt_state},
                         extras={"loss": losses[-1] if losses else None})
            manager.wait()
    finally:
        prefetch.close()

    wall = time.perf_counter() - t_train0
    result = {
        "steps": steps - start_step,
        "start_step": start_step,
        "final_loss": losses[-1] if losses else None,
        "first_loss": losses[0] if losses else None,
        "losses": losses,
        "step_s": step_s,
        "wall_s": wall,
        "straggler": watchdog.summary(),
        "topology": topology.as_dict(),
    }
    return result


def build_parser() -> argparse.ArgumentParser:
    """The ``python -m repro.launch.train`` argument parser (also rendered
    into docs/CLI.md by :mod:`repro.core.clidoc`)."""
    p = argparse.ArgumentParser(prog="python -m repro.launch.train")
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true", help="use the reduced config")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=20)
    p.add_argument("--mesh", action="store_true")
    p.add_argument("--d-model", type=int, default=None, help="override width")
    p.add_argument("--n-groups", type=int, default=None, help="override depth")
    p.add_argument("--report", action="store_true",
                   help="emit report.html at finalize: flips the active "
                        "measurement's report flag when launched under "
                        "repro.scorep, else starts a measurement of its own")
    p.add_argument("--static-plan", dest="static_plan", default="",
                   help="static_plan.json from `analysis plan`: applied to "
                        "the active measurement (or the one --report starts)")
    return p


def main(argv=None) -> int:
    ns = build_parser().parse_args(argv)
    enable_compile_cache()

    owns_measurement = False
    if ns.report:
        m = rmon.active()
        if m is not None:
            m.config.report = True
        else:
            rmon.init(experiment="train", report=True,
                      static_plan=ns.static_plan,
                      substrates=("profiling", "tracing", "metrics", "memory"))
            owns_measurement = True
    if ns.static_plan and not owns_measurement:
        m = rmon.active()
        if m is not None:
            from repro.core.staticpass import apply_plan, load_plan

            apply_plan(m, load_plan(ns.static_plan))

    cfg = get_smoke_config(ns.arch) if ns.smoke else get_config(ns.arch)
    overrides = {}
    if ns.d_model:
        overrides["d_model"] = ns.d_model
    if ns.n_groups:
        overrides["n_groups"] = ns.n_groups
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    result = train(
        cfg,
        steps=ns.steps,
        global_batch=ns.global_batch,
        seq_len=ns.seq_len,
        lr=ns.lr,
        seed=ns.seed,
        ckpt_dir=ns.ckpt_dir,
        ckpt_every=ns.ckpt_every,
        use_mesh=ns.mesh,
    )
    print(result)
    if owns_measurement:
        run_dir = rmon.finalize()
        if run_dir:
            print(f"report: {run_dir}/report.html")
    ok = result["final_loss"] is not None and np.isfinite(result["final_loss"])
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
