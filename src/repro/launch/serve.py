"""Serving driver: batched prefill + greedy decode with monitoring.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-34b --smoke \
        --batch 4 --prompt-len 32 --gen 32
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as rmon
from repro.core.memsys import rss_bytes
from repro.configs import get_config, get_smoke_config
from repro.dist import serve as dserve
from repro.launch.cache import enable_compile_cache
from repro.models import lm_init


def serve(
    cfg,
    *,
    batch: int = 4,
    prompt_len: int = 32,
    gen: int = 32,
    seed: int = 0,
    use_mesh: bool = False,
) -> Dict[str, Any]:
    from repro.launch.mesh import elastic_setup

    cfg, mesh, mesh_ctx, topology = elastic_setup(cfg, rmon.current_topology(), use_mesh)

    key = jax.random.PRNGKey(seed)
    with rmon.region("init", module="serve"):
        params = lm_init(key, cfg)
        if mesh is not None:
            from repro.dist import sharding as shd

            params = jax.device_put(params, shd.params_shardings(mesh, params))
    max_len = prompt_len + gen + (cfg.frontend.n_tokens if cfg.frontend else 0)
    prompts = jax.random.randint(key, (batch, prompt_len), 2, cfg.vocab)
    host_batch = {"tokens": prompts}
    if cfg.frontend is not None:
        host_batch["patches"] = jax.random.normal(
            key, (batch, cfg.frontend.n_tokens, cfg.frontend.dim), jnp.bfloat16)
    if cfg.encoder is not None:
        host_batch["frames"] = jax.random.normal(
            key, (batch, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16)

    prefill_fn = jax.jit(dserve.make_prefill_step(cfg, max_len))
    decode_fn = jax.jit(dserve.make_decode_step(cfg))

    t0 = time.perf_counter()
    with rmon.region("prefill", module="serve"), mesh_ctx():
        logits, cache = jax.block_until_ready(prefill_fn(params, host_batch))
    t_prefill = time.perf_counter() - t0
    rmon.metric("serve.prefill_ms", t_prefill * 1e3)
    # Slot memory watermark after prefill: the KV cache for all slots is
    # materialized here, so this is the high-water mark per batch of slots.
    rmon.metric("serve.prefill_rss_mb", rss_bytes() / 1e6)

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    generated = [tok]
    t1 = time.perf_counter()
    for i in range(gen - 1):
        with rmon.region("decode_step", module="serve"), mesh_ctx():
            logits, cache = decode_fn(params, cache, tok)
            logits = jax.block_until_ready(logits)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        generated.append(tok)
    t_decode = time.perf_counter() - t1
    rmon.metric("serve.decode_tok_s", batch * (gen - 1) / max(t_decode, 1e-9))
    rmon.metric("serve.decode_rss_mb", rss_bytes() / 1e6)

    out = jnp.concatenate(generated, axis=1)
    return {
        "batch": batch,
        "prompt_len": prompt_len,
        "generated": int(out.shape[1]),
        "prefill_s": t_prefill,
        "decode_tok_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
        "finite": bool(np.all(np.isfinite(np.asarray(logits)))),
        "sample_tokens": np.asarray(out[0, :8]).tolist(),
        "topology": topology.as_dict(),
    }


def build_parser() -> argparse.ArgumentParser:
    """The ``python -m repro.launch.serve`` argument parser (also rendered
    into docs/CLI.md by :mod:`repro.core.clidoc`)."""
    p = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--mesh", action="store_true")
    p.add_argument("--report", action="store_true",
                   help="emit report.html at finalize: flips the active "
                        "measurement's report flag when launched under "
                        "repro.scorep, else starts a measurement of its own")
    p.add_argument("--static-plan", dest="static_plan", default="",
                   help="static_plan.json from `analysis plan`: applied to "
                        "the active measurement (or the one --report starts)")
    p.add_argument("--agent", action="store_true",
                   help="run the live-monitoring agent alongside the workload "
                        "(/report, /stats.json, /healthz); attaches to the "
                        "active measurement when launched under repro.scorep, "
                        "else starts a measurement of its own")
    p.add_argument("--agent-port", type=int, default=0,
                   help="agent HTTP port (0 = ephemeral)")
    p.add_argument("--loop", type=int, default=1,
                   help="repeat the serve workload N times (live-monitoring "
                        "demos/smokes: keeps events flowing; Ctrl-C exits "
                        "cleanly after the current iteration)")
    return p


def main(argv=None) -> int:
    ns = build_parser().parse_args(argv)
    enable_compile_cache()
    owns_measurement = False
    if ns.report or ns.agent:
        m = rmon.active()
        if m is None:
            rmon.init(experiment="serve", report=ns.report,
                      agent=ns.agent, agent_port=ns.agent_port,
                      static_plan=ns.static_plan,
                      substrates=("profiling", "tracing", "metrics", "memory"))
            owns_measurement = True
        else:
            if ns.report:
                m.config.report = True
            if ns.agent:
                m.attach_agent(ns.agent_port)
    if ns.static_plan and not owns_measurement:
        m = rmon.active()
        if m is not None:
            from repro.core.staticpass import apply_plan, load_plan

            apply_plan(m, load_plan(ns.static_plan))
    cfg = get_smoke_config(ns.arch) if ns.smoke else get_config(ns.arch)
    result = None
    try:
        for i in range(max(1, ns.loop)):
            result = serve(cfg, batch=ns.batch, prompt_len=ns.prompt_len,
                           gen=ns.gen, use_mesh=ns.mesh)
            if ns.loop > 1:
                rmon.metric("serve.iteration", i + 1)
    except KeyboardInterrupt:
        pass  # clean exit mid-loop: fall through to finalize below
    if result is not None:
        print(result)
    if owns_measurement:
        run_dir = rmon.finalize()
        if run_dir and ns.report:
            print(f"report: {run_dir}/report.html")
    return 0 if (result is not None and result["finite"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
