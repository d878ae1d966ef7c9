"""The ``train`` kind: the program's jitted train step (``dist.train.
make_train_step`` under ``jax.jit`` with donation, as ``launch.train``
builds it) driven over a window of whole steps.

Set-up builds one step and one state, runs the first ``checked_steps``
steps through the window's own call, reads what the check needs from that
state, and hands the same step and state to the window. After the window
the program's state is freed and the plain reference retraces those first
steps from the same weights and batches.
"""

from __future__ import annotations

import gc
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import cell as cellmod
from . import common, flops, traffic
from .weights import leaf_names, make_init, norms


def run(cell: cellmod.Cell, device: Dict[str, Any], hooks: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    import repro.core as rmon
    from repro.dist.train import abstract_state, make_train_step
    from repro.optim import adamw

    t = cell.traffic
    opt_spec = t["optimizer"]
    cfg = cell.program_config()
    monitor = cellmod.start_monitor(cell)
    params_shapes, _ = abstract_state(cfg)
    init = make_init(params_shapes)
    key = common.seed_key(cell.seed)
    opt_cfg = adamw.AdamWConfig(**{k: opt_spec[k] for k in
                                   ("lr", "b1", "b2", "eps", "weight_decay", "grad_clip_norm")})
    params = jax.jit(init)(key)
    opt_state = jax.jit(adamw.init)(params)
    step_fn = hooks.get("wrap_step", lambda f: f)(
        jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1)))
    vocab = cell.model["vocab"]
    tokens_per_step = t["global_batch"] * t["seq_len"]
    batch_fn = hooks.get("wrap_batch", lambda f: f)(
        lambda i: traffic.train_batch(t, vocab, cell.seed, i))
    losses: List[float] = []

    def make_batch(i: int):
        with TraceAnnotation("bench.batch"):
            return {k: jnp.asarray(v) for k, v in batch_fn(i).items()}

    def step(i: int, batch):
        """Runs step i on ``batch`` and returns batch i + 1, made on the host
        while the device runs step i (``launch.train`` prefetches likewise)."""
        nonlocal params, opt_state
        with TraceAnnotation("bench.step"), rmon.region("train_step", module="train"):
            params, opt_state, stats = step_fn(params, opt_state, batch)
            nxt = make_batch(i + 1)
            stats = jax.block_until_ready(stats)
        with TraceAnnotation("bench.record"):
            loss = float(stats["loss"])
            losses.append(loss)
            rmon.metric("train.loss", loss)
            rmon.metric("train.tokens", tokens_per_step)
        return nxt

    n_layers = cell.model["n_groups"]
    grad_norms = jax.jit(lambda tree, s: [n * s for n in norms(tree, n_layers)])
    delta_norms = jax.jit(lambda p, k: norms(jax.tree.map(jnp.subtract, p, init(k)), n_layers))
    n_check = t["checked_steps"]
    batch = step(0, make_batch(0))
    # The first gradient as AdamW got it (clipped): m_1 = (1 - b1) g_1.
    g1 = jax.device_get(grad_norms(opt_state["m"], 1.0 / (1.0 - opt_cfg.b1)))
    for i in range(1, n_check):
        batch = step(i, batch)
    d3 = jax.device_get(delta_norms(params, key))

    trace_dir = common.out_dir(cell.name) / "trace"
    if cell.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t_window = time.perf_counter()
    setup_s = t_window - cell.t0
    i = n_check
    with TraceAnnotation("bench.window"):
        while True:
            batch = step(i, batch)
            i += 1
            if time.perf_counter() - t_window >= cell.seconds:
                break
    window_s = time.perf_counter() - t_window
    if cell.trace:
        jax.profiler.stop_trace()
    steps = i - n_check
    mem_peak = common.memory_peak_bytes(cell.chips)
    for leaf in jax.tree.leaves((params, opt_state, batch)):
        leaf.delete()
    del params, opt_state, batch, step_fn
    gc.collect()
    rmon.finalize()
    del monitor

    print(f"device bytes in use before the reference: "
          f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}", file=sys.stderr)
    ref = cell.reference()
    model = cell.model
    batches = [traffic.train_batch(t, vocab, cell.seed, j) for j in range(n_check)]
    t_ref = time.perf_counter()
    want = hooks.get("reference", common_reference)(ref, model, init, key, batches, opt_spec)
    ref_s = time.perf_counter() - t_ref

    got = {"losses": losses[:n_check],
           "grad_norms": g1[0].tolist(), "grad_layer_norms": g1[1].tolist(),
           "delta_norms": d3[0].tolist(), "delta_layer_norms": d3[1].tolist()}
    checks = train_checks(got, want, cell.workload["limits"], leaf_names(params_shapes))
    ctx = {
        "kind": "train",
        "setup_s": setup_s,
        "window_s": window_s,
        "tokens": steps * tokens_per_step,
        "steps": steps,
        "flops_per_token": flops.train_flops_per_token(cell.counted_model(cfg), t["seq_len"]),
        "peaks": common.peaks(device["kind"]),
        "chips": cell.chips,
        "ref_s": ref_s,
    }
    device = dict(device, memory_peak_bytes=mem_peak)
    breakdown = None
    if cell.trace:
        from . import trace

        summary = trace.reduce(trace.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir)
        ctx["trace"] = summary
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
    failed = sum(1 for x in losses if not np.isfinite(x))
    cellmod.finish(cell, ctx, device, checks, attempted=len(losses), failed=failed,
                   breakdown=breakdown)


def common_reference(ref, model, init, key, batches, opt_spec, mode: str = "f32"):
    from references.common import train_reference

    row_loss = lambda p, tok, lab: ref.row_loss(model, p, tok, lab, mode)
    return train_reference(row_loss, init, key, batches, opt_spec,
                           lambda tree: norms(tree, model["n_groups"]))


NUMBERS = ("loss_gap", "grad_gap", "grad_gap_median", "delta_gap", "delta_gap_median")


def train_checks(got: Dict[str, Any], want: Dict[str, Any], limits: Dict[str, float],
                 names: Optional[List[str]] = None):
    """The numbers compared for a train cell, each with its limit; a number
    whose name the cell's ``limits`` lacks is printed and not compared.

    loss_gap: |program - reference| of the first step's loss; the later
    steps' gaps are printed beside it. grad_gap, delta_gap: worst counted
    leaf of the clipped first gradient's norm and of the change over the
    checked steps; grad_gap_median, delta_gap_median: the median of the
    same over the model's leaves, a leaf stacked over the layers counting
    once for each layer (leaves with a reference gradient under 1e-3 of the
    median leaf's are not counted). ``names``: the leaves' names, to print
    the worst leaves by."""
    def counted(ref):
        return [r >= 1e-3 * float(np.median(ref)) for r in ref]

    gaps = [abs(a - b) for a, b in zip(got["losses"], want["losses"])]
    print(f"loss gap by step: {gaps}", file=sys.stderr)
    c, c_layer = counted(want["grad_norms"]), counted(want["grad_layer_norms"])
    grad = cellmod.leaf_gaps(got["grad_norms"], want["grad_norms"], c)
    delta = cellmod.leaf_gaps(got["delta_norms"], want["delta_norms"], c)
    grad_layer = [g for g in cellmod.leaf_gaps(got["grad_layer_norms"], want["grad_layer_norms"], c_layer)
                  if g is not None]
    delta_layer = [g for g in cellmod.leaf_gaps(got["delta_layer_norms"], want["delta_layer_norms"], c_layer)
                   if g is not None]
    if names:
        for what, leaf_gaps in (("grad", grad), ("delta", delta)):
            worst = sorted((g, n) for g, n in zip(leaf_gaps, names) if g is not None)[-3:]
            print(f"worst {what} leaves: {worst[::-1]}", file=sys.stderr)
    grad = [g for g in grad if g is not None]
    delta = [g for g in delta if g is not None]
    values = dict(zip(NUMBERS, (gaps[0], max(grad), float(np.median(grad_layer)),
                                max(delta), float(np.median(delta_layer)))))
    for name, v in values.items():
        if name not in limits:
            print(f"not compared {name}: {v!r}", file=sys.stderr)
    return [(n, v, limits[n]) for n, v in values.items() if n in limits]
