#!/usr/bin/env python3
"""Bring-up smoke run of the monitored train and serve path on a TPU.

    python chip_smoke.py               # one chip: train, serve, kernels
    python chip_smoke.py --four-chips  # four chips: --mesh train vs one chip

Everything runs in this one process, since a chip belongs to one process at
a time, and JAX is touched only after the arguments are parsed.  Each phase
prints its own lines.  The last line of stdout is one JSON object naming the
device, printed only when every phase passed on a TPU; with no TPU, or with
no ``src/repro`` next to this file, the script exits non-zero without it.
The numbers it prints come from one run and are not benchmark results.

Phases on one chip:
  train    mamba2-370m at full width, global batch 8 x seq 2048, 5 steps,
           under a measurement started the way ``launch.train --report``
           starts one (default instrumenter; profiling, tracing, metrics
           and memory substrates)
  serve    the same config under the same measurement: batch 8, prompt
           1024, 32 generated tokens
  kernels  the Pallas kernels from ``kernels/ops.py``, compiled for the chip
           at real widths, against the plain references in ``kernels/ref.py``;
           the SSD scan's and flash attention's gradients (their backward
           kernels) too, against autodiff of the references

With ``--four-chips`` only the sharded path runs: the ``--mesh`` train path
(data 1 x model 4) and, for comparison, the same config, seed and batch on
one chip, in the same process.  It runs under a measurement with the
``none`` instrumenter (regions and metrics, no Python call hooks): the
default instrumenter multiplies the host's tracing time many times over,
and this phase checks numerics and placement, which the hooks do not touch.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "mamba2-370m"
TRAIN = dict(global_batch=8, seq_len=2048, steps=5)
SERVE = dict(batch=8, prompt_len=1024, gen=32)
SEED = 0
# |first loss - ln(vocab)| at a random init
FIRST_LOSS_TOL = 1.0
# per-step |loss(mesh) - loss(one chip)|
MESH_LOSS_TOL = 2e-2
KERNEL_SEQ = 4096
# (atol, rtol) per kernel: pass when |out - ref| <= atol + rtol * |ref|
# everywhere.  Flash attention reads and writes bf16; the SSD kernel's
# matmuls run at HIGHEST precision (it reads 3.2e-4 at |ref| up to 5.6 on
# a v5e; one bf16 pass through the MXU reads 2.4e-2), and so do its
# gradient's; the RG-LRU scan is elementwise fp32.  References run at
# "highest" matmul precision.
KERNEL_TOL = {
    "flash_gqa": (2e-2, 2e-2),
    "flash_window": (2e-2, 2e-2),
    "rg_lru": (1e-4, 1e-4),
    "ssd": (1e-3, 0.0),
    "ssd_vjp": (1e-3, 0.0),
    "flash_vjp": (2e-2, 2e-2),
}
# The flash gradient's head shapes: (query heads, KV heads, head_dim) of
# mistral-nemo-12b and of granite-4.0-h-micro (two heads a lane group).
FLASH_VJP_HEADS = {"mistral": (32, 8, 128), "granite": (32, 8, 64)}
# The SSD gradient's shapes: the reference's autodiff keeps the (P, N)
# state of every position, 1 MiB each at one sequence of 32 heads.
SSD_VJP_SHAPE = dict(batch=1, seq=2048)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def start_measurement(experiment: str, **overrides):
    import repro.core as rmon

    return rmon.init(experiment=experiment, report=True, out_dir=str(ROOT / "repro-traces"),
                     substrates=("profiling", "tracing", "metrics", "memory"), **overrides)


def finish_measurement(steps: int) -> None:
    """Finalize the measurement and check its ``metrics.json`` holds one
    ``train.step_s`` sample per train step."""
    import repro.core as rmon

    run_dir = rmon.finalize()
    doc = json.loads((Path(run_dir) / "metrics.json").read_text())
    n = len(doc.get("series", {}).get("train.step_s", []))
    log(f"monitor: run dir {run_dir}; metrics.json holds {n} train.step_s samples")
    check(n == steps, f"expected {steps} train.step_s samples, found {n}")


def report_train(result, global_batch: int, seq_len: int, steps: int, vocab: int):
    losses, step_s = result["losses"], result["step_s"]
    check(len(losses) == steps, f"expected {steps} losses, got {losses}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    log("train: losses " + " ".join(f"{x:.6f}" for x in losses))
    log("train: step ms " + " ".join(f"{s * 1e3:.3f}" for s in step_s))
    later = step_s[1:]
    steady = sorted(later)[len(later) // 2]
    log(f"train: tokens/s over steps 2..{steps}: {global_batch * seq_len * len(later) / sum(later):.1f}")
    log(f"train: first step {step_s[0]:.3f} s, of which compile ~{step_s[0] - steady:.3f} s "
        f"(first step less the median later step)")
    expect = math.log(vocab)
    check(abs(losses[0] - expect) <= FIRST_LOSS_TOL,
          f"first loss {losses[0]:.4f} is not within {FIRST_LOSS_TOL} of ln({vocab}) = {expect:.4f}")
    log(f"train: first loss {losses[0]:.4f} vs ln({vocab}) = {expect:.4f}")


def phase_train(cfg, *, global_batch: int, seq_len: int, steps: int) -> None:
    import jax

    from repro.launch.train import train

    result = train(cfg, steps=steps, global_batch=global_batch, seq_len=seq_len,
                   seed=SEED, log_every=1)
    report_train(result, global_batch, seq_len, steps, cfg.vocab)
    log(f"train: device 0 peak_bytes_in_use {peak_bytes(jax.devices()[0])}")


def phase_serve(cfg, *, batch: int, prompt_len: int, gen: int) -> None:
    import jax

    from repro.launch.serve import serve

    result = serve(cfg, batch=batch, prompt_len=prompt_len, gen=gen, seed=SEED)
    log(f"serve: prefill {result['prefill_s'] * 1e3:.3f} ms, decode "
        f"{result['decode_tok_per_s']:.1f} tok/s, sample {result['sample_tokens']}")
    check(result["finite"], "serve: logits are not finite")
    check(result["batch"] == batch and result["generated"] == gen,
          f"serve: expected {gen} tokens for each of {batch} sequences, got {result}")
    log(f"serve: {result['generated']} tokens for each of {result['batch']} sequences, "
        f"logits finite")
    log(f"serve: device 0 peak_bytes_in_use since start {peak_bytes(jax.devices()[0])}")


def kernel_cases(seq: int, seed: int):
    """(name, jitted kernel wrapper, its static kwargs, reference, its
    static kwargs, args) at the widths of the configurations that use each
    kernel."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    k = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(next(k), shape) * scale).astype(dtype)

    # mistral-nemo-12b: 32 q heads over 8 kv heads, head_dim 128 (causal);
    # gemma3-12b local layers: 16 over 8, head_dim 256, window 1024
    for name, h, kh, d, window in (("flash_gqa", 32, 8, 128, None),
                                   ("flash_window", 16, 8, 256, 1024)):
        mask = dict(causal=True, window=window)
        qkv = (normal((1, seq, h, d), jnp.bfloat16),
               normal((1, seq, kh, d), jnp.bfloat16),
               normal((1, seq, kh, d), jnp.bfloat16))
        yield name, ops.flash_attention, mask, ref.flash_attention_ref, mask, qkv
    # recurrentgemma-2b: lru_width 2560
    a = jax.random.uniform(next(k), (2, seq, 2560), minval=0.5, maxval=0.999)
    yield "rg_lru", ops.rg_lru_scan, {}, ref.rg_lru_scan_ref, {}, (a, normal(a.shape, scale=0.5))
    # mamba2-370m: 32 heads x 64, d_state 128, one B/C group, chunk 64
    ssd_args = (
        normal((2, seq, 32, 64), scale=0.5),
        jax.random.uniform(next(k), (2, seq, 32), minval=0.01, maxval=0.2),
        -jnp.exp(jax.random.uniform(next(k), (32,), minval=-2.0, maxval=1.0)),
        normal((2, seq, 1, 128), scale=0.5),
        normal((2, seq, 1, 128), scale=0.5),
    )
    yield "ssd", ops.ssd_chunk_scan, dict(chunk=64), ref.ssd_scan_ref, {}, ssd_args


def _output(result):
    """The output sequence of a kernel or reference (scans also return
    their final state)."""
    return result[0] if isinstance(result, tuple) else result


def within(name: str, out, expect, label: str = "") -> bool:
    """Log how far ``out`` is from ``expect`` against ``name``'s tolerance;
    True when it is within."""
    import numpy as np

    atol, rtol = KERNEL_TOL[name]
    out, expect = np.asarray(out, np.float64), np.asarray(expect, np.float64)
    err = np.abs(out - expect)
    max_err = float(np.max(err))
    excess = float(np.max(err - rtol * np.abs(expect)))
    ok = bool(np.all(np.isfinite(out))) and excess <= atol
    log(f"kernels: {name}{label} shape {tuple(out.shape)} max-abs err {max_err:.3e} "
        f"(max |ref| {float(np.max(np.abs(expect))):.3e}; atol {atol:g}, rtol {rtol:g}) "
        f"{'ok' if ok else 'FAIL'}")
    return ok


def ssd_vjp_ok(*, batch: int, seq: int, seed: int) -> bool:
    """The SSD kernel pair's gradient (dx, d dt, d a, dB, dC), with
    cotangents on the output and the final state, against autodiff of the
    token-by-token reference.

    The reference runs in float64 on the host: in float32 its own rounding
    in d a, a sum over every position of values up to about 2e3, reaches
    1.6e-3 against float64 (CPU, 1 x 2048 tokens), above the tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    args = (jax.random.normal(k[0], (batch, seq, 32, 64)) * 0.5,
            jax.random.uniform(k[1], (batch, seq, 32), minval=0.01, maxval=0.2),
            -jnp.exp(jax.random.uniform(k[2], (32,), minval=-2.0, maxval=1.0)),
            jax.random.normal(k[3], (batch, seq, 1, 128)) * 0.5,
            jax.random.normal(k[4], (batch, seq, 1, 128)) * 0.5)
    cotangents = (jax.random.normal(k[5], (batch, seq, 32, 64)),
                  jax.random.normal(k[6], (batch, 32, 64, 128)))

    def grads(scan, cts):
        return jax.jit(lambda *z: jax.vjp(scan, *z)[1](cts))

    compiled = grads(lambda *z: ops.ssd_chunk_scan(*z, chunk=64), cotangents).lower(*args).compile()
    n_kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    check(n_kernels == 2, f"ssd_vjp: expected the forward and backward kernels, found {n_kernels}")
    got = jax.block_until_ready(compiled(*args))
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True):
        wide = [jax.device_put(np.asarray(v, np.float64), cpu) for v in (*args, *cotangents)]
        want = grads(ref.ssd_scan_ref, tuple(wide[5:]))(*wide[:5])
        want = [np.asarray(v) for v in want]
    ok = True
    for name, out, expect in zip(("dx", "ddt", "da", "dB", "dC"), got, want):
        ok = within("ssd_vjp", out, expect, f" {name}") and ok
    return ok


def flash_vjp_ok(*, seq: int, seed: int) -> bool:
    """The flash kernels' gradient (dq, dk, dv) of one bfloat16 sequence
    at each cell's head shape, against autodiff of the naive reference in
    float32 at "highest" matmul precision.  The kernels round the
    probabilities and dS to bfloat16 for their matmuls, as the XLA path
    does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    ok = True
    for name, (h, kh, d) in FLASH_VJP_HEADS.items():
        k = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, kk, v, do = (jax.random.normal(key, (1, seq, n, d)).astype(jnp.bfloat16)
                        for key, n in zip(k, (h, kh, kh, h)))

        def grads(attend):
            return jax.jit(lambda *z: jax.vjp(attend, *z)[1](do.astype(z[0].dtype)))

        compiled = grads(ops.flash_attention).lower(q, kk, v).compile()
        names = [n for n in ("fwd", "bwd_dkv", "bwd_dq") if f"flash_attention_{n}" in compiled.as_text()]
        check(len(names) == 3, f"flash_vjp {name}: expected the forward and both backward kernels, found {names}")
        got = jax.block_until_ready(compiled(q, kk, v))
        with jax.default_matmul_precision("highest"):
            want = grads(ref.flash_attention_ref)(*(z.astype(jnp.float32) for z in (q, kk, v)))
        for label, out, expect in zip(("dq", "dk", "dv"), got, want):
            ok = within("flash_vjp", out, np.asarray(expect), f" {name} {label}") and ok
    return ok


def phase_kernels(*, seq: int) -> None:
    import functools

    import jax

    failed = []
    for name, kernel, kernel_kw, reference, ref_kw, args in kernel_cases(seq, SEED):
        compiled = kernel.lower(*args, **kernel_kw).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        # run the very program that was checked, not a second compile of it
        out = _output(jax.block_until_ready(compiled(*args)))
        with jax.default_matmul_precision("highest"):
            expect = jax.block_until_ready(jax.jit(functools.partial(reference, **ref_kw))(*args))
        if not within(name, out, _output(expect)):
            failed.append(name)
        del out, expect
    if not ssd_vjp_ok(**SSD_VJP_SHAPE, seed=SEED):
        failed.append("ssd_vjp")
    if not flash_vjp_ok(seq=seq, seed=SEED):
        failed.append("flash_vjp")
    check(not failed, f"kernels outside tolerance: {failed}")


def phase_four_chips(cfg, *, global_batch: int, seq_len: int, steps: int) -> None:
    import jax

    from repro.launch.train import train

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    sharded = train(cfg, steps=steps, global_batch=global_batch, seq_len=seq_len,
                    seed=SEED, use_mesh=True, log_every=1)
    log(f"mesh: topology {sharded['topology']}")
    report_train(sharded, global_batch, seq_len, steps, cfg.vocab)
    peaks = [peak_bytes(d) for d in devices]
    log("mesh: peak_bytes_in_use per device " + " ".join(str(p) for p in peaks))

    single = train(cfg, steps=steps, global_batch=global_batch, seq_len=seq_len,
                   seed=SEED, use_mesh=False, log_every=1)
    log("one chip: reference run on device 0")
    report_train(single, global_batch, seq_len, steps, cfg.vocab)
    log(f"one chip: device 0 peak_bytes_in_use since start {peak_bytes(devices[0])}")

    diffs = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    log("mesh vs one chip: |loss diff| per step " + " ".join(f"{d:.3e}" for d in diffs)
        + f" (tolerance {MESH_LOSS_TOL:g})")
    check(max(diffs) <= MESH_LOSS_TOL, f"mesh losses differ from one chip by {max(diffs):.3e}")
    if all(p is not None for p in peaks):
        check(max(peaks) <= 2 * min(peaks), f"state is not spread over the devices: {peaks}")


def run_phases(phases) -> bool:
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        log(f"== {name}")
        try:
            fn()
            log(f"== {name} passed in {time.perf_counter() - t0:.1f} s")
        except Exception:
            traceback.print_exc()
            log(f"== {name} FAILED after {time.perf_counter() - t0:.1f} s")
            ok = False
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --mesh train path on four chips and its "
                         "one-chip comparison")
    ns = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: no src/repro next to {Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"error: JAX found no device: {e}", file=sys.stderr)
        return 1
    device = devices[0]
    if device.platform != "tpu":
        print(f"error: no TPU; JAX sees {device.platform} ({device.device_kind})",
              file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {device.platform} {device.device_kind} x{len(devices)}; jax {jax.__version__}")

    cfg = get_config(ARCH)
    if ns.four_chips:
        start_measurement("chip-smoke-mesh", instrumenter="none")
        phases = [
            ("four chips", lambda: phase_four_chips(cfg, **TRAIN)),
            ("monitor", lambda: finish_measurement(2 * TRAIN["steps"])),
        ]
    else:
        start_measurement("chip-smoke")
        phases = [
            ("train", lambda: phase_train(cfg, **TRAIN)),
            ("serve", lambda: phase_serve(cfg, **SERVE)),
            ("monitor", lambda: finish_measurement(TRAIN["steps"])),
            ("kernels", lambda: phase_kernels(seq=KERNEL_SEQ)),
        ]
    if not run_phases(phases):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
