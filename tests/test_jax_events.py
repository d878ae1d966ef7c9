"""JAX integration tests: HLO collective parsing, compiled metrics, model
layer scopes in the optimized HLO, the monitor's spans in a profiler trace,
and compile events as metrics."""

import gc
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

import repro.core as rmon
from repro.core import jax_events
from repro.core.jax_events import LAYER_SCOPES, collective_stats, compiled_metrics, scope

HLO_SAMPLE = """
  %all-reduce.2 = f32[4,128]{1,0} all-reduce(%dot), channel_id=1, replica_groups=[4,2]<=[8], use_global_device_ids=true, to_apply=%add
  %all-gather.1 = bf16[8,256]{1,0} all-gather(%p), channel_id=2, replica_groups=[2,4]<=[8], dimensions={0}
  %reduce-scatter.3 = f32[2,64]{1,0} reduce-scatter(%q), channel_id=3, replica_groups=[1,8]<=[8], to_apply=%add
  %collective-permute.1 = f32[16]{0} collective-permute(%r), channel_id=4, source_target_pairs={{0,1},{1,0}}
  %notacollective = f32[4]{0} add(%a, %b)
"""


def test_collective_stats_parsing():
    stats = collective_stats(HLO_SAMPLE)
    ar = stats["all-reduce"]
    assert ar["count"] == 1
    assert ar["result_bytes"] == 4 * 128 * 4
    # group size 2 -> ring factor 2*(2-1)/2 = 1.0
    assert ar["wire_bytes"] == pytest.approx(4 * 128 * 4 * 1.0)
    ag = stats["all-gather"]
    assert ag["count"] == 1 and ag["result_bytes"] == 8 * 256 * 2
    # group size 4 -> (4-1)/4
    assert ag["wire_bytes"] == pytest.approx(8 * 256 * 2 * 0.75)
    rs = stats["reduce-scatter"]
    assert rs["count"] == 1 and rs["wire_bytes"] == pytest.approx(2 * 64 * 4 * 7 / 8)
    cp = stats["collective-permute"]
    assert cp["count"] == 1 and cp["wire_bytes"] == 16 * 4


# Async-ified collective forms, as XLA emits them post-SPMD: the *-start op
# carries the transfer (tuple-shaped result for all-gather/collective-permute)
# and the paired *-done op must not double count.
HLO_ASYNC_SAMPLE = """
  %all-reduce-start.1 = f32[1024]{0} all-reduce-start(f32[1024]{0} %p), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  %all-reduce-done.1 = f32[1024]{0} all-reduce-done(f32[1024]{0} %all-reduce-start.1)
  %all-gather-start.2 = (f32[8,128]{1,0}, f32[32,128]{1,0}) all-gather-start(f32[8,128]{1,0} %q), channel_id=2, replica_groups=[2,4]<=[8], dimensions={0}
  %all-gather-done.2 = f32[32,128]{1,0} all-gather-done((f32[8,128]{1,0}, f32[32,128]{1,0}) %all-gather-start.2)
  %collective-permute-start.3 = (f32[64]{0}, f32[64]{0}, u32[], u32[]) collective-permute-start(f32[64]{0} %r), channel_id=3, source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.3 = f32[64]{0} collective-permute-done((f32[64]{0}, f32[64]{0}, u32[], u32[]) %collective-permute-start.3)
"""


def test_collective_stats_async_forms_counted_once():
    stats = collective_stats(HLO_ASYNC_SAMPLE)
    ar = stats["all-reduce"]
    assert ar["count"] == 1  # start counted, done deduped
    assert ar["result_bytes"] == 1024 * 4
    # group size 4 -> ring factor 2*(4-1)/4
    assert ar["wire_bytes"] == pytest.approx(1024 * 4 * 1.5)
    ag = stats["all-gather"]
    assert ag["count"] == 1
    # tuple result (input, output): the gathered output is the byte count
    assert ag["result_bytes"] == 32 * 128 * 4
    assert ag["wire_bytes"] == pytest.approx(32 * 128 * 4 * 0.75)
    cp = stats["collective-permute"]
    assert cp["count"] == 1
    assert cp["result_bytes"] == 64 * 4 and cp["wire_bytes"] == 64 * 4


def test_collective_stats_reduce_scatter_start_uses_scattered_result():
    # reduce-scatter's async tuple is (input, output) with the *smaller*
    # scattered output as the real result — max() over the tuple would
    # overcount by the group-size factor.
    hlo = """
  %reduce-scatter-start.1 = (f32[800]{0}, f32[100]{0}) reduce-scatter-start(f32[800]{0} %p), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
  %reduce-scatter-done.1 = f32[100]{0} reduce-scatter-done((f32[800]{0}, f32[100]{0}) %reduce-scatter-start.1)
"""
    rs = collective_stats(hlo)["reduce-scatter"]
    assert rs["count"] == 1
    assert rs["result_bytes"] == 100 * 4
    assert rs["wire_bytes"] == pytest.approx(100 * 4 * 7 / 8)


def test_collective_stats_sync_and_async_mixed():
    stats = collective_stats(HLO_SAMPLE + HLO_ASYNC_SAMPLE)
    assert stats["all-reduce"]["count"] == 2
    assert stats["all-gather"]["count"] == 2
    # operand references to %all-reduce-start must not be miscounted
    assert stats["reduce-scatter"]["count"] == 1


def test_compiled_metrics_on_real_lowering():
    def f(x, w):
        return jnp.sum(jnp.tanh(x @ w))

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    m = compiled_metrics(compiled)
    # matmul flops = 2*64*128*256 (plus epilogue)
    assert m["hlo_flops"] >= 2 * 64 * 128 * 256
    assert m["hlo_bytes"] > 0
    assert m["collective_wire_bytes"] == 0.0  # single device


# -- model layer scopes --------------------------------------------------------

def _scope_in(name: str, op_name: str) -> bool:
    """``name`` is a component of ``op_name``, bare or wrapped by a transform
    (``jvp(layer_stack)``, ``transpose(jvp(embed))``)."""
    return re.search(rf"(^|/|\(){re.escape(name)}(\)|/|$)", op_name) is not None


@pytest.mark.parametrize("arch,scopes,rematted", [
    ("mamba2-370m",
     ("embed", "norm", "layer_stack", "ssd_proj", "ssd_scan", "head_loss", "optimizer"),
     "ssd_scan"),
    ("mistral-nemo-12b",
     ("embed", "norm", "layer_stack", "attn_proj", "attn_core", "mlp", "head_loss", "optimizer"),
     "attn_core"),
    ("granite-4.0-h-micro",
     ("embed", "norm", "layer_stack", "ssd_proj", "ssd_scan", "attn_proj", "attn_core", "mlp",
      "head_loss", "optimizer"),
     "attn_core"),
])
def test_train_step_hlo_names_every_layer_scope(arch, scopes, rematted):
    from repro.configs import get_smoke_config
    from repro.dist.train import abstract_state, batch_shapes, make_train_step
    from repro.optim import adamw

    # remat="full" as the full configs inherit it from configs/base.py
    cfg = get_smoke_config(arch).scaled(remat="full")
    params, opt = abstract_state(cfg)
    step = jax.jit(make_train_step(cfg, adamw.AdamWConfig()), donate_argnums=(0, 1))
    hlo = step.lower(params, opt, batch_shapes(cfg, 2, 64)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for name in scopes:
        assert any(_scope_in(name, o) for o in op_names), name
    recomputed = [o for o in op_names if "rematted_computation" in o]
    assert any(_scope_in(rematted, o.split("rematted_computation", 1)[1]) for o in recomputed)


def test_param_cast_scope_under_bf16_compute():
    from repro.dist.train import _cast_params_for_compute

    params = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    hlo = jax.jit(lambda p: _cast_params_for_compute(p, jnp.bfloat16)).lower(params).as_text(
        debug_info=True)
    assert "param_cast" in hlo


def test_scope_takes_only_table_names():
    assert set(LAYER_SCOPES) >= {"embed", "ssd_scan", "attn_core", "optimizer"}
    with scope("mlp"):
        pass
    with pytest.raises(ValueError, match="not a layer scope"):
        scope("attention")


# -- the monitor's spans in the profiler's trace ----------------------------------

def _repro_spans(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(jax_events.SPAN_PREFIX):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def test_regions_and_gc_pauses_are_profiler_spans(tmp_path):
    rmon.init(instrumenter="none", substrates=("metrics",), run_dir=str(tmp_path / "mon"))
    try:
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            with rmon.region("step", module="train"):
                with rmon.region("batch", module="train"):
                    gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        rmon.finalize()
    spans = {name: (s, e) for name, s, e in _repro_spans(str(tmp_path / "trace"))}
    assert set(spans) == {"repro/train/step", "repro/train/batch", "repro/gc"}
    (o0, o1), (i0, i1), (g0, g1) = spans["repro/train/step"], spans["repro/train/batch"], spans["repro/gc"]
    assert o0 <= i0 <= g0 <= g1 <= i1 <= o1


def test_no_span_is_built_with_the_profiler_off(tmp_path, monkeypatch):
    built = []

    class Counting(jax_events.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax_events, "TraceAnnotation", Counting)
    m = rmon.init(instrumenter="none", substrates=("metrics",), run_dir=str(tmp_path / "mon"))
    try:
        for _ in range(100):
            with rmon.region("step", module="train"):
                pass
        gc.collect()
        assert built == []
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            with rmon.region("step", module="train"):
                gc.collect()
        finally:
            jax.profiler.stop_trace()
        assert built == ["repro/train/step", "repro/gc"]
    finally:
        rmon.finalize()
    assert m.gc.span is None and m.gc._callback not in gc.callbacks


def test_memory_substrate_reads_the_measurement_gc_watcher(tmp_path):
    m = rmon.init(instrumenter="none", substrates=("memory",), run_dir=str(tmp_path / "mon"),
                  memory_period=0.01)
    try:
        assert m.substrate("memory").gc is m.gc
        assert sum(1 for cb in gc.callbacks if getattr(cb, "__self__", None) is m.gc) == 1
        gc.collect()
    finally:
        run = rmon.finalize()
    with open(os.path.join(run, "memory.json")) as fh:
        doc = json.load(fh)
    assert doc["gc"]["collections"] >= 1 and doc["gc"]["collections"] == m.gc.collections


# -- compile events as metrics ------------------------------------------------------

def _bridge_listeners():
    return [cb for cb in jax_monitoring.get_event_duration_listeners()
            if isinstance(getattr(cb, "__self__", None), jax_events.JaxBridge)]


_JAX_AFTER_START = textwrap.dedent("""
    import json, os, sys
    import repro.core as rmon
    rmon.init(instrumenter="none", substrates=("metrics",), run_dir=sys.argv[1])
    assert "jax" not in sys.modules
    import jax, jax.numpy as jnp
    from jax._src import monitoring
    with rmon.region("first", module="t"):  # the first region after the import
        jax.block_until_ready(jax.jit(lambda x: x * 5 - 2)(jnp.ones(3)))
    run = rmon.finalize()
    from repro.core.jax_events import JaxBridge
    left = [cb for cb in monitoring.get_event_duration_listeners()
            if isinstance(getattr(cb, "__self__", None), JaxBridge)]
    print(json.dumps({"left": len(left)}))
""")


@pytest.mark.parametrize("jax_imported", ["before_start", "after_start"])
def test_compile_events_become_metrics(tmp_path, jax_imported):
    run = str(tmp_path / "mon")
    if jax_imported == "before_start":
        rmon.init(instrumenter="none", substrates=("metrics",), run_dir=run)
        try:
            assert len(_bridge_listeners()) == 1
            jax.block_until_ready(jax.jit(lambda x: x * 7 + 3)(jnp.ones(5)))
        finally:
            rmon.finalize()
        left = len(_bridge_listeners())
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
        p = subprocess.run([sys.executable, "-c", _JAX_AFTER_START, run], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr
        left = json.loads(p.stdout.strip().splitlines()[-1])["left"]
    assert left == 0
    with open(os.path.join(run, "metrics.json")) as fh:
        doc = json.load(fh)
    for name in ("jax.compile.trace_s", "jax.compile.lower_s", "jax.compile.backend_s"):
        assert doc["metrics"][name]["count"] >= 1, name
        assert all(v >= 0 for _, v in doc["series"][name])


def test_persistent_cache_hit_records_cache_load(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    f = lambda x: jnp.sin(x) * 11.0 - 0.5
    try:
        jax.block_until_ready(jax.jit(f)(jnp.ones(7)))  # written to the cache
        jax.clear_caches()
        rmon.init(instrumenter="none", substrates=("metrics",), run_dir=str(tmp_path / "mon"))
        try:
            jax.block_until_ready(jax.jit(f)(jnp.ones(7)))  # read back from it
        finally:
            run = rmon.finalize()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    with open(os.path.join(run, "metrics.json")) as fh:
        doc = json.load(fh)["metrics"]
    assert doc["jax.compile.cache_load_s"]["count"] >= 1
    assert doc["jax.compile.backend_s"]["max"] >= doc["jax.compile.cache_load_s"]["max"]
