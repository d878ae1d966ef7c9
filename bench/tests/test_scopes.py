"""The reduction by layer scope against the small trace recorded on one TPU
v5e (tests/data/sample.xplane.pb: program ``jit__lambda``, three runs of
``copy-start``, ``copy-done``, ``convolution_tanh_fusion``, ``fusion``),
with HLO text written here to name its operations; and the compile reader."""

import json
import sys
import types
from pathlib import Path

import pytest

from harness import common, scopes

SAMPLE = str(Path(__file__).resolve().parent / "data" / "sample.xplane.pb")
NAMES = frozenset({"embed", "layer_stack", "attn_core", "mlp", "optimizer"})
SELF_NS = {"copy-start": 40, "copy-done": 9, "convolution_tanh_fusion": 269859, "fusion": 272639}

HLO = """HloModule jit__lambda, entry_computation_layout={(bf16[2048,2048]{1,0})->bf16[2048,2048]{1,0}}

ENTRY %main.1 (a: bf16[2048,2048]) -> bf16[2048,2048] {
  %copy-start = (bf16[2048,2048]{1,0}, bf16[2048,2048]{1,0}, u32[]) copy-start(bf16[2048,2048]{1,0} %a), metadata={op_name="jit(f)/attn_core/copy"}
  %copy-done = bf16[2048,2048]{1,0} copy-done((bf16[2048,2048]{1,0}, bf16[2048,2048]{1,0}, u32[]) %copy-start)
  %convolution_tanh_fusion = bf16[2048,2048]{1,0} fusion(bf16[2048,2048]{1,0} %copy-done), kind=kOutput, calls=%f1, metadata={op_name="jit(f)/jvp(mlp)/checkpoint/rematted_computation/tanh" source_file="m.py" source_line=3}
  ROOT %fusion = bf16[2048,2048]{1,0} fusion(bf16[2048,2048]{1,0} %convolution_tanh_fusion), kind=kOutput, calls=%f2, metadata={op_name="jit(f)/transpose(jvp(layer_stack))/while/body/dot_general"}
}
"""


def test_sample_by_scope_adds_up():
    r = scopes.reduce(SAMPLE, HLO, NAMES)
    assert r["module"] == "jit__lambda"
    assert r["not_in_hlo"] == 0 and r["other_programs"] == 0
    assert r["scope_ns"] == {"attn_core": 40, "unscoped": 9,
                             "mlp": SELF_NS["convolution_tanh_fusion"],
                             "layer_stack": SELF_NS["fusion"]}
    assert r["total_ns"] == sum(SELF_NS.values()) == sum(r["scope_ns"].values())
    assert r["recompute_s"] == pytest.approx(SELF_NS["convolution_tanh_fusion"] * 1e-9)
    assert r["recompute_scope_s"] == {"mlp": pytest.approx(SELF_NS["convolution_tanh_fusion"] * 1e-9)}
    assert r["unscoped_ops"] == [["copy-done", 9e-9]]
    # no monitor spans in this trace: idle time goes to the benchmark's spans
    idle = r["idle_by_span"]
    assert set(idle) <= {"bench.sleep", "bench.step", "outside spans"}
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)
    assert idle["bench.sleep"] > 0.060
    start, seconds, split = r["long_gaps"][0]
    assert 0 < start < r["window_s"] and seconds == pytest.approx(sum(split.values()))
    m = scopes.per_step(r, 3)
    assert m["device_ms.mlp"] == pytest.approx(1e3 * SELF_NS["convolution_tanh_fusion"] * 1e-9 / 3)
    assert m["device_unscoped"] == pytest.approx(100 * 9e-9 / r["busy_s"])


def test_operations_the_hlo_lacks_or_of_another_program():
    lacking = "\n".join(line for line in HLO.splitlines() if "ROOT %fusion" not in line)
    r = scopes.reduce(SAMPLE, lacking, NAMES)
    assert r["not_in_hlo"] == 3  # one event in each of the three runs
    assert r["missing_ops"] == [["fusion", pytest.approx(SELF_NS["fusion"] * 1e-9)]]
    assert r["scope_ns"]["unscoped"] == 9 + SELF_NS["fusion"]
    other = scopes.reduce(SAMPLE, HLO.replace("HloModule jit__lambda", "HloModule jit_step"), NAMES)
    assert other["other_programs"] == 12 and other["total_ns"] == 0


@pytest.mark.parametrize("op_name,layer", [
    ("jit(train_step)/jvp(layer_stack)/while/body/closed_call/ssd_proj/ssd_scan/mul", "layer_stack"),
    ("jit(train_step)/jvp(layer_stack)/while/body/closed_call/attn_core/div", "attn_core"),
    ("jit(train_step)/transpose(jvp(embed))/jit(_take)/broadcast_in_dim", "embed"),
    ("jit(train_step)/optimizer/jit(mlp)/sqrt", "optimizer"),
    ("params['embed']", None),
    ("", None),
])
def test_innermost_scope(op_name, layer):
    assert scopes.layer_of(op_name, NAMES) == layer


def test_idle_split_by_innermost_host_span():
    host = [("repro/train/train_step", 0, 60), ("repro/gc", 20, 40)]
    spans = [("bench.window", 0, 200), ("bench.step", 0, 60), ("bench.record", 60, 100)]
    assert scopes.split_idle([(0, 100), (150, 160)], host, spans) == {
        "repro/train/train_step": 40, "repro/gc": 20, "bench.record": 40, "outside spans": 10}


def test_program_table_is_found():
    assert "ssd_scan" in scopes.layer_scopes()


def _read_compile(monkeypatch, tmp_path, series):
    import importlib.util

    run = tmp_path / "bench_out" / "cell.a" / "monitor"
    run.mkdir(parents=True)
    (run / "metrics.json").write_text(json.dumps({"series": series}))
    monkeypatch.setattr(common, "OUT", tmp_path / "bench_out")
    monkeypatch.setattr(sys, "argv", ["bench/run.py", "--workload", "cell.a", "--seed", "1"])
    monkeypatch.setitem(sys.modules, "__main__", types.SimpleNamespace(T0=10.0))
    path = Path(common.BENCH) / "metrics" / "setup_compile_s.py"
    spec = importlib.util.spec_from_file_location("setup_compile_s", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # window from 10 + 5 s to 10 + 5 + 25 s on the host clock
    return mod.read({"kind": "train", "setup_s": 5.0, "window_s": 25.0})


def test_setup_compile_reads_samples_before_the_window(monkeypatch, tmp_path, capsys):
    series = {"jax.compile.trace_s": [[11e9, 0.5], [16e9, 0.25]],
              "jax.compile.backend_s": [[12e9, 2.0], [14e9, None]],
              "jax.compile.cache_load_s": [[12e9, 1.5]],
              "train.loss": [[20e9, 3.0]]}
    assert _read_compile(monkeypatch, tmp_path, series) == 2.5
    assert "compile samples in the window: 1" in capsys.readouterr().err


def test_setup_compile_reads_nothing_without_compile_samples(monkeypatch, tmp_path):
    assert _read_compile(monkeypatch, tmp_path, {"train.loss": [[20e9, 3.0]]}) is None
