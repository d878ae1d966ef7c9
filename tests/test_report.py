"""Unified HTML report (repro.core.report) tests.

Covers the acceptance contract: a run dir yields one self-contained
report.html (no network references) joining time + memory + governor
sections, ``--diff`` renders regression deltas, and the embedded JSON
payload round-trips byte-exactly against the data model.
"""

import json
import os

import pytest

import repro.core as rmon
from repro.core.analysis import MissingArtifact, main as analysis_main
from repro.core.report import (
    REPORT_SCHEMA_VERSION,
    build_report,
    extract_payload,
    render_report,
    write_report,
)
from repro.core.schema import SCHEMA_KEY
from repro.core.topology import ProcessTopology


def _leaf(n):
    return sum(range(n))


def _work(iters):
    for _ in range(iters):
        _leaf(400)


def _make_run(tmp_path, name, iters=30, rank=None, world=1, **cfg):
    d = str(tmp_path / name)
    kwargs = dict(
        instrumenter="profile",
        substrates=("profiling", "tracing", "metrics", "memory"),
        run_dir=d,
        experiment=name,
        memory_period=0.01,
    )
    if rank is not None:
        kwargs["topology"] = ProcessTopology(rank=rank, world_size=world)
    kwargs.update(cfg)
    rmon.init(**kwargs)
    with rmon.region("phase"):
        _work(iters)
    rmon.metric("test.value", float(iters))
    rmon.finalize()
    return d


# -- data model ---------------------------------------------------------------


def test_artifacts_carry_schema_version(tmp_path):
    run = _make_run(tmp_path, "stamped")
    for artifact in ("profile.json", "memory.json", "metrics.json", "meta.json"):
        with open(os.path.join(run, artifact)) as fh:
            doc = json.load(fh)
        assert doc[SCHEMA_KEY] == REPORT_SCHEMA_VERSION, artifact


def test_build_report_joins_time_and_memory(tmp_path):
    run = _make_run(tmp_path, "joined")
    doc = build_report(run)
    assert doc[SCHEMA_KEY] == REPORT_SCHEMA_VERSION
    by_name = {r["region"]: r for r in doc["regions"]}
    leaf = next(r for n, r in by_name.items() if "_leaf" in n)
    # time columns from profile.json
    assert leaf["visits"] > 0 and leaf["excl_ns"] > 0
    # memory columns joined from memory.json (attribution may land on any
    # region, but the columns must be populated for at least one row)
    assert any(
        r["alloc_bytes"] is not None and r["alloc_bytes"] > 0
        for r in doc["regions"]
    )
    assert doc["memory"]["rss_peak_bytes"] > 0
    assert "test.value" in doc["metrics"]
    assert any(k.startswith("mem.") for k in doc["timelines"])
    # no governor ran
    assert doc["governor"] is None and doc["merge"] is None and doc["diff"] is None


def test_build_report_missing_dir_raises(tmp_path):
    empty = tmp_path / "nothing"
    empty.mkdir()
    with pytest.raises(MissingArtifact):
        build_report(str(empty))


# -- rendering ----------------------------------------------------------------


def test_report_payload_roundtrip(tmp_path):
    run = _make_run(tmp_path, "roundtrip")
    doc = build_report(run)
    page = render_report(doc)
    # byte-exact after a JSON normalization pass (tuples -> lists etc.)
    assert extract_payload(page) == json.loads(json.dumps(doc))


def test_report_self_contained(tmp_path):
    run = _make_run(tmp_path, "selfcontained")
    page = open(write_report(run)).read()
    for needle in ("https://", "http://", "cdn.", "@import", 'src="//'):
        assert needle not in page
    # joined sections actually rendered
    assert "Regions" in page and "Timelines" in page
    assert page.count("<svg") >= 1
    assert 'table class="sortable"' in page


def test_report_escapes_hostile_region_names(tmp_path):
    d = str(tmp_path / "hostile")
    rmon.init(instrumenter="none", substrates=("profiling",), run_dir=d,
              experiment="hostile")
    with rmon.region('</script><b>x'):
        _leaf(10)
    rmon.finalize()
    page = open(write_report(d)).read()
    # The hostile name must appear nowhere unescaped — neither in the HTML
    # body nor inside the embedded JSON payload.
    assert "</script><b>x" not in page
    assert extract_payload(page)  # payload still parses


def test_governor_section(tmp_path):
    run = _make_run(tmp_path, "governed", substrates=("profiling",), budget=0.5)
    doc = build_report(run)
    assert doc["governor"] is not None
    assert doc["governor"]["budget"] == 0.5
    page = render_report(doc)
    assert "Overhead governor" in page


# -- diff mode ----------------------------------------------------------------


def test_report_diff_mode(tmp_path):
    base = _make_run(tmp_path, "base", iters=5)
    cur = _make_run(tmp_path, "cur", iters=400)
    doc = build_report(cur, diff_base=base)
    rows = doc["diff"]["profile"]
    assert rows, "diff must produce rows"
    top = rows[0]
    assert top["delta_ns"] > 0  # cur is slower
    page = render_report(doc)
    assert "Run-vs-run diff" in page
    assert extract_payload(page)["diff"]["base"] == base


# -- merge root ---------------------------------------------------------------


def test_report_merge_root_heatmap(tmp_path):
    from repro.core.merge import merge_runs

    a = _make_run(tmp_path, "exp-r0", iters=10, rank=0, world=2)
    b = _make_run(tmp_path, "exp-r1", iters=80, rank=1, world=2)
    summary = merge_runs([a, b], str(tmp_path / "merged_trace.json"))
    assert summary[SCHEMA_KEY] == REPORT_SCHEMA_VERSION
    profile = summary["profile"]
    assert profile["ranks"] == [0, 1]
    assert profile["regions"] and len(profile["excl_ns"]) == len(profile["regions"])
    assert profile["imbalance"], "two unequal ranks must show imbalance"
    with open(tmp_path / "merged_trace_summary.json", "w") as fh:
        json.dump(summary, fh)
    page = open(write_report(str(tmp_path))).read()
    assert "Cross-rank view" in page
    assert "Per-region exclusive time by rank" in page
    payload = extract_payload(page)
    assert payload["merge"]["profile"]["ranks"] == [0, 1]


# -- CLI + finalize wiring ----------------------------------------------------


def test_analysis_report_cli(tmp_path, capsys):
    run = _make_run(tmp_path, "cli")
    out = str(tmp_path / "custom.html")
    assert analysis_main(["report", run, "--out", out]) == 0
    assert os.path.exists(out)
    assert analysis_main(["report", str(tmp_path / "missing")]) == 2
    assert "error:" in capsys.readouterr().err


def test_analysis_report_smoke(tmp_path):
    out = str(tmp_path / "smoke.html")
    assert analysis_main(["report", "--smoke", "--out", out]) == 0
    assert os.path.exists(out)


def test_measurement_report_flag(tmp_path):
    run = _make_run(tmp_path, "atfinalize", report=True)
    path = os.path.join(run, "report.html")
    assert os.path.exists(path)
    payload = extract_payload(open(path).read())
    assert payload["regions"]


def test_report_config_env_roundtrip():
    from repro.core import MeasurementConfig

    cfg = MeasurementConfig(report=True)
    env = cfg.to_env()
    assert env["REPRO_MONITOR_REPORT"] == "1"
    assert MeasurementConfig.from_env(env).report is True
    assert MeasurementConfig.from_env({}).report is False


def test_launch_train_report_flag(tmp_path, monkeypatch):
    """`launch.train --report` outside a scorep session starts its own
    measurement and emits report.html at finalize (training stubbed out —
    the glue, not the model, is under test)."""
    pytest.importorskip("jax")
    import repro.launch.train as lt

    monkeypatch.setattr(lt, "train", lambda cfg, **kw: {"final_loss": 1.0})
    monkeypatch.setattr(lt, "get_smoke_config", lambda arch: object())
    monkeypatch.setattr(lt, "enable_compile_cache", lambda: None)
    monkeypatch.chdir(tmp_path)
    assert lt.main(["--arch", "stub", "--smoke", "--report"]) == 0
    runs = list((tmp_path / "repro-traces").glob("train-*"))
    assert runs, "launcher must have created its own run dir"
    assert (runs[0] / "report.html").exists()


def test_launch_train_report_flag_under_scorep(tmp_path, monkeypatch):
    """`launch.train --report` inside an active measurement (the scorep
    bootstrap case) flips the active config's report flag instead of
    nesting a second measurement."""
    pytest.importorskip("jax")
    import repro.launch.train as lt

    monkeypatch.setattr(lt, "train", lambda cfg, **kw: {"final_loss": 1.0})
    monkeypatch.setattr(lt, "get_smoke_config", lambda arch: object())
    monkeypatch.setattr(lt, "enable_compile_cache", lambda: None)
    d = str(tmp_path / "outer")
    rmon.init(instrumenter="profile", substrates=("profiling",), run_dir=d,
              experiment="outer")
    try:
        assert lt.main(["--arch", "stub", "--smoke", "--report"]) == 0
        assert rmon.active() is not None, "launcher must not finalize a measurement it doesn't own"
        assert rmon.active().config.report is True
    finally:
        rmon.finalize()
    assert os.path.exists(os.path.join(d, "report.html"))


def test_decimate_never_exceeds_cap():
    from repro.core.report.model import decimate

    for n in (479, 480, 481, 960, 1000):
        series = [[i, float(i)] for i in range(n)]
        out = decimate(series, max_points=240)
        assert len(out) <= 240, n
        assert out[-1] == series[-1], "final point must survive decimation"
        assert out[0] == series[0]


def test_newer_schema_version_is_reported(tmp_path):
    import warnings as warnings_mod

    run = _make_run(tmp_path, "fromfuture")
    prof_path = os.path.join(run, "profile.json")
    with open(prof_path) as fh:
        doc = json.load(fh)
    doc[SCHEMA_KEY] = REPORT_SCHEMA_VERSION + 1
    with open(prof_path, "w") as fh:
        json.dump(doc, fh)
    with warnings_mod.catch_warnings(record=True) as caught:
        warnings_mod.simplefilter("always")
        build_report(run)
    assert any("newer than this reader" in str(w.message) for w in caught)


def test_diff_mode_without_profiling_substrate(tmp_path):
    """Diff mode degrades per-half: runs recorded without profiling still
    report, with the profile half null and the memory half populated."""

    def mem_run(name):
        d = str(tmp_path / name)
        rmon.init(instrumenter="none", substrates=("metrics", "memory"),
                  run_dir=d, experiment=name, memory_period=0.01)
        _work(20)
        rmon.finalize()
        return d

    base, cur = mem_run("mbase"), mem_run("mcur")
    doc = build_report(cur, diff_base=base)
    assert doc["diff"]["profile"] is None
    assert doc["diff"]["memory"] is not None
    render_report(doc)  # must not raise


def test_all_nan_series_does_not_claim_timeline_slot(tmp_path):
    d = str(tmp_path / "nans")
    rmon.init(instrumenter="none", substrates=("metrics",), run_dir=d,
              experiment="nans")
    for _ in range(4):
        rmon.metric("bad.loss", float("nan"))
        rmon.metric("good.loss", 1.0)
    rmon.finalize()
    doc = build_report(d)
    assert "bad.loss" not in doc["timelines"]
    assert "good.loss" in doc["timelines"]


def test_smoke_report_cleans_up_run_dir(tmp_path):
    import glob as glob_mod

    from repro.core.analysis import smoke_report

    out = str(tmp_path / "smoke.html")
    before = set(glob_mod.glob(os.path.join(tempfile_dir(), "repro-report-smoke-*")))
    assert smoke_report(out_path=out) == out
    after = set(glob_mod.glob(os.path.join(tempfile_dir(), "repro-report-smoke-*")))
    assert after == before, "smoke must remove its throwaway run dir"


def tempfile_dir():
    import tempfile

    return tempfile.gettempdir()
