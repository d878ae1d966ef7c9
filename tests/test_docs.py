"""Documentation surface tests — docs can't drift from the code.

Three gates:
  * docs/CLI.md must be byte-identical to a fresh render of the live
    argparse parsers (repro.core.clidoc).
  * every public name in ``repro.core.__all__`` must carry a real
    docstring (or, for plain data objects, live in a documented module).
  * README.md / docs/ARTIFACTS.md must keep documenting the artifacts and
    flows they advertise (artifact names, schema-version policy, the
    quickstart command CI executes).
"""

import inspect
import os

import pytest

import repro.core as rmon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    path = os.path.join(REPO, *parts)
    assert os.path.exists(path), f"missing documentation file {path}"
    with open(path, encoding="utf-8") as fh:
        return fh.read()


# -- generated CLI docs -------------------------------------------------------


def test_cli_md_in_sync():
    pytest.importorskip("jax")  # the launch parsers import jax at module level
    from repro.core.clidoc import generate

    on_disk = _read("docs", "CLI.md")
    assert on_disk == generate(), (
        "docs/CLI.md is stale — regenerate with "
        "`PYTHONPATH=src python -m repro.core.clidoc`"
    )


# -- docstring coverage on the public API -------------------------------------


def test_public_api_docstrings():
    missing = []
    for name in rmon.__all__:
        obj = getattr(rmon, name)
        if inspect.isclass(obj) or inspect.isfunction(obj):
            doc = inspect.getdoc(obj)
            if not doc or len(doc) < 20:
                missing.append(name)
        else:
            # Plain data objects (registries, constants) can't carry their
            # own docstring — the package module exposing them must be
            # documented instead (repro.core always is; this guards against
            # future undocumented data exports).
            if not (rmon.__doc__ or "").strip():
                missing.append(name)
    assert not missing, f"public API names lacking docstrings: {missing}"


def test_artifact_contract_module_docstrings():
    """The modules owning artifact schemas must state their contracts."""
    import repro.core.analysis
    import repro.core.governor
    import repro.core.measurement
    import repro.core.memsys.substrate
    import repro.core.merge
    import repro.core.report
    import repro.core.schema
    import repro.core.substrates

    for module, needle in [
        (repro.core.measurement, "region"),
        (repro.core.substrates, "profile.json"),
        (repro.core.memsys.substrate, "memory.json"),
        (repro.core.governor, "governor.json"),
        (repro.core.merge, "merge"),
        (repro.core.report, "report"),
        (repro.core.schema, "report_schema_version"),
        (repro.core.analysis, "exit code 2"),
    ]:
        doc = module.__doc__ or ""
        assert len(doc) > 100, f"{module.__name__} needs a contract docstring"
        assert needle in doc, f"{module.__name__} docstring must mention {needle!r}"


# -- hand-written docs keep their promises ------------------------------------


def test_artifacts_md_documents_every_artifact():
    doc = _read("docs", "ARTIFACTS.md")
    for artifact in (
        "profile.json",
        "memory.json",
        "metrics.json",
        "governor.json",
        "meta.json",
        "defs.json",
        "merged_trace_summary.json",
        "static_plan.json",
        "report.html",
        "report_schema_version",
    ):
        assert artifact in doc, f"docs/ARTIFACTS.md must document {artifact}"
    from repro.core.schema import REPORT_SCHEMA_VERSION

    assert f"version is **{REPORT_SCHEMA_VERSION}**" in doc, (
        "docs/ARTIFACTS.md must state the current report_schema_version "
        "(update the doc when bumping repro.core.schema.REPORT_SCHEMA_VERSION)"
    )


def test_artifacts_md_documents_profiler_spans_and_compile_metrics():
    from repro.core.jax_events import COMPILE_EVENTS, LAYER_SCOPES, SPAN_PREFIX
    from repro.core.memsys.poller import GC_SPAN

    doc = _read("docs", "ARTIFACTS.md")
    for needle in [*COMPILE_EVENTS, *COMPILE_EVENTS.values(), *LAYER_SCOPES, GC_SPAN,
                   f"{SPAN_PREFIX}<module>/<name>"]:
        assert f"`{needle}`" in doc, f"docs/ARTIFACTS.md must document {needle!r}"


def test_readme_advertises_executable_flows():
    readme = _read("README.md")
    # The quickstart command CI actually executes, verbatim.
    assert "examples/quickstart.py" in readme
    assert "repro.scorep" in readme
    assert "analysis report" in readme
    # Links into the docs tree.
    assert "docs/ARTIFACTS.md" in readme and "docs/CLI.md" in readme
