"""Unit tests for ``benchmarks/paper_headline.py``, the one writer of
``BENCH_paper.json``, and its paper-headline gates.

``check_paper_gates`` is a pure function of the ``BENCH_paper.json``
artifact dict, so the pass/fail semantics (and the stderr WARNING
surface CI greps) are testable without running any benchmark: a
synthetic failing section must exit non-zero, a passing one zero.
``main`` is driven over the same synthetic artifacts: its exit code is
the gates', and the file it writes carries the schema version.
"""

import importlib.util
import json
import pathlib
import sys

import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault(name, mod)
    spec.loader.exec_module(mod)
    return mod


paper_headline = _load("_paper_headline_under_test",
                       _ROOT / "benchmarks" / "paper_headline.py")


def _artifact(**over):
    """A minimal passing BENCH_paper.json artifact; keyword overrides
    replace whole sections."""
    art = {
        "dlwa": {"reduction_at_10pct": 0.86,
                 "occupancies": [0.1, 0.3], "n_zones": 4.0,
                 "traditional_dlwa": [10.0, 3.3],
                 "silent_dlwa": [1.36, 1.06],
                 "dlwa_reduction": [0.86, 0.68]},
        "wear": {"wear_reduction": 0.68, "occupancy": 0.3,
                 "n_zones": 8.0, "cycles": 8.0,
                 "traditional_erases": 2816.0, "silent_erases": 896.0},
        "exec": {"speedup": 3.14, "occupancy": 0.3, "n_zones": 8.0,
                 "cycles": 4.0, "traditional_s": 392.0,
                 "silent_s": 124.7, "host_pages": 162201.0},
        "recompiles": {"delta_total": 0.0, "entries": {}, "delta": {}},
        "meta": {"schema_version": paper_headline.SCHEMA_VERSION},
    }
    art.update(over)
    return art


def test_passing_artifact_exits_zero(capsys):
    assert paper_headline.check_paper_gates(_artifact()) == 0
    assert capsys.readouterr().err == ""


@pytest.mark.parametrize("section,bad,phrase", [
    ("dlwa", {"reduction_at_10pct": 0.79}, "DLWA reduction"),
    ("wear", {"wear_reduction": 0.0}, "no wear"),
    ("wear", {"wear_reduction": -0.1}, "no wear"),
    ("exec", {"speedup": 1.0}, "execution speedup"),
    ("exec", {"speedup": 0.8}, "execution speedup"),
    ("recompiles", {"delta_total": 2.0}, "recompiled"),
])
def test_failing_section_exits_nonzero(capsys, section, bad, phrase):
    art = _artifact()
    art[section] = {**art[section], **bad}
    assert paper_headline.check_paper_gates(art) == 1
    err = capsys.readouterr().err
    assert err.startswith("WARNING:") and phrase in err


def test_gate_floors_are_inclusive_exclusive_as_documented(capsys):
    """The DLWA floor is inclusive (>= 80%); wear and speedup floors
    are strict (> 0, > 1x)."""
    art = _artifact()
    floor = paper_headline.PAPER_DLWA_REDUCTION_FLOOR
    art["dlwa"]["reduction_at_10pct"] = floor
    assert paper_headline.check_paper_gates(art) == 0
    capsys.readouterr()


def test_every_failed_gate_gets_its_own_warning(capsys):
    art = _artifact(
        dlwa={**_artifact()["dlwa"], "reduction_at_10pct": 0.1},
        wear={**_artifact()["wear"], "wear_reduction": -0.5},
        exec={**_artifact()["exec"], "speedup": 0.5},
        recompiles={"delta_total": 3.0})
    assert paper_headline.check_paper_gates(art) == 1
    warnings = [ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("WARNING:")]
    assert len(warnings) == 4


def test_paper_report_feeds_the_gates_end_to_end(capsys):
    """`headline.paper_report` at tiny geometry produces exactly the
    artifact surface `check_paper_gates` consumes (whether tiny
    geometry clears the zn540-calibrated floors is not the point), and
    its own recompile probe must read zero."""
    from repro.core import headline
    from repro.core.geometry import FlashGeometry, ZoneGeometry

    flash = FlashGeometry(n_channels=4, ways_per_channel=1,
                          blocks_per_lun=8, pages_per_block=4,
                          page_bytes=4096)
    rep = headline.paper_report(
        flash, ZoneGeometry(parallelism=4, n_segments=2),
        occupancies=(0.1, 0.5), dlwa_zones=2, wear_zones=2,
        wear_cycles=2, exec_cycles=1, max_active=3)
    assert rep["recompiles"]["delta_total"] == 0
    assert rep["dlwa"]["reduction_at_10pct"] \
        == rep["dlwa"]["dlwa_reduction"][0]
    assert rep["wear"]["traditional_erases"] > rep["wear"]["silent_erases"]
    assert rep["exec"]["speedup"] > 1.0
    assert paper_headline.check_paper_gates(rep) in (0, 1)
    capsys.readouterr()


def test_build_headline_engine_rejects_half_specified_geometry():
    from repro.core import headline
    from repro.core.geometry import zn540

    flash, zone = zn540()
    with pytest.raises(ValueError, match="together"):
        headline.build_headline_engine(flash, None)
    with pytest.raises(ValueError, match="together"):
        headline.build_headline_engine(None, zone)


def test_repo_artifact_passes_the_gates(capsys):
    """The checked-in BENCH_paper.json must clear its own gates."""
    path = _ROOT / "BENCH_paper.json"
    if not path.exists():
        pytest.skip("BENCH_paper.json not generated in this checkout")
    assert paper_headline.check_paper_gates(json.loads(path.read_text())) == 0
    capsys.readouterr()


@pytest.mark.parametrize("reduction,rc", [(0.86, 0), (0.79, 1)],
                         ids=["pass", "fail"])
def test_paper_headline_exits_by_the_gates(monkeypatch, tmp_path, capsys,
                                           reduction, rc):
    from repro.core import headline

    art = _artifact()
    art["dlwa"]["reduction_at_10pct"] = reduction
    del art["meta"]
    monkeypatch.setattr(headline, "paper_report", lambda **_kw: art)
    out = tmp_path / "BENCH_paper.json"
    assert paper_headline.main(["--out", str(out)]) == rc
    written = json.loads(out.read_text())
    assert written["dlwa"]["reduction_at_10pct"] == reduction
    assert written["meta"]["schema_version"] == paper_headline.SCHEMA_VERSION
    assert ("DLWA reduction" in capsys.readouterr().err) == bool(rc)
