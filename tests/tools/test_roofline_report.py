"""tools/roofline_report.py: the ranked fusion-headroom ledger CLI.

Pins the ranking contract (most headroom first, ``_unattributed`` always
last), the honest-diagnostics exits (1 on attribution-off logs naming
FL4HEALTH_STAGE_ATTRIBUTION=0, 2 on unreadable log/trace), the --json
shape, and the --trace fold-in of measured per-stage device time.
"""

import json
import lzma
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO / "tools"))

import roofline_report  # noqa: E402

pytestmark = pytest.mark.roofline


def _stage(program, stage, flops, headroom, **kw):
    base = {"ts": 0.0, "event": "stage", "program": program,
            "stage": stage, "flops": flops, "transcendentals": 0.0,
            "bytes_accessed": 1e6, "ops": 4, "custom_calls": 0,
            "fusion_headroom_bytes": headroom}
    base.update(kw)
    return base


def _log(tmp_path, events, name="metrics.jsonl"):
    path = tmp_path / name
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return str(path)


def _staged_log(tmp_path):
    return _log(tmp_path, [
        {"ts": 0.0, "event": "round", "round": 1, "compiles": 1},
        _stage("fit_round", "server_update", 1e6, 4e5,
               intensity_flops_per_byte=1.0),
        _stage("fit_round", "local_train", 9e9, 2e6,
               intensity_flops_per_byte=150.0, bound="compute",
               ridge_flops_per_byte=224.0, fusion_headroom_frac=0.3),
        _stage("fit_round", "_unattributed", 5e10, 9e9),
    ])


class TestRanking:
    def test_headroom_desc_unattributed_last(self):
        ranked = roofline_report.rank_stages([
            _stage("p", "_unattributed", 1e12, 1e12),
            _stage("p", "dp_clip", 1.0, 100.0),
            _stage("p", "local_train", 1.0, 900.0),
        ])
        assert [r["stage"] for r in ranked] == [
            "local_train", "dp_clip", "_unattributed"
        ]

    def test_flops_break_headroom_ties(self):
        ranked = roofline_report.rank_stages([
            _stage("p", "a", 10.0, None),
            _stage("p", "b", 99.0, None),
        ])
        assert [r["stage"] for r in ranked] == ["b", "a"]


class TestCli:
    def test_table_ranked_with_bound_column(self, tmp_path, capsys):
        rc = roofline_report.main([_staged_log(tmp_path)])
        assert rc == 0
        out = capsys.readouterr().out
        lines = out.splitlines()
        assert lines[0].split()[:3] == ["rank", "program", "stage"]
        body = [ln for ln in lines[2:] if ln.strip()]
        # local_train (2e6 headroom) outranks server_update (4e5);
        # _unattributed sinks to the bottom despite its huge numbers
        assert "local_train" in body[0] and "compute" in body[0]
        assert "server_update" in body[1]
        assert "_unattributed" in body[2]

    def test_json_emits_ranked_ledger(self, tmp_path, capsys):
        rc = roofline_report.main([_staged_log(tmp_path), "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert [r["stage"] for r in doc["ledger"]] == [
            "local_train", "server_update", "_unattributed"
        ]
        # unknown-roofline rows never grow fabricated fields
        assert "bound" not in doc["ledger"][1]

    def test_unknown_chip_footer_not_fabricated(self, tmp_path, capsys):
        # no row carries a bound -> the footer says so explicitly
        path = _log(tmp_path, [_stage("fit_round", "local_train",
                                      1e6, 1e3)])
        assert roofline_report.main([path]) == 0
        out = capsys.readouterr().out
        assert "bound classification unavailable" in out

    def test_attribution_off_log_exits_1_with_hint(self, tmp_path, capsys):
        path = _log(tmp_path, [
            {"ts": 0.0, "event": "round", "round": 1, "compiles": 1},
        ])
        rc = roofline_report.main([path])
        assert rc == 1
        err = capsys.readouterr().err
        assert "no 'stage' events" in err
        assert "FL4HEALTH_STAGE_ATTRIBUTION=0" in err

    def test_missing_log_exits_2(self, tmp_path, capsys):
        rc = roofline_report.main([str(tmp_path / "nope.jsonl")])
        assert rc == 2
        assert "cannot read" in capsys.readouterr().err


# device self milliseconds under local_train and server_update in the
# recorded capture, read by hand (the Mosaic flash calls are 2.8 of the 3.4)
MEASURED = (3.406, 0.0537)


class TestTraceFold:
    """``--trace`` over a real TPU capture: the recorded toy flash cell
    (benchmarks/fixtures), where the scopes sit in the op metadata."""

    def _profile_dir(self, tmp_path):
        folder = tmp_path / "xprof" / "plugins" / "profile" / "run1"
        folder.mkdir(parents=True)
        fixture = REPO / "benchmarks" / "fixtures" / "trace_small.xplane.pb.xz"
        with lzma.open(fixture) as f:
            (folder / "host.xplane.pb").write_bytes(f.read())
        return tmp_path / "xprof"

    def test_measured_ms_folds_into_ledger(self, tmp_path, capsys):
        log = _log(tmp_path, [
            _stage("fit_round", "local_train", 9e9, 2e6),
            _stage("fit_round", "server_update", 1e6, 4e5),
            _stage("fit_round", "dp_clip", 1e6, 1e5),
        ])
        rc = roofline_report.main([
            log, "--trace", str(self._profile_dir(tmp_path)), "--json",
        ])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        by = {r["stage"]: r for r in doc["ledger"]}
        # the fixture's whole capture, device self time by scope
        assert by["local_train"]["measured_ms"] == pytest.approx(MEASURED[0], rel=1e-3)
        assert by["server_update"]["measured_ms"] == pytest.approx(MEASURED[1], rel=1e-3)
        # stages absent from the capture stay honest: no fake zero
        assert "measured_ms" not in by["dp_clip"]

    def test_the_xplane_file_itself_and_the_table_column(self, tmp_path, capsys):
        xplane = next(self._profile_dir(tmp_path).rglob("*.xplane.pb"))
        rc = roofline_report.main([_staged_log(tmp_path), "--trace", str(xplane)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "measured_ms" in out.splitlines()[0]

    @pytest.mark.parametrize("content", [None, b"{torn"])
    def test_missing_or_corrupt_trace_exits_2(self, tmp_path, capsys, content):
        path = tmp_path / "bad.xplane.pb"
        if content is not None:
            path.write_bytes(content)
        rc = roofline_report.main([_staged_log(tmp_path),
                                   "--trace", str(path)])
        assert rc == 2
        assert "cannot read trace" in capsys.readouterr().err


class TestLatestWins:
    def test_rerun_in_same_log_dedupes_to_latest(self, tmp_path, capsys):
        path = _log(tmp_path, [
            _stage("fit_round", "local_train", 1.0, 1.0),
            _stage("fit_round", "local_train", 7e9, 3e6),
        ])
        rc = roofline_report.main([path, "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        (row,) = doc["ledger"]
        assert row["flops"] == 7e9
