"""tools/roofline_report.py: measured device time per ``fl_stage::`` scope,
and ``local_train``'s by ``fl_layer::`` part and pass.

One mode: a profile directory or an ``.xplane.pb`` in, ``stage -> device
self ms`` and ``part -> pass -> ms`` out (tables or ``--json``), exit 2 on a
capture that is missing or torn. Read over real TPU captures: the recorded
toy flash cell and the toy adapter cell (benchmarks/fixtures), where the
scopes sit in the op metadata.
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

# device self milliseconds under local_train and server_update in the
# recorded capture, read by hand (the Mosaic flash calls are 2.8 of the 3.4)
MEASURED = (3.406, 0.0537)


def _profile_dir(tmp_path, name="trace_small"):
    folder = tmp_path / "xprof" / "plugins" / "profile" / "run1"
    folder.mkdir(parents=True)
    fixture = REPO / "benchmarks" / "fixtures" / f"{name}.xplane.pb.xz"
    with lzma.open(fixture) as f:
        (folder / "host.xplane.pb").write_bytes(f.read())
    return tmp_path / "xprof"


class TestTraceFold:
    def test_json_of_a_profile_dir(self, tmp_path, capsys):
        rc = roofline_report.main([str(_profile_dir(tmp_path)), "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        by = out["measured_ms"]
        # the fixture's whole capture, device self time by scope
        assert by["local_train"] == pytest.approx(MEASURED[0], rel=1e-3)
        assert by["server_update"] == pytest.approx(MEASURED[1], rel=1e-3)
        # a stage the capture never ran has no row: no fake zero
        assert set(by) == {"local_train", "server_update", "_unattributed"}
        # that program had no fl_layer:: scope: every op of local_train once,
        # and all of it under no part; the four passes are the stage's time
        parts = out["layer_pass_ms"]
        assert set(parts) == {"_total", "_unscoped"}
        assert parts["_total"] == parts["_unscoped"]
        assert set(parts["_total"]) == {"forward", "recompute", "backward",
                                        "update"}
        assert sum(parts["_total"].values()) == pytest.approx(
            by["local_train"])

    def test_table_of_the_xplane_file_itself(self, tmp_path, capsys):
        xplane = next(_profile_dir(tmp_path).rglob("*.xplane.pb"))
        rc = roofline_report.main([str(xplane)])
        assert rc == 0
        lines = capsys.readouterr().out.splitlines()
        assert lines[0].split() == ["stage", "device_ms", "share"]
        body = [ln.split() for ln in lines[2:lines.index("")]]
        # most device time first; the shares are of the capture's busy time
        assert body[0][0] == "local_train"
        assert float(body[0][1]) == pytest.approx(MEASURED[0], rel=1e-3)
        assert [float(r[1]) for r in body] == sorted(
            (float(r[1]) for r in body), reverse=True)
        assert sum(float(r[2].rstrip("%")) for r in body) == pytest.approx(
            100.0, abs=0.2)

    def test_the_part_and_pass_table_of_the_adapter_capture(self, tmp_path,
                                                            capsys):
        """Under the stage table: ``local_train`` by part and pass, parts by
        their time, what no part holds and the whole last."""
        rc = roofline_report.main(
            [str(_profile_dir(tmp_path, "trace_jamba_small"))])
        assert rc == 0
        lines = capsys.readouterr().out.splitlines()
        table = lines[lines.index("") + 1:]
        assert table[0].startswith("local_train by part and pass")
        assert table[1].split() == ["part", "forward", "recompute",
                                    "backward", "update", "evaluate"]
        rows = {r[0]: r[1:] for r in (ln.split() for ln in table[3:])}
        assert list(rows) == ["mamba_mixer", "ssm_scan", "attention",
                              "_unscoped", "_total"]
        # the scan is a part of the mixer, pass by pass; that program's
        # evaluation had no stage
        for col in range(3):
            assert 0 < float(rows["ssm_scan"][col]) < float(
                rows["mamba_mixer"][col]) < float(rows["_total"][col])
        assert {r[4] for r in rows.values()} == {"-"}

    @pytest.mark.parametrize("content", [None, b"{torn"])
    def test_missing_or_corrupt_trace_exits_2(self, tmp_path, capsys, content):
        path = tmp_path / "bad.xplane.pb"
        if content is not None:
            path.write_bytes(content)
        assert roofline_report.main([str(path)]) == 2
        assert "cannot read trace" in capsys.readouterr().err

    def test_a_directory_with_no_capture_exits_2(self, tmp_path, capsys):
        assert roofline_report.main([str(tmp_path)]) == 2
        assert "cannot read trace" in capsys.readouterr().err
