"""tools/trace_top_ops.py: Chrome-trace summarizer.

Pins the loader's exit-2 contract (missing / corrupt / torn traces get a
diagnostic, never a traceback) and the gzip round-trip. Per-stage device
time is read from the raw ``.xplane.pb`` (tests/observability/
test_trace_readers.py, tests/tools/test_roofline_report.py).
"""

import gzip
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO / "tools"))

import trace_top_ops  # noqa: E402

pytestmark = pytest.mark.roofline

TOOL = str(REPO / "tools" / "trace_top_ops.py")


def _trace() -> dict:
    """Minimal Chrome trace: one TPU lane, two staged ops (one staged via
    args.long_name, the fusion case), one unstaged op, one counter event
    that must be ignored (no ``dur``)."""
    return {"traceEvents": [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 0, "dur": 1200,
         "name": "jit(fit)/fl_stage::local_train/dot_general"},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 1200, "dur": 300,
         "name": "jit(fit)/fl_stage::local_train/add"},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 1500, "dur": 500,
         "name": "fusion.7",
         "args": {"long_name": "jit(fit)/fl_stage::server_update/sub"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 2000, "dur": 100,
         "name": "copy.1"},
        {"ph": "C", "pid": 1, "tid": 2, "ts": 0,
         "name": "jit(fit)/fl_stage::local_train/counter"},
    ]}


def _write_plain(tmp_path) -> str:
    path = tmp_path / "vm.trace.json"
    path.write_text(json.dumps(_trace()))
    return str(path)


def _write_gz(tmp_path) -> str:
    path = tmp_path / "vm.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(_trace(), f)
    return str(path)


class TestLoad:
    def test_plain_json_round_trip(self, tmp_path):
        trace = trace_top_ops.load(_write_plain(tmp_path))
        assert len(trace["traceEvents"]) == 7

    def test_gzipped_round_trip(self, tmp_path):
        trace = trace_top_ops.load(_write_gz(tmp_path))
        assert len(trace["traceEvents"]) == 7

    def test_corrupt_json_raises_trace_error(self, tmp_path):
        path = tmp_path / "bad.trace.json"
        path.write_text("{not json at all")
        with pytest.raises(trace_top_ops.TraceError, match="corrupt"):
            trace_top_ops.load(str(path))

    def test_torn_gzip_raises_trace_error(self, tmp_path):
        # a capture killed mid-write: valid gzip header, truncated stream
        whole = gzip.compress(json.dumps(_trace()).encode())
        path = tmp_path / "torn.trace.json.gz"
        path.write_bytes(whole[: len(whole) // 2])
        with pytest.raises(trace_top_ops.TraceError):
            trace_top_ops.load(str(path))

    def test_non_object_top_level_raises(self, tmp_path):
        path = tmp_path / "list.trace.json"
        path.write_text("[1, 2, 3]")
        with pytest.raises(trace_top_ops.TraceError, match="expected"):
            trace_top_ops.load(str(path))


class TestSummarize:
    def test_lane_totals_and_top_ops(self):
        lines = trace_top_ops.summarize(_trace(), top=2)
        assert lines[0].startswith("== /device:TPU:0 / XLA Ops:")
        assert "2.10 ms busy" in lines[0]
        # top-2 cut: the dot (1.20 ms) leads, copy.1 falls off
        assert "dot_general" in lines[1]
        assert all("copy.1" not in ln for ln in lines)


class TestCli:
    def _run(self, *argv):
        return subprocess.run([sys.executable, TOOL, *argv],
                              capture_output=True, text=True)

    def test_ok_trace_prints_lane_summary(self, tmp_path):
        out = self._run(_write_gz(tmp_path))
        assert out.returncode == 0
        assert "== /device:TPU:0 / XLA Ops:" in out.stdout
        assert "local_train" in out.stdout

    def test_missing_path_exits_2(self, tmp_path):
        out = self._run(str(tmp_path / "nope.trace.json.gz"))
        assert out.returncode == 2
        assert "not found" in out.stderr
        assert "Traceback" not in out.stderr

    def test_corrupt_trace_exits_2_no_traceback(self, tmp_path):
        path = tmp_path / "bad.trace.json"
        path.write_text('{"traceEvents": [tr')
        out = self._run(str(path))
        assert out.returncode == 2
        assert "corrupt" in out.stderr
        assert "Traceback" not in out.stderr

    def test_torn_gzip_exits_2(self, tmp_path):
        whole = gzip.compress(json.dumps(_trace()).encode())
        path = tmp_path / "torn.trace.json.gz"
        path.write_bytes(whole[:20])
        out = self._run(str(path))
        assert out.returncode == 2
        assert "Traceback" not in out.stderr
