"""benchmarks/run.py harness contract: a raising bench module must exit
non-zero and must mark the failure inside the emitted JSON, so CI can
never upload a partial trajectory as green.  Every bench runs in a child
process, and the harness itself never imports JAX."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import benchmarks.run as runmod  # noqa: E402


def _patch(monkeypatch, tmp_path, modules):
    """Write each (name, source of ``run``) as a module the bench child
    processes import from ``tmp_path``."""
    for name, src in modules:
        (tmp_path / f"{name}.py").write_text(textwrap.dedent(src))
    monkeypatch.setenv("PYTHONPATH", str(tmp_path))
    monkeypatch.setattr(runmod, "MODULES", [name for name, _ in modules])
    monkeypatch.setattr(runmod, "JSON_PATH", str(tmp_path / "bench.json"))
    monkeypatch.setattr(sys, "argv", ["run.py"])
    return tmp_path / "bench.json"


OK = """
    def run(lines):
        lines.append("ok_metric,2,fine")
"""


def test_run_exits_nonzero_when_a_module_raises(monkeypatch, tmp_path):
    boom = """
    def run(lines):
        lines.append("partial_metric,1,emitted-before-crash")
        raise RuntimeError("kaboom")
    """
    json_path = _patch(monkeypatch, tmp_path, [("_ok", OK), ("_boom", boom)])
    with pytest.raises(SystemExit) as exc:
        runmod.main()
    assert exc.value.code == 1
    data = json.loads(json_path.read_text())
    # the partial JSON is still written (the trajectory survives) ...
    assert data["ok_metric"]["derived"] == "fine"
    assert data["partial_metric"]["derived"] == "emitted-before-crash"
    # ... but it is self-describing about the failure
    assert data["_boom_wall"]["derived"].startswith("FAILED")
    assert data["bench_run_failures"]["count"] == 1
    assert "_boom" in data["bench_run_failures"]["derived"]
    assert "kaboom" in data["bench_run_failures"]["derived"]


def test_run_exits_zero_and_marks_no_failures_when_green(monkeypatch,
                                                         tmp_path):
    json_path = _patch(monkeypatch, tmp_path, [("_ok", OK)])
    runmod.main()                       # no SystemExit
    data = json.loads(json_path.read_text())
    assert data["bench_run_failures"]["count"] == 0
    assert data["ok_metric"]["us_per_call"] == 2.0


def test_run_rejects_unknown_selection(monkeypatch, tmp_path):
    _patch(monkeypatch, tmp_path, [("_ok", OK)])
    monkeypatch.setattr(sys, "argv", ["run.py", "no_such_bench"])
    with pytest.raises(SystemExit) as exc:
        runmod.main()
    assert exc.value.code == 2


def test_each_bench_runs_in_its_own_process(monkeypatch, tmp_path):
    pid = """
    import os
    def run(lines):
        lines.append(f"{__name__},{os.getpid()},child")
    """
    json_path = _patch(monkeypatch, tmp_path, [("_pid1", pid), ("_pid2", pid)])
    runmod.main()
    data = json.loads(json_path.read_text())
    pids = {data["_pid1"]["us_per_call"], data["_pid2"]["us_per_call"]}
    assert len(pids) == 2 and float(os.getpid()) not in pids


def test_harness_parent_never_imports_jax():
    root = Path(__file__).resolve().parents[1]
    code = "import sys, benchmarks.run; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, check=True).stdout
    assert out.strip() == "False"
