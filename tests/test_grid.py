"""The paper-repro grid driver: its parent process never imports JAX, and
off the CPU its children (each of which needs the chip) run one at a time."""
import os
import subprocess
import sys

from repro.experiments import grid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_grid_parent_builds_jobs_without_jax():
    code = (
        "import sys\n"
        "from repro.experiments import grid\n"
        "jobs = (grid.grid_jobs() + grid.overlap_jobs()\n"
        "        + grid.scenario_jobs())\n"
        "assert jobs, 'no jobs'\n"
        "print('JAX_IMPORTED', 'jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert "JAX_IMPORTED False" in out.stdout, out.stderr[-2000:]


def _overlap_jobs(tmp_path, n):
    """n jobs that record start/end markers in one shared log."""
    log = tmp_path / "log"
    code = ("import sys, time\n"
            "open(sys.argv[1], 'a').write('start\\n'); time.sleep(1.0)\n"
            "open(sys.argv[1], 'a').write('end\\n')\n")
    return log, [(f"j{i}", [sys.executable, "-c", code, str(log)])
                 for i in range(n)]


def test_run_pool_serializes_children_off_the_cpu(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    log, jobs = _overlap_jobs(tmp_path, 2)
    assert grid.run_pool(jobs, max_procs=5) == []
    assert log.read_text().split() == ["start", "end", "start", "end"]


def test_run_pool_runs_children_concurrently_on_the_cpu(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    log, jobs = _overlap_jobs(tmp_path, 2)
    assert grid.run_pool(jobs, max_procs=2) == []
    assert log.read_text().split()[:2] == ["start", "start"]
