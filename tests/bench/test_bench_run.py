"""``bench/run.py`` refuses to run without a TPU, and in a directory that
holds only the benchmark: a non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys

from tiny import ROOT


def run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gepo_rollout.qwen3-1.7b", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(p):
    return not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_no_tpu_no_result():
    p = run(ROOT, {})
    assert p.returncode != 0 and no_result(p)
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "tests" / "bench", tmp_path / "tests" / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, {})
    assert p.returncode != 0 and no_result(p)


def test_memory_peak_counts_reserved_bytes(monkeypatch):
    """The device's peak is the buffers' peak and the bytes the runtime
    reserved for the programs' temporaries, on the fullest chip."""
    sys.path.insert(0, str(ROOT))
    from bench import run as harness

    class Dev:
        def __init__(self, used, reserved):
            self.st = {"peak_bytes_in_use": used}
            if reserved is not None:
                self.st["peak_bytes_reserved"] = reserved

        def memory_stats(self):
            return self.st

    env = harness.Env(None)
    monkeypatch.setattr(harness.Env, "devices",
                        property(lambda self: [Dev(4, 9), Dev(7, None)]))
    env.read_memory()
    assert env.memory_peak == 13


def test_judge_compares_only_numbers_with_limits():
    """A number the cell's limits leave out is not compared; compiles in
    the window always are, with the limit 0; a number that is not finite
    fails."""
    sys.path.insert(0, str(ROOT))
    from bench import run as harness
    checks, limits, ok = harness.judge(
        {"update_gap": 0.001, "loss_gap": 9.0}, {"update_gap": 0.005}, 0)
    assert checks == {"update_gap": 0.001, "window_compiles": 0.0}
    assert limits == {"update_gap": 0.005, "window_compiles": 0.0} and ok
    assert not harness.judge({"update_gap": 0.001}, {"update_gap": 0.005},
                             1)[2]
    assert not harness.judge({"update_gap": float("nan")},
                             {"update_gap": 0.005}, 0)[2]
    assert not harness.judge({"update_gap": 0.006}, {"update_gap": 0.005},
                             0)[2]
