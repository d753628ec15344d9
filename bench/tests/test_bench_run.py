"""The harness refuses to report without a TPU, and in a directory that
holds only the benchmark."""
import importlib.util
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_tpu_no_result(capsys):
    run = _run_module()
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "phi3-mini.chat", "--seed", str(2**31 + 1),
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "needs 1 TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_benchmark_alone_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and bench/: the program is missing, so no run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "phi3-mini.chat", "--seed", "5", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("config", ["phi3-mini-3.8b", "minicpm3-4b"])
def test_configuration_file_is_the_registry_model(config):
    """The registry's model holds every field the file's reference asks
    of it, and a changed width is refused."""
    import json
    from bench import harness
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    assert harness.program_config(cfg).name == config
    with pytest.raises(SystemExit):
        harness.program_config({**cfg, "hidden_size": cfg["hidden_size"] // 2})
