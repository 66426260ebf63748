"""The harness is driven by data: a new cell is new files plus an entry in
``workloads``. And a run without a TPU refuses and prints no result."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

import _bench_paths as bp


def _copy_checkout(dst, with_program=True):
    shutil.copytree(bp.BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace*",
                                                  "__pycache__"))
    shutil.copy(os.path.join(bp.ROOT, "BENCHMARK.json"), dst)
    if with_program:
        os.symlink(os.path.join(bp.ROOT, "src"), os.path.join(dst, "src"))


def test_new_cell_from_new_files_only(tmp_path):
    _copy_checkout(tmp_path)
    bench = tmp_path / "bench"
    config, traffic = bp.shrink(bp.load("configs", "caida-netflow"),
                                bp.load("traffic", "sat"), by=64)
    config.update(name="tiny-netflow", mix=[0.5, 0.3, 0.2])
    traffic.update(name="tiny-closed")
    (bench / "configs" / "tiny-netflow.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny-closed.json").write_text(json.dumps(traffic))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.sat", "config": "tiny-netflow",
                              "traffic": "tiny-closed", "chips": 1,
                              "why": "a cell added by data alone"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    mod_spec = importlib.util.spec_from_file_location(
        "bench_copy_run", bench / "run.py")
    run = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(run)
    cell, cfg, tr, e2e, per_layer = run.load_cell("tiny.sat")
    assert cfg["mix"] == [0.5, 0.3, 0.2] and tr["name"] == "tiny-closed"
    assert {m["name"] for m in e2e} == {"ci_half_width_pct", "setup_s"}
    assert per_layer == []
    result, rows = run.measure(cell, cfg, tr, e2e, per_layer, 77, 1.5,
                               False, log=lambda *a, **k: None)
    assert result["correct"], rows
    assert set(result["metrics"]) == {"ci_half_width_pct", "setup_s"}
    assert list(result)[-1] == "checks"


def _run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "netflow.sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    out = _run_cli(bp.ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    _copy_checkout(tmp_path, with_program=False)
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def test_unknown_executor_is_refused():
    import deploy
    cfg = dict(bp.load("configs", "caida-netflow"), executor="batched")
    with pytest.raises(ValueError, match="pipelined"):
        deploy.executor(cfg, None)
