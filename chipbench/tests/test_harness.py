"""The harness end to end on the CPU, at LeNet-5 size, in interpret mode.

Each test builds a checkout of its own: ``BENCHMARK.json`` naming one cell,
a LeNet-5 configuration, a traffic mix and one extra metric written here,
the real ``metrics/`` readers and ``src/`` beside them.  The harness has to
find all of them by name.  Its look for a chip is steered to the CPU inside
the test; everything else runs as on the chip: HTTP server, generator
processes, window, reference check.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
REPO = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO / "src"))

import reference  # noqa: E402
import run  # noqa: E402

LENET = {"kind": "sequential", "layers": [
    {"name": "conv1", "type": "conv", "out": 6, "k": 5, "stride": 1,
     "pad": 2, "relu": True},
    {"name": "pool1", "type": "pool", "mode": "max", "k": 2, "stride": 2,
     "pad": 0},
    {"name": "conv2", "type": "conv", "out": 16, "k": 5, "stride": 1,
     "pad": 0, "relu": True},
    {"name": "pool2", "type": "pool", "mode": "max", "k": 2, "stride": 2,
     "pad": 0},
    {"name": "fc1", "type": "fc", "out": 120, "relu": True},
    {"name": "fc2", "type": "fc", "out": 84, "relu": True},
    {"name": "fc3", "type": "fc", "out": 10, "relu": False}]}
SHAPE = (1, 28, 28)


def lenet_config(engine: str) -> dict:
    layers = reference.build(LENET)
    params = reference.make_weights(layers, SHAPE, 0)
    images = np.random.default_rng(1).normal(0, 1, (2,) + SHAPE).astype(
        np.float32)
    checks = ({"max_diff_steps": {"limit": 0}} if engine == "nv_small"
              else {"max_rel_err": {"limit": 0.05}})
    return {
        "name": f"lenet5_{engine}", "graph": "lenet5", "engine": engine,
        "input_shape": list(SHAPE), "arch": LENET, "reduced": [],
        "server": {"backend": "baremetal", "max_batch": 8,
                   "max_wait_us": 200.0, "max_queue": 256, "max_retries": 2,
                   "trace_sample": 1},
        "checks": checks, "control": "int4",
        "fused_kernels": ['custom_call_target="tpu_custom_call"'],
        "calibration": {"seed": 0, "images": 2, "percentile": 99.99,
                        "scales": reference.calibrate(layers, SHAPE, params,
                                                      images)}}


def make_checkout(tmp: pathlib.Path, engine: str = "nv_small",
                  loop: str = "closed", cfg: dict = None) -> pathlib.Path:
    """A checkout holding one cell, with files named only here: LeNet-5 at
    ``engine``, or the configuration ``cfg``."""
    cb = tmp / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        (cb / sub).mkdir(parents=True)
    for f in (HERE / "metrics").glob("*.py"):
        (cb / "metrics" / f.name).write_text(f.read_text())
    (tmp / "src").symlink_to(REPO / "src")
    cfg = cfg or lenet_config(engine)
    (cb / "configs" / "tiny_net.json").write_text(json.dumps(cfg))
    traffic = ({"loop": "closed", "clients": 4, "processes": 2}
               if loop == "closed" else
               {"loop": "open", "rate_per_s": 40.0, "processes": 2,
                "threads_per_process": 4})
    traffic.update(pool_size=4, warm_s=0.5, timeout_s=60.0)
    (cb / "traffic" / "trickle.json").write_text(json.dumps(traffic))
    # a metric that exists only in this checkout
    (cb / "metrics" / "answered_share.py").write_text(
        "def read(rec):\n"
        "    return 100.0 * (rec['attempted'] - rec['failed'])"
        " / rec['attempted']\n")
    e2e = "img_per_s" if loop == "closed" else "p50_ms"
    doc = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_net", "source": "LeNet-5",
                     "file": "chipbench/configs/tiny_net.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny_net.trickle", "config": "tiny_net",
                       "traffic": "trickle", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": e2e, "unit": "x", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "answered_share", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "test", "moves": e2e}]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture
def on_cpu(monkeypatch):
    """Skip the harness's look for a chip: take the CPU device.  A run
    starts at its call to ``run.main``, not at the test process's start, so
    its bounds count from there."""
    import jax
    monkeypatch.setattr(run, "process_start", time.monotonic)
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    v5e = run.load_peak("TPU v5 lite")
    monkeypatch.setattr(run, "load_peak", lambda kind: v5e)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")


def run_cell(root, capsys, trace=0, seed=2 ** 31 + 7) -> dict:
    rc = run.main(["--workload", "tiny_net.trickle", "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)], root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_finds_files_by_name_and_is_correct(tmp_path, on_cpu, capsys):
    root = make_checkout(tmp_path)
    res, err = run_cell(root, capsys)
    assert res["correct"] is True, err[-2000:]
    assert set(res["metrics"]) == {"img_per_s", "setup_s"}
    assert res["metrics"]["img_per_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"] == {"max_diff_steps": {"value": 0.0, "limit": 0},
                             "unanswered": {"value": 0, "limit": 0}}
    assert err.rstrip().splitlines()[-2].startswith(
        "[chipbench] check max_diff_steps")
    # the per-layer run reports the metric that only this checkout has
    res, _ = run_cell(root, capsys, trace=1)
    assert set(res["metrics"]) == {"answered_share"}
    assert res["metrics"]["answered_share"]["value"] == 100.0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_open_loop_bf16(tmp_path, on_cpu, capsys):
    root = make_checkout(tmp_path, engine="nv_full", loop="open")
    res, err = run_cell(root, capsys)
    assert res["correct"] is True, err[-2000:]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert res["metrics"]["p50_ms"]["value"] > 0


def _alter_answer(ex_cls):
    """Flip the first logit of every answer where the executor makes it."""
    orig = ex_cls._finish_out

    def broken(self, y_bytes):
        y = np.array(y_bytes, np.uint8)
        y[..., 0] ^= 0x40
        return orig(self, y)
    return broken


def _swap_lanes(orig):
    """Hand each lane of a batch the answer of the next lane."""
    def broken(self, launched):
        res = orig(self, launched)
        if res.output_int8.ndim > 1 and res.output_int8.shape[0] > 1:
            res.output_int8 = np.roll(res.output_int8, 1, axis=0)
            res.output = np.roll(res.output, 1, axis=0)
        return res
    return broken


def _drop_half(orig):
    """Leave out the second half of each batch's images: those lanes are
    computed on zeros."""
    def broken(self, X, lanes=None):
        X = np.array(X)
        n = X.shape[0] if lanes is None else lanes
        X[(n + 1) // 2:n] = 0
        return orig(self, X, lanes)
    return broken


@pytest.mark.parametrize("fault", ["answer_altered", "lanes_swapped",
                                   "half_batch_dropped"])
def test_broken_timed_path_is_not_correct(tmp_path, on_cpu, capsys,
                                          monkeypatch, fault):
    """Each fault is planted in a half of a launch, ``submit_batch`` or
    ``finish``, which a whole launch (``run_batch``) and a split one both
    call."""
    from repro.core.executor import BareMetalExecutor
    if fault == "answer_altered":
        monkeypatch.setattr(BareMetalExecutor, "_finish_out",
                            _alter_answer(BareMetalExecutor))
    elif fault == "lanes_swapped":
        monkeypatch.setattr(BareMetalExecutor, "finish",
                            _swap_lanes(BareMetalExecutor.finish))
    else:
        monkeypatch.setattr(BareMetalExecutor, "submit_batch",
                            _drop_half(BareMetalExecutor.submit_batch))
    root = make_checkout(tmp_path)
    res, err = run_cell(root, capsys)
    assert res["correct"] is False, err[-2000:]
    assert res["checks"]["max_diff_steps"]["value"] > 0


def test_no_chip_no_result(tmp_path, capsys, monkeypatch):
    """On a platform other than tpu: non-zero exit, no result line."""
    monkeypatch.setattr(run, "process_start", time.monotonic)
    root = make_checkout(tmp_path)
    rc = run.main(["--workload", "tiny_net.trickle", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "needs a TPU" in out.err


def test_without_the_program_no_result(tmp_path):
    """A checkout holding only BENCHMARK.json and chipbench/ fails."""
    import subprocess
    root = make_checkout(tmp_path)
    (root / "src").unlink()
    (root / "chipbench" / "run.py").write_text((HERE / "run.py").read_text())
    for f in ("loadgen.py", "reference.py", "spec.py", "readlib.py",
              "opcount.py", "xtrace.py"):
        (root / "chipbench" / f).write_text((HERE / f).read_text())
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "tiny_net.trickle", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=root, capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
