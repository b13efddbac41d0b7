"""The run's two bounds, on the CPU at LeNet-5 size.

Set-up has to end within ``run.SETUP_LIMIT_S`` of process start and the whole
run within ``run.RUN_LIMIT_S``.  A run past either ends itself: non-zero
exit, no result line, and on stderr the stage it was in and every thread's
traceback.  Such a run ends its process, so it runs in a child process here.
A run that ends in time leaves no timer armed in its process.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from test_harness import HERE, REPO, make_checkout, on_cpu, run_cell  # noqa: F401,E501

import run  # noqa: E402

STALL_S = 600.0
CHILD = """
import pathlib, sys, time
sys.path[:0] = [{here!r}, {src!r}]
import jax
import run
from repro.core import graph
from repro.runtime import Session

run.require_chips = lambda n: jax.devices()
v5e = run.load_peak("TPU v5 lite")
run.load_peak = lambda kind: v5e
run.SETUP_LIMIT_S, run.RUN_LIMIT_S = {setup_s!r}, {run_s!r}


def slow_graph():
    time.sleep({stall_s!r})
    return graph.lenet5()


graph.slow_graph = slow_graph
close = Session.close


def slow_close(self, *a, **kw):
    time.sleep({stall_s!r})
    return close(self, *a, **kw)


if {stall!r} == "close":
    Session.close = slow_close
sys.exit(run.main(["--workload", "tiny_net.trickle", "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0"],
                  root=pathlib.Path({root!r})))
"""


@pytest.mark.parametrize("stall, setup_s, run_s, stage, frame", [
    ("graph", 20.0, 200.0, "set-up: bundle compiling", "slow_graph"),
    ("close", 100.0, 40.0, "window 1.0 s", "slow_close")])
def test_a_run_past_its_bound_ends_itself(tmp_path, stall, setup_s, run_s,
                                          stage, frame):
    """(graph) the configuration's graph builder stalls inside set-up;
    (close) ``Session.close`` stalls in the drain after the window.  Each
    run ends at the bound of its stage: in the drain the run bound, though
    it is here the shorter, has taken the set-up bound's place."""
    from test_harness import lenet_config
    cfg = lenet_config("nv_small")
    if stall == "graph":
        cfg["graph"] = "slow_graph"
    root = make_checkout(tmp_path, cfg=cfg)
    code = CHILD.format(here=str(HERE), src=str(REPO / "src"),
                        setup_s=setup_s, run_s=run_s, stall_s=STALL_S,
                        stall=stall, root=str(root))
    t = time.monotonic()
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    took = time.monotonic() - t
    assert p.returncode != 0
    assert took < (setup_s if stall == "graph" else run_s) + 20
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    lines = p.stderr.splitlines()
    timeout_at = next(i for i, ln in enumerate(lines)
                      if ln.startswith("Timeout ("))
    last = [ln for ln in lines[:timeout_at] if ln.startswith("[chipbench]")]
    assert last[-1].startswith(f"[chipbench] {stage}"), p.stderr[-3000:]
    assert "most recent call first" in p.stderr
    assert f"in {frame}" in p.stderr


def test_a_run_in_time_leaves_no_timer(tmp_path, on_cpu, capsys,  # noqa: F811
                                       monkeypatch):
    """In this test's own process: the run prints its result, and sleeping
    past both limits afterwards does not end the process."""
    monkeypatch.setattr(run, "SETUP_LIMIT_S", 20.0)
    monkeypatch.setattr(run, "RUN_LIMIT_S", 30.0)
    t = time.monotonic()
    res, err = run_cell(make_checkout(tmp_path), capsys)
    assert res["correct"] is True, err[-2000:]
    assert "[chipbench] bounds: setup_s" in err
    for name in ("jax", "bundle compiling", "session load", "warm-up b1",
                 "warm-up b8", "generators", "warm traffic"):
        assert f"[chipbench] set-up: {name} (+" in err
    time.sleep(max(0.0, t + run.RUN_LIMIT_S + 2.0 - time.monotonic()))
    assert time.monotonic() - t > run.RUN_LIMIT_S
