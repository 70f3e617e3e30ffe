"""End-to-end CLI smoke: the train and serve launchers (subprocess, tiny)."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(args, timeout=480):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    return subprocess.run([sys.executable, "-m", *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


def test_train_then_serve_roundtrip(tmp_path):
    ck = str(tmp_path / "ckpt")
    out = _run(["repro.launch.train", "--devices", "8", "--rounds", "2",
                "--lar", "2", "--seq", "64", "--batch", "2",
                "--ckpt-every", "2", "--ckpt-dir", ck])
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[done]" in out.stdout
    assert "[ckpt]" in out.stdout

    out = _run(["repro.launch.serve", "--ckpt-dir", ck, "--batch", "2",
                "--prompt-len", "4", "--gen", "4"])
    assert out.returncode == 0, out.stderr[-3000:]
    assert "restored step 2" in out.stdout
    assert "[decode]" in out.stdout


def test_train_async_rounds_flag():
    """--async-rounds drives the semi-async SPMD path (DESIGN.md §6) and
    auto-enables flat_agg for the raveled pending buffer."""
    out = _run(["repro.launch.train", "--devices", "8", "--rounds", "2",
                "--lar", "2", "--seq", "32", "--batch", "2",
                "--async-rounds", "2", "--csr", "0.5"])
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[done]" in out.stdout
    assert "implies --flat-agg" in out.stdout


def test_train_adaptive_mu_flag(tmp_path):
    out = _run(["repro.launch.train", "--devices", "8", "--rounds", "2",
                "--lar", "1", "--seq", "32", "--batch", "2", "--csr", "0.3",
                "--adaptive-mu"])
    assert out.returncode == 0, out.stderr[-3000:]
    # the controller must have moved mu away from the base once csr_obs
    # was observed low
    assert "mu=(0.0" in out.stdout


def test_train_scenario_json(tmp_path):
    """--scenario-json runs a declarative ScenarioSpec (DESIGN.md §7)
    through the fedsim engines — any figure cell from the CLI."""
    from repro.core.scenario import ScenarioSpec
    from repro.core.h2fed import H2FedParams
    from repro.core.heterogeneity import HeterogeneityModel
    spec = ScenarioSpec(n_agents=8, n_rsus=4, batch=8, n_train=400,
                        n_test=100, partition="dirichlet", engine="async",
                        hp=H2FedParams(lar=2, local_epochs=1),
                        het=HeterogeneityModel(csr=0.8, max_delay=1,
                                               delay_p=0.3),
                        rounds=2)
    path = tmp_path / "spec.json"
    path.write_text(spec.to_json())
    out = _run(["repro.launch.train", "--scenario-json", str(path)])
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"cache_key={spec.cache_key}" in out.stdout
    assert "engine=async partition=dirichlet" in out.stdout
    assert "[round   2]" in out.stdout
    assert "[done]" in out.stdout


def test_train_scenario_fleet_store_host(tmp_path):
    """--fleet-store host / --chunk-agents override the spec and run the
    cohort-streamed engine (fedsim/streaming, DESIGN.md §8)."""
    from repro.core.scenario import ScenarioSpec
    from repro.core.h2fed import H2FedParams
    from repro.core.heterogeneity import HeterogeneityModel
    spec = ScenarioSpec(n_agents=10, n_rsus=4, batch=8, n_train=400,
                        n_test=100, hp=H2FedParams(lar=2, local_epochs=1),
                        het=HeterogeneityModel(csr=0.8), rounds=2)
    path = tmp_path / "spec.json"
    path.write_text(spec.to_json())
    out = _run(["repro.launch.train", "--scenario-json", str(path),
                "--fleet-store", "host", "--chunk-agents", "4"])
    assert out.returncode == 0, out.stderr[-3000:]
    assert "fleet_store=host chunk_agents=4" in out.stdout
    assert "[round   2]" in out.stdout
    assert "[done]" in out.stdout
