"""chip_smoke.py: its phases at a tiny size on the CPU, and the script's
refusal to run without a TPU.

The phases run with the route check (``assert_pallas_route``) replaced
inside the test only; tests/test_tpu_compile.py compiles the kernels for
a described chip.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.core.heterogeneity import HeterogeneityModel  # noqa: E402

# a fleet of 8 needs more than 10% connected to learn within the rounds
TINY = dict(n_agents=8, n_rsus=4, batch=16, n_train=2_000, n_test=400,
            pretrain_target=0.5,
            het=HeterogeneityModel(csr=0.6, scd=1, lar=5))


@pytest.fixture(scope="module")
def tiny_cell():
    res = chip_smoke.paper_spec(**TINY).resolve()
    params, pre_acc = chip_smoke.pretrained(res)
    return res, params, pre_acc


@pytest.fixture
def no_route_check(monkeypatch):
    monkeypatch.setattr(chip_smoke, "assert_pallas_route",
                        lambda res, params: None)


def test_paper_spec_is_the_paper_cell():
    s = chip_smoke.paper_spec()
    assert (s.n_agents, s.n_rsus, s.het.csr) == (100, 10, 0.1)
    assert (s.partition, s.excluded_labels) == ("scenario_two", (7, 8, 9))
    res = chip_smoke.paper_spec(**TINY).resolve()
    params, _ = chip_smoke.pretrained(res)
    from repro.core import flatten
    assert flatten.spec_of(params).n == 31_810        # 784-40-10 MLP


def test_flat_phase_routes_agree(tiny_cell, no_route_check):
    res, params, pre_acc = tiny_cell
    diff = chip_smoke.phase_flat(res, params, pre_acc)
    assert diff == 0.0          # off the chip both runs take the XLA route


def test_async_and_bf16_phases(tiny_cell):
    res, params, pre_acc = tiny_cell
    chip_smoke.phase_async(res, params, pre_acc)
    chip_smoke.phase_bf16(res, params, pre_acc)


def test_run_cell_refuses_no_gain(tiny_cell):
    res, params, _ = tiny_cell
    with pytest.raises(AssertionError, match="not above pretrained"):
        chip_smoke.run_cell(res, params, 1.0, "flat")


SHARDED_CODE = f"""
import sys
sys.path.insert(0, {str(ROOT)!r})
import jax
import chip_smoke
assert len(jax.devices()) == 4, jax.devices()
from repro.core.heterogeneity import HeterogeneityModel
res = chip_smoke.paper_spec(**{TINY!r}).resolve()
params, _ = chip_smoke.pretrained(res)
print("worst", chip_smoke.phase_sharded(res, params))
"""


def test_sharded_phase_on_4_devices(forced_devices_run):
    out = forced_devices_run(SHARDED_CODE, devices=4)
    assert "sharded rsu_sharded: mesh {'pod': 2, 'data': 2}" in out, out
    assert "sharded model_shards=2: mesh {'data': 2, 'model': 2}" in out, out
    worst = float(out.strip().splitlines()[-1].split()[-1])
    assert np.isfinite(worst) and worst <= 1e-4


def _run_script(cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=full, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("env", [{}, {"REPRO_INTERPRET": "1"}],
                         ids=["cpu", "interpret"])
def test_script_refuses_without_a_chip(env):
    out = _run_script(ROOT, **env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "chip_smoke:" in out.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
