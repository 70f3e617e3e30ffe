"""Operation and byte counts of the chip benchmark, by hand, and its table
of peaks."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import counts, harness, reference  # noqa: E402

PAPER = json.loads((harness.CHIP / "configs" / "h2fed_mlp.json").read_text())
MLP = harness.load_model(PAPER["model"])
# the FedAvg paper's MNIST 2NN (arXiv 1602.05629), a wider MLP
TWO_NN = {"input_dim": 784, "hidden_dims": [200, 200], "n_classes": 10,
          "n_params": 199_210}


def test_parameter_counts_match_the_configs():
    assert MLP.n_params(PAPER) == PAPER["n_params"] == 31_810
    assert MLP.n_params(TWO_NN) == TWO_NN["n_params"] == 199_210


@pytest.mark.parametrize("config, flops", [
    # forward 2*(784*40 + 40*10), weight grads the same, input grad of the
    # second layer only: 2*400
    (PAPER, 2 * 31_760 + 2 * 31_760 + 2 * 400),
    # 784*200 + 200*200 + 200*10 = 198,800 weights; input grads of the
    # last two layers: 40,000 + 2,000
    (TWO_NN, 4 * 198_800 + 2 * 42_000),
])
def test_mlp_flops_per_sample(config, flops):
    assert MLP.flops_per_sample(config, {}) == flops


def test_agg_blend_bytes_at_the_paper_shapes():
    # 10 of 100 agents connected, 7 of 10 RSUs hit, N = 31,810 fp32:
    # read 10 rows, write 7 rows
    assert counts.agg_blend_bytes(10, 7, 31_810, 4) == 17 * 31_810 * 4
    assert counts.agg_blend_flops(10, 31_810) == 2 * 10 * 31_810


def test_cloud_blend_bytes_at_the_paper_shapes():
    # 9 RSUs carry mass: read 9 fp32 rows, write the fp32 cloud model
    assert counts.cloud_blend_bytes(9, 31_810, 4) == 9 * 31_810 * 4 \
        + 31_810 * 4
    assert counts.cloud_blend_bytes(0, 31_810, 4) == 0
    assert counts.cloud_blend_flops(9, 31_810) == 2 * 9 * 31_810


def test_least_seconds_takes_the_binding_roof():
    peak = counts.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9
    assert counts.least_seconds(197e12, 0, peak) == 1.0
    assert counts.least_seconds(0, 819e9, peak) == 1.0
    assert counts.least_seconds(1e6, 819e9, peak) == 1.0


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_agg_kernel_seconds_reads_only_the_kernel():
    trace = {"ops": {"_fused_agg_blend.3": 0.25, "fusion.1": 1.0,
                     "_fused_agg_blend": 0.5}}
    assert counts.agg_kernel_seconds(trace) == 0.75
    assert counts.agg_kernel_seconds(None) == 0.0


def test_realized_counts_follow_the_draws():
    t = {"lar": 3, "csr": 0.3, "scd": 2, "fsr": 0.5, "local_epochs": 2}
    assign = np.arange(40) % 5
    got = reference.realized(7, t, 40, 2, 3, assign, 5)
    rng, rem = jax.random.key(7), jnp.zeros((40,), jnp.int32)
    for r in range(2):
        rng, keys = reference.round_keys(rng, t["lar"])
        mass = np.zeros(5)
        for i, key in enumerate(keys):
            rem, mask, steps = reference.draws(key, rem, t, 40, 3)
            mask, steps = np.asarray(mask), np.asarray(steps)
            assert got["connected"][r, i] == mask.sum()
            assert got["live_steps"][r, i] == steps[mask].sum()
            hit = np.bincount(assign[mask], minlength=5)
            assert got["rsus_hit"][r, i] == (hit > 0).sum()
            mass += hit
        assert got["cloud_rsus"][r] == (mass > 0).sum()
