"""The benchmark's copy of the data recipe (the ``mlp`` model kind's, and
the shared scenario-II split) against the program's: at the paper's size
and seed both give the same label shards, counts and RSUs."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import datagen, harness  # noqa: E402
from repro.data.partition import pretrain_split, scenario_two  # noqa: E402
from repro.data.synthetic import mnist_class_task  # noqa: E402

TRAFFIC = {p.stem: json.loads(p.read_text())
           for p in sorted((harness.CHIP / "traffic").glob("*.json"))}
PAPER = json.loads((harness.CHIP / "configs" / "h2fed_mlp.json").read_text())
MLP = harness.load_model(PAPER["model"])


@pytest.fixture(scope="module")
def paper():
    t = TRAFFIC["paper_csr10"]
    seed = t["data_seed"]
    train, test = mnist_class_task(n_train=t["n_train"], n_test=t["n_test"],
                                   noise=t["noise"], seed=seed)
    pre, pool = pretrain_split(train, t["excluded_labels"],
                               frac=t["pretrain_frac"], seed=seed)
    fed = scenario_two(pool, n_agents=t["n_agents"], n_rsus=t["n_rsus"],
                       seed=seed)
    return MLP.plan(t, seed), train, test, pre, fed


def test_plan_gives_the_programs_shards_at_the_paper_size(paper):
    s, train, test, pre, fed = paper
    np.testing.assert_array_equal(s.y_train, train.y)
    np.testing.assert_array_equal(s.y_test, test.y)
    np.testing.assert_array_equal(s.y_train[s.pre_idx], pre.y)
    np.testing.assert_array_equal(s.y_train[s.agent_idx], fed.y)
    np.testing.assert_array_equal(s.n_per_agent, fed.n_per_agent)
    np.testing.assert_array_equal(s.rsu_assign, fed.rsu_assign)
    # the same samples, not only the same labels
    np.testing.assert_array_equal(train.x[s.agent_idx], fed.x)
    np.testing.assert_array_equal(train.x[s.pre_idx], pre.x)


def test_prototypes_are_the_programs(paper):
    s, train, *_ = paper
    # noise-free rows of the program's generator: x = proto * brightness
    # + noise, so the per-class mean approaches the prototype's shape
    for c in range(3):
        mean = train.x[train.y == c].mean(0)
        assert np.corrcoef(mean, s.protos[c])[0, 1] > 0.9


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_every_mix_gives_its_stated_shards(name):
    t = TRAFFIC[name]
    s = MLP.plan(t, t["data_seed"])
    assert s.agent_idx.shape == (t["n_agents"], t["samples_per_agent"])
    # no sample is handed to two agents, and none of the OEM pool's
    assert len(np.unique(s.agent_idx)) == s.agent_idx.size
    assert not np.intersect1d(s.agent_idx, s.pre_idx).size
    assert not np.isin(s.y_train[s.pre_idx], t["excluded_labels"]).any()
    labs = s.y_train[s.agent_idx]
    assert (np.array([len(np.unique(r)) for r in labs]) == 2).all()


def test_a_dry_label_pool_is_refused():
    y = np.repeat(np.arange(10), 5)
    with pytest.raises(ValueError):
        datagen.scenario_two_idx(y, 40, 4, 2, 0, 10)


def test_pixels_follow_the_recipe():
    protos = np.random.default_rng(0).random((10, 784)).astype(np.float32)
    idx = np.arange(64)
    y = idx % 10
    x = np.asarray(MLP.pixels(jax.random.key(1), protos, idx, y, 0.0))
    bright = x / protos[y]
    # without noise each row is its prototype scaled by one brightness
    assert np.allclose(bright, bright[:, :1], rtol=1e-5)
    assert bright.min() >= 0.7 - 1e-6 and bright.max() <= 1.3 + 1e-6
    noisy = np.asarray(MLP.pixels(jax.random.key(1), protos, idx, y, 0.8))
    assert noisy.min() >= 0.0 and noisy.max() <= 1.5
    again = np.asarray(MLP.pixels(jax.random.key(1), protos, idx[::-1],
                                  y[::-1], 0.8))
    np.testing.assert_array_equal(noisy, again[::-1])


def test_pretrain_stops_at_the_target():
    t = dict(TRAFFIC["paper_csr10"])
    data = MLP.make(t, PAPER, run_seed=3)
    assert data.x.shape == (100, 96, 784)
    assert t["pretrain_target"] <= data.pre_acc < 0.9
    assert 1 <= data.pre_epochs <= t["pretrain_max_epochs"]
