"""What the paper cell reads, pinned bit for bit on the CPU: its data and
pretrained model, the spec it hands the program, the loss the program
trains with, three reference rounds in fp32 and in the control, and its
work counts.  The digests were taken before the cell's model-specific code
moved behind ``models/mlp.py``; a change that moves any of them changes
what the cell measures."""
import hashlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness  # noqa: E402

PAPER = "h2fed_mlp.paper_csr10"
SEED = 11
# sha256 of each array's key path, dtype, shape and bytes, first 16 hex
PINNED = {
    "x": "4496eedc53d485b1",
    "y": "e947f3eef4c03674",
    "x_test": "1ffd3fbe42cf3437",
    "y_test": "1898f4e1012e0bb0",
    "params": "c122365a37bf3a64",
    "n_per_agent": "4e2dbc5ff189fa35",
    "rsu_assign": "98314e1f5bf34975",
    "pretrained": (0.6805000305175781, 5),
    "spec": "01e4e04c0e228bcd",
    "fp32": ("46315d7489774ca1", "b36f195a9a2749fc", "b4264b010c5120e8"),
    "bf16x3": ("a4ac839a8face223", "22364bc8065d5459", "c245af71cb9a210d"),
    "n_params": 31_810,
    "flops_per_sample": 127_840,
}


def digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype),
                     str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.fixture(scope="module")
def read():
    cell = harness.load_cell(PAPER)
    data, res = harness.prepare(cell, SEED)
    got = {k: digest(getattr(data, k)) for k in (
        "x", "y", "x_test", "y_test", "params", "n_per_agent",
        "rsu_assign")}
    got["pretrained"] = (data.pre_acc, data.pre_epochs)
    got["spec"] = res.spec.cache_key
    for mode in ("fp32", "bf16x3"):
        got[mode] = tuple(digest(r) for r in harness.reference_rounds(
            cell, data, res, mode=mode))
    got["n_params"] = cell.model.n_params(cell.config)
    got["flops_per_sample"] = cell.model.flops_per_sample(cell.config,
                                                          cell.traffic)
    return cell, got


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_paper_cell_reads_what_it_read(read, name):
    _, got = read
    assert got[name] == PINNED[name]


def test_the_paper_cell_trains_on_the_programs_mlp_loss(read):
    from repro.models import mlp
    cell, _ = read
    assert cell.model.program_loss(cell.config) is mlp.loss_fn
