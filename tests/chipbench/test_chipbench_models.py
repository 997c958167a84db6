"""Model kinds (``chipbench/models/<model>.py``): the paper's two models read
what they read before they moved there, and a new kind plugs in by files
alone."""
import hashlib
import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, harness
from chipbench_util import shrink

ROOT = harness.ROOT
SEED = 2 ** 33 + 21

# sha256 (first 16 hex digits) of the data and of the reference's outputs
# (float32, and the bfloat16 control), states kept, for each cell at
# ``shrink`` size and SEED on the CPU backend; recorded from the harness as
# it stood before the model kinds moved to models/ (commit 91f42e9), when
# data.py made the data and reference.py held both models
BEFORE_THE_MOVE = {
    "linear-c10.full": {"data": "41e83f7ca52ae506",
                        "reference": "345ae581eb92bf82",
                        "control": "8a3777f53e2c2e21"},
    "cnn-c10.full": {"data": "41e83f7ca52ae506",
                     "reference": "2029dd14ff5c610e",
                     "control": "5be8408698ef0451"},
}


def digest(obj) -> str:
    h = hashlib.sha256()

    def feed(o):
        if isinstance(o, dict):
            for k in sorted(o):
                h.update(repr(k).encode())
                feed(o[k])
        elif isinstance(o, (list, tuple)):
            h.update(b"[")
            for v in o:
                feed(v)
            h.update(b"]")
        else:
            a = np.asarray(o)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(np.ascontiguousarray(a).tobytes())
    feed(obj)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("cell_name", sorted(BEFORE_THE_MOVE))
def test_moved_kinds_reproduce_data_and_reference_bit_for_bit(cell_name):
    cell = shrink(harness.load_cell(cell_name))
    keys = harness.run_keys(SEED)
    data = cell["kind"].make_data(cell["mix"], cell["cfg"], keys["data"],
                                  SEED)
    got = {"data": digest(data)}
    for tag, dtype in (("reference", jnp.float32), ("control", jnp.bfloat16)):
        got[tag] = digest(harness.reference_outputs(
            cell, data, keys, dtype=dtype, fault=None, seed=SEED, keep=True))
    assert got == BEFORE_THE_MOVE[cell_name]


TOY_KIND = '''"""The program's linear model on feature rows of another
generator: each class a random sign pattern, each example's signs flipped
with probability 0.3."""
import math

import jax
import jax.numpy as jnp

from chipbench.models import _classifier
from chipbench.models.linear import (  # noqa: F401
    apply, correct_counts, init_model, mutual_loss, param_shapes,
    run_correct, shrink)


def trainer_kwargs(cfg):
    return {"feat_dim": cfg["feat_dim"], "num_classes": cfg["num_classes"],
            "model": "linear"}


def make_data(traffic, cfg, key, seed):
    R, n = traffic["samples_per_client"], traffic["train_per_client"]
    F, C = cfg["feat_dim"], cfg["num_classes"]
    labels = jnp.asarray(_classifier.client_labels(traffic, C, seed))
    signs = jnp.sign(jax.random.normal(jax.random.fold_in(key, 0), (C, F)))
    flip = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.3,
                                labels.shape + (F,))
    x = jnp.where(flip, -1.0, 1.0) * signs[labels] / math.sqrt(F)
    return {"train_x": x[:, :n], "train_y": labels[:, :n],
            "test_x": x[:, n:], "test_y": labels[:, n:]}
'''


def test_model_kind_added_by_files_alone(tmp_path):
    """A kind, its work count, a configuration, a traffic mix, a limit file
    and the entries, in a copy of the benchmark, make a cell that runs to
    ``correct: true`` with no file of the copy edited but BENCHMARK.json."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    here = tmp_path / "chipbench"
    (here / "models/linear_toy.py").write_text(TOY_KIND)
    (here / "counts/linear_toy.py").write_text(
        "def forward_macs(cfg):\n"
        "    return cfg['feat_dim'] * cfg['num_classes']\n")
    cfg = json.loads((here / "configs/p4-linear-cifar10.json").read_text())
    cfg.update(name="toy-signs", model="linear_toy")
    (here / "configs/toy-signs.json").write_text(json.dumps(cfg))
    shutil.copy(here / "traffic/full-m256.json", here / "traffic/signs.json")
    shutil.copy(here / "limits/linear-c10.full.json",
                here / "limits/toy-signs.full.json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-signs", "source": "a test", "reduced": [],
        "file": "chipbench/configs/toy-signs.json", "why": "a new kind"})
    bench["workloads"].append({
        "name": "toy-signs.full", "config": "toy-signs", "traffic": "signs",
        "chips": 1, "why": "a new kind of model"})
    for m in bench["per_layer"]:
        m["workloads"].append("toy-signs.full")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = shrink(harness.load_cell("toy-signs.full", root=str(tmp_path)))
    assert cell["kind"].__file__ == str(here / "models/linear_toy.py")
    keys = harness.run_keys(3)
    toy = cell["kind"].make_data(cell["mix"], cell["cfg"], keys["data"], 3)
    linear = harness.model_kind({"model": "linear"})
    paper = linear.make_data(cell["mix"], cell["cfg"], keys["data"], 3)
    assert toy["train_x"].shape == paper["train_x"].shape
    assert not np.allclose(toy["train_x"], paper["train_x"])
    assert counts.step_flops_per_example(
        cell["cfg"], str(here / "counts")) == 6 * 2 * 64 * 10
    out = harness.run(cell, 2 ** 33 + 3, 0.1, True, t0=0.0,
                      require_chip=False, kernels={"backend": "ref"},
                      log=lambda m: None)
    assert out["correct"] is True
    assert set(out["checks"]) == set(cell["limits"])
    assert out["metrics"]["compiles_in_window"]["value"] == 0.0
