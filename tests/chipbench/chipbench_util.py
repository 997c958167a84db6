"""Helpers shared by the benchmark's tests."""
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def shrink(cell):
    """The cell at a size a CPU test run holds: 16 clients of 40 rows, 64
    features, groups of 4, two rounds a chunk, the jnp kernel backend."""
    cfg, mix = cell["cfg"], cell["mix"]
    mix.update(clients=16, samples_per_client=40, train_per_client=32,
               local_batch=32, eval_every=2)
    cfg["dp"]["per_example_chunk"] = 8
    cfg["feat_dim"] = 64
    if cfg["model"] == "cnn":
        cfg["cnn_shape"] = [4, 4, 4]
    cfg["p4"]["group_size"] = 4
    return cell


def cell_names():
    """The cells BENCHMARK.json declares."""
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]
