"""Helpers shared by the benchmark's tests."""
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def shrink(cell):
    """The cell at a size a CPU test run holds: 16 clients of 40 rows,
    groups of 4, two rounds a chunk, and the model at its kind's ``shrink``
    size (64 features for the paper's models)."""
    cfg, mix = cell["cfg"], cell["mix"]
    mix.update(clients=16, samples_per_client=40, train_per_client=32,
               local_batch=32, eval_every=2)
    cfg["dp"]["per_example_chunk"] = 8
    cfg["p4"]["group_size"] = 4
    cell["kind"].shrink(cfg, mix)
    return cell


def cell_names():
    """The cells BENCHMARK.json declares."""
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]
