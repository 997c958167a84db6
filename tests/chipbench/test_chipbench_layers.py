"""The per-layer readers over the program's op -> layer table: shares of the
busy time on a synthetic reduced trace, nothing without a trace or without
the layer, and the breakdown the layer report prints."""
import pytest

from chipbench import harness, trace_reduce
from chipbench.metrics import _layers

TABLE = {
    "gather.1": ("sample_batches",),
    "fusion.2": ("local_update", "proxy_dp_grad", "per_example_grads"),
    "copy.3": ("local_update", "proxy_dp_grad", "per_example_grads"),
    "sq_norms.7": ("local_update", "proxy_dp_grad", "dp_clip"),
    "fusion.4": ("local_update", "metrics_step"),
    "fusion.5": ("local_update", "private_grad"),
    "reduce.6": ("aggregate",),
    "while.8": (),
}
TRACE = {"busy_s": 10.0, "window_s": 10.5, "ops": {
    "gather.1": 0.2, "fusion.2": 4.0, "copy.3": 1.0, "sq_norms.7": 2.0,
    "fusion.4": 0.5, "fusion.5": 1.5, "reduce.6": 0.1, "while.8": 0.2,
    "fusion.99": 0.5}}

SHARES = {"client_step_share": 90.0, "per_example_grad_share": 50.0,
          "metrics_step_share": 5.0, "batch_gather_share": 2.0,
          "aggregate_share": 1.0}


class Ctx:
    def __init__(self, trace):
        self.trace = trace


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(_layers, "op_table", lambda: TABLE)


@pytest.mark.parametrize("name", sorted(SHARES))
def test_readers_give_the_layers_share(table, name):
    assert harness.reader(name)(Ctx(TRACE)) == pytest.approx(SHARES[name])


@pytest.mark.parametrize("name", sorted(SHARES))
def test_readers_read_nothing_without_a_trace(monkeypatch, name):
    def no_table():
        raise AssertionError("the table is built only with a trace")
    monkeypatch.setattr(_layers, "op_table", no_table)
    assert harness.reader(name)(Ctx(None)) is None


def test_a_layer_the_program_does_not_name_reads_nothing(monkeypatch):
    monkeypatch.setattr(_layers, "op_table", lambda: {})
    assert harness.reader("client_step_share")(Ctx(TRACE)) is None
    monkeypatch.setattr(_layers, "op_table",
                        lambda: {"gather.1": ("sample_batches",)})
    assert harness.reader("aggregate_share")(Ctx(TRACE)) is None


def test_the_client_step_holds_its_inner_layers(table):
    ctx = Ctx(TRACE)
    inner = sum(harness.reader(n)(ctx) for n in
                ("per_example_grad_share", "metrics_step_share"))
    dp_clip = 100.0 * 2.0 / TRACE["busy_s"]
    assert inner + dp_clip <= harness.reader("client_step_share")(ctx) <= 100


def test_breakdown_splits_busy_time():
    out = _layers.breakdown(TRACE, TABLE)
    assert out["layers_s"]["local_update"] == pytest.approx(9.0)
    assert out["layers_s"]["proxy_dp_grad"] == pytest.approx(7.0)
    assert out["no_layer_s"] == pytest.approx(0.2)
    assert out["unattributed_s"] == pytest.approx(0.5)
    assert out["found_share"] == pytest.approx(95.0)
    assert out["top_unattributed"] == [("fusion.99", 0.5)]
    assert out["top_ops"][:2] == [("fusion.2", 4.0, TABLE["fusion.2"]),
                                  ("sq_norms.7", 2.0, TABLE["sq_norms.7"])]
    assert ("fusion.99", 0.5, None) in out["top_ops"]


def test_program_spans_keep_out_of_the_harness_labels():
    from repro.obs.spans import RESERVED
    assert set(trace_reduce.HOST_LABELS) | {"window"} <= set(RESERVED)
