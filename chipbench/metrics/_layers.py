"""Device time by layer of the round step in a reduced trace.

The program names its layers with ``jax.named_scope``
(``repro/obs/layers.py``), and ``repro.obs.layers.op_layers()`` maps each
HLO instruction of the chunk it dispatched last to the layers on its
``op_name`` path, read from the compiled module's own text. A trace's op
names are those instruction names, so a layer's device time is the summed
self time of the ops whose layers hold it. Ops the table does not know
(other programs in the slice, such as the evaluation) are unattributed.
A program without named layers gives an empty table, and nothing is read.
"""


def op_table():
    try:
        from repro.obs.layers import op_layers
    except ImportError:
        return {}
    return op_layers()


def share(ctx, layer):
    """The layer's device time over the device's busy time in the traced
    slice, in %; None without a trace or without the layer in the table."""
    t = ctx.trace
    if t is None or t["busy_s"] <= 0:
        return None
    table = op_table()
    if not any(layer in layers for layers in table.values()):
        return None
    s = sum(v for k, v in t["ops"].items() if layer in table.get(k, ()))
    return 100.0 * s / t["busy_s"]


def breakdown(trace, table, top=5):
    """Seconds under each layer (a layer nested in another counts in
    both), under no layer, and not in the table, with the largest
    unattributed ops and the largest ops of all with their layers."""
    layers, none, missing = {}, 0.0, {}
    for op, s in trace["ops"].items():
        found = table.get(op)
        if found is None:
            missing[op] = s
        elif not found:
            none += s
        for name in found or ():
            layers[name] = layers.get(name, 0.0) + s
    busy = trace["busy_s"]
    return {"busy_s": busy, "layers_s": layers, "no_layer_s": none,
            "unattributed_s": sum(missing.values()),
            "found_share": 100.0 * (busy - sum(missing.values())) / busy,
            "top_unattributed": sorted(missing.items(),
                                       key=lambda kv: -kv[1])[:top],
            "top_ops": [(op, s, table.get(op)) for op, s in
                        sorted(trace["ops"].items(),
                               key=lambda kv: -kv[1])[:2 * top]]}
