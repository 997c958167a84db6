"""Work of one chip's share of Mellum2-12B-A2.5B on one sequence of
``seq_len`` tokens (``models/mellum2.py``).

Per token and layer the matrix products are the attention projections
(q, k, v, o), the router over every published expert, and the held
experts' three products, counted for the held choices a token makes on
average: top-k of the published experts, so k·held/published of them.
The output head is one product over the vocabulary slice; the embedding
is a gather. Attention counts its score and value products over the keys a
query may read: every earlier position on a full layer, at most
``sliding_window`` of them on a sliding one (masked keys are not work the
model needs, as the CNN's count leaves out SAME padding)."""


def _keys(s: int, window: int) -> int:
    """Keys read by the s queries of a causal layer, summed."""
    if not window:
        return s * (s + 1) // 2
    w = min(window, s)
    return w * (w + 1) // 2 + (s - w) * w


def layer_macs(cfg) -> dict:
    """Multiply-accumulates of one sequence's forward pass, by part."""
    s, d = cfg["seq_len"], cfg["hidden_size"]
    hd, hq = cfg["head_dim"], cfg["num_attention_heads"]
    kvh, f = cfg["num_key_value_heads"], cfg["moe_intermediate_size"]
    E = cfg["deployment"]["num_experts_published"]
    held, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    out = {"projections": 0, "router": 0, "experts": 0, "attention": 0}
    for t in cfg["layer_types"]:
        window = cfg["sliding_window"] if t == "sliding_attention" else 0
        out["projections"] += s * d * hd * (2 * hq + 2 * kvh)
        out["router"] += s * d * E
        out["experts"] += s * k * held * 3 * d * f // E
        out["attention"] += 2 * hq * hd * _keys(s, window)
    out["head"] = s * d * cfg["vocab_size"]
    return out


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one example's (one sequence's) forward."""
    return sum(layer_macs(cfg).values())
