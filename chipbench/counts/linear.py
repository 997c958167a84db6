"""Work of the paper's linear model (one dense layer + softmax)."""


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one example's forward pass: x (F) @ w (F, C)."""
    return cfg["feat_dim"] * cfg["num_classes"]
