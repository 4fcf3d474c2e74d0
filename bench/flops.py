"""Operation and byte counts of one training step, from a configuration's
shapes alone.  The benchmark's yardstick: nothing here reads the program.

The configuration dicts are the files under ``bench/configs/``, with the
HuggingFace-style keys they share (``hidden_size``, ``num_attention_heads``,
...).
"""

from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"]


def layer_matrices(cfg: dict) -> list:
    """The (in_dim, out_dim) matrices of one layer: q, k, v, o, gate, up,
    down."""
    d, h, kv, hd, ff = _dims(cfg)
    return [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d),
            (d, ff), (d, ff), (ff, d)]


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matrix product per token: every layer's
    projections and the output head (tied or not).  The embedding lookup is
    a gather and does not count."""
    per_layer = sum(m * n for m, n in layer_matrices(cfg))
    return cfg["num_hidden_layers"] * per_layer \
        + cfg["vocab_size"] * cfg["hidden_size"]


def param_count(cfg: dict) -> int:
    """Every parameter the model holds: matrices, embedding (and untied
    head), norms and biases."""
    d, h, kv, hd, _ = _dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = sum(m * n for m, n in layer_matrices(cfg)) + 2 * d
    if cfg.get("attention_bias"):
        per_layer += h * hd + 2 * kv * hd
    head = 0 if cfg["tie_word_embeddings"] else V * d
    return L * per_layer + V * d + head + d


def model_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Model FLOP of one training step (forward and backward), PaLM's
    appendix-B count: 6 x matmul parameters per token, plus 12 x layers x
    heads x head_dim x sequence per token for the attention scores and
    values (the full square, as the program computes it).  Recomputation
    (remat) does not count."""
    d, h, kv, hd, _ = _dims(cfg)
    tokens = batch * seq
    attn = 12.0 * cfg["num_hidden_layers"] * h * hd * seq
    return tokens * (6.0 * matmul_params(cfg) + attn)


def gram_ns_flops(m: int, n: int, num_steps: int = 5) -> float:
    """FLOP of Gram-space Newton-Schulz on one m x n matrix at the
    symmetric-product count: half a SYRK for G0 = X X^T, (4k - 3) symmetric
    m x m products at half a GEMM each, and the full m x m @ m x n product
    for Q_k X0.  The short side is the Gram side.  The same count holds
    whether the jnp path or the Pallas kernels run it."""
    if m > n:
        m, n = n, m
    rect = 2.0 * m * m * n
    mm = 2.0 * m * m * m
    return rect / 2.0 + (4 * num_steps - 3) * mm / 2.0 + rect


def optimizer_work(cfg: dict, mode: str, ns_steps: int = 5,
                   chips: int = 1) -> dict:
    """The optimizer's least work per step and per chip.

    ``flops``: Gram NS over every Muon matrix (none under AdamW).
    ``bytes``: each parameter read and written once, its gradient read once,
    and each optimizer-state buffer read and written once, in float32:
    momentum for Muon matrices, the two AdamW moments for the rest.
    Owner mode spreads both evenly over ``chips``."""
    L = cfg["num_hidden_layers"]
    mats = layer_matrices(cfg)
    n_matrix = L * sum(m * n for m, n in mats)
    n_all = param_count(cfg)
    if mode == "adamw":
        flops, n_muon = 0.0, 0
    else:
        flops = L * sum(gram_ns_flops(m, n, ns_steps) for m, n in mats)
        n_muon = n_matrix
    n_adam = n_all - n_muon
    nbytes = 4.0 * (n_all * 3 + n_muon * 2 + n_adam * 4)
    return {"flops": flops / chips, "bytes": nbytes / chips}
