"""Work counts the metrics divide by, from shapes alone.

``train_flops_per_token``: the operations the forward and backward passes
of a dense decoder need per trained token: matrix multiplications only
(2 per multiply-add), the backward pass twice the forward, attention
counted causally (each query attends to the keys at or before it, within
the sliding window), recomputation from rematerialization not counted.

``summarize_work``: what the pattern-summary algorithm needs for one call
on a real ``(E, n)`` block of utilization rows: one read of every sample
(f32) and of each row's target, and one write of each row's three outputs.
"""
from __future__ import annotations


def mean_context(seq_len: int, window: int = 0) -> float:
    """Mean number of keys a causal query attends to over a sequence."""
    w = window if window and window < seq_len else seq_len
    # positions t = 0 .. S-1 attend to min(t + 1, w) keys
    full = w * (w + 1) / 2 + (seq_len - w) * w
    return full / seq_len


def layer_matmul_params(c: dict) -> int:
    """Weights one decoder layer multiplies each token by."""
    d, h, kv, hd, ff = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = (3 if c["mlp"] in ("swiglu", "geglu") else 2) * d * ff
    return attn + mlp


def forward_flops_per_token(c: dict, seq_len: int) -> float:
    layers = c["num_hidden_layers"]
    ctx = mean_context(seq_len, c.get("sliding_window") or 0)
    per_layer = (2 * layer_matmul_params(c)
                 + 4 * c["num_attention_heads"] * c["head_dim"] * ctx)
    head = 2 * c["hidden_size"] * c["vocab_size"]
    return layers * per_layer + head


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(c, seq_len)


def summarize_work(rows: int, samples: int) -> dict:
    """Operations and bytes one summarize call needs on ``(rows, samples)``:
    a handful of operations per sample (prefix sum, run length, threshold
    test, selection) and the bytes of one pass over the rows."""
    return {"ops": 4.0 * rows * samples,
            "bytes": 4.0 * rows * samples + 4.0 * rows + 12.0 * rows}


def least_time_s(work: dict, flops_s: float, bytes_s: float):
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bytes/s, and which of the two it is."""
    t_ops, t_bytes = work["ops"] / flops_s, work["bytes"] / bytes_s
    return (t_bytes, "memory") if t_bytes >= t_ops else (t_ops, "compute")


def utilization(tokens_per_s: float, c: dict, seq_len: int, chips: int,
                peak_flops: float) -> float:
    """Model FLOP utilization in %: trained tokens per second times the
    FLOPs each needs, over the chips' peak."""
    return 100.0 * tokens_per_s * train_flops_per_token(c, seq_len) / (
        chips * peak_flops)
