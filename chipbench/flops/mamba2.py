"""Model FLOPs of mamba2 training, from the configuration file's sizes.

Dense forward and backward (3 x forward), without the forward that remat
recomputes: the in and out projections, the depthwise conv, the SSD chunked
scan as the chunked algorithm computes it (each chunk's Q x Q block dense),
and the tied LM head. Elementwise work (norms, gates, softplus, exp) is not
counted, as users of MFU do not count it.
"""
from __future__ import annotations

from chipbench.models.mamba2 import sizes


def forward_per_token(cfg: dict) -> float:
    """Forward FLOPs of one token of one sequence."""
    z = sizes(cfg)
    d, e, n, g, q = (z["d_model"], z["d_inner"], z["d_state"], z["groups"],
                     z["chunk"])
    proj = 2 * d * z["d_in_proj"] + 2 * e * d
    conv = 2 * z["d_conv"] * z["conv_dim"]
    # intra-chunk: C.B scores per group over the chunk, then the scores
    # times x for every head; chunk states (B x) and their read-out (C h)
    ssd = 2 * q * g * n + 2 * q * e + 2 * n * e + 2 * n * e
    return z["layers"] * (proj + conv + ssd) + 2 * d * z["vocab"]


def train_per_token(cfg: dict) -> float:
    return 3 * forward_per_token(cfg)


def dithered_backward_bytes(cfg: dict, tokens: int) -> float:
    """Least HBM bytes of one step's dithered backward of every in and out
    projection: g, x and w read once, dx and dW written once."""
    z = sizes(cfg)
    width = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["dtype"]]

    def layer(k: int, n: int) -> int:
        return width * (tokens * n + 2 * tokens * k + 2 * k * n)

    return z["layers"] * (layer(z["d_model"], z["d_in_proj"]) +
                          layer(z["d_inner"], z["d_model"]))
