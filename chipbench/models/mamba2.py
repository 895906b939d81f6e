"""mamba2 family: the program's model built from a configuration file, and
the weights the benchmark makes for it.

``build`` hands the sizes of ``configs/<name>.json`` to the program's model
code (the system under test). ``init`` makes the weights from a key in the
program's parameter layout, on the device in one jitted call; the program
and the plain reference both start from them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    """Derived sizes of a mamba2 configuration file."""
    s = cfg["ssm_cfg"]
    d, n, g = cfg["d_model"], s["d_state"], s["ngroups"]
    d_inner = s["expand"] * d
    heads = d_inner // s["headdim"]
    m = cfg["pad_vocab_size_multiple"]
    vocab = -(-cfg["vocab_size"] // m) * m
    return {"d_model": d, "layers": cfg["n_layer"], "vocab": vocab,
            "d_inner": d_inner, "heads": heads, "head_dim": s["headdim"],
            "d_state": n, "groups": g, "d_conv": s["d_conv"],
            "chunk": s["chunk_size"], "conv_dim": d_inner + 2 * g * n,
            "d_in_proj": 2 * d_inner + 2 * g * n + heads}


def build(cfg: dict):
    """The program's model (``repro.models.api.Model``) at these sizes."""
    from repro.models.api import ssm_model
    from repro.models.mamba import SSMConfig, SSMLMConfig

    z = sizes(cfg)
    return ssm_model(SSMLMConfig(
        name=cfg.get("name", "mamba2"), n_layers=z["layers"], vocab=z["vocab"],
        ssm=SSMConfig(d_model=z["d_model"], d_inner=z["d_inner"],
                      head_dim=z["head_dim"], d_state=z["d_state"],
                      n_groups=z["groups"], d_conv=z["d_conv"],
                      chunk=z["chunk"]),
        dtype=jnp.dtype(cfg["dtype"]), remat=cfg["remat"]))


def init(cfg: dict, key: jax.Array) -> dict:
    """Weights in the program's layout and dtype (jit this)."""
    z = sizes(cfg)
    L, d, H = z["layers"], z["d_model"], z["heads"]
    dtype = jnp.dtype(cfg["dtype"])
    k = iter(jax.random.split(key, 6))

    def normal(shape, std):
        return jax.random.normal(next(k), shape, jnp.float32) * std

    # A in [1, 16] and dt log-uniform in [0.001, 0.1] (Mamba-2's init);
    # dt_bias is softplus^-1(dt)
    a = jax.random.uniform(next(k), (L, H), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(next(k), (L, H)) *
                 (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    layers = {
        "ln": jnp.ones((L, d)),
        "mixer": {
            "in_proj": normal((L, d, z["d_in_proj"]), d ** -0.5),
            "conv_w": normal((L, z["d_conv"], z["conv_dim"]),
                             z["d_conv"] ** -0.5),
            "conv_b": jnp.zeros((L, z["conv_dim"])),
            "A_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((L, H)),
            "norm": jnp.ones((L, z["d_inner"])),
            "out_proj": normal((L, z["d_inner"], d), z["d_inner"] ** -0.5),
        },
    }
    params = {"embed": {"table": normal((z["vocab"], d), 0.02)},
              "layers": layers, "head": {"ln_f": jnp.ones((d,))}}
    return jax.tree.map(lambda a: a.astype(dtype), params)
