"""GPT-2 (Radford et al. 2019): the program's model from the published
``config.json`` keys, and a plain reference forward pass.

The reference is pre-LN, learned positions, tied output head, tanh-GELU
(``gelu_new``, as published), LayerNorm epsilon 1e-5 (as published). It
reads the program's parameter tree but shares no code with it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def build(sizes: dict, *, interpret: bool = False):
    """The program's ``GPT`` for the published ``sizes``."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["n_embd"],
        num_layers=sizes["n_layer"], num_heads=sizes["n_head"],
        ffn_size=sizes["n_inner"], max_position=sizes["n_positions"],
        dropout=0.0)
    return GPT(cfg)


def _layer_norm(p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def reference_logits(params, ids, n_head: int):
    """(B, S) ids -> (B, S, V) logits, float32, full causal attention
    over the whole sequence. Call under
    ``jax.default_matmul_precision("highest")``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    b, s = ids.shape
    x = p["wte"]["weight"][ids] + p["wpe"]["weight"][jnp.arange(s)][None]
    d = x.shape[-1]
    dh = d // n_head
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(len(p["blocks"])):
        blk = p["blocks"][str(i)]
        h = _layer_norm(blk["ln1"], x)
        qkv = h @ blk["attn"]["qkv_proj"]["weight"] \
            + blk["attn"]["qkv_proj"]["bias"]
        q, k, v = (t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(dh))
        att = jnp.where(causal[None, None], att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", att, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + o @ blk["attn"]["out_proj"]["weight"] \
            + blk["attn"]["out_proj"]["bias"]
        h = _layer_norm(blk["ln2"], x)
        h = _gelu_tanh(h @ blk["mlp"]["fc1"]["weight"]
                       + blk["mlp"]["fc1"]["bias"])
        x = x + h @ blk["mlp"]["fc2"]["weight"] + blk["mlp"]["fc2"]["bias"]
    x = _layer_norm(p["ln_f"], x)
    return x @ p["wte"]["weight"].T
