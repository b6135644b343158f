"""Seeded weights of a dense GQA decoder, made by the benchmark on the
chip, in the type they are served in and in the layout the program's
``Model.param_specs`` names.  The program and the reference both read
these; neither makes weights of its own.

Layout (L layers, D model width, Hp query heads padded by the program to a
multiple of its tensor-parallel factor, K kv heads, hd head size, F the
feed-forward width, V the vocabulary):

    embed    [V, D]          tied with the output head
    final_ln [D]             stored as scale - 1 (the program's RMSNorm
                             multiplies by 1 + stored)
    layers/ln1, ln2 [L, D]   likewise
    layers/w_q  [L, D, Hp*hd]     query head h at columns h*hd..
    layers/w_kv [L, D, 2*K*hd]    keys, then values
    layers/w_o  [L, Hp*hd, D]
    layers/w_up [L, D, F]         the SiLU branch of the gated MLP
    layers/w_gate [L, D, F]       the linear branch
    layers/w_down [L, F, D]

Padding heads (those the published model does not have) are zero in w_q
and w_o, so a forward pass equals the published head count's.  Each leaf
is drawn from its own key, so one leaf can be drawn again alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: standard deviation of the embedding (and tied head) entries
EMBED_STD = 0.02
#: standard deviation of the norm scales around 1
NORM_STD = 0.05


def shapes(c: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, for a configuration dict (``bench/configs``)."""
    L, D = c["num_hidden_layers"], c["hidden_size"]
    hd = D // c["num_attention_heads"]
    hp, k = c["padded_heads"], c["num_key_value_heads"]
    f, v = c["intermediate_size"], c["vocab_size"]
    return {
        "embed": (v, D), "final_ln": (D,),
        "layers/ln1": (L, D), "layers/ln2": (L, D),
        "layers/w_q": (L, D, hp * hd), "layers/w_kv": (L, D, 2 * k * hd),
        "layers/w_o": (L, hp * hd, D),
        "layers/w_up": (L, D, f), "layers/w_gate": (L, D, f),
        "layers/w_down": (L, f, D),
    }


def head_mask(c: dict):
    """[Hp] bool: which of the padded query heads the published model has.
    The program maps query head h to kv head h // (Hp / K); the published
    model's head j of kv group g = j // (H / K) sits at position
    g * (Hp / K) + j % (H / K), and the rest of each group is padding."""
    hp, h, k = (c["padded_heads"], c["num_attention_heads"],
                c["num_key_value_heads"])
    per_group, real = hp // k, h // k
    return jnp.arange(hp) % per_group < real


def leaf(name: str, shape, key, c: dict, dtype):
    """One leaf, drawn from ``key``."""
    if name.endswith("ln1") or name.endswith("ln2") or name == "final_ln":
        return (NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name == "embed":
        return (EMBED_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    # fan-in of the published model (the padding heads carry zeros)
    fan_in = c["hidden_size"] if name.endswith("w_o") else shape[-2]
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    hd = c["hidden_size"] // c["num_attention_heads"]
    if name.endswith("w_q"):
        w = w * jnp.repeat(head_mask(c), hd)[None, None, :]
    elif name.endswith("w_o"):
        w = w * jnp.repeat(head_mask(c), hd)[None, :, None]
    return w.astype(dtype)


def keys(seed_key, c: dict) -> dict:
    names = sorted(shapes(c))
    return dict(zip(names, jax.random.split(seed_key, len(names))))


def nest(flat: dict) -> dict:
    """'layers/w_q' keys -> the program's nested tree."""
    out: dict = {}
    for k, v in flat.items():
        node = out
        *path, last = k.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


def make(seed_key, c: dict, shardings: dict | None = None,
         dtype=jnp.bfloat16) -> dict:
    """Every leaf in one jitted call on the device, nested as the program
    wants it; ``shardings`` (nested like the result) places each leaf."""
    shp = shapes(c)

    def body(key):
        ks = keys(key, c)
        return nest({n: leaf(n, shp[n], ks[n], c, dtype) for n in shp})

    return jax.jit(body, out_shardings=shardings)(seed_key)


def make_leaf(seed_key, c: dict, name: str, dtype=jnp.bfloat16):
    """Leaf ``name`` alone, equal to the one ``make`` draws."""
    shp = shapes(c)
    return jax.jit(lambda key: leaf(name, shp[name], keys(key, c)[name], c,
                                    dtype))(seed_key)
