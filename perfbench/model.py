"""A configuration file, read into the sizes the benchmark works with, and
the random weights of a run, made from ``--seed``.

A configuration is ``configs/<name>.json``: the model's published
``config.json`` keys at the top level (a key the benchmark cut to size is
listed under ``reduced``) and, beside them, ``serving`` (the
``repro.serving.ServingConfig`` fields the deployment runs with), the
sizes it ``assumed`` and the ``deployment`` it stands for.

The weights are the benchmark's own: the program is handed them, and the
reference makes the same ones again, leaf by leaf, from the same seed.
Every leaf is drawn from its own key, ``fold_in(fold_in(seed key, leaf),
layer)``, so a leaf comes out bit for bit the same whether it is made in
the one jitted call that builds the whole tree or alone.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent

# leaves of one decoder block, in key order: (name, fan-in axis or None)
BLOCK_LEAVES = (
    ("attn/wq", 0), ("attn/wk", 0), ("attn/wv", 0), ("attn/wo", 0),
    ("attn/q_norm", None), ("attn/k_norm", None),
    ("ln1", None), ("ln2", None),
    ("ffn/wi_gate", 0), ("ffn/wi_up", 0), ("ffn/wo", 0),
)
TOP_LEAVES = (("embed", 1), ("lm_head", 0), ("final_norm", None))


@dataclass(frozen=True)
class Dims:
    """The sizes of a dense Qwen3-style decoder."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c.get("hidden_act", "silu") != "silu" or c.get("attention_bias"):
            raise ValueError("only bias-free SwiGLU decoders are supported")
        if c.get("tie_word_embeddings"):
            raise ValueError("tied embeddings are not supported")
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]),
                   dtype=c.get("torch_dtype", "bfloat16"))

    def leaf_shape(self, name: str):
        d, hd = self.d_model, self.head_dim
        return {
            "attn/wq": (d, self.heads * hd), "attn/wk": (d, self.kv_heads * hd),
            "attn/wv": (d, self.kv_heads * hd),
            "attn/wo": (self.heads * hd, d),
            "attn/q_norm": (hd,), "attn/k_norm": (hd,),
            "ln1": (d,), "ln2": (d,),
            "ffn/wi_gate": (d, self.d_ff), "ffn/wi_up": (d, self.d_ff),
            "ffn/wo": (self.d_ff, d),
            "embed": (self.vocab, d), "lm_head": (d, self.vocab),
            "final_norm": (d,),
        }[name]


def load_config(name: str, root: Path = HERE / "configs") -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds above 2**32 included)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_leaf(dims: Dims, key, name: str, layer: int, fan_in_axis):
    """One leaf in the served dtype.  Matrices are N(0, 1/fan_in); norm
    scales are ones, as a freshly initialised Qwen3 has them."""
    shape = dims.leaf_shape(name)
    dt = getattr(jnp, dims.dtype)
    if fan_in_axis is None:
        return jnp.ones(shape, dt)
    names = [n for n, _ in BLOCK_LEAVES + TOP_LEAVES]
    k = jax.random.fold_in(jax.random.fold_in(key, names.index(name)), layer)
    std = shape[fan_in_axis] ** -0.5
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for name, v in flat.items():
        node = out
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def make_params(dims: Dims, seed: int):
    """The whole parameter tree, on the device, in one jitted call, laid out
    as ``repro.models`` serves it: per-block leaves stacked on a leading
    layer axis under ``blocks``."""

    @jax.jit
    def build(key):
        top = {n: make_leaf(dims, key, n, 0, ax) for n, ax in TOP_LEAVES}
        blocks = {n: jnp.stack([make_leaf(dims, key, n, i, ax)
                                for i in range(dims.layers)])
                  for n, ax in BLOCK_LEAVES}
        tree = _nest(top)
        tree["blocks"] = _nest(blocks)
        return tree

    return build(seed_key(seed))


def block_leaves(dims: Dims, seed: int, layer: int) -> dict:
    """Block ``layer``'s leaves alone, equal to ``make_params``' slice."""
    key = seed_key(seed)
    return _nest({n: make_leaf(dims, key, n, layer, ax)
                  for n, ax in BLOCK_LEAVES})


def top_leaf(dims: Dims, seed: int, name: str):
    ax = dict(TOP_LEAVES)[name]
    return make_leaf(dims, seed_key(seed), name, 0, ax)
