"""Seeded random weights of the dense decoder, made on the device.

The benchmark makes the weights itself, in one jitted call from the run's
seed, as float32 (the type the program serves from: it holds float32
masters and computes in the configuration's compute dtype).  The same call
gives the plain reference the same weights later in the run, so the
reference takes nothing that the program made.

What belongs to one architecture is read through its reference module,
``reference/<name>.py``, which the configuration file names
(``"reference"``).  This file holds the dense decoder's part, which
``reference/dense_decoder.py`` takes from here: ``shapes`` (every weight's
shape and kind) and ``program_tree`` (the weights laid out as the
program's dense decoder takes them, ``repro.models`` parameters of one
layer group stacked over layers; ``run.py`` checks that layout against the
program's own before serving).  ``draw`` is for every architecture: it
makes any ``shapes`` table in one jitted call, laid out and placed as the
caller asks, so that a weight is made on the chips that hold it.  With
``jax_threefry_partitionable`` on (JAX's default), the values do not
depend on the placement: a sharded call gives, bit for bit, what one
device gives.

Scales keep activations of order one through depth: matrices are normal
with variance 1/fan_in, biases 0.1-normal, norm scales 1 + 0.1-normal and
the embedding unit-normal, so the logits spread over a few units.  A
table tied to the LM head is the head's matrix too, so it takes the head's
variance 1/d_model (the norm before the first layer rescales its rows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (more than 32 bits): the low
    word seeds the key and the high word is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(m: dict) -> dict:
    """Name -> (shape, kind) of every weight, in a fixed order."""
    d, H, KV, hd, ff, V, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                              m["head_dim"], m["d_ff"], m["vocab"],
                              m["n_layers"])
    out = {
        "embed": ((V, d), "tied" if m["tie_embeddings"] else "embed"),
        "norm1": ((L, d), "norm"),
        "wq": ((L, d, H * hd), "matrix"),
        "wk": ((L, d, KV * hd), "matrix"),
        "wv": ((L, d, KV * hd), "matrix"),
        "wo": ((L, H * hd, d), "matrix"),
        "norm2": ((L, d), "norm"),
        "w_gate": ((L, d, ff), "matrix"),
        "w_up": ((L, d, ff), "matrix"),
        "w_down": ((L, ff, d), "matrix"),
        "final_norm": ((d,), "norm"),
    }
    if m["qkv_bias"]:
        out.update({"bq": ((L, H * hd), "bias"), "bk": ((L, KV * hd), "bias"),
                    "bv": ((L, KV * hd), "bias")})
    if not m["tie_embeddings"]:
        out["lm_head"] = ((d, V), "matrix")
    return out


def _draw(key, shape, kind):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        return x * (shape[-2] ** -0.5)
    if kind == "bias":
        return 0.1 * x
    if kind == "norm":
        return 1.0 + 0.1 * x
    if kind == "tied":
        return x * (shape[-1] ** -0.5)
    return x                                    # embedding rows


def draw(spec: dict, seed: int, layout=None, shardings=None):
    """Every weight of ``spec`` (name -> (shape, kind), as ``shapes``
    gives it) for ``seed``, in one jitted call: a flat name -> array dict,
    or ``layout`` of it.  ``shardings``, given the abstract result, returns
    the call's ``out_shardings``: each device then makes only its own
    shards.  Without it the weights are made on the default device."""
    def gen(key):
        w = {name: _draw(jax.random.fold_in(key, i), shape, kind)
             for i, (name, (shape, kind)) in enumerate(spec.items())}
        return w if layout is None else layout(w)
    key = seed_key(seed)
    placed = {} if shardings is None else {
        "out_shardings": shardings(jax.eval_shape(gen, key))}
    return jax.jit(gen, **placed)(key)


def make(m: dict, seed: int, layout=None, shardings=None):
    """All weights of the dense decoder ``m`` for ``seed`` (see
    ``draw``)."""
    return draw(shapes(m), seed, layout, shardings)


def program_tree(w: dict) -> dict:
    """The flat weights as the program's dense decoder parameter tree."""
    def dense(name, bias=None):
        p = {"w": w[name]}
        if bias is not None and bias in w:
            p["b"] = w[bias]
        return p
    layer = {
        "norm1": {"scale": w["norm1"]},
        "mixer": {"wq": dense("wq", "bq"), "wk": dense("wk", "bk"),
                  "wv": dense("wv", "bv"), "wo": dense("wo")},
        "norm2": {"scale": w["norm2"]},
        "ffn": {"wi_gate": dense("w_gate"), "wi_up": dense("w_up"),
                "wo": dense("w_down")},
    }
    tree = {"embed": {"table": w["embed"]}, "groups": [[layer]],
            "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        tree["lm_head"] = {"w": w["lm_head"]}
    return tree
