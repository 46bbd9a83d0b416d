"""Seeded weights and optimizer state for the benchmark's training cells.

The benchmark makes its own weights; the program under test and the
reference both start from them.  The tree follows the trainer's state
layout (``params`` / ``opt`` / ``step``; layer leaves stacked along a
leading layer axis in one scanned group), but its shapes come from the
configuration file's published keys and its values from ``--seed``:
nothing here is read from the program.  ``harness.build`` checks the tree
against the trainer's own state shapes before handing it over.

Norm weights are stored as an offset from one (gain = 1 + w), which is the
trainer's convention; the reference reads them the same way.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import registry

Tree = Dict[str, Any]


def seed32(seed: int) -> int:
    """A 32-bit key seed from any non-negative whole-number ``--seed``."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def seed_key(seed: int):
    """The weights' random key for ``--seed``; an argument of the jitted
    makers, so that their compiled programs do not depend on the seed."""
    return jax.random.key(seed32(seed))


def padded_vocab(vocab: int) -> int:
    return ((vocab + 255) // 256) * 256


def layer_spec(hf: Dict[str, Any]
               ) -> Dict[str, Tuple[Tuple[int, ...], str, Any]]:
    """Per-layer leaves: path -> (shape, dtype, init rule), from the
    architecture's module (``registry.arch``); a rule is the name of one
    that :func:`_leaf` knows, or a function (key, shape) -> float32
    values."""
    return registry.arch(hf["model_type"]).layer_spec(hf)


def _leaf(key, shape, dtype, rule):
    f32 = jnp.float32
    if callable(rule):
        v = rule(key, shape)
    elif rule == "fan_in":
        std = 1.0 / math.sqrt(shape[-2])
        v = jax.random.truncated_normal(key, -2.0, 2.0, shape, f32) * std
    elif rule == "embed":
        v = jax.random.normal(key, shape, f32) * 0.02
    elif rule == "gain":
        v = jax.random.normal(key, shape, f32) * 0.05
    elif rule == "small":
        v = jax.random.normal(key, shape, f32) * 0.02
    else:
        raise ValueError(rule)
    return v.astype(dtype)


def _nest(flat: Dict[str, Any]) -> Tree:
    out: Tree = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split(".")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def init_params(hf: Dict[str, Any], key) -> Tree:
    """The parameter tree, made from ``key`` (call under ``jax.jit``)."""
    k = lambda path: jax.random.fold_in(key, zlib.crc32(path.encode()))  # noqa: E731
    n_layers = hf["num_hidden_layers"]
    d = hf["hidden_size"]
    layer = {}
    for path, (shape, dtype, rule) in layer_spec(hf).items():
        layer[path] = _leaf(k("layers." + path), (n_layers,) + shape,
                            dtype, rule)
    return {
        "embed": {"tok": _leaf(k("embed.tok"),
                               (padded_vocab(hf["vocab_size"]), d),
                               hf["torch_dtype"], "embed")},
        "groups": [[_nest(layer)]],
        "final_norm": _leaf(k("final_norm"), (d,), hf["torch_dtype"],
                            "gain"),
    }


def init_state(hf: Dict[str, Any], key) -> Tree:
    """Params plus the AdamW state at step 0 (f32 moments at zero, and an
    f32 master copy of the params where any is of a lower precision, as
    the trainer keeps one)."""
    params = init_params(hf, key)
    zeros = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)
    opt = {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, zeros)}
    if any(t.dtype != jnp.float32 for t in jax.tree.leaves(params)):
        opt["master"] = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}


def master_params(hf: Dict[str, Any], key) -> Tree:
    """The f32 parameters at step 0: the weights, widened."""
    return jax.tree.map(lambda t: t.astype(jnp.float32),
                        init_params(hf, key))


# -- per-leaf norms ----------------------------------------------------------

def leaf_names(hf: Dict[str, Any]) -> List[str]:
    """One name per compared leaf: each layer's slice of a stacked leaf,
    the embedding and the final norm."""
    names = ["embed.tok"]
    for l in range(hf["num_hidden_layers"]):
        names += [f"layers.{l}.{p}" for p in layer_spec(hf)]
    return names + ["final_norm"]


def leaf_norms(tree: Tree, hf: Dict[str, Any]) -> jax.Array:
    """The f32 norm of every leaf of :func:`leaf_names`, as one vector (call
    under ``jax.jit``)."""
    layer = tree["groups"][0][0]
    f = lambda t: jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32))))  # noqa: E731
    out = [f(tree["embed"]["tok"])]
    per_path = {}
    for path in layer_spec(hf):
        node = layer
        for part in path.split("."):
            node = node[part]
        t = node.astype(jnp.float32)
        per_path[path] = jnp.sqrt(jnp.sum(jnp.square(t),
                                          axis=tuple(range(1, t.ndim))))
    for l in range(hf["num_hidden_layers"]):
        out += [per_path[p][l] for p in layer_spec(hf)]
    out.append(f(tree["final_norm"]))
    return jnp.stack(out)


def check_layout(ours: Tree, theirs: Tree) -> None:
    """Raise unless two trees (of arrays or shape structs) agree in
    structure, shapes and dtypes."""
    a = jax.tree_util.tree_flatten_with_path(ours)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    sa = {jax.tree_util.keystr(p): (tuple(v.shape), str(v.dtype)) for p, v in a}
    sb = {jax.tree_util.keystr(p): (tuple(v.shape), str(v.dtype)) for p, v in b}
    if sa != sb:
        diff = sorted(set(sa.items()) ^ set(sb.items()))[:6]
        raise ValueError(f"the benchmark's state layout differs from the "
                         f"trainer's: {diff}")
