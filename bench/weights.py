"""Weights and adapters made on the device from ``--seed``, in the dtype
they are served in, each tree by one jitted call.

A large leaf is drawn a block of its leading axis at a time (a layer of a
stacked leaf, a slab of vocabulary rows), so no float32 copy of a whole leaf
exists at any point.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
_BLOCK_ELEMS = 1 << 25       # f32 elements drawn at once: 128 MiB
_PARAMS, _ADAPTERS = 0, 1
_STACKS = 1 << 16         # keys of the adapter stacks after the first


def key_from_seed(seed: int) -> jax.Array:
    """All of ``seed``'s bits: ``PRNGKey`` keeps only the low 32."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _normal(key, shape, std, dtype=BF16):
    rest = math.prod(shape[1:])
    if len(shape) < 2 or math.prod(shape) <= _BLOCK_ELEMS:
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    n0 = shape[0]
    c = max(d for d in range(1, n0 + 1)
            if n0 % d == 0 and (d * rest <= _BLOCK_ELEMS or d == 1))
    keys = jax.random.split(key, n0 // c)
    blocks = jax.lax.map(
        lambda k: (jax.random.normal(k, (c,) + tuple(shape[1:]), jnp.float32)
                   * std).astype(dtype), keys)
    return blocks.reshape(shape)


def make_params(cfg, seed: int, init_range: float) -> Dict:
    """The program's parameter tree (`transformer.model_defs`): matrices
    N(0, init_range), norms 1, zeros where the definition says zeros."""
    from repro.models import transformer as tf
    from repro.models.param import is_def

    leaves, treedef = jax.tree.flatten(tf.model_defs(cfg), is_leaf=is_def)
    kinds = [(tuple(d.shape), d.init) for d in leaves]

    @jax.jit
    def gen(key):
        out = []
        for i, (shape, init) in enumerate(kinds):
            if init == "zeros":
                out.append(jnp.zeros(shape, BF16))
            elif init == "ones":
                out.append(jnp.ones(shape, BF16))
            else:
                out.append(_normal(jax.random.fold_in(key, i), shape,
                                   init_range))
        return out

    key = jax.random.fold_in(key_from_seed(seed), _PARAMS)
    return jax.tree.unflatten(treedef, gen(key))


def adapter_stds(rank: int, relative: float, init_range: float) -> Dict:
    """Factor stds that give each adapter's delta W elements of std
    ``relative * init_range``: raw LoRA dW = B A sums ``rank`` products;
    jd dW = U Sigma V^T sums ``rank**2``, with Sigma ~ N(0, 1/rank)."""
    f = math.sqrt(relative * init_range / math.sqrt(rank))
    return {"factor": f, "sigma": 1.0 / math.sqrt(rank)}


def adapter_stacks(cfg) -> Dict[str, tuple]:
    """The program's adapter stacks and the leading axes of each
    (`transformer.lora_defs_tree`): ``{"layers": (L,)}`` for a dense model;
    a model whose layers come in kinds has a stack of each."""
    from repro.models import transformer as tf
    from repro.models.param import is_def

    return {key: tuple(jax.tree.leaves(per, is_leaf=is_def)[0].shape[:-2])
            for key, per in tf.lora_defs_tree(cfg).items()}


def make_adapters(cfg, dims: Dict[str, tuple], adapters: Dict, seed: int,
                  init_range: float) -> Dict:
    """The adapter collection in the layout `RealModelExecutor` takes:
    ``{stack: {target: {...}}}`` for each of the program's adapter stacks
    (`adapter_stacks`; ``"layers"`` with a leading layer axis L for a dense
    model), each target ``t`` of shape ``dims[t] = (d_in, d_out)`` (the
    architecture module's ``adapter_dims``).

    ``lora``: A (L, n, r, d_in), B (L, n, d_out, r), one pair per adapter.
    ``jd``: one shared basis per cluster, U (L, k, d_out, r) and
    V (L, k, d_in, r), a full Sigma (L, n, r, r) per adapter, and
    ``cluster_of`` (L, n) with adapter i in cluster ``i % k``."""
    mode, n, r = adapters["mode"], adapters["count"], adapters["rank"]
    stacks = adapter_stacks(cfg)
    std = adapter_stds(r, adapters["relative_size"], init_range)
    targets = list(adapters["targets"])
    if mode == "jd" and adapters.get("sigma", "full") != "full":
        raise ValueError("jd collections here carry a full Sigma")
    if mode not in ("lora", "jd"):
        raise ValueError(f"unknown adapter mode {mode!r}")
    k = int(adapters.get("clusters", 1))

    def stack(key, L):
        out = {}
        for i, t in enumerate(targets):
            di, do = dims[t]
            ka, kb, ks = jax.random.split(jax.random.fold_in(key, i), 3)
            if mode == "lora":
                out[t] = {"A": _normal(ka, L + (n, r, di), std["factor"]),
                          "B": _normal(kb, L + (n, do, r), std["factor"])}
            else:
                cl = jnp.arange(n, dtype=jnp.int32) % k
                out[t] = {"U": _normal(ka, L + (k, do, r), std["factor"]),
                          "V": _normal(kb, L + (k, di, r), std["factor"]),
                          "sigma": _normal(ks, L + (n, r, r), std["sigma"]),
                          "cluster_of": jnp.broadcast_to(cl, L + (n,))}
        return out

    @jax.jit
    def gen(key):
        # the first stack draws from the key itself, each later one from
        # a key of its own
        return {name: stack(key if j == 0 else jax.random.fold_in(
                    key, _STACKS + j), L)
                for j, (name, L) in enumerate(stacks.items())}

    return gen(jax.random.fold_in(key_from_seed(seed), _ADAPTERS))
