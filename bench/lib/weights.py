"""Served weights: the program's parameter layout filled from ``draw``.

The layout (leaf names, stacked layer axis, dtypes) and the logical
sharding specs are read from the program's own ``Model.init`` by
``jax.eval_shape``; every value comes from ``draw``, so the reference can
regenerate it. Both models are built on the device in one jitted call, in
the dtype they are served in. On a mesh each leaf is made straight into the
sharding that the program's specs give it under ``INFERENCE_RULES``, so no
chip ever holds a whole model; the draw does not depend on the layout
(partitionable threefry), so the values are those of one device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.tree_util import DictKey, SequenceKey

from .draw import draw


def _canonical(path):
    """Program leaf path -> (canonical name, stacked over layers?)."""
    keys = [p.key if isinstance(p, DictKey) else p.idx for p in path
            if isinstance(p, (DictKey, SequenceKey))]
    if keys[0] == "groups":
        # groups[0] is the one-kind pattern of a dense model: leaves
        # (n_layers, ...) named <block part>/<weight>
        if keys[1] != 0:
            raise ValueError(f"unexpected layer pattern leaf {keys}")
        return ".".join(str(k) for k in keys[2:]), True
    if keys[0] in ("embed", "lm_head", "final_norm") and len(keys) == 1:
        return keys[0], False
    raise ValueError(f"the benchmark cannot fill parameter leaf {keys}")


def _fill(shapes, key, dims: dict, tag: str):
    def leaf(path, sds):
        name, stacked = _canonical(path)
        if stacked:
            n = sds.shape[0]
            v = jax.vmap(lambda l: draw(key, dims, tag, name, l))(
                jnp.arange(n, dtype=jnp.int32))
        else:
            v = draw(key, dims, tag, name)
        if v.shape != sds.shape:
            raise ValueError(f"{tag}/{name}: draw {v.shape} != layout "
                             f"{sds.shape}")
        return v.astype(sds.dtype)
    return jax.tree_util.tree_map_with_path(leaf, shapes)


def layout(model):
    """(shapes of the program's params, their logical specs), nothing
    allocated."""
    specs = []

    def init(k):
        params, s = model.init(k)
        specs.append(s)
        return params
    return jax.eval_shape(init, jax.random.PRNGKey(0)), specs[0]


def build(models, key, mesh=None):
    """models: [(program Model, canonical dims, tag)]. Returns the params
    of each, made in one jitted call from ``key``: on the default device,
    or with ``mesh`` in the program's shardings over it."""
    layouts = [layout(m) for m, _, _ in models]

    def make(k):
        return tuple(_fill(lay, k, dims, tag)
                     for (lay, _), (_, dims, tag) in zip(layouts, models))
    if mesh is None:
        return jax.jit(make)(key)
    from repro.sharding.rules import INFERENCE_RULES, make_param_shardings
    shardings = tuple(make_param_shardings(specs, lay, mesh, INFERENCE_RULES)
                      for lay, specs in layouts)
    return jax.jit(make, out_shardings=shardings)(key)
