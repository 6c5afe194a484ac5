"""Layout `single`: the whole model on one chip, no mesh.

`build(model, devices, seed) -> (mesh_or_None, params)`.  The weights
are the benchmark's own data, made from `--seed` on the device in one
jitted call and in the dtype they are served in; nothing is taken from
the program's initialisers, so the plain reference (`reference.py`) and
the program read the same numbers and neither made them.  The tree is
the one `models/decode.py` reads with `scan_layers` (a leading layer
axis); `tests/test_rehearsal.py` pins it against `Transformer.init`.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmarks import cost


def shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    """leaf path -> (shape, fan_in or None for a norm scale)."""
    d = model['hidden_size']
    hd = cost.head_dim(model)
    h_q = model['num_attention_heads']
    h_kv = model['num_key_value_heads']
    f = model['intermediate_size']
    v = model['vocab_size']
    n = model['num_hidden_layers']
    return {
        ('embed', 'embedding'): ((v, d), 2500),     # std 0.02
        ('final_norm', 'scale'): ((d,), None),
        ('lm_head', 'kernel'): ((d, v), d),
        ('layers', 'layer', 'attn_norm', 'scale'): ((n, d), None),
        ('layers', 'layer', 'mlp_norm', 'scale'): ((n, d), None),
        ('layers', 'layer', 'attn', 'q_proj', 'kernel'):
            ((n, d, h_q, hd), d),
        ('layers', 'layer', 'attn', 'k_proj', 'kernel'):
            ((n, d, h_kv, hd), d),
        ('layers', 'layer', 'attn', 'v_proj', 'kernel'):
            ((n, d, h_kv, hd), d),
        ('layers', 'layer', 'attn', 'o_proj', 'kernel'):
            ((n, h_q, hd, d), h_q * hd),
        ('layers', 'layer', 'mlp', 'gate_proj', 'kernel'): ((n, d, f), d),
        ('layers', 'layer', 'mlp', 'up_proj', 'kernel'): ((n, d, f), d),
        ('layers', 'layer', 'mlp', 'down_proj', 'kernel'): ((n, f, d), f),
    }


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build(model: Dict[str, Any], devices, seed: int):
    import jax
    import jax.numpy as jnp

    spec = shapes(model)
    dtype = jnp.dtype(model['torch_dtype'])

    def make(key):
        tree: Dict[str, Any] = {}
        for i, (path, (shape, fan_in)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if fan_in is None:
                # Norm scales near 1 and not all equal, so a dropped
                # scale shows; f32 as the program keeps them.
                leaf = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                leaf = (jax.random.normal(k, shape, jnp.float32) *
                        fan_in ** -0.5).astype(dtype)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return tree

    with jax.default_device(devices[0]):
        params = jax.jit(make)(seed_key(seed))
    return None, params
