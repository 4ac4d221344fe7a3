"""Seeded models from a configuration's tensor list, made on the device.

A configuration file lists its tensors in architecture order::

    "tensors": [
      ["model.embed_tokens.weight", [11568, 2048], "normal"],
      {"repeat": "num_hidden_layers", "tensors": [
        ["model.layers.{i}.input_layernorm.weight", [2048], "ones"], ...]},
      ["lm_head.weight", [2048, 11568], "normal"]
    ]

``normal`` draws N(0, initializer_range); ``zeros`` and ``ones`` are
constant. A fine-tune adds to every matrix a perturbation of N(0, 1)
times ``rel_std`` times that matrix's RMS, and keeps vectors as they are.

Everything is drawn in one jitted call from the seed, in float32 (the
type the store is given), so the same seed gives the same models.
"""

from __future__ import annotations

import math

INITS = ("normal", "zeros", "ones")


def tensor_specs(config: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(name, shape, init)`` of every tensor, in architecture order."""
    out = []
    for entry in config["tensors"]:
        if isinstance(entry, dict):
            for i in range(int(config[entry["repeat"]])):
                out += [(n.format(i=i), tuple(s), init)
                        for n, s, init in entry["tensors"]]
        else:
            name, shape, init = entry
            out.append((name, tuple(shape), init))
    for name, _, init in out:
        if init not in INITS:
            raise ValueError(f"{name}: unknown init {init!r}")
    return out


def n_params(config: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in tensor_specs(config))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, wider than 32 bits too."""
    import jax

    key = jax.random.key(seed % (1 << 32))
    return jax.random.fold_in(key, (seed >> 32) % (1 << 32))


def _draw(config: dict, seed: int, n_finetunes: int, rel_std: float):
    """``(base, tuned)`` on the device: ``tuned`` holds, per matrix, its
    ``n_finetunes`` fine-tuned copies stacked on a leading axis."""
    import jax
    import jax.numpy as jnp

    specs = tensor_specs(config)
    std = float(config["initializer_range"])

    def build(key):
        base, tuned = {}, {}
        for i, (name, shape, init) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if init == "normal":
                w = std * jax.random.normal(k, shape, jnp.float32)
            else:
                w = jnp.full(shape, 0.0 if init == "zeros" else 1.0,
                             jnp.float32)
            base[name] = w
            if len(shape) >= 2 and n_finetunes:
                rms = jnp.sqrt(jnp.mean(jnp.square(w)))
                noise = jax.random.normal(jax.random.fold_in(k, 1 << 30),
                                          (n_finetunes, *shape), jnp.float32)
                tuned[name] = w + (rel_std * rms) * noise
        return base, tuned

    return jax.jit(build)(seed_key(seed))


def _split(config: dict, base: dict, tuned: dict, n_finetunes: int):
    order = [name for name, _, _ in tensor_specs(config)]
    return ({n: base[n] for n in order},
            [{n: tuned[n][j] if n in tuned else base[n] for n in order}
             for j in range(n_finetunes)])


def make_models_on_device(config: dict, seed: int, n_finetunes: int,
                          rel_std: float):
    """``(base, [fine-tune, ...])`` as dicts of device arrays."""
    base, tuned = _draw(config, seed, n_finetunes, rel_std)
    return _split(config, base, tuned, n_finetunes)


def make_models(config: dict, seed: int, n_finetunes: int, rel_std: float):
    """As :func:`make_models_on_device`, copied to host numpy arrays."""
    import jax

    base, tuned = jax.device_get(_draw(config, seed, n_finetunes, rel_std))
    return _split(config, base, tuned, n_finetunes)
