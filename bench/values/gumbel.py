"""Gumbel-skewed values: ``loc - scale·ln E`` with ``E ~ Exp(1)``, float32.

``values.loc`` and ``values.scale`` are ranges; each metric (or each pool
window) draws its own ``loc`` and ``scale`` from them, then its values.
"""
import numpy as np

from generator import Windows


def build(config: dict, traffic: dict, seed: int) -> Windows:
    dist = config["values"]
    per_window = int(config["values_per_window"])

    def draw(rng: np.random.Generator, n_windows: int) -> np.ndarray:
        loc = rng.uniform(*dist["loc"])
        scale = rng.uniform(*dist["scale"])
        e = rng.standard_exponential((n_windows, per_window), np.float32)
        np.maximum(e, np.finfo(np.float32).tiny, out=e)  # ln 0 would be infinite
        return (loc - scale * np.log(e)).astype(np.float32)

    return Windows(config, traffic, seed, draw)
