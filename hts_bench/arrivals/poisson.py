"""Poisson arrivals at ``rate_per_s``: exponential gaps, enough of them to
cover the window with room to spare."""
import numpy as np


def due_times(rng: np.random.Generator, a: dict,
              seconds: float) -> np.ndarray:
    rate = a["rate_per_s"]
    n = int(rate * seconds + 8 * np.sqrt(rate * seconds)) + 16
    return np.cumsum(rng.exponential(1 / rate, n))
