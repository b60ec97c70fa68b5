"""Plain reference of the toy configuration: every stage doubles."""
import numpy as np


def chain(x: np.ndarray, n_stages: int) -> np.ndarray:
    return x.astype(np.float32) * np.float32(2.0 ** n_stages)
