"""Seeds: a run's ``--seed`` mixed into the 31-bit range that
``jax.random.key`` and ``SketchConfig.seed`` take. A configuration's rows
are made by the module its ``rows`` key names, ``bench/rows/<rows>.py``.
"""
from __future__ import annotations

import numpy as np


def seed31(seed: int) -> int:
    """A run's ``--seed`` (any whole number, larger than 32 bits hold)
    mixed into the non-negative 31-bit range that ``jax.random.key`` and
    ``SketchConfig.seed`` take. Distinct seeds stay distinct in practice."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def fit_seed(seed: int, i: int) -> int:
    """The sketch seed of a run's ``i``-th fit: ``--seed`` and ``i`` mixed
    into 31 bits, so each fit of a run draws its own sketch."""
    sequence = np.random.SeedSequence(int(seed), spawn_key=(int(i),))
    return int(sequence.generate_state(1)[0] >> 1)
