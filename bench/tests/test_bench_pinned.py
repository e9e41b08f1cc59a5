"""The serve cell reads what it read before its driver, rows and
reference moved into files of their own: its rows, its request schedule
and its reference's scores and predictions, at the tiny size for one
seed, against values pinned from the harness as it was
(``data/serve_poisson_pinned.json``). Random draws are pinned bit for
bit; what passes through a matrix product to within float32 rounding."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench_tiny import tiny_cell
from harness.data import seed31

PINNED = json.loads((Path(__file__).with_name("data")
                     / "serve_poisson_pinned.json").read_text())
SEED = 2**33 + 7


def _sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def _matches(values, pinned, step):
    v = np.asarray(values, np.float64)
    np.testing.assert_allclose(v[::step], pinned["every"], rtol=1e-5,
                               atol=1e-6)
    assert v.sum() == pytest.approx(pinned["sum"], rel=1e-5, abs=1e-4)
    assert (v * v).sum() == pytest.approx(pinned["sumsq"], rel=1e-5)


@pytest.fixture(scope="module")
def cell_and_rows():
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        cell = tiny_cell("susy_falkon.serve_poisson")
        rows = cell.rows.make(cell.config, SEED)
        yield cell, rows
    finally:
        jax.config.update("jax_enable_x64", was)


def test_rows(cell_and_rows):
    _, (X, y, Xt, yt) = cell_and_rows
    assert _sha(X, Xt) == PINNED["X_Xt_sha256"]
    _matches(y, PINNED["y"], 128)
    _matches(yt, PINNED["yt"], 16)


def test_request_schedule(cell_and_rows):
    cell, (_, _, Xt, _) = cell_and_rows
    driver = object.__new__(cell.driver.Driver)     # no fit, no engine
    driver.seed, driver.traffic = SEED, cell.traffic
    due, rows = driver.schedule(1.0, Xt.shape[0])
    assert _sha(due, rows) == PINNED["schedule_sha256"]


def test_reference_scores_and_predictions(cell_and_rows):
    cell, (X, y, Xt, _) = cell_and_rows
    c = cell.config
    idx = np.random.default_rng(5).integers(0, c["n_train"], c["p"])
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        scores, pred = cell.reference.scores_and_predictions(
            c, seed31(SEED), X, y, Xt, jax.numpy.asarray(idx))
    finally:
        jax.config.update("jax_enable_x64", was)
    _matches(scores, PINNED["ref_scores"], 128)
    _matches(pred, PINNED["ref_pred"], 16)
