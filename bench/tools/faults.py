"""Faults planted under a run, to show that the comparison deciding
``correct`` catches them. Each ``plant_<name>(patch)`` takes a
``patch(obj, attr, value)`` (pytest's ``monkeypatch.setattr``, or
``setattr`` in a process of its own) and breaks the timed path:

altered_answer      one served answer, in one batch inside the window,
                    is changed where it is produced
altered_prediction  one held-out prediction of every fit is changed
                    where it is produced
pcg_step_unchanged  every PCG step returns the state it was given
half_rows           every PCG iteration's kernel pass reads the first
                    half of the rows alone, doubled in place of the rest
"""
import functools
import itertools


def plant_altered_answer(patch, batch: int = 40) -> None:
    from repro.serve.slot import PublishedModel
    predict = PublishedModel.predict_padded
    calls = itertools.count()

    def altered(self, X, bucket):
        y = predict(self, X, bucket)
        if next(calls) == batch:
            y = y.copy()
            y[0] += 1.0
        return y
    patch(PublishedModel, "predict_padded", altered)


def plant_altered_prediction(patch) -> None:
    from repro.api import SketchedKRR
    predict = SketchedKRR.predict

    def altered(self, X_test):
        return predict(self, X_test).at[0].add(1.0)
    patch(SketchedKRR, "predict", altered)


def plant_pcg_step_unchanged(patch) -> None:
    import jax.numpy as jnp
    from repro.core import distributed

    def unchanged(matvec, msolve, operands, bfloor, x, r, pvec, rz):
        rel = jnp.max(jnp.sqrt(jnp.sum(r * r, axis=0)) / bfloor)
        return x, r, pvec, rz, rel
    patch(distributed, "pcg_step", unchanged)


def plant_half_rows(patch) -> None:
    from repro.core import distributed

    @functools.lru_cache(maxsize=None)
    def half_matvec(ops):
        def matvec(operands, v):
            A, w, X = operands["A"], operands["w"], operands["X"]
            kv = 2.0 * ops.gram_matvec(X[:X.shape[0] // 2], operands["Z"],
                                       w * v)
            return w * kv.astype(A.dtype) + operands["nlam"] * (A @ v)
        return matvec
    patch(distributed, "sketch_normal_matvec", half_matvec)
