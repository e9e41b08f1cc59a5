"""Faults planted under a run, to show that the comparison deciding
``correct`` catches them. Each ``plant_<name>(patch)`` takes a
``patch(obj, attr, value)`` (pytest's ``monkeypatch.setattr``, or
``setattr`` in a process of its own) and breaks the timed path:

altered_answer   one served answer, in one batch inside the window,
                 is changed where it is produced
"""
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
