"""Engine decode batching (``serving/engine.py``): the mean share of the
arena's slots that hold a decoding request, over the window's fused decode
rounds.  Source: the ``live`` count of the engine's ``sample`` events."""


def read(rec):
    live = [n for t, n in rec.samples if rec.in_window(t)]
    if not live or not rec.slots:
        return None
    return 100.0 * sum(live) / (len(live) * rec.slots)
