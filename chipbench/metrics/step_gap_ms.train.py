"""Mean device-idle time between the end of one train-step program and the start of the next, in ms."""


def read(m):
    lo, hi = m.trace.window
    runs = sorted((s, e) for s, e, name, _ in m.trace.programs if "train_step" in name and s >= lo and e <= hi)
    gaps = [max(b[0] - a[1], 0.0) for a, b in zip(runs, runs[1:])]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
