"""Model FLOP/s utilisation of the whole train step, in %: required operations per
token (chipbench.flops) times the tokens per second of the traced window, over
the chip's peak."""

from chipbench import flops


def read(m):
    if m.steps <= 0 or m.window_s <= 0:
        return None
    per_token = flops.train_flops_per_token(m.family, m.model, int(m.traffic["seq_len"]))
    return 100.0 * per_token * m.tokens / m.window_s / m.peaks["flops"]
