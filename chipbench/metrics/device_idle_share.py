"""Device (TPU): the share of the traced window in which no operation
ran, mean over the chips, in percent."""


def read(facts):
    return 100.0 * (1.0 - facts.trace.busy_s() / facts.trace.window_s())
