"""Open loop, Poisson arrivals on the wall clock at the mix's
``rate_per_s``, from ``-lead_in_s`` to the window's close."""

import loops
import traffic


def drive(feeder, mix: dict, vocab: int, seconds: float, seed: int, tracer):
    items = traffic.open_loop(mix, vocab, seconds, seed)
    return loops.drive_open(feeder, items, seconds, tracer)
