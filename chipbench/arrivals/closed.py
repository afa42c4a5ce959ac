"""Closed loop: the queue kept ``queue_depth`` deep from a seeded stream
of ``stream_len`` distinct requests, after ``warm_steps`` steps."""

import loops
import traffic


def drive(feeder, mix: dict, vocab: int, seconds: float, seed: int, tracer):
    loop = traffic.ClosedLoop(mix, vocab, seed)
    return loops.drive_closed(feeder, loop, seconds, int(mix["warm_steps"]),
                              tracer)
